"""Ask the chip's compiler, without the chip (``tests/test_aot_compile.py`` says
what that is worth and what it is not): the benchmark's steps of
Laguna-S-2.1, Kimi Linear and OLMoE at their published widths.  Two long
steps and a short one, one of three such files, because the driver hands a
worker a FILE at a time and a long step holds four to five cores for minutes:
queued last (``tests/conftest.py``), they fill the cores the run's last
workers leave.  Three cases, not two: a worker is handed its next file when
two cases are left to it, and one that holds a file of two would take the
next such file as well while other workers sit idle."""

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from test_aot_compile import _sds, v5e  # noqa: F401


def test_laguna_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `laguna-s-2.1-l16k` step on one chip: Laguna-S-2.1 at
    its published widths, the first 5 of 48 layers (a full layer with the
    dense FFN, three window layers and a full one with experts: three runs,
    inlined), 8 of 256 routed experts a layer held here beside the shared
    one, 12,544 rows of the vocabulary, 1 x 16,384 tokens, the configuration
    file's remat, AdamW with bfloat16 moments, weights and state donated.
    The compiler's own peak is 14.09 GB of 16.91 (15.75 GiB); with float32
    moments it refuses the step by 437 MB (my compile of PR 40: 2.5 GB of the
    plan is the five layers' log-sum-exp columns padded to 128 lanes).  Two
    flash kernels a layer, the window layers' under `swa`, none replayed."""
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-s-2.1.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.laguna_s_2_1()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=12544)
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 8, 12544)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 3, 1]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 811_017_216
    assert run["optimizer"]["moments_dtype"] == "bfloat16"
    optimizer = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9,
                            b2=0.95, weight_decay=0.1)
    state = jax.eval_shape(optimizer.init, params)      # bfloat16, as they
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((1, 16384), jnp.int32, one)
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"])
    program = step.lower(place(params), place(state), tokens,
                         tokens).compile()
    kernels = [line for line in program.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (5, 5)
    window = [line for line in kernels if "/swa/" in line]
    assert (named("flash_fwd", window), named("flash_bwd", window)) == (3, 3)
    assert len(window) == 6
    # as before PR 41, and since PR 52 a pass's two scatter-adds a layer
    assert len(kernels) == 5 * 2 + 4 * 13
    assert named("scatter_add_rows") == 4 * 2
    for line in kernels:
        if "flash_" in line:      # q's 72 or 48 heads, K and V at their 8
            heads = [int(n) for n in re.findall(
                r"bf16\[(\d+),16384,128\]",
                line.split("operand_layout_constraints")[1])]
            assert heads[0] in (48, 72) and heads[1:3] == [8, 8]
    # Under the 14.09 GB of the step with K and V repeated and the sliced
    # rotation (compiled here at PR 40 and again at PR 41): 13.06 GB.
    peak = program.memory_analysis().peak_memory_in_bytes
    assert 12.0e9 < peak < 14.0e9


def test_kimi_linear_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `kimi-linear-48b-a3b-l16k` step on one chip:
    Kimi-Linear-48B-A3B at its published widths, the first 5 of 27 layers (KDA
    and a dense FFN; KDA, KDA, MLA, KDA with experts: four runs, inlined), 8
    of 256 routed experts a layer held here beside the shared one, 20,480
    rows of the vocabulary, 1 x 16,384 tokens, flash with keys of 192 and
    values of 128, the configuration file's remat, AdamW with float32
    moments, weights and state donated.  It fits the chip: the compiler's
    own peak is 13.41 GB of 16.91 (15.75 GiB) and the sum the cell reports
    13.89 GB.  Two flash kernels for the one MLA layer and, for each of the
    four expert layers, the grouped matmuls of one pass of the held experts'
    loops: `gmm` forward (3), for the rows' gradients (3) and, the backward
    loop forming what it does not keep, gate and up again (2), `tgmm` for
    the weights' gradients (3); the forward loop that `"full"` replays is
    dead there and gone.  A KDA layer's recurrence is two more, `kda_fwd`
    and `kda_bwd`, the 256 chunks a grid axis each runs in turn: no loop is
    left under `kda`, and the forward kernel that `"full"` would replay is
    dead, its output, states, inverses and `P` kept.  The layer's passes
    round the recurrence are six more (`ops/kda_mixer.py`): `kda_pre` and
    `kda_post` forward and, kept by their inputs alone, formed again under
    `"full"`,
    `kda_pre_bwd` and `kda_post_bwd` once; they stand under `attn`, not under
    `kda`."""
    import dataclasses
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.kimi_linear_48b_a3b()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=20480)
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 8, 20480)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 2, 1, 1]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 602_450_816
    adamw = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9, b2=0.95,
                        weight_decay=0.1)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def update(grads, state, params):       # moments float32, as the runner
        updates, state = adamw.update(f32(grads), state, f32(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    optimizer = optax.GradientTransformation(lambda p: adamw.init(f32(p)),
                                             update)
    state = jax.eval_shape(optimizer.init, params)
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((1, 16384), jnp.int32, one)
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"])
    program = step.lower(place(params), place(state), tokens,
                         tokens).compile()
    text = program.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd")) == (1, 1)
    assert all("/mla/" in line for line in kernels if "flash_" in line)
    assert named(r"jit\(gmm\)") == 4 * 8 and named(r"jit\(tgmm\)") == 4 * 3
    assert (named("kda_fwd"), named("kda_bwd")) == (4, 4)
    assert (named(r"kda_pre(?!_bwd)"), named(r"kda_post(?!_bwd)")) == (8, 8)
    assert (named("kda_pre_bwd"), named("kda_post_bwd")) == (4, 4)
    recurrence = lambda line: "kda_fwd" in line or "kda_bwd" in line
    assert all(("/kda/" in line) == recurrence(line)
               and re.search(r"[/(]attn[/)]", line)
               for line in kernels if "kda_" in line)
    assert named("scatter_add_rows") == 4 * 2         # a pass's, each way
    assert len(kernels) == 46 + 2 * 4 + 6 * 4 + 2 * 4
    # what "full" forms again: the way in and the way out, never a
    # recurrence or a flash kernel
    assert not any("rematted_computation" in line for line in kernels
                   if "flash_" in line or recurrence(line))
    assert sum("rematted_computation" in line for line in kernels
               if "kda_" in line) == 2 * 4
    assert not any(" while(" in line and "/kda/" in line for line in text)
    m = program.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both float32 moments donated: 10 bytes a parameter
    assert m.alias_size_in_bytes > 10 * 602_000_000
    # That it compiled is the check that it fits 15.75 GiB.  The compiler's
    # own peak is 13.41 GB and arguments plus temporaries, the sum the cell
    # reports as `hbm_program_gb`, 13.89 GB: a record of the plan, not a
    # limit of the chip.  Before the four KDA layers kept their tiles'
    # inverses and `P` (`ops.kda.residual_bytes`: 4 x (0.134 + 0.067) = 0.81
    # GB from forward to backward) they read 12.94 and 13.69; the sum grew by
    # 0.20 and not by 0.81 because its temporaries are the highest point of a
    # heap that the compiler packs anew, not a sum of what is kept; the peak
    # by 0.47.
    from torchmpi_tpu.ops import kda

    kept = kda.residual_bytes(1, 16384, cfg.kda_heads, cfg.kda_head_dim,
                              jnp.bfloat16)
    assert 4 * (kept["kda_inverse"] + kept["kda_p"]) == 6 * 2**27
    assert 8e9 < m.peak_memory_in_bytes < 14e9
    assert m.peak_memory_in_bytes < held < 14e9


def test_olmoe_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `olmoe-1b-7b-l4096` step on one chip: OLMoE-1B-7B at
    its published widths, 2 of 16 layers, inlined (`llama.apply` scans no
    stack this shallow), 4 x 4096 tokens, flash, remat "dots", AdamW with
    bfloat16 moments, weights and state donated.  It fits the chip, and
    holds, for each layer, the two flash kernels and the grouped matmuls of
    the sorted dispatch: `gmm` for gate, up and down forward and the three
    gradients of the rows (6: the 9 products a layer requires and none again,
    remat "dots" keeping the gate and up products by their names and nothing
    reading the down product's), `tgmm` for the three gradients of the
    weights.  What the scan cost is not there: no
    layer's expert weights copied out of the stack by a `dynamic-slice`, no
    gradient written into it by a `dynamic-update-slice` (22.4 ms of a
    316.65 ms step and 3.65 GB of the plan: PERF_LEDGER.jsonl, PR 28)."""
    import dataclasses
    import re

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(llama.olmoe_1b_7b(), n_layers=2)
    assert cfg.n_layers <= llama._INLINE_MAX_LAYERS
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 1_045_186_560
    optimizer = optax.adamw(4e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    state = jax.eval_shape(optimizer.init, jax.tree.map(
        lambda a: _sds(a.shape, jnp.bfloat16, one), params))
    mesh = Mesh([v5e[0]], ("dp",))
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat="dots", loss_chunk=512)
    tokens = _sds((4, 4096), jnp.int32, one)
    compiled = step.lower(place(params), place(state), tokens, tokens).compile()
    text = compiled.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    # (inlined, a forward kernel's scope reads `jvp(moe.experts)/jit(gmm)`)
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert (named("flash_fwd"), named("flash_bwd")) == (2, 2)
    assert named(r"moe\.experts\)?/jit\(gmm\)") == 12
    assert named(r"moe\.experts\)?/jit\(tgmm\)") == 6
    assert len(kernels) == 22
    assert not named(r"rematted_computation.*jit\(t?gmm\)")
    # No slice of the stacked expert weights, (2, 64, 2048, 1024) and its
    # transpose, cut or written at an index the program computes.
    expert = re.compile(r"bf16\[(2,)?64,(2048,1024|1024,2048)\]")
    assert not [line for line in text
                if re.search(r"dynamic-(update-)?slice", line)
                and expert.search(line)]
    # The head: three products over the vocabulary in one scan body (logits,
    # dh, dW), and no replay of `h_c @ head` in a backward scan.
    head = [line for line in text
            if "head_loss" in line and " convolution(" in line]
    assert len(head) == 3 and not any("rematted" in line for line in head)
    assert all("jvp(head_loss)/while/body" in line for line in head)
    assert sum('head_loss)/while"' in line and " while(" in line
               for line in text) == 1
    assert sum("bf16[4,512,50304]" in line.split(" convolution(")[0]
               for line in head) == 1
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both moments donated: all but the tokens and a few norms
    assert m.argument_size_in_bytes - m.alias_size_in_bytes < 1e6
    assert m.alias_size_in_bytes > 3 * 2 * 1_045_000_000
    # The plan: 12.04 GB, 6.27 of weights and moments (3 x 2 bytes x 1.045 G)
    # and 5.77 of temporaries.  The gate and up products kept for the backward
    # pass are 2 layers x 2 x (8 x 16,384 rows) x 1024 x 2 bytes = 1.07 GB,
    # yet the plan that replayed them held 11.83 (temporaries 5.56): its peak
    # lies in the last layer's backward pass, where the replayed pair stood
    # too, so only the first layer's pair, less what it displaces, is new.
    assert 9e9 < held < 12.5e9
