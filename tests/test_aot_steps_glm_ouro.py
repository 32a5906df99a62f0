"""Ask the chip's compiler, without the chip (``tests/test_aot_compile.py`` says
what that is worth and what it is not): the benchmark's steps of
GLM-4.7-Flash and Ouro-2.6B at their published widths, and a Llama slice's on
dp x tp.  Two long steps and a short one, one of three such files, because
the driver hands a worker a FILE at a time and a long step holds four to five
cores for minutes: queued last (``tests/conftest.py``), they fill the cores
the run's last workers leave.  Three cases, not two: a worker is handed its
next file when two cases are left to it, and one that holds a file of two
would take the next such file as well while other workers sit idle."""

import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from torchmpi_tpu.runtime import topology

from test_aot_compile import _kernels, _sds, v5e  # noqa: F401


def test_glm_flash_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `glm-4.7-flash-l16k` step on one chip: GLM-4.7-Flash at
    its published widths, the first 5 of 47 layers (a dense FFN, then four
    with experts: two runs, inlined) and the multi-token-prediction module, 8
    of 64 routed experts a layer held here beside the shared one, 19,360 rows
    of the vocabulary (151.25 tiles of 128), 1 x 16,384 tokens, flash at
    heads of 256, the configuration file's remat, AdamW with float32 moments,
    weights and state donated.  The compiler's own peak is 13.05 GB of 16.91
    (15.75 GiB) since PR 41 (15.39 before it, where `"dots"` was refused by 58
    MB).  Two flash kernels for each of the six latent layers,
    the module's under `mtp`, none replayed, and eleven grouped matmuls for
    each of the five expert layers, as in the Kimi Linear step."""
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-4.7-flash.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.glm_4_7_flash()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=19360)
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"], file["num_nextn_predict_layers"]) == (
        5, 8, 19360, 1)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 4]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 706_518_848
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
    kernels = [line for line in program.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (6, 6)
    assert all("/mla/" in line for line in kernels if "flash_" in line)
    module = [line for line in kernels if re.search(r"[(/]mtp[)/]", line)]
    assert (named("flash_fwd", module), named("flash_bwd", module)) == (1, 1)
    # an expert layer: 11 grouped matmuls and, since PR 52, the pass's two
    # scatter-adds (`ops/scatter_add_rows.py`), forward and backward
    assert len(kernels) == 6 * 2 + 5 * 13 and len(module) == 2 + 13
    assert named("scatter_add_rows") == 5 * 2
    peak = program.memory_analysis().peak_memory_in_bytes
    # 15.39 GB until PR 41, whose rotation (`llama._rotate_pairs`) leaves
    # the compiler no sequence-on-the-lanes copies of q and k to keep.
    assert 12.0e9 < peak < 14.0e9


def test_ouro_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `ouro-2.6b-b2-l4096` step on one chip: Ouro-2.6B at
    its published widths, 8 of 48 layers run four times (32 layer
    applications: the layer scan inside, the recurrent steps inlined round
    it), 2 x 4096 tokens, flash, the configuration file's remat (three steps
    `"full"`, one `"dots"`), the four heads through one chunked call, AdamW
    with float32 moments, weights and state donated.  `benchmark/sizing.py`
    knows no function for this runner, so this is the cell's plan: 16.80 GB
    under the chip's 15.75 GiB (16.91 GB), where the plan that replayed the
    forward kernels held 15.07 (15.16 with no barrier round the scanned
    layers' checkpoints).  The o and lse of the 24 layer applications under
    `"full"` are 24 x (33.6 + 0.5) MB = 0.82 GB, and the compiler's own peak
    (`peak_memory_in_bytes`) rose by just that, 12.60 to 13.42 GB; arguments
    plus temporaries, the sum the cell reports, count it twice.  `"dots"` at
    every step is refused."""
    import dataclasses
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "ouro-2.6b.json")) as fh:
        run = json.load(fh)["run"]
    cfg = dataclasses.replace(llama.ouro_2_6b(), n_layers=8)
    assert cfg.n_layers > llama._INLINE_MAX_LAYERS and cfg.ut_steps == 4
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 612_438_017
    adamw = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def update(grads, state, params):       # moments float32, as the runner
        updates, state = adamw.update(f32(grads), state, f32(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    optimizer = optax.GradientTransformation(lambda p: adamw.init(f32(p)),
                                             update)
    state = jax.eval_shape(optimizer.init, params)
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((2, 4096), jnp.int32, one)

    def compiled(remat):
        step = llama.make_train_step(cfg, mesh, attn="flash",
                                     optimizer=optimizer, remat=remat,
                                     loss_chunk=run["loss_chunk"])
        return step.lower(place(params), place(state), tokens, tokens).compile()

    program = compiled(run["remat"])
    text = program.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    # One forward and one backward kernel in each recurrent step's scan body
    # and no other: a step that recomputes its layers ("full") keeps the
    # forward kernel's o and lse as a "dots" step does, and still recomputes
    # the rest (the SwiGLU's products under `rematted_computation/ffn`).
    assert run["remat"] == ["full", "full", "full", "dots"]
    assert sum("flash_fwd" in line for line in kernels) == 4
    assert sum("flash_bwd" in line for line in kernels) == 4
    assert len(kernels) == 8
    assert not any("rematted_computation" in line for line in kernels)
    assert sum("rematted_computation/ffn" in line and " convolution(" in line
               for line in text) >= 3
    # The head: three products over the vocabulary in one scan body, on the
    # 4 x 2 rows of all the recurrent steps' states, none replayed.
    head = [line for line in text
            if "head_loss" in line and " convolution(" in line]
    assert len(head) == 3 and not any("rematted" in line for line in head)
    assert sum("bf16[8,512,49152]" in line.split(" convolution(")[0]
               for line in head) == 1
    m = program.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both float32 moments donated: 10 bytes a parameter
    assert m.alias_size_in_bytes > 10 * 612_000_000
    assert 0.25 * 16e9 < held < 15.75 * 2**30
    with pytest.raises(Exception, match="hbm"):
        compiled("dots")


def test_llama_flash_step_on_dp_tp(v5e, monkeypatch):
    """``make_train_step(attn="flash")`` on dp=2 x tp=2: the Mosaic kernel
    must reach the compiler inside a shard_map (under GSPMD it is refused:
    "Mosaic kernels cannot be automatically partitioned").  The step reads
    the running backend to choose interpret mode, so the test answers for
    it; no option of the program does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = topology._build_llama_dp_tp("v5e-4", attn="flash")
    compiled = jax.jit(fn).lower(*args).compile()
    assert _kernels(compiled) > 0
    # `tiny`'s 4 heads over 2 K/V heads, split over tp at BOTH counts: a
    # device's kernels take its 2 query heads and its 1 K/V head, which the
    # two share by the kernels' index maps, and `flash_bwd` gives dk and dv
    # at that one head (no repeat stands in the shard_map's body).
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "flash_" in line]
    heads = lambda line: [int(n) for n in re.findall(
        r"(?:bf16|f32)\[(\d+),\d+,16\]",
        line.split("operand_layout_constraints")[1])]
    assert calls and all(heads(line)[:3] == [2 * heads(line)[1],
                                            heads(line)[1], heads(line)[2]]
                         for line in calls)
    # The chunked head under GSPMD (head columns over tp, rows over dp): the
    # program with a checkpointed chunk held 11 all-reduces of 255,364 bytes,
    # four in a chunk (max and target logit, then max and sum again in the
    # replay) and dh's after the scan.  Now three in a chunk (max, sum, and
    # the target logit with the chunk's dh: the same bytes a step), and dW is
    # still summed over dp once, after the scan, with the other gradients.
    # The text counts an instruction once: `tiny`'s two layers are inlined,
    # so each layer's four activation all-reduces over tp (16,384 bytes,
    # attention and FFN, forward and backward) stands there itself, where a
    # scan's body stood once whatever its trips (9 instructions, 246,916
    # bytes), and the layers' dp sums ride with the embedding's.
    text = compiled.as_text()
    stats = topology.hlo_collective_stats(text)
    assert set(stats["counts"]) == {"all-reduce:f32"}
    assert stats["total"] <= 12
    assert sum(stats["operand_bytes"].values()) <= 386_692
    in_chunk = [line for line in text.splitlines()
                if " all-reduce(" in line and "head_loss" in line]
    assert len(in_chunk) == 3 and all("while/body" in line for line in in_chunk)
