"""Ask the chip's compiler, without the chip (``tests/test_aot_compile.py`` says
what that is worth and what it is not): the benchmark's steps of Mellum2 on
an ep axis of four chips, and of Kimi Linear and OLMoE on dp x tp, at their
published widths.  Two long steps and a short one, one of three such files,
because the driver hands a worker a FILE at a time and a long step holds four
to five cores for minutes: queued last (``tests/conftest.py``), they fill the
cores the run's last workers leave.  Three cases, not two: a worker is handed
its next file when two cases are left to it, and one that holds a file of two
would take the next such file as well while other workers sit idle."""

import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.runtime import topology

from test_aot_compile import _sds, v5e  # noqa: F401


def test_mellum2_ep4_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `mellum2-12b-a2.5b-ep4-l8k` step on the four chips of
    a described v5e 2x2, a mesh of ``ep`` = 4: Mellum2-12B-A2.5B at its
    published widths, one whole period of 28 layers (three window layers and
    a full one, experts in each), all 64 experts, 16 a chip, the whole
    vocabulary on every chip, 8 x 8,192 tokens, two rows a chip, the
    configuration file's remat, AdamW with bfloat16 moments, weights and state
    donated.  The first program of this file that is one program across four
    chips.  Since PR 50 the expert layers gather the TOKENS over ``ep`` (4
    ranks, 8 choices a token: ``llama._ep_form``) and the compiler's own peak
    a chip is 10.61 GB of 16.91 (15.75 GiB); with the unit exchange it was
    12.92 GB (a first pass of the whole uniform share a peer and overflow
    passes a quarter of it, my compile of PR 46; 13.53 GB when every pass was
    the share, and then with float32 moments 17.1 GB and refused; at half the
    share a pass 12.02 GB with bfloat16 moments and 15.60 with float32, my
    compiles of PR 44; the latter ran on the chip): two rows a chip fit, the
    moments' type is the file's choice.  The flash kernels stand in their
    ``shard_map`` over ``ep`` (the batch's rows), the experts' grouped
    matmuls are Mosaic kernels too (every axis of the mesh is the expert
    layer's ``shard_map``'s), and what crosses the axis is ``all-gather``s of
    rows and ``all-to-all``s of partial sums by name."""
    import json
    import os

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchmpi_tpu.models import llama
    from torchmpi_tpu.models._common import mesh_spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as fh:
        file = json.load(fh)
    with open(os.path.join(root, "benchmark", "traffic",
                           "ep4-l8k.json")) as fh:
        mix = json.load(fh)
    run = file["run"]
    published = llama.mellum2_12b_a2_5b()
    cfg = dataclasses.replace(published, n_layers=4,
                              layer_kinds=published.layer_kinds[:4])
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (4, 64, 98304)
    assert [n for *_, n in llama.layer_runs(cfg)] == [3, 1]
    assert mix["mesh"] == {"ep": 4} and (mix["batch"], mix["seq_len"]) == (
        8, 8192)
    mesh = Mesh(np.array(v5e[:4]), ("ep",))
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 2_123_976_960
    placed = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, NamedSharding(
            mesh, mesh_spec(s, mesh, a.shape))), params,
        llama.param_specs(cfg))
    a_chip = sum(int(np.prod(a.sharding.shard_shape(a.shape)))
                 for a in jax.tree.leaves(placed))
    assert a_chip == 934_891_776                # 16 of 64 experts a layer
    # AdamW as `benchmark/runners/step_tokens_adamw.py:_optimizer` builds it:
    # both moments in the file's type, whatever the weights'.
    moments = jnp.dtype(run["optimizer"]["moments_dtype"])
    assert moments == jnp.bfloat16
    adamw = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9, b2=0.95,
                        weight_decay=0.1)
    cast = lambda tree: jax.tree.map(lambda a: a.astype(moments), tree)

    def update(grads, state, p):
        updates, state = adamw.update(cast(grads), state, cast(p))
        return jax.tree.map(lambda u, a: u.astype(a.dtype), updates,
                            p), state

    optimizer = optax.GradientTransformation(
        lambda p: adamw.init(cast(p)), update)
    like = iter(jax.tree.leaves(placed) * 2)
    state = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, next(like).sharding if a.ndim
                       else NamedSharding(mesh, P())),
        jax.eval_shape(optimizer.init, params))
    assert llama.batch_spec(cfg, mesh) == P("ep", None)
    tokens = _sds((mix["batch"], mix["seq_len"]), jnp.int32,
                  NamedSharding(mesh, llama.batch_spec(cfg, mesh)))
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"],
                                 with_delivered=True)
    program = step.lower(placed, state, tokens, tokens).compile()
    text = program.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (4, 4)
    window = [line for line in kernels if "/swa/" in line]
    assert (named("flash_fwd", window), named("flash_bwd", window)) == (3, 3)
    # A layer's grouped matmuls: 3 forward, and gate and up again with the
    # three products' two gradients each backward, ONE body each way (a pass
    # is one block; the forward pass's replay under "full" keeps nothing the
    # backward pass reads, so it is not there).  And, since PR 52, the pass's
    # float32 scatter-add each way (`ops/scatter_add_rows.py`: the results to
    # their tokens' partial sums forward, the rows' cotangents backward).
    assert len(kernels) == 4 * 2 + 4 * 13
    assert named("scatter_add_rows") == 4 * 2
    # XLA's scatter of a pass's rows into the sums is gone, whichever way the
    # sums are shaped, and the loops' carry is added to in place: the float32
    # sums are copied nowhere in the program
    sums = r"f32\[65536,(1,)?2304\]"
    assert not [line for line in text.splitlines()
                if re.search(sums + r"\S* (scatter|copy)\(", line)]
    assert llama.ep_exchange_plan(cfg, 2 * 8192, 4) == {
        "form": "tokens", "pass_rows": 8192, "overflow_pass_rows": 8192,
        "block_rows": 8192,
        "rows_forward": 2 * 3 * 16384, "rows_backward": 3 * 3 * 16384,
        "rows_forward_overflow": 0, "rows_backward_overflow": 0,
        "bytes_forward": 452_984_832, "bytes_backward": 679_477_248}
    moved = [re.search(r"= (\w+\[[\d,]*\])\S* (all-to-all|all-gather)\(",
                       line) for line in text.splitlines()]
    moved = [(m.group(2), m.group(1), m.string) for m in moved if m]
    assert moved and all("moe.exchange" in line for *_, line in moved)
    shapes = lambda kind: [s for k, s, _ in moved if k == kind]
    # a layer sends the partial sums home forward and the rows' cotangents
    # (and the router weights', 2 MB) backward: no block of units crosses
    assert sorted(shapes("all-to-all")) == (
        ["bf16[4,16384,2304]"] * 4 * 2 + ["f32[4,1,131072]"] * 4)
    # and gathers its rows forward and its rows and the result's cotangent
    # backward, 302 MB each (some inside the compiler's fusions with what
    # reads them, where the text names the instruction twice), with the
    # choices and the router's weights, 2 MB each
    gathered = shapes("all-gather")
    assert set(gathered) == {"bf16[65536,2304]", "f32[524288]", "s32[524288]"}
    assert gathered.count("bf16[65536,2304]") >= 4 * 3
    peak = program.memory_analysis().peak_memory_in_bytes
    assert 10.2e9 < peak < 11.2e9
    assert peak > 0.25 * 16e9                   # the benchmark's floor


def test_kimi_linear_step_on_dp_tp_runs_each_devices_kernels(monkeypatch):
    """On more than one device a KDA layer between its projections (the way
    in, the recurrence, the way out) runs in ONE ``shard_map`` over the batch
    and the heads (``llama._kda_sharded``), as flash does: its kernels are
    Mosaic's, and the compiler refuses to partition one
    (``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned``; bare under GSPMD this step does not lower).  Kimi Linear's
    first four layers at published widths (KDA, KDA, KDA, MLA; a share of the
    experts) on dp=2 x tp=2: each device runs ``kda_fwd`` and ``kda_bwd``
    once a KDA layer on its own row of the batch and its 16 of 32 heads,
    beside them ``kda_pre`` and ``kda_post`` twice (``"full"`` forms them
    again from their inputs) and ``kda_pre_bwd`` and ``kda_post_bwd`` once,
    and the latent layer's two flash kernels on its 16 heads."""
    import dataclasses

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    published = llama.kimi_linear_48b_a3b()
    cfg = dataclasses.replace(
        published, n_layers=4, layer_kinds=published.layer_kinds[:4],
        experts_held=(0, 8), vocab=20480)
    assert [m for m, _ in cfg.layer_kinds] == ["kda", "kda", "kda", "mla"]
    mesh = topology.topology_mesh("v5e-4", {"dp": 2, "tp": 2})
    args = topology._llama_arg_structs(cfg, mesh, llama.param_specs, 2, 4096)

    def lowered():
        step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                     loss_chunk=512)
        return jax.jit(lambda p, t, y: step(p, None, t, y)).lower(*args)

    text = lowered().compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert (named("kda_fwd"), named("kda_bwd")) == (3, 3)
    assert (named(r"kda_pre(?!_bwd)"), named(r"kda_post(?!_bwd)")) == (6, 6)
    assert (named("kda_pre_bwd"), named("kda_post_bwd")) == (3, 3)
    assert (named("flash_fwd"), named("flash_bwd")) == (1, 1)
    assert "jit(gmm)" not in text
    # a device's o and states: its one row, 4096 tokens in 64 chunks, 16 heads
    fwd = next(line for line in kernels if "kda_fwd" in line)
    assert "[1,4096,2048]" in fwd and "[64,1,16,128,128]" in fwd
    # the way in's four results on the same rows and heads
    pre = next(line for line in kernels if "kda_pre" in line
               and "kda_pre_bwd" not in line)
    assert pre.split(" custom-call(")[0].count("[1,4096,2048]") == 4
    import functools

    from torchmpi_tpu.ops.kda_mixer import kda_mixer
    monkeypatch.setattr(llama, "_kda_sharded", lambda mesh, heads, eps:
                        functools.partial(kda_mixer, eps=eps))
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lowered()


def test_olmoe_step_on_dp_tp_takes_the_compilers_grouped_matmul(monkeypatch):
    """On more than one device the sorted dispatch leaves the grouped matmul
    to `lax.ragged_dot`, which the compiler partitions under GSPMD (its own
    Mosaic kernel, named `ragged-dot-none`); megablox's, a Mosaic kernel of
    ours, it would refuse to.  One layer at published widths on dp=2 x tp=2:
    the 9 products the layer requires, the gate and up products kept through
    remat "dots" by the names they carry in this form too."""
    import dataclasses

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(llama.olmoe_1b_7b(), n_layers=1)
    mesh = topology.topology_mesh("v5e-4", {"dp": 2, "tp": 2})
    args = topology._llama_arg_structs(cfg, mesh, llama.param_specs, 4, 4096)
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="dots",
                                 loss_chunk=512)
    text = jax.jit(lambda p, t, y: step(p, None, t, y)).lower(
        *args).compile().as_text()
    assert text.count('op_name="ragged-dot-none"') == 9
    assert "jit(gmm)" not in text
