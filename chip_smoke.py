#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the cross-chip phase only

One process, JAX imported once, no child that needs the chip.  It drives the
trainer through the entry points a user calls (``mpi.start()`` ->
``AllReduceSGDEngine`` / ``llama.make_train_step`` -> ``mpi.stop()``) at the
full width of the models, with depth cut and weights made from ``--seed``,
checks what comes out by the repo's own means, and fails on the first check
that does not hold.

* Phase A, the engine at full width: ResNet-50 (1000 classes, 224x224,
  bf16, batch 128) under ``AllReduceSGDEngine(mode="compiled")``.  Eight
  distinct host batches go in through the default input path, then
  pre-staged on the device, then as float32 through a casting
  ``DataPipeline`` (the host-buffer reuse that is on only off the CPU): the
  three must give the same losses bit for bit.  One batch repeated must
  make the loss fall.
* Phase B, the kernels in a training step: Llama-3-8B widths cut to 4
  layers, bf16, B=1, L=4096, ``make_train_step(attn="flash", remat="dots",
  loss_chunk=512)`` on a one-device mesh; then flash against full attention
  at L=1024.  The compiled step must contain the Mosaic kernel.
* ``--chips 4``: the collectives on four devices with their algebraic
  answers, the engine at dp=4 against one device, the Llama step on
  dp=2 x tp=2 (flash) and sp=4 (ring-flash) against one device, and the
  Pallas ring allreduce against ``lax.psum``.

The phases are functions of their sizes and return what they measured, so
``tests/test_chip_smoke.py`` runs them tiny on the CPU mesh.  ``main`` alone
decides what only a chip can show: the platform, the kernel in the HLO, the
peak bytes the backend reports.

Everything is printed for information; the one result is the last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed here are not a metric (one window, no repeats).
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

import numpy as np

# bf16 keeps 8 bits of mantissa (eps = 2**-8 ~ 0.4%).  Two programs that
# round in a different order (flash against full attention, four devices
# against one) agree on a loss to a few eps, and on a gradient norm, which
# sums ~2e9 rounded terms through 4 layers of backward, to about ten.
LOSS_RTOL = 2e-2
GNORM_RTOL = 5e-2

# Plain SGD step size for the Llama phases, set from bf16 runs of the 8B
# slice on the CPU: at 0.01 the loss on a repeated batch falls every step
# (12.3 -> 8.6 in four steps at L=512); at 0.02 it overshoots on the fourth.
LLAMA_LR = 0.01
# For ResNet-50 from He init: at bench.py's 0.1 the loss on a repeated
# batch climbs (7.2 -> 14 in eight float32 steps at batch 32 on the CPU);
# at 0.02 it falls every step (7.2 -> 3.6).
RESNET_LR = 0.02

_NATIVE_BUILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "torchmpi_tpu", "_native", "_build")


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    say(f"ok: {what}")


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


def native_libraries() -> set:
    """The native host libraries (hostcomm.cpp, ps.cpp) that first use has
    compiled into ``_native/_build/``.  The trainer path must add none."""
    if not os.path.isdir(_NATIVE_BUILD):
        return set()
    return {f for f in os.listdir(_NATIVE_BUILD) if f.endswith(".so")}


# ------------------------------------------------------------------ engine


@functools.lru_cache(maxsize=None)
def _seeded_init(init, cfg, dtype):
    """``init(key, cfg, dtype=dtype)`` jitted once per configuration: op by
    op it is a compile per layer shape, and every leg and mesh of a phase
    starts from the same seeded weights."""
    import jax

    return jax.jit(lambda key: init(key, cfg, dtype=dtype))


LEGS = ("streamed", "resident", "cast", "repeated")


def phase_engine(devices, *, depth, width, n_classes, image, batch, steps,
                 dtype, seed, lr, legs=LEGS, timing_steps=0):
    """Phase A: ``mpi.start(devices=...)`` ->
    ``AllReduceSGDEngine(mode="compiled")`` on a ResNet -> ``engine.train``
    once per leg -> ``mpi.stop()``.

    Legs (each from the same seeded weights, ``steps`` steps, global batch
    ``batch``): ``streamed`` — distinct host batches through the default
    input path; ``resident`` — the same batches pre-staged on the device;
    ``cast`` — the same batches as float32 through ``DataPipeline(cast=)``;
    ``repeated`` — the first batch ``steps`` times.  Returns the per-step
    losses of each leg and, with ``timing_steps``, the compile seconds and
    the per-step milliseconds under both fences."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.data import DataPipeline
    from torchmpi_tpu.data.staging import stage_rank_major
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import resnet
    from torchmpi_tpu.runtime.communicator import RANK_AXIS

    mpi.start(devices=devices)
    comm = mpi.stack.current()
    mesh = comm.mesh()
    p = comm.size
    cfg = resnet.config(depth=depth, n_classes=n_classes,
                        width_multiplier=width, stem_space_to_depth=True)
    np_dtype = np.dtype(dtype)

    rng = np.random.default_rng(seed)
    n_distinct = steps if set(legs) - {"repeated"} else 1
    x32 = rng.standard_normal((n_distinct, p, batch // p, image, image, 3),
                              dtype=np.float32)
    y = rng.integers(0, n_classes, (n_distinct, p, batch // p)).astype(
        np.int32)
    host = list(zip(x32.astype(np_dtype), y))
    sh = NamedSharding(mesh, P(RANK_AXIS))
    resident = [(stage_rank_major(xb, sh), stage_rank_major(yb, sh))
                for xb, yb in host]

    params0 = jax.device_get(_seeded_init(resnet.init, cfg, dtype)(
        jax.random.PRNGKey(seed))[0])
    losses = []
    engine = AllReduceSGDEngine(
        resnet.make_loss_fn(cfg), lr=lr, comm=comm, mode="compiled",
        hooks={"on_update": lambda st: losses.append(st["loss"])})

    out = {"losses": {}}
    t0 = time.perf_counter()
    float(engine.train(params0, resident[:1])["loss"])
    out["compile_s"] = time.perf_counter() - t0

    sources = {
        "streamed": host,
        "resident": resident,
        "cast": DataPipeline(list(zip(x32, y)), mesh, cast=np_dtype),
        "repeated": resident[:1] * steps,
    }
    for leg in legs:
        del losses[:]
        engine.train(params0, sources[leg])
        out["losses"][leg] = [float(l) for l in losses]
        say(f"engine p={p} {leg}: losses "
            + " ".join(f"{l:.4f}" for l in out["losses"][leg]))
        check(np.isfinite(out["losses"][leg]).all()
              and len(out["losses"][leg]) == steps,
              f"engine p={p} {leg}: {steps} finite losses")
    got = out["losses"]
    for leg in ("streamed", "cast"):
        if leg in got and "resident" in got:
            check(got[leg] == got["resident"],
                  f"engine p={p}: {leg} losses == resident losses, "
                  f"bit for bit")
    if "repeated" in got:
        check(got["repeated"][-1] < got["repeated"][0],
              f"engine p={p}: loss falls on a repeated batch "
              f"({got['repeated'][0]:.4f} -> {got['repeated'][-1]:.4f})")

    if timing_steps:
        window = resident[:1] * timing_steps
        t0 = time.perf_counter()
        jax.block_until_ready(engine.train(params0, window)["loss"])
        out["ms_block_until_ready"] = (
            (time.perf_counter() - t0) / timing_steps * 1e3)
        t0 = time.perf_counter()
        last = engine.train(params0, window)["loss"]
        float(last)
        out["ms_float_loss"] = (time.perf_counter() - t0) / timing_steps * 1e3
        # What a readiness check on a finished array costs here: the
        # engine's in-flight window (engine/sgdengine.py _bound_inflight)
        # was once unbounded on TPU because this cost ~60 ms.
        reads = []
        for _ in range(100):
            t0 = time.perf_counter()
            last.block_until_ready()
            reads.append(time.perf_counter() - t0)
        out["ready_check_us"] = float(np.median(reads)) * 1e6
    out["peak_bytes"] = (devices[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    mpi.stop()
    return out


# ------------------------------------------------------------------- llama


def _llama_batch(cfg, batch, seq, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)
    return tokens, targets


def run_llama_step(mesh, attn, *, cfg, batch, seq, steps, dtype, seed, lr):
    """``llama.make_train_step(attn=..., remat="dots", loss_chunk=...)`` on
    ``mesh``: seeded weights placed by ``shard_params``, one seeded batch
    repeated ``steps`` times.  The step is compiled once ahead of time and
    that executable runs, so the HLO text read for the kernel is the
    program that produced the losses."""
    import jax

    from torchmpi_tpu.models import llama

    name = "x".join(f"{k}{v}" for k, v in mesh.shape.items()) + ":" + attn
    params = llama.shard_params(
        _seeded_init(llama.init, cfg, dtype)(jax.random.PRNGKey(seed)),
        mesh, cfg)
    tokens, targets = _llama_batch(cfg, batch, seq, seed)
    step = llama.make_train_step(cfg, mesh, lr=lr, attn=attn, remat="dots",
                                 loss_chunk=min(512, seq))
    t0 = time.perf_counter()
    compiled = step.lower(params, None, tokens, targets).compile()
    out = {"name": name, "compile_s": time.perf_counter() - t0,
           "kernel_calls": compiled.as_text().count("tpu_custom_call")}
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, _, loss = compiled(params, None, tokens, targets)
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    out["losses"] = losses
    out["step_ms"] = float(np.median(times)) * 1e3
    say(f"llama {name} B={batch} L={seq}: compile "
        f"{out['compile_s']:.1f} s, {out['kernel_calls']} tpu_custom_call, "
        f"{out['step_ms']:.1f} ms/step (median of {steps}, float(loss) "
        f"fence), losses " + " ".join(f"{l:.4f}" for l in losses))
    check(np.isfinite(losses).all(), f"llama {name}: finite losses")
    check(losses[-1] < losses[0],
          f"llama {name}: loss falls on a repeated batch")
    return out, params


def phase_kernels(devices, *, cfg, seq, cmp_seq, steps, dtype, seed, lr):
    """Phase B: the flash step on a one-device mesh, then the loss and the
    gradient norm of ``attn="flash"`` against ``attn="full"`` at
    ``cmp_seq`` on the weights the steps left."""
    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 1}, devices=devices[:1])
    out, params = run_llama_step(mesh, "flash", cfg=cfg, batch=1, seq=seq,
                                 steps=steps, dtype=dtype, seed=seed, lr=lr)
    batch = _llama_batch(cfg, 1, cmp_seq, seed + 1)
    cmp = {}
    for attn in ("flash", "full"):
        loss_fn = llama.make_loss_fn(cfg, mesh, attn=attn, remat="dots",
                                     loss_chunk=min(512, cmp_seq))

        def loss_and_gnorm(p, b):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            return loss, jnp.sqrt(sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(g)))

        loss, gnorm = jax.jit(loss_and_gnorm)(params, batch)
        cmp[attn] = (float(loss), float(gnorm))
    out["flash_full_loss_diff"] = rel_diff(cmp["flash"][0], cmp["full"][0])
    out["flash_full_gnorm_diff"] = rel_diff(cmp["flash"][1], cmp["full"][1])
    say(f"llama L={cmp_seq} flash (loss, |grad|) = {cmp['flash']}, "
        f"full = {cmp['full']}")
    check(np.isfinite(cmp["flash"]).all() and np.isfinite(cmp["full"]).all(),
          "flash and full: finite loss and gradient norm")
    check(out["flash_full_loss_diff"] <= LOSS_RTOL,
          f"flash vs full loss: relative difference "
          f"{out['flash_full_loss_diff']:.2e} <= {LOSS_RTOL}")
    check(out["flash_full_gnorm_diff"] <= GNORM_RTOL,
          f"flash vs full gradient norm: relative difference "
          f"{out['flash_full_gnorm_diff']:.2e} <= {GNORM_RTOL}")
    return out


# -------------------------------------------------------------- cross-chip


def run_collectives(n_elems):
    """The top-level collectives on ``eager.fill_by_rank`` payloads of
    ``n_elems`` float32 per rank (rank r holds r everywhere, so the answers
    are algebraic), and where each result's shards sit."""
    import jax.numpy as jnp

    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager

    comm = mpi.stack.current()
    p = comm.size
    ranks = np.arange(p, dtype=np.float32)
    x = eager.fill_by_rank(comm, (n_elems,))
    cases = {
        "allreduce": (mpi.allreduce(x), ranks.sum()),
        "broadcast": (mpi.broadcast(x, root=p - 1), p - 1.0),
        "allgather": (mpi.allgather(x), ranks[None, :, None]),
        "reduce_scatter": (mpi.reduce_scatter(x), ranks.sum()),
        "alltoall": (mpi.alltoall(x), ranks[None, :, None]),
    }
    for name, (got, want) in cases.items():
        if name == "alltoall":   # rank r's chunk i came from rank i
            got = got.reshape(p, p, n_elems // p)
        check(bool(jnp.all(got == want)),
              f"{name} of ranks 0..{p - 1}, {n_elems * 4} bytes per rank: "
              f"algebraic answer, shape {got.shape}")
        owners = {s.device for s in got.addressable_shards}
        check(owners == set(comm.devices),
              f"{name}: result shards sit on {len(owners)} devices")


def run_pallas_ring(n_elems, seed):
    """``pallas_ring.ring_allreduce`` against ``lax.psum`` (the eager
    allreduce) on seeded float32 payloads; returns the largest difference."""
    import jax.numpy as jnp

    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager, pallas_ring

    comm = mpi.stack.current()
    x = eager.shard(comm, np.random.default_rng(seed).standard_normal(
        (comm.size, n_elems), dtype=np.float32))
    ring = pallas_ring.ring_allreduce(comm, x)
    diff = float(jnp.max(jnp.abs(ring - mpi.allreduce(x))))
    check(diff <= 1e-5,
          f"pallas ring allreduce p={comm.size}, {n_elems} elements: "
          f"max |ring - psum| = {diff:.2e} <= 1e-5")
    return diff


def phase_cross_chip(devices, *, engine, llama_sizes, payloads, ring_elems):
    """What exists only across chips, and what each is compared with."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.parallel import make_mesh

    n = len(devices)
    out = {}

    one = phase_engine(devices[:1], legs=("repeated",), **engine)
    many = phase_engine(devices, legs=("repeated",), **engine)
    diffs = [rel_diff(a, b) for a, b in zip(one["losses"]["repeated"],
                                            many["losses"]["repeated"])]
    out["engine_loss_diff"] = max(diffs)
    check(out["engine_loss_diff"] <= LOSS_RTOL,
          f"engine dp={n} vs one device, step for step: largest relative "
          f"loss difference {out['engine_loss_diff']:.2e} <= {LOSS_RTOL}")
    out["engine_peak_bytes"] = many["peak_bytes"]

    mpi.start(devices=devices)
    for n_elems in payloads:
        run_collectives(n_elems)

    out["kernel_calls"] = {}
    ref = None
    for axes, attn in (({"dp": 1}, "flash"),
                       ({"dp": n // 2, "tp": 2}, "flash"),
                       ({"dp": 1, "sp": n}, "ring")):
        mesh = make_mesh(axes, devices=devices[:math.prod(axes.values())])
        # [0]: the weights the steps left go at once; the next mesh needs
        # the room on device 0.
        got = run_llama_step(mesh, attn, **llama_sizes)[0]
        key = got["name"]
        out["kernel_calls"][key] = got["kernel_calls"]
        if ref is None:
            ref = got["losses"]
            continue
        out[f"llama_loss_diff {key}"] = max(
            rel_diff(a, b) for a, b in zip(ref, got["losses"]))
        check(out[f"llama_loss_diff {key}"] <= LOSS_RTOL,
              f"llama {key} vs one-device flash, step for step: largest "
              f"relative loss difference "
              f"{out[f'llama_loss_diff {key}']:.2e} <= {LOSS_RTOL}")

    # Last on purpose: this is the kernel's first run with a neighbour, and
    # everything above is already printed if it hangs.
    out["ring_diff"] = run_pallas_ring(ring_elems, llama_sizes["seed"])
    mpi.stop()
    return out


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the cross-chip phase and no other")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    say(f"jax {jax.__version__}, devices {dev}, compile cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or '<checkout>/.jax_cache'}")
    if dev["platform"] != "tpu":
        say(f"no accelerator: platform is {dev['platform']!r}, not 'tpu'")
        return 1
    if dev["count"] != args.chips:
        say(f"--chips {args.chips} needs exactly {args.chips} device(s), "
            f"JAX reports {dev['count']}")
        return 1

    import dataclasses

    from torchmpi_tpu.models import llama

    resnet50 = dict(depth=50, width=1.0, n_classes=1000, image=224, batch=128,
                    steps=8, dtype=jnp.bfloat16, seed=args.seed, lr=RESNET_LR)
    slice_8b = dataclasses.replace(llama.llama3_8b(), n_layers=4)
    t_start = time.perf_counter()
    native_before = native_libraries()

    if args.chips == 1:
        a = phase_engine(devices, timing_steps=32, **resnet50)
        say(f"phase A: compile+first step {a['compile_s']:.1f} s; "
            f"{a['ms_block_until_ready']:.2f} ms/step fenced by "
            f"jax.block_until_ready, {a['ms_float_loss']:.2f} ms/step fenced "
            f"by float(loss) (32 resident steps each, engine.train set-up "
            f"included); readiness check on a finished array "
            f"{a['ready_check_us']:.1f} us; peak_bytes_in_use "
            f"{a['peak_bytes']}")
        check(a["peak_bytes"], "the backend reports peak_bytes_in_use")
        b = phase_kernels(devices, cfg=slice_8b, seq=4096, cmp_seq=1024,
                          steps=3, dtype=jnp.bfloat16, seed=args.seed,
                          lr=LLAMA_LR)
        check(b["kernel_calls"] > 0,
              f"phase B: the compiled step holds {b['kernel_calls']} "
              f"tpu_custom_call (no interpret-mode fallback)")
        say(f"phase B: peak_bytes_in_use "
            f"{devices[0].memory_stats()['peak_bytes_in_use']}")
    else:
        x = phase_cross_chip(
            devices, engine=resnet50,
            llama_sizes=dict(cfg=slice_8b, batch=2, seq=2048, steps=2,
                             dtype=jnp.bfloat16, seed=args.seed,
                             lr=LLAMA_LR),
            payloads=(1024, 16 * 1024 * 1024), ring_elems=65536)
        for key, calls in x["kernel_calls"].items():
            check(calls > 0, f"llama {key}: the compiled step holds "
                             f"{calls} tpu_custom_call")
        say(f"cross-chip: engine peak_bytes_in_use {x['engine_peak_bytes']}")

    built = native_libraries() - native_before
    check(not built, f"the trainer path built no native host library "
                     f"(present before: {sorted(native_before) or 'none'}; "
                     f"built now: {sorted(built) or 'none'})")
    say(f"all phases passed in {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
