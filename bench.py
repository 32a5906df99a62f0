#!/usr/bin/env python
"""Driver benchmark: ResNet-50 training throughput under AllReduceSGDEngine —
the headline metric in BASELINE.json ("ResNet-50 images/sec/chip
(AllReduceSGDEngine)") — with a roofline account (MFU vs chip peak).

Protocol mirrors the reference harness (reference: torchmpi/tester.lua:41-47,
79-101 — warmup runs discarded, timed runs averaged).  Steady-state step
time is measured as a two-point slope, ``(T(N2) - T(N1)) / (N2 - N1)`` with
a ``float(loss)`` read fencing each run, which cancels any fixed cost per
measurement.  (Rounds 2-5 needed that: their set-up, which no longer
exists, added a large fixed latency per dispatch and ``block_until_ready``
did not fence there.  chip_smoke.py prints the step time under both fences
on today's machine; the benchmark PR decides whether the slope stays.)

A run without a TPU is an error: a throughput of the CPU backend is not
this benchmark's metric and is never printed in its shape.

Measured four ways, innermost to outermost, so the breakdown attributes
time between compute and input pipeline:
  1. compute-only    — compiled step on device-resident batches
  2. engine+resident — AllReduceSGDEngine over device-resident batches
                       (DevicePrefetchIterator-staged; the reported metric)
  3. engine+host     — one engine run over plain rank-major numpy batches
                       with data_pipeline=off: quantifies the UNPIPED
                       host->device staging cliff (diagnostic only)
  4. streamed        — non-resident batches through the DataPipeline
                       (torchmpi_tpu/data): host-generated, background-
                       staged, never pre-staged — the "input" artifact
                       section perf_gate's input series gate

MFU: FLOPs come from XLA's own cost model on the compiled engine step
(``lowered.compile().cost_analysis()``) when available, else the analytic
conv count (``resnet.flops_per_image``, MAC=2 FLOPs, x3 for fwd+bwd).
Peak is looked up from the device kind.

Prints exactly ONE JSON line on stdout; diagnostics go to stderr and feed
BASELINE.md.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def peak_flops(device):
    """bf16 peak FLOP/s of ``device`` — the ONE table lives in
    ``obs/numerics.py`` (the live tmpi_mfu_estimate gauge reads it too,
    so a new TPU generation lands in both MFU numbers together)."""
    from torchmpi_tpu.obs.numerics import device_peak_flops

    return device_peak_flops(device)


def lower_step_once(step, args):
    """ONE (lowered, compiled) pair shared by the cost/memory probes below
    — lowering only traces (no execution, no donation), and a second
    compile of an 8B-width step would cost minutes for nothing."""
    try:
        lowered = step.lower(*args)
    except Exception as e:  # noqa: BLE001 — backend-dependent surface
        log(f"bench: lower() for cost/memory analysis failed ({e!r})")
        return None, None
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001
        log(f"bench: AOT compile for cost/memory analysis failed ({e!r})")
        compiled = None
    return lowered, compiled


def xla_step_flops(lowered, compiled):
    """FLOPs of one engine step per XLA's cost model, if exposed."""
    for obj in (lowered, compiled):
        if obj is None:
            continue
        try:
            ca = obj.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            f = float(ca.get("flops", 0.0))
            if f > 0:
                return f
        except Exception:  # noqa: BLE001
            continue
    return None


def peak_hbm_bytes(compiled):
    """Peak device memory for the reported config — the reference tester's
    per-benchmark GPU memory column (torchmpi/tester.lua:46,104-109).

    Primary: the PJRT allocator's own high-water mark (shared probe:
    ``utils.tester.peak_hbm_bytes``, available on TPU backends).
    Fallback: the compiled step's static memory analysis (argument +
    output + temp) — what the compiler reserved, which on ahead-of-time-
    planned backends is the peak to within the allocator's slack.
    """
    from torchmpi_tpu.utils import tester

    hbm = tester.peak_hbm_bytes()
    if hbm is not None:
        return hbm, "memory_stats"
    try:
        m = compiled.memory_analysis()
        total = int(m.argument_size_in_bytes + m.output_size_in_bytes
                    + m.temp_size_in_bytes)
        if total > 0:
            return total, "memory_analysis"
    except Exception:  # noqa: BLE001
        pass
    return None, None


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import resnet
    from torchmpi_tpu.runtime.communicator import RANK_AXIS
    from torchmpi_tpu.utils.data import DevicePrefetchIterator

    devices = jax.devices()
    n_dev = len(devices)
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind, "count": n_dev}
    log(f"bench: {device}")
    if device["platform"] != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX reports {device}")

    mpi.start()
    comm = mpi.stack.current()
    mesh = comm.mesh()

    # Space-to-depth stem measured faster on v5e (BASELINE.md);
    # BENCH_S2D=0 reverts to the plain 7x7/2 stem.
    s2d = bool(int(os.environ.get("BENCH_S2D", "1")))
    cfg = resnet.config(depth=50, n_classes=1000, stem_space_to_depth=s2d)
    dtype = jnp.bfloat16
    image = 224
    batch_candidates = [128, 64]   # 128 probed fastest on v5e (BASELINE.md)
    n1, n2 = 10, 40                # long slope window: chip throughput
                                   # varies run to run; average more
    if os.environ.get("BENCH_BATCH"):
        batch_candidates = [int(os.environ["BENCH_BATCH"])]

    loss_fn = resnet.make_loss_fn(cfg)
    rng = np.random.default_rng(0)
    cast = np.dtype("bfloat16")

    def make_batches(per_chip_batch, n_batches):
        """Rank-major (p, b, ...) host batches, images pre-cast to the
        compute dtype (halves staging bytes on bf16)."""
        x = rng.standard_normal((n_dev, per_chip_batch, image, image, 3),
                                dtype=np.float32)
        x = x.astype(cast)
        y = rng.integers(0, cfg.n_classes, (n_dev, per_chip_batch)).astype(np.int32)
        return [(x, y)] * n_batches

    def run_engine(engine, params, batches):
        """One train() call; returns (seconds, final state), fenced by a
        device->host loss read."""
        t0 = time.perf_counter()
        state = engine.train(params, batches)
        float(state["loss"])
        return time.perf_counter() - t0, state

    chosen = None
    for per_chip in batch_candidates:
        engine = AllReduceSGDEngine(loss_fn, lr=0.1, comm=comm, mode="compiled")
        params, _ = resnet.init(jax.random.PRNGKey(0), cfg, dtype=dtype)
        try:
            t0 = time.perf_counter()
            resident = list(DevicePrefetchIterator(
                make_batches(per_chip, 1), mesh, depth=1))
            _, state = run_engine(engine, params, resident * n1)
            log(f"bench: batch/chip={per_chip} compiled+warmed in "
                f"{time.perf_counter()-t0:.1f}s loss={float(state['loss']):.4f}")
            chosen = (per_chip, engine, state["params"], resident)
            break
        except Exception as e:  # OOM probe: fall through to smaller batch
            msg = str(e)
            if "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "OOM" in msg:
                log(f"bench: batch/chip={per_chip} OOM, trying smaller")
                continue
            raise
    assert chosen is not None, "all batch sizes OOMed"
    per_chip, engine, params, resident = chosen
    global_batch = per_chip * n_dev

    # --- (1)+(2) INTERLEAVED slope windows: engine vs bare compiled step ---
    # Throughput drifted a few percent minute to minute on the rounds 2-5
    # set-up (2729 vs 2817 img/s same-day in round 4; not re-measured on
    # today's machine), so a single window aliased weather into the round
    # gate.  Three interleaved (engine, compute) window pairs,
    # medians per mode: drift hits both modes alike and the median drops
    # the odd window out — the headline compares ACROSS rounds, not just
    # within a session.
    import statistics

    sh = NamedSharding(mesh, P(RANK_AXIS))
    xd, yd = resident[0][0].array, resident[0][1].array
    step = engine._compiled_step

    def bare(p, o, n):
        t0 = time.perf_counter()
        for _ in range(n):
            p, o, loss = step(p, o, xd, yd)
        float(loss)
        return time.perf_counter() - t0, p, o

    import jax.numpy as _jnp

    n_windows = 3
    eng_s, cmp_s = [], []
    p_bare = o_bare = None
    for w in range(n_windows):
        ta, state = run_engine(engine, params, resident * n1)
        params = state["params"]
        tb, state = run_engine(engine, params, resident * n2)
        params = state["params"]
        eng_s.append((tb - ta) / (n2 - n1))
        if p_bare is None:
            # Bare path gets OWN copies: the compiled step donates its
            # (params, opt_state) args, and the engine still needs its.
            p_bare = jax.tree.map(_jnp.copy, params)
            o_bare = jax.tree.map(_jnp.copy, state["opt_state"])
        tc1, p_bare, o_bare = bare(p_bare, o_bare, n1)
        tc2, p_bare, o_bare = bare(p_bare, o_bare, n2)
        cmp_s.append((tc2 - tc1) / (n2 - n1))
    step_s = statistics.median(eng_s)
    compute_s = statistics.median(cmp_s)
    ips_engine = global_batch / step_s / n_dev
    log(f"bench: engine windows ms/step: "
        f"{[round(s * 1e3, 2) for s in eng_s]} -> median {step_s*1e3:.2f}")
    log(f"bench: compute windows ms/step: "
        f"{[round(s * 1e3, 2) for s in cmp_s]} -> median {compute_s*1e3:.2f}")

    # --- (3) engine + host batches: staging on the critical path -----------
    # ADJACENT resident/host pair (a comparator from minutes earlier would
    # alias the same drift the medians above exist to cancel).  Pinned to
    # data_pipeline=off: this cell quantifies the UNPIPED cliff (the
    # number the streamed cell below exists to kill); under the default
    # auto mode the engine would wrap these bare host batches and measure
    # the pipeline instead.
    from torchmpi_tpu.runtime import config as _config

    t_a, state = run_engine(engine, params, resident * n1)
    params = state["params"]
    prior_pipe = _config.get("data_pipeline")
    _config.set("data_pipeline", "off")
    try:
        t_host, state = run_engine(engine, params,
                                   make_batches(per_chip, n1))
    finally:
        _config.set("data_pipeline", prior_pipe)
    params = state["params"]
    host_extra = (t_host - t_a) / n1
    batch_mb = resident[0][0].array.nbytes / 1e6
    p2, o2 = p_bare, o_bare

    # --- (4) STREAMED: non-resident data through the input pipeline --------
    # The ROADMAP item-1 acceptance cell: batches are host-generated and
    # NEVER pre-staged — the DataPipeline's background threads assemble
    # and device_put them while the compiled step runs.  Two-point slope
    # like every other cell, adjacent to its own compute comparator
    # (compute_s, measured minutes ago, rides the same medians the
    # resident ratio uses — the streamed/compute ratio is what crosses
    # rounds).  Stats (bytes/step, overlap fraction) come from the
    # pipeline's own StageStats, no obs feed required.
    from torchmpi_tpu.data import DataPipeline

    def streamed(n):
        return DataPipeline(make_batches(per_chip, n), mesh)

    t_s1, state = run_engine(engine, params, streamed(n1))
    params = state["params"]
    pipe2 = streamed(n2)
    t_s2, state = run_engine(engine, params, pipe2)
    params = state["params"]
    streamed_s = (t_s2 - t_s1) / (n2 - n1)
    in_stats = pipe2.stats.snapshot()
    out_input = {
        "compute_only_ms": round(compute_s * 1e3, 3),
        "resident_ms": round(step_s * 1e3, 3),
        "streamed_ms": round(streamed_s * 1e3, 3),
        "streamed_over_compute": round(streamed_s / compute_s, 4),
        "streamed_over_resident": round(streamed_s / step_s, 4),
        "staged_bytes_per_step": in_stats["staged_bytes_per_batch"],
        "overlap_fraction": in_stats["overlap_fraction"],
        "stage_ms_mean": round(
            in_stats["stage_s"] / max(in_stats["batches"], 1) * 1e3, 3),
        "wait_ms_mean": round(
            in_stats["wait_s"] / max(in_stats["batches"], 1) * 1e3, 3),
        "unpiped_host_extra_ms": round(host_extra * 1e3, 3),
    }

    # ------------------------------------------------------------- roofline
    log(f"bench: compute-only    {global_batch/compute_s/n_dev:8.1f} img/s/chip "
        f"({compute_s*1e3:.2f} ms/step)")
    log(f"bench: engine+resident {ips_engine:8.1f} img/s/chip "
        f"({step_s*1e3:.2f} ms/step)  <- reported")
    log(f"bench: engine loop overhead vs compute-only: "
        f"{(step_s-compute_s)*1e3:+.2f} ms/step")
    log(f"bench: host staging adds {host_extra*1e3:+.2f} ms/step for "
        f"{batch_mb:.0f} MB/batch "
        f"({batch_mb/max(host_extra,1e-9)/1e3:.2f} GB/s host->device, "
        f"pipeline OFF)")
    log(f"bench: streamed (pipeline) {global_batch/streamed_s/n_dev:8.1f} "
        f"img/s/chip ({streamed_s*1e3:.2f} ms/step, "
        f"{out_input['streamed_over_compute']:.3f}x compute-only, "
        f"overlap {out_input['overlap_fraction']:.3f}, "
        f"{out_input['staged_bytes_per_step']/1e6:.1f} MB staged/step)")

    lowered, compiled = lower_step_once(step, (p2, o2, xd, yd))
    hbm, hbm_src = peak_hbm_bytes(compiled)
    if hbm is not None:
        log(f"bench: peak HBM {hbm/1e9:.3f} GB/chip ({hbm_src})")

    step_flops = xla_step_flops(lowered, compiled)
    src = "xla cost_analysis"
    if step_flops is None:
        step_flops = 3.0 * resnet.flops_per_image(cfg, image) * global_batch
        src = "analytic conv count x3"
    peak = peak_flops(devices[0])
    achieved = step_flops / step_s / n_dev
    log(f"bench: step FLOPs = {step_flops/1e9:.1f} G ({src}); "
        f"achieved {achieved/1e12:.1f} TFLOP/s/chip")
    if peak:
        log(f"bench: MFU = {achieved/peak*100:.1f}% of {peak/1e12:.0f} TFLOP/s "
            f"bf16 peak (compute-only MFU "
            f"{step_flops/compute_s/n_dev/peak*100:.1f}%)")

    # Optional profiler trace of the steady-state window (TPU_PROFILE=1),
    # with device time by scope and pass printed from it.
    if int(os.environ.get("TPU_PROFILE", "0")):
        from torchmpi_tpu.utils.profiler import load_capture, step_profile

        d = "/tmp/torchmpi_tpu_bench_trace"
        jax.profiler.start_trace(d)
        try:
            run_engine(engine, p2, resident * 6)
        finally:
            jax.profiler.stop_trace()
        log(f"bench: profiler trace written to {d}")
        try:
            for row in step_profile(load_capture(d),
                                    compiled.as_text()).table().splitlines():
                log(f"bench:   {row}")
        except Exception as e:  # noqa: BLE001 — best-effort diagnostic:
            # a corrupt/stale capture must not abort the benchmark after
            # the full chip run completed.
            log(f"bench: breakdown unavailable ({e})")

    ips_compute = global_batch / compute_s / n_dev
    out = {
        "metric": "resnet50 train throughput (AllReduceSGDEngine)",
        # Every result names the device it ran on.
        "device": device,
        # value = MEDIAN of 3 interleaved slope windows (round-5 gate
        # stability: a single window aliased run-to-run drift — 2729 vs
        # 2817 same-day in r04; the median is the cross-round comparable).
        "value": round(ips_engine, 2),
        "unit": "images/sec/chip",
        # Same-session companion numbers so cross-session variance
        # can be factored out of the round gate: the compute-only median
        # from THIS run and the engine/compute ratio (the part the engine
        # actually controls — ~1.0 means the engine adds nothing on top of
        # the chip's compute; absolute img/s moves a few percent between
        # sessions, the ratio does not).
        "compute_only": round(ips_compute, 2),
        "engine_over_compute": round(ips_engine / ips_compute, 4),
        "window_spread": round((max(eng_s) - min(eng_s)) / step_s, 4),
        # Streaming input plane (ROADMAP item 1; gated by perf_gate's
        # input_overlap_fraction + streamed_over_compute series).
        "input": out_input,
        # Peak device bytes for this config (reference tester.lua:46's GPU
        # memory column): allocator high-water mark where the backend
        # exposes one, compiled-step memory analysis otherwise.
        "peak_hbm_bytes": hbm,
    }
    if hbm_src:
        out["peak_hbm_source"] = hbm_src
    if peak:
        out["mfu_engine"] = round(achieved / peak, 4)
        out["mfu_compute"] = round(step_flops / compute_s / n_dev / peak, 4)

    # Observability satellite (new keys, old keys unchanged): a short
    # obs-instrumented run AFTER the timed windows (which ran with
    # obs_trace at its configured value — off by default, so the default
    # headline numbers are untouched) contributes a per-phase span
    # breakdown of the engine step, plus a metrics-registry snapshot of
    # the native counters.
    try:
        from torchmpi_tpu.obs import metrics as obs_metrics
        from torchmpi_tpu.obs import native as obs_native
        from torchmpi_tpu.obs import tracer as obs_tracer
        from torchmpi_tpu.runtime import config as _config

        prior_trace = bool(_config.get("obs_trace"))
        _config.set("obs_trace", True)
        obs_native.apply_config()
        try:
            obs_tracer.drain()
            run_engine(engine, params, resident * 4)
            spans = obs_tracer.drain()
        finally:
            _config.set("obs_trace", prior_trace)
            obs_native.apply_config()
        out["phase_breakdown"] = obs_tracer.breakdown(spans)
        obs_metrics.registry.scrape_native()
        out["obs_metrics"] = obs_metrics.registry.snapshot()
    except Exception as e:  # noqa: BLE001 — the headline must still print
        log(f"bench: obs instrumentation unavailable ({e!r})")

    # Autotune satellite (new keys, old keys unchanged; AFTER the timed
    # windows, which ran at the configured autotune_mode — off by default,
    # so the headline numbers are untouched): a quick measured pass +
    # autotuned-vs-default A/B through the real resolve() path, and the
    # ready-order-vs-barrier async drain A/B with its overlap fractions —
    # the sections scripts/perf_gate.py gates as their own series.
    try:
        from torchmpi_tpu.collectives import autotune

        out["autotune"] = autotune.bench_section(comm=comm)
        out["autotune"]["overlap"] = autotune.overlap_ab()
        log(f"bench: autotune A/B ratio "
            f"{out['autotune']['ab']['ratio']} "
            f"(default {out['autotune']['ab']['default_ms']} ms, "
            f"autotuned {out['autotune']['ab']['autotuned_ms']} ms); "
            f"overlap ready {out['autotune']['overlap']['ready']} vs "
            f"barrier {out['autotune']['overlap']['barrier']}")
    except Exception as e:  # noqa: BLE001 — the headline must still print
        log(f"bench: autotune section unavailable ({e!r})")

    # MFU satellite (new keys, old keys unchanged): the roofline number
    # sat ~34% compute-bound across rounds 3-5 (no longer reproducible: the
    # records and their set-up are gone), so this cell attacks
    # the compute side directly.  (a) bf16-coverage A/B: the SAME model
    # stepped with all-bf16 vs all-f32 params+batches on the bare
    # compiled path — if the f32 arm is ~2x slower the MXU already runs
    # bf16 everywhere and the 34% is layout/memory-bound, not dtype
    # coverage; a ratio near 1x means f32 ops are leaking into the hot
    # path and coverage IS the next lever.  (b) the tester.mfu_sweep
    # (batch, remat) grid over the llama train step with its
    # mfu_estimate column (numerics.probe_step_flops) — where the knee
    # sits tells the next round which batch/remat cell to pin.
    try:
        import dataclasses

        from torchmpi_tpu.utils import tester as _tester

        out_mfu = {}
        try:
            def coverage_arm(dt):
                eng2 = AllReduceSGDEngine(loss_fn, lr=0.1, comm=comm,
                                          mode="compiled")
                p0, _ = resnet.init(jax.random.PRNGKey(0), cfg, dtype=dt)
                x = rng.standard_normal(
                    (n_dev, per_chip, image, image, 3), dtype=np.float32)
                if dt == jnp.bfloat16:
                    x = x.astype(np.dtype("bfloat16"))
                y = rng.integers(0, cfg.n_classes,
                                 (n_dev, per_chip)).astype(np.int32)
                res = list(DevicePrefetchIterator([(x, y)], mesh, depth=1))
                _, st = run_engine(eng2, p0, res * n1)  # compile + warm
                ta, st = run_engine(eng2, st["params"], res * n1)
                tb, _ = run_engine(eng2, st["params"], res * n2)
                return (tb - ta) / (n2 - n1)

            bf16_s = coverage_arm(jnp.bfloat16)
            f32_s = coverage_arm(jnp.float32)
            cell = {
                "bf16_ms": round(bf16_s * 1e3, 3),
                "f32_ms": round(f32_s * 1e3, 3),
                # >1 means bf16 is pulling its weight end to end.
                "f32_over_bf16": round(f32_s / bf16_s, 4),
            }
            if step_flops is not None and peak:
                cell["bf16_mfu"] = round(
                    step_flops / bf16_s / n_dev / peak, 4)
            out_mfu["coverage_ab"] = cell
            log(f"bench: bf16-coverage A/B {cell['bf16_ms']} ms bf16 vs "
                f"{cell['f32_ms']} ms f32 "
                f"(f32/bf16 {cell['f32_over_bf16']}x)")
        except Exception as e:  # noqa: BLE001 — the sweep below still runs
            log(f"bench: bf16-coverage A/B unavailable ({e!r})")

        sweep_args = dict(batch_sizes=(8, 16), remats=("none", "dots"),
                          seq_len=128, iters=3)
        # llama's train step shards over a 'dp' axis; bench's own mesh
        # is the 1-D ring, so only forward it when the axis matches.
        mfu_mesh = mesh if "dp" in getattr(mesh, "shape", {}) else None
        rows = _tester.mfu_sweep(report=log, mesh=mfu_mesh, **sweep_args)
        out_mfu["sweep"] = [dataclasses.asdict(r) for r in rows]
        if out_mfu:
            out["mfu"] = out_mfu
    except Exception as e:  # noqa: BLE001 — the headline must still print
        log(f"bench: mfu section unavailable ({e!r})")

    # Numerics-plane satellite (new keys, old keys unchanged; AFTER the
    # timed windows, which ran at the configured numerics_mode — off by
    # default, so the headline numbers are untouched): sentinel-on vs
    # off engine step slope (warmup after each mode flip absorbs the
    # rebuild/recompile the compile key forces) and the audit's
    # digest-fold cost — the "numerics" section scripts/perf_gate.py
    # gates as numerics.sentinel_overhead_ms with an absolute band.
    try:
        from torchmpi_tpu.obs import numerics as obs_numerics

        prior_mode = str(_config.get("numerics_mode"))
        # Fresh host params: the obs satellite's instrumented run above
        # donated the previous device tree (device_put aliases a
        # replicated array, and the compiled step donates its inputs).
        params, _ = resnet.init(jax.random.PRNGKey(0), cfg, dtype=dtype)

        def numerics_slope(mode):
            nonlocal params
            _config.set("numerics_mode", mode)
            _t, st = run_engine(engine, params, resident * 2)
            params = st["params"]
            t1_, st = run_engine(engine, params, resident * n1)
            params = st["params"]
            t2_, st = run_engine(engine, params, resident * n2)
            params = st["params"]
            return (t2_ - t1_) / (n2 - n1)

        try:
            s_off = numerics_slope("off")
            s_on = numerics_slope("sentinel")
        finally:
            _config.set("numerics_mode", prior_mode)
        t0_d = time.perf_counter()
        _paths, _digs = obs_numerics.leaf_digests(params)
        obs_numerics.fold_digests(_digs)
        audit_ms = (time.perf_counter() - t0_d) * 1e3
        interval = int(_config.get("numerics_audit_interval"))
        out["numerics"] = {
            "sentinel_off_ms": round(s_off * 1e3, 3),
            "sentinel_on_ms": round(s_on * 1e3, 3),
            "sentinel_overhead_ms": round((s_on - s_off) * 1e3, 3),
            "audit_ms": round(audit_ms, 3),
            "audit_interval": interval,
            "audit_amortized_ms": round(audit_ms / max(interval, 1), 4),
        }
        log(f"bench: numerics sentinels {out['numerics']['sentinel_on_ms']}"
            f" ms/step vs {out['numerics']['sentinel_off_ms']} off "
            f"(+{out['numerics']['sentinel_overhead_ms']} ms); audit "
            f"digest {out['numerics']['audit_ms']} ms every "
            f"{interval} steps")
    except Exception as e:  # noqa: BLE001 — the headline must still print
        log(f"bench: numerics section unavailable ({e!r})")

    # Job-history-plane satellite (new keys, old keys unchanged; AFTER
    # the timed windows, which ran at the configured journal_enabled —
    # off by default, so the headline numbers are untouched): the
    # journaling-on vs off A/B around a short engine train window (the
    # hot path has no emit sites — the delta is the armed-but-idle
    # plane's cost and must sit in the noise), raw emit throughput
    # (events/s, bytes/event) and retention behaviour under a
    # small-segment burst — the "journal" section scripts/perf_gate.py
    # gates as journal.overhead_ms with the trace guard's absolute band.
    try:
        import tempfile

        from torchmpi_tpu.obs import journal as obs_journal

        jdir = tempfile.mkdtemp(prefix="tmpi_bench_journal_")
        prior_journal = bool(_config.get("journal_enabled"))
        prior_jdir = str(_config.get("journal_dir"))
        samples = {"off": [], "on": []}
        try:
            for _ in range(2):
                for label, flag in (("off", False), ("on", True)):
                    obs_journal.reset()
                    _config.set("journal_enabled", flag)
                    _config.set("journal_dir", jdir)
                    t1_, st = run_engine(engine, params, resident * n1)
                    params = st["params"]
                    t2_, st = run_engine(engine, params, resident * n2)
                    params = st["params"]
                    samples[label].append((t2_ - t1_) / (n2 - n1))
        finally:
            obs_journal.reset()
            _config.set("journal_enabled", prior_journal)
            _config.set("journal_dir", prior_jdir)
        j_off = round(min(samples["off"]) * 1e3, 3)
        j_on = round(min(samples["on"]) * 1e3, 3)
        # Write throughput + retention: the SAME burst probe the RCA
        # drill records, so the two artifact shapes feeding perf_gate's
        # journal series cannot diverge.
        _config.set("journal_enabled", True)
        _config.set("journal_dir", jdir)
        try:
            burst = obs_journal.burst_stats(jdir)
        finally:
            _config.set("journal_enabled", prior_journal)
            _config.set("journal_dir", prior_jdir)
        out["journal"] = {
            "journal_off_ms": j_off,
            "journal_on_ms": j_on,
            "overhead_ms": round(j_on - j_off, 3),
            **burst,
        }
        log(f"bench: journal on {j_on} ms/step vs {j_off} off "
            f"(+{out['journal']['overhead_ms']} ms); "
            f"{out['journal']['events_per_s']} events/s at "
            f"{out['journal']['bytes_per_event']} B/event, "
            f"{out['journal']['segments_kept']} segment(s) kept")
    except Exception as e:  # noqa: BLE001 — the headline must still print
        log(f"bench: journal section unavailable ({e!r})")

    print(json.dumps(out), flush=True)
    mpi.stop()


if __name__ == "__main__":
    main()
