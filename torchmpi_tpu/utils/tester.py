"""Benchmark/correctness harness for collectives — the tester equivalent.

The reference's harness (torchmpi/tester.lua + test/collectives_all.lua)
sweeps tensor sizes 2^8..2^upper with random jitter, skips warmup runs,
checks correctness on the first run of each config, and reports GB/s through
a per-collective communication-volume model (reference: tester.lua:41-47
sweep+jitter, :61-126 timing/report; collectives_all.lua:313-318 ring
allreduce volume ``2*n*(p-1)/p``).

One driver doubles as correctness test and benchmark, selected by flag —
testing idea #3 of SURVEY.md §4.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..collectives import eager
from ..runtime.communicator import Communicator


# Per-collective communication volume models in *bytes on the bus*, as a
# function of (elements, element_size, p).  These mirror the reference's
# models so GB/s numbers are comparable as fraction-of-link-bandwidth:
#   allreduce   2*n*(p-1)/p      (ring: reduce-scatter + allgather;
#                                 collectives_all.lua:313-318)
#   broadcast   n                (pipelined; :261-264)
#   reduce      n                (:215-218)
#   sendreceive n                (one hop; :363-367)
#   allgather   n*(p-1)          (:453-457)
#   reduce_scatter n*(p-1)/p     (half the allreduce ring)
VOLUME_MODELS: Dict[str, Callable[[int, int, int], float]] = {
    "allreduce": lambda n, es, p: 2.0 * n * es * (p - 1) / p,
    "broadcast": lambda n, es, p: float(n * es),
    "reduce": lambda n, es, p: float(n * es),
    "sendreceive": lambda n, es, p: float(n * es),
    "allgather": lambda n, es, p: float(n * es * (p - 1)),
    "reduce_scatter": lambda n, es, p: float(n * es * (p - 1) / p),
    "alltoall": lambda n, es, p: float(n * es * (p - 1) / p),
}


@dataclasses.dataclass
class BenchResult:
    collective: str
    elements: int
    dtype: str
    p: int
    mean_seconds: float
    min_seconds: float
    bus_gbs: float          # volume model / mean time
    checked: bool
    # Peak device bytes observed DURING this config's runs (the reference
    # tester's per-benchmark GPU memory column,
    # torchmpi/tester.lua:46,104-109): the allocator high-water mark where
    # the backend exposes ``memory_stats`` (TPU) — and only when THIS
    # config raised it (the mark is process-lifetime-monotonic, so a
    # config running below an earlier config's peak reports None rather
    # than inheriting that peak).  None also on backends without
    # allocator stats (XLA-CPU), where eager dispatch has no single
    # compiled step to cost-analyze.
    peak_hbm_bytes: Optional[int] = None


def peak_hbm_bytes() -> Optional[int]:
    """Allocator high-water mark of local device 0, where exposed."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend-dependent surface
        return None
    if not stats:
        return None
    for key in ("peak_bytes_in_use", "bytes_in_use"):
        if key in stats:
            return int(stats[key])
    return None


def _expected(collective: str, comm: Communicator, n: int) -> Optional[np.ndarray]:
    """Algebraic expectation for fill=rank inputs (reference:
    collectives_all.lua:52-54,298-303: fill=rank => allreduce = p(p-1)/2)."""
    p = comm.size
    if collective == "allreduce":
        return np.full((p, n), p * (p - 1) / 2.0, np.float64)
    if collective == "broadcast":
        return np.zeros((p, n), np.float64)  # root 0's fill
    if collective == "reduce":
        out = np.tile(np.arange(p, dtype=np.float64)[:, None], (1, n))
        out[0] = p * (p - 1) / 2.0
        return out
    if collective == "sendreceive":
        out = np.tile(np.arange(p, dtype=np.float64)[:, None], (1, n))
        out[(p - 1) if p > 1 else 0] = 0.0
        return out
    return None  # allgather/reduce_scatter shapes differ; checked separately


# The collectives the pallas ring namespace implements (public: benchmark
# CLIs validate their --collectives list against this).
PALLAS_COLLECTIVES = ("allreduce", "reduce_scatter", "allgather")

# Per-collective call arguments for the sweep's fixed topology (root 0;
# sendreceive 0 -> last rank, reference: collectives_all.lua:363-367).
_CALL_ARGS: Dict[str, Callable[[Communicator], dict]] = {
    "broadcast": lambda comm: {"root": 0},
    "reduce": lambda comm: {"root": 0},
    "sendreceive": lambda comm: {
        "src": 0, "dst": comm.size - 1 if comm.size > 1 else 0},
}


def run_collective(collective: str, comm: Communicator, x: jax.Array,
                   impl: str = "xla"):
    """Dispatch through the runtime selector (collectives/selector.py):
    ``impl`` pins a namespace at the head of the preference order via
    ``resolve(prefer=...)``, so the sweep exercises exactly the machinery
    the nn/engine layer uses rather than a private if-chain.

    Note the pallas namespace keeps its reference-mirroring small-message
    fallback (collectives_cuda.cpp:641-648): to force rings at every sweep
    size, set ``config.set("small_allreduce_size_gpu", 0)`` first (the
    bench CLI does)."""
    from ..collectives import selector

    if impl not in ("xla", "pallas"):
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    if impl == "pallas" and collective not in PALLAS_COLLECTIVES:
        raise ValueError(
            f"impl='pallas' supports {PALLAS_COLLECTIVES}, not {collective!r}")
    if collective not in VOLUME_MODELS:
        raise ValueError(f"unknown collective {collective!r}")
    fn = selector.resolve(collective, prefer=impl)
    return fn(comm, x, **_CALL_ARGS.get(collective, lambda c: {})(comm))


def check_collective(collective: str, comm: Communicator, n: int,
                     impl: str = "xla") -> None:
    """First-run correctness with rank-dependent fills (reference:
    tester 'check on first run', collectives_all.lua per-collective checks)."""
    p = comm.size
    x = eager.fill_by_rank(comm, (n,), dtype=jnp.float32)
    out = eager.to_numpy(run_collective(collective, comm, x,
                                        impl=impl)).astype(np.float64)
    exp = _expected(collective, comm, n)
    if exp is not None:
        np.testing.assert_allclose(out, exp, rtol=1e-5)
        return
    if collective == "allgather":
        for viewer in range(p):
            for r in range(p):
                np.testing.assert_allclose(out[viewer, r], r)
    elif collective == "reduce_scatter":
        np.testing.assert_allclose(out, np.tile(
            np.full((n // p,), p * (p - 1) / 2.0), (p, 1)))
    elif collective == "alltoall":
        # fill=rank: rank r's chunk j lands as rank j's chunk r, so every
        # rank's output is values 0..p-1 each repeated n/p times.
        exp_row = np.repeat(np.arange(p, dtype=np.float64), n // p)
        np.testing.assert_allclose(out, np.tile(exp_row, (p, 1)))
    else:  # a collective without a check must not bench "checked" green
        raise ValueError(f"no correctness check for {collective!r}")


def _fence(out, mode: str):
    """Completion fence for timing.  ``"block"`` = block_until_ready;
    ``"value"`` = read one element to host, which fences even where
    block_until_ready does not (it did not on the rounds 2-5 set-up; see
    BASELINE.md measurement protocol)."""
    if mode == "value":
        # Slice on device BEFORE the host read: one element crosses the
        # wire, not the whole (possibly tens-of-MB) shard.
        shard = out.addressable_shards[0].data
        np.asarray(shard[(0,) * shard.ndim])
    elif mode == "block":
        jax.block_until_ready(out)
    else:
        raise ValueError(f"fence must be 'block' or 'value', got {mode!r}")


def run_one_config(
    collective: str,
    comm: Communicator,
    elements: int,
    dtype=jnp.float32,
    warmup: int = 10,
    iters: int = 10,
    check: bool = True,
    jitter: bool = True,
    seed: int = 0,
    fence: str = "block",
    impl: str = "xla",
) -> BenchResult:
    """Benchmark one (collective, size) config — reference:
    tester.runOneConfig (tester.lua:61-126): warmup skip, barrier-fenced
    timing, GB/s from the volume model.

    ``jitter`` adds a random <=128-element offset to the size so results
    aren't tuned to powers of two (reference: collectives_all.lua:26,43-47).
    ``fence="value"`` uses a device->host element read instead of
    block_until_ready (the rounds 2-5 protocol, BASELINE.md).
    """
    rng = np.random.RandomState(seed + elements)
    n = int(elements + (rng.randint(0, 128) if jitter else 0))
    p = comm.size
    if collective in ("reduce_scatter", "alltoall"):
        n = max(p, (n // p) * p)  # divisibility
    # High-water mark before this config touches the device: the
    # allocator's peak is process-lifetime-monotonic, so only an INCREASE
    # during this config is attributable to it (see BenchResult).
    hbm_before = peak_hbm_bytes()
    if check:
        check_collective(collective, comm, n, impl=impl)

    x = eager.fill_by_rank(comm, (n,), dtype=dtype)
    # warmup (compile + steady-state; reference: tester.lua:79-86)
    for _ in range(max(warmup, 1)):
        out = run_collective(collective, comm, x, impl=impl)
    _fence(out, fence)

    times: List[float] = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = run_collective(collective, comm, x, impl=impl)
        _fence(out, fence)
        times.append(time.perf_counter() - t0)

    es = np.dtype(dtype).itemsize if dtype != jnp.bfloat16 else 2
    volume = VOLUME_MODELS[collective](n, es, p)
    mean_t = float(np.mean(times))
    hbm_after = peak_hbm_bytes()
    hbm = (hbm_after if hbm_after is not None
           and (hbm_before is None or hbm_after > hbm_before) else None)
    return BenchResult(
        collective=collective,
        elements=n,
        dtype=np.dtype(dtype).name if dtype != jnp.bfloat16 else "bfloat16",
        p=p,
        mean_seconds=mean_t,
        min_seconds=float(np.min(times)),
        bus_gbs=volume / mean_t / 1e9,
        checked=check,
        peak_hbm_bytes=hbm,
    )


@dataclasses.dataclass
class MFUResult:
    """One row of :func:`mfu_sweep` — the compute-side twin of
    :class:`BenchResult`.  ``mfu_estimate`` is achieved FLOP/s per chip
    over bf16 peak (None off-TPU: an MFU against an unknown peak is
    noise, ``numerics.device_peak_flops``'s contract); ``step_flops`` is
    XLA's own cost model via ``numerics.probe_step_flops`` and is
    available on CPU hosts too, so the sweep still ranks configs by
    flops-per-second where no peak exists."""
    batch: int
    seq_len: int
    remat: str
    mean_seconds: float
    min_seconds: float
    step_flops: Optional[float]
    flops_per_s: Optional[float]       # step_flops / mean_seconds
    mfu_estimate: Optional[float]      # flops_per_s / chips / bf16 peak
    peak_hbm_bytes: Optional[int] = None


def mfu_sweep(
    batch_sizes: Sequence[int] = (2, 4, 8),
    remats: Sequence[str] = ("none", "dots"),
    seq_len: int = 32,
    warmup: int = 1,
    iters: int = 3,
    mesh=None,
    cfg=None,
    report: Optional[Callable[[str], None]] = print,
) -> List["MFUResult"]:
    """The compute-side MFU attack: sweep a llama training step over
    (batch, remat) and record an ``mfu_estimate`` column per config —
    rounds 3-5 kept reporting MFU stuck ~34% compute-bound (round 5, no
    longer reproducible), and this
    sweep is the instrument that says WHICH batch/remat cell moves it
    (remat trades recompute FLOPs for HBM; a bigger batch amortizes the
    non-matmul overhead).  FLOPs come from XLA's analytical cost model
    (``numerics.probe_step_flops`` — one re-trace, no execution), the
    peak from ``numerics.device_peak_flops``.
    """
    import jax

    from ..models import llama
    from ..obs import numerics as _numerics
    from ..parallel.mesh import make_mesh

    cfg = cfg or llama.tiny()
    if mesh is None:
        mesh = make_mesh({"dp": -1})
    n_dev = int(np.prod(list(mesh.shape.values())))
    peak = _numerics.device_peak_flops()
    results: List[MFUResult] = []
    for remat in remats:
        step = llama.make_train_step(cfg, mesh, lr=0.1, remat=remat)
        for b in batch_sizes:
            # dp-sharded batches must divide the dp axis.
            b_eff = max(n_dev, (b // n_dev) * n_dev)
            params = llama.init(jax.random.PRNGKey(0), cfg)
            tokens = jnp.zeros((b_eff, seq_len), jnp.int32)
            targets = jnp.zeros((b_eff, seq_len), jnp.int32)
            jitted = jax.jit(
                lambda p, t, y, _s=step: _s(p, None, t, y))
            flops = _numerics.probe_step_flops(
                jitted, (params, tokens, targets))
            hbm_before = peak_hbm_bytes()
            out = jitted(params, tokens, targets)
            for _ in range(max(warmup, 1) - 1):
                out = jitted(params, tokens, targets)
            jax.block_until_ready(out)
            times: List[float] = []
            for _ in range(iters):
                t0 = time.perf_counter()
                out = jitted(params, tokens, targets)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
            mean_t = float(np.mean(times))
            fps = (flops / mean_t) if flops else None
            mfu = (fps / n_dev / peak) if (fps and peak) else None
            hbm_after = peak_hbm_bytes()
            hbm = (hbm_after if hbm_after is not None
                   and (hbm_before is None or hbm_after > hbm_before)
                   else None)
            r = MFUResult(
                batch=b_eff, seq_len=seq_len, remat=remat,
                mean_seconds=mean_t, min_seconds=float(np.min(times)),
                step_flops=flops, flops_per_s=fps, mfu_estimate=mfu,
                peak_hbm_bytes=hbm)
            results.append(r)
            if report:
                mfu_s = "     n/a" if mfu is None else f"{mfu:8.4f}"
                fps_s = ("      n/a" if fps is None
                         else f"{fps / 1e12:9.4f}")
                report(f"mfu b={b_eff:<4} L={seq_len:<4} remat={remat:<5} "
                       f"t={mean_t * 1e3:9.2f}ms tflops={fps_s} "
                       f"mfu={mfu_s}")
    return results


def sweep(
    comm: Communicator,
    collectives: Sequence[str] = ("allreduce", "broadcast", "allgather"),
    min_pow: int = 8,
    max_pow: int = 23,
    dtype=jnp.float32,
    warmup: int = 10,
    iters: int = 10,
    check_first: bool = True,
    report: Optional[Callable[[str], None]] = print,
    fence: str = "block",
    impl: str = "xla",
) -> List[BenchResult]:
    """Size sweep 2^min_pow..2^max_pow (reference protocol:
    collectives_all.lua:554-598 parametrized matrix)."""
    results: List[BenchResult] = []
    for coll in collectives:
        first = True
        for po in range(min_pow, max_pow + 1):
            r = run_one_config(coll, comm, 1 << po, dtype=dtype, warmup=warmup,
                               iters=iters, check=check_first and first,
                               fence=fence, impl=impl)
            first = False
            results.append(r)
            if report:
                mem = ("" if r.peak_hbm_bytes is None
                       else f" hbm={r.peak_hbm_bytes/1e6:8.1f} MB")
                report(f"{coll:>14} n=2^{po:<2} ({r.elements:>8}) p={r.p} "
                       f"t={r.mean_seconds*1e6:9.1f}us bus={r.bus_gbs:8.3f} "
                       f"GB/s{mem}")
    return results
