"""Profiling: steady-state step-window traces.

The reference brackets steps 3..8 of training with cudaProfilerStart/Stop
under nvprof so traces cover a steady-state window, skipping warmup
(reference: torchmpi/engine/sgdengine.lua:38-63, scripts/wrap.sh:60-67).
TPU-native equivalent: ``jax.profiler`` start/stop around the same window,
producing a Perfetto/TensorBoard trace (SURVEY.md §5.1).

Also ports the bench-timer discipline: warmup-skip timing
(tester.lua:61-126) and the async dispatch-latency assertion (<50us in the
reference, collectives_all.lua:192-199) as a reusable check.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable, Dict, Optional

import jax


class StepWindowProfiler:
    """Trace steps [start_step, end_step) of a training loop.

    Call :meth:`step` once per iteration (or install via
    :func:`profiler_hooks` into the engine).  Idempotent after the window.
    """

    def __init__(self, logdir: str = "/tmp/torchmpi_tpu_trace",
                 start_step: int = 3, end_step: int = 8,
                 enabled: Optional[bool] = None):
        self.logdir = logdir
        self.start_step = start_step
        self.end_step = end_step
        # Env-gated like NVPROF=1 (reference: wrap.sh:60-67).
        self.enabled = (bool(int(os.environ.get("TPU_PROFILE", "0")))
                        if enabled is None else enabled)
        self._active = False
        self._t0_ns: Optional[int] = None
        self.trace_path: Optional[str] = None

    def step(self, t: int) -> None:
        if not self.enabled:
            return
        if t == self.start_step and not self._active:
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._t0_ns = time.monotonic_ns()
        elif t >= self.end_step and self._active:
            self.stop()

    def _find_run_dir(self) -> str:
        """The run directory this capture actually wrote.  jax.profiler
        dumps under ``<logdir>/plugins/profile/<run_timestamp>/`` — the
        logdir root holds every capture ever taken there, so pointing
        trace_path at it made "the trace I just took" ambiguous.  Newest
        run dir wins; a capture layout we don't recognize falls back to
        the logdir."""
        import glob

        runs = [d for d in glob.glob(
            os.path.join(self.logdir, "plugins", "profile", "*"))
            if os.path.isdir(d)]
        return max(runs, key=os.path.getmtime) if runs else self.logdir

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.trace_path = self._find_run_dir()
            # The window registers as an observability span so the merged
            # timeline (torchmpi_tpu/obs/export.py) shows exactly which
            # steps the device capture covers.  No-op with obs_trace off.
            from ..obs import tracer as _tracer

            if self._t0_ns is not None and _tracer.enabled():
                _tracer.record("profiler.window", self._t0_ns,
                               time.monotonic_ns(),
                               _tracer.current_correlation(),
                               trace_path=self.trace_path,
                               start_step=self.start_step,
                               end_step=self.end_step)
            self._t0_ns = None


def profiler_hooks(profiler: StepWindowProfiler) -> Dict[str, Callable]:
    """Engine hooks installing the window (reference: the engine's NVPROF
    hook windowing, sgdengine.lua:38-63).  Compose with other hook dicts —
    e.g. ``obs.tracer.hooks()`` — via :func:`compose_hooks`."""
    return {
        "on_update": lambda state: profiler.step(state["t"]),
        "on_end": lambda state: profiler.stop(),
    }


def compose_hooks(*hook_dicts: Dict[str, Callable]) -> Dict[str, Callable]:
    """Merge engine hook dicts: for each hook name, every contributor runs
    in argument order.  The engine's hook table holds ONE callable per
    name, so installing both the profiler window and the obs tracer marks
    previously meant hand-writing a wrapper — this is that wrapper."""
    merged: Dict[str, list] = {}
    for hooks in hook_dicts:
        for name, fn in hooks.items():
            merged.setdefault(name, []).append(fn)

    def _chain(fns):
        def run(state):
            for fn in fns:
                fn(state)
        return run

    return {name: _chain(fns) for name, fns in merged.items()}


@contextlib.contextmanager
def trace(logdir: str = "/tmp/torchmpi_tpu_trace"):
    """Explicit trace block for benchmarks."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


class Timer:
    """Warmup-skipping wall timer (reference: tester.lua:61-126 protocol:
    discard warmup runs, average the timed runs, barrier-fenced by the
    caller)."""

    def __init__(self, warmup: int = 10, runs: int = 10):
        self.warmup = warmup
        self.runs = runs

    def measure(self, fn: Callable[[], Any]) -> float:
        """Mean seconds per call of ``fn`` (which must block on completion)."""
        for _ in range(self.warmup):
            fn()
        t0 = time.perf_counter()
        for _ in range(self.runs):
            fn()
        return (time.perf_counter() - t0) / self.runs


def assert_dispatch_latency(fn: Callable[[], Any], budget_s: float = 5e-5,
                            tries: int = 20) -> float:
    """Best observed async-dispatch latency of ``fn`` (which must NOT block);
    warns past ``budget_s`` — the reference's <50us launch assertion
    (collectives_all.lua:192-199).  Returns the best latency."""
    best = float("inf")
    for _ in range(tries):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    if best > budget_s:
        import warnings

        warnings.warn(f"async dispatch latency {best*1e6:.1f}us exceeds "
                      f"budget {budget_s*1e6:.0f}us")
    return best


# --------------------------------------------------------------------------
# trace analysis: per-op roofline attribution from a captured trace
# (the tool behind BASELINE.md's ResNet/ViT breakdowns — the TPU-native
# analogue of reading an nvprof table, reference: scripts/wrap.sh NVPROF
# runs whose output the reference's docs quote)
# --------------------------------------------------------------------------

def _categorize(name: str) -> str:
    """Heuristic op category for an XLA-Ops timeline event."""
    import re

    m = re.match(r"%([a-zA-Z_\-]+)", name)
    base = m.group(1) if m else name[:24]
    if base.startswith("convolution"):
        return "convolution"
    if base in ("copy-start", "copy-done", "slice-start", "slice-done",
                "dynamic-slice-start", "dynamic-slice-done"):
        return "async DMA (copy/slice)"
    if base.startswith("all-reduce") or base.startswith("all-gather") \
            or base.startswith("all-to-all") or base.startswith("reduce-scatter") \
            or base.startswith("collective-permute"):
        return "collective: " + base.split(".")[0].lstrip("%")
    if base.startswith("select-and-scatter"):
        return "select-and-scatter (pool bwd)"
    if base.startswith("reduce-window"):
        return "reduce-window (pool fwd)"
    if "fusion" in base:
        kind = base.replace("_fusion", "").replace("fusion", "").strip("_.")
        return f"fusion: {kind}" if kind else "fusion: generic"
    return base


def op_breakdown(trace_dir: str, top: int = 25):
    """Aggregate the XLA-Ops timeline of a captured trace into per-category
    and per-op durations, normalized per step.

    ``trace_dir`` is the logdir a :class:`StepWindowProfiler` /
    :func:`trace` block wrote.  Steps are auto-detected from the most
    frequent top-level ``jit_*`` module event.  Returns a dict::

        {"steps": int, "total_ms_per_step": float,
         "categories": [(name, ms_per_step, share), ...],
         "top_ops": [(name, ms_per_step), ...]}

    Only device (TPU) traces carry the per-op timeline; a CPU trace raises
    a ``ValueError`` naming what was missing rather than returning zeros.
    """
    import collections
    import glob

    from jax.profiler import ProfileData

    files = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir!r} — did the "
                         f"trace block run?")
    # Newest capture wins (benchmark logdirs accumulate runs).
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    per_op: collections.Counter = collections.Counter()
    # Step count = executions of the dominant jit_* module on ONE timeline
    # line (module events echo on several lines; summing across lines
    # over-counts).
    op_planes = 0    # device planes contributing an XLA-Ops line: under
    #                  SPMD each runs the same program, so totals average
    #                  over planes rather than summing device-count-fold.
    # Module accounting spans ALL lines first: the dominant jit_* module is
    # chosen by GLOBAL duration (an auxiliary jit that owns its own line
    # would otherwise win there and inflate the step count), then steps =
    # its max per-line event count (events echo on several lines).
    mod_dur: dict = {}
    mod_cnt_per_line: dict = {}
    for plane in pd.planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                op_planes += 1
                for ev in line.events:
                    per_op[ev.name] += ev.duration_ns
            else:
                cnt: collections.Counter = collections.Counter()
                for ev in line.events:
                    if ev.name.startswith("jit_"):
                        key = ev.name.split("(")[0]
                        mod_dur[key] = mod_dur.get(key, 0) + ev.duration_ns
                        cnt[key] += 1
                for key, c in cnt.items():
                    mod_cnt_per_line[key] = max(
                        mod_cnt_per_line.get(key, 0), c)
    if not per_op:
        raise ValueError(
            "trace has no 'XLA Ops' timeline (CPU traces record only host "
            "threads) — capture on a TPU backend")
    steps = (mod_cnt_per_line[max(mod_dur, key=mod_dur.get)]
             if mod_dur else 1)
    norm = steps * max(op_planes, 1)
    cats: collections.Counter = collections.Counter()
    for name, ns in per_op.items():
        cats[_categorize(name)] += ns
    total = sum(per_op.values())
    return {
        "steps": steps,
        "device_planes": op_planes,
        "total_ms_per_step": total / 1e6 / norm,
        "categories": [(c, ns / 1e6 / norm, ns / total)
                       for c, ns in cats.most_common()],
        "top_ops": [(n.split(" = ")[0], ns / 1e6 / norm)
                    for n, ns in per_op.most_common(top)],
    }


def print_breakdown(trace_dir: str, top: int = 15) -> None:
    b = op_breakdown(trace_dir, top=top)
    print(f"# {b['steps']} steps, {b['total_ms_per_step']:.2f} ms/step "
          f"attributed on the XLA-Ops timeline")
    for c, ms, share in b["categories"]:
        if share >= 0.002:
            print(f"{ms:9.2f} ms/step {100*share:5.1f}%  {c}")
    print("# top ops:")
    for n, ms in b["top_ops"][:top]:
        print(f"{ms:9.2f} ms/step  {n[:100]}")


if __name__ == "__main__":   # python -m torchmpi_tpu.utils.profiler <dir>
    import sys

    print_breakdown(sys.argv[1] if len(sys.argv) > 1
                    else "/tmp/torchmpi_tpu_trace")
