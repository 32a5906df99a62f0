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

import collections
import os
import re
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

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

    def step(self, t: int, state: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        if t == self.start_step and not self._active:
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._t0_ns = time.monotonic_ns()
        elif t >= self.end_step and self._active:
            self.stop(state)

    def _find_run_dir(self) -> str:
        newest = _newest_capture(self.logdir)
        return os.path.dirname(newest) if newest else self.logdir

    def stop(self, state: Optional[Dict[str, Any]] = None) -> None:
        """Close the window; with the engine's ``state``, after the steps in
        flight (the capture then ends on a whole step), and keep the text of
        its compiled step beside the capture (``engine.last_run.device``)."""
        if self._active:
            jax.block_until_ready(state and state.get("loss"))
            jax.profiler.stop_trace()
            self._active = False
            self.trace_path = self._find_run_dir()
            if state is not None and state["engine"].mode == "compiled":
                with open(os.path.join(self.trace_path, "step.hlo.txt"),
                          "w") as fh:
                    fh.write(state["engine"].step_text(state))
                state["run"].profiler = self
            # The window registers as an observability span so the merged
            # timeline (torchmpi_tpu/obs/export.py) shows which steps the
            # capture covers, and where: the offset.  No-op with obs_trace off.
            from ..obs import tracer as _tracer

            if self._t0_ns is not None and _tracer.enabled():
                _tracer.record("profiler.window", self._t0_ns,
                               time.monotonic_ns(),
                               _tracer.current_correlation(),
                               trace_path=self.trace_path,
                               start_step=self.start_step,
                               end_step=self.end_step,
                               epoch_offset_ns=(time.time_ns()
                                                - time.monotonic_ns()))
            self._t0_ns = None

    def profile(self) -> "StepProfile":
        """The capture this window took, joined to the text kept beside it."""
        with open(os.path.join(self.trace_path, "step.hlo.txt")) as fh:
            return step_profile(load_capture(self.trace_path), fh.read())


def profiler_hooks(profiler: StepWindowProfiler) -> Dict[str, Callable]:
    """Engine hooks installing the window (reference: the engine's NVPROF
    hook windowing, sgdengine.lua:38-63).  Compose with other hook dicts —
    e.g. ``obs.tracer.hooks()`` — via :func:`compose_hooks`."""
    return {
        "on_update": lambda state: profiler.step(state["t"], state),
        "on_end": lambda state: profiler.stop(state),
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


# The reader of a device capture (docs/observability.md): the one place in the
# package that opens an ``.xplane.pb``.  Offline: nothing calls it unasked.

OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
MIN_GAP_NS = 20_000     # shorter pauses between two ops are the device's own
PASSES = ("forward", "backward", "recomputed", "optimizer", "other")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_EVENT = re.compile(r"%?([\w.\-]+)")        # the instruction of an event
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


class Label(NamedTuple):
    """What the text of an executable says of one instruction."""
    scope: Optional[str]                # innermost of ``models.SCOPES``
    pass_: str = "other"                # one of ``PASSES``
    kernel: Optional[str] = None        # a Mosaic kernel's ``name=``
    collective: Optional[str] = None    # its opcode, where it is one
    mixed: bool = False                 # a fusion of two passes, a tied vote


def _newest_capture(path: str) -> Optional[str]:
    import glob

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_capture(path: str) -> Dict[str, Any]:
    """``{"profile_start_ns": int, "devices": {plane: {line: [(name,
    start_ns, duration_ns)]}}}``: the ``/device:TPU:<n>`` planes of an
    ``.xplane.pb`` (the newest under a directory) as plain lists; starts
    count from ``profile_start_ns``, the capture's on ``time.time_ns()``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = _newest_capture(path)
    if not path:
        raise ValueError("no .xplane.pb there: did the trace block run?")
    out: Dict[str, Any] = {"profile_start_ns": 0, "devices": {}}
    for plane in ProfileData.from_file(path).planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            out["devices"][plane.name] = {
                line.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events] for line in plane.lines}
        elif plane.name == "Task Environment":
            out["profile_start_ns"] = int(
                dict(plane.stats).get("profile_start_time", 0))
    return out


def instruction_labels(hlo_text: str, scopes=None) -> Dict[str, Label]:
    """``{instruction: Label}`` from ``compiled.as_text()``, off each
    ``op_name``, a path such as ``jit(step)/transpose(jvp(jvp()))/checkpoint/
    rematted_computation/attn/mla/dot_general``.
    ``scope``: the INNERMOST component among ``models.SCOPES``, by position
    and no order of priority (``attn/mla`` is ``mla``; of two branches in a
    layer each keeps its own).  A fusion without one takes what most
    instructions of its computation carry; ``mixed``: that vote was tied, or
    two passes sit inside.
    ``pass_``: ``jax``'s own marks and nothing else: ``optimizer`` under that
    scope, else ``recomputed`` on ``rematted_computation``, else
    ``backward`` on ``transpose(``, else ``forward`` on ``jvp(``, else
    ``other``.  A hand-written rule's work belongs to the pass it RUNS in
    (``_chunked_nll``'s ``dh`` and ``dW``: forward; what ``flash_bwd``,
    ``_held_swiglu_bwd`` and ``kda_bwd`` form of the forward again:
    backward), so ``recomputed`` is what a remat policy replays, no more.
    ``kernel``: a ``tpu_custom_call``'s ``name=``.  ``collective``: the
    opcode (the NAME says nothing: ``all_to_all.7``).  A text without the
    program's names (from a compile cache older than they) raises
    ``ValueError``: never zeros."""
    if scopes is None:
        from ..models import SCOPES as scopes
    own, calls, inside, where = {}, {}, {}, None    # inside: the votes
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+) ", line)
            where = m.group(1) if m and line.rstrip().endswith("{") else None
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        op = _OP_NAME.search(line)
        parts = re.findall(r"[^/();]+", op.group(1)) if op else []
        scope = next((p for p in reversed(parts) if p in scopes), None)
        pass_ = ("optimizer" if "optimizer" in parts else
                 "recomputed" if "rematted_computation" in parts else
                 "backward" if "transpose" in parts else
                 "forward" if "jvp" in parts else "other")
        kernel = None
        if opcode == "custom-call" and '"tpu_custom_call"' in line:
            kernel = (parts[parts.index("pallas_call") - 1]
                      if "pallas_call" in parts[1:]
                      else re.sub(r"\.\d+$", "", name))
        own[name] = Label(scope, pass_, kernel, opcode if opcode.replace(
            "-start", "") in COLLECTIVES else None)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if where and (scope or pass_ != "other"):
            inside.setdefault(where, []).append((scope, pass_))
    if not any(lab.scope for lab in own.values()):
        raise ValueError("no instruction carries a name of the program: an "
                         "executable from a compile cache older than the "
                         "names, or another program's text")
    count = collections.Counter
    for name, lab in own.items():
        seen = inside.get(calls.get(name, ""))
        if not seen:
            continue
        top = count(s for s, _ in seen if s).most_common(2)
        scope = lab.scope or (top[0][0] if top else None)
        passes = count(p for s, p in seen if s == scope).most_common(1)
        own[name] = lab._replace(
            scope=scope,
            pass_=(passes[0][0] if passes and lab.pass_ == "other"
                   else lab.pass_),
            mixed=len({p for _, p in seen if p != "other"}) > 1 or (
                lab.scope is None and len(top) > 1 and top[0][1] == top[1][1]))
    return own


def _self_times(events) -> List[Tuple[str, int]]:
    """``[(name, self_ns)]``: a ``while`` (a layer scan, a chunked head)
    spans the events of its body, so an event counts less its children."""
    out, stack = [], []                     # stack of [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    return out + [(name, self_ns) for name, _, self_ns in stack]


def _whole_steps(modules) -> Optional[Tuple[int, int, int]]:
    """``(start, end, steps)``: the executions of the program that took most
    of the time, the first left out (the profiler may start inside it)."""
    by_program: Dict[str, list] = {}
    for name, s, d in modules:
        by_program.setdefault(name.split("(")[0], []).append((s, s + d))
    runs = sorted(max(by_program.values(), default=[],
                      key=lambda r: sum(e - s for s, e in r)))[1:]
    return (runs[0][0], runs[-1][1], len(runs)) if runs else None


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(s + d, t1) - max(s, t0))
            for n, s, d in events if s < t1 and s + d > t0]


class StepProfile:
    """Device time of a captured step program, ms a step over whole steps.
    ``rows``: ``{(chip, scope, pass_, kernel): ms}``, SELF time of the chip's
    ``XLA Ops``, what carries no name under ``"unnamed"``; :meth:`by` sums
    them, a scope's passes to the scope and all to ``op_self_ms``.
    ``chips``: ``{chip: {...}}``: ``steps``; ``t0_ns``, ``t1_ns`` (the window;
    an ``_ns`` plus ``profile_start_ns`` is on ``time.time_ns()``);
    ``window_ms``, ``busy_ms`` (the ops' union), ``op_self_ms``, ``mixed_ms``
    (in ``mixed`` instructions); ``idle_share``; ``unnamed`` (its ten
    longest, ``[(name, ms)]``); ``idle`` (pauses of ``MIN_GAP_NS`` or more,
    ``[(start_ns, end_ns)]``).  ``collectives``: :func:`step_profile`."""

    def __init__(self, profile_start_ns, rows, chips, collectives):
        self.profile_start_ns = profile_start_ns
        self.rows, self.chips, self.collectives = rows, chips, collectives
        for chip, c in chips.items():       # the two identities
            for of in ("scope", "pass_"):
                assert abs(sum(self.by(of, chip=chip).values())
                           - c["op_self_ms"]) < 1e-6

    def by(self, *fields, chip=None) -> Dict[Any, float]:
        """``rows`` summed by ``"scope"``, ``"pass_"``, ``"kernel"`` (several:
        keyed by the tuple), of one chip or the mean over the chips."""
        at = [("chip", "scope", "pass_", "kernel").index(f) for f in fields]
        out: Dict[Any, float] = collections.defaultdict(float)
        for key, ms in self.rows.items():
            if chip in (None, key[0]):
                k = tuple(key[i] for i in at)
                out[k if len(k) > 1 else k[0]] += (
                    ms if chip else ms / len(self.chips))
        return dict(out)

    def gaps(self, stamps, epoch_offset_ns: int) -> List[Tuple]:
        """``[(chip, start_ns, ns, step, phase)]``: each idle gap, its start
        on ``time.time_ns()``, named by the phase of the engine's run record
        (``step_stamps``, ``epoch_offset_ns``) that covers most of it, a
        phase by the stamp that ENDS it; ``None`` outside the record."""
        names = ("t_batch", "t_entry", "t_staged", "t_dispatched", "t_sync",
                 "t_synced", "t_done", "t_stepped", "t_end")
        marks = sorted(
            (t + epoch_offset_ns - self.profile_start_ns, row[0], name)
            for row in stamps for name, t in zip(
                names, (row[1], *row[6:12], row[2], row[3])))
        out = []
        for chip, c in self.chips.items():
            for s, e in c["idle"]:
                most = max(((min(e, b[0]) - max(s, a[0]), b[1], b[2])
                            for a, b in zip(marks, marks[1:])
                            if b[0] > s and a[0] < e), default=(0, None, None))
                out.append((chip, self.profile_start_ns + s, e - s) + most[1:])
        return out

    def summary(self) -> Dict[str, Any]:
        """The means over the chips, plain data: ``engine.last_run.device``."""
        n = len(self.chips)
        out = {key: sum(c[key] for c in self.chips.values()) / n
               for key in ("steps", "window_ms", "busy_ms", "idle_share",
                           "op_self_ms", "mixed_ms")}
        coll: Dict[str, Any] = collections.defaultdict(collections.Counter)
        for (_, scope, _), v in self.collectives.items():
            coll[scope].update({k: x / n for k, x in v.items()})
        return dict(out, chips=n, by_pass=self.by("pass_"),
                    by_scope=self.by("scope"), by_kernel=self.by("kernel"),
                    collectives={k: dict(v) for k, v in coll.items()})

    def table(self) -> str:
        """What the CLI prints."""
        s, cells, cols = self.summary(), self.by("scope", "pass_"), PASSES + (
            "all",)
        out = ["# " + ", ".join(f"{k} {s[k]:.6g}" for k in (
            "chips", "steps", "window_ms", "idle_share", "mixed_ms")),
            "%-14s" % "scope" + "".join("%11s" % p for p in cols)]
        for scope, ms in sorted(s["by_scope"].items(), key=lambda kv: -kv[1]
                                ) + [("all", s["op_self_ms"])]:
            of = dict(s["by_pass"] if scope == "all" else
                      {p: cells.get((scope, p), 0.0) for p in PASSES}, all=ms)
            out.append("%-14s" % scope + "".join(
                "%11.3f" % of.get(p, 0.0) for p in cols))
        out += [f"{ms:11.3f}  kernel {k} ({p})" for (k, p), ms in sorted(
            self.by("kernel", "pass_").items(), key=lambda kv: -kv[1]) if k]
        chip, c = next(iter(self.chips.items()))
        out += [f"{ms:11.3f}  unnamed on {chip}: {name[:150]}"
                for name, ms in c["unnamed"]]
        out += [f"{v['wait_ms']:11.3f} wait {v['transfer_ms']:9.3f} transfer "
                f"{v['late_ms']:9.3f} late {v['early_ms']:9.3f} early "
                f"{v['calls']:6.1f} calls  {k[1]} {k[2]} on {k[0]}"
                for k, v in sorted(self.collectives.items(),
                                   key=lambda kv: kv[0][1:] + kv[0])]
        return "\n".join(out)


def step_profile(capture: Dict[str, Any], hlo_text: str,
                 scopes=None) -> StepProfile:
    """Join a capture (:func:`load_capture`) to its executable's text over
    whole steps (from the second execution of the program that took most of
    the time, each chip by its ``XLA Modules``), by SELF time, so a scanned
    stack is counted once; ``ValueError`` without a whole step.
    ``collectives``: ``{(chip, scope, kind): {"calls", "late_ms", "wait_ms",
    "transfer_ms", "early_ms"}}`` a step: each synchronous collective of
    ``XLA Ops`` and asynchronous one of ``Async XLA Ops`` is matched across
    the chips by instruction and occurrence from the window's END (chips may
    enter a capture a step apart); ``late_ms`` is a chip's arrival after the
    first, ``wait_ms`` runs from its arrival to the last chip's, the rest is
    ``transfer_ms``.  The chips of a host are read in one session, which
    converts each chip's counter to its clock; what two conversions differ
    by reads as arrival.  Chips leave a collective together, so
    ``early_ms``, a chip's leaving before the last, bounds that error: a
    wait no larger says nothing."""
    labels = instruction_labels(hlo_text, scopes)
    none = Label(None)
    rows, chips, met = collections.defaultdict(float), {}, {}   # met: the
    #                    (start, end) a chip of each collective's occurrence
    for chip, lines in sorted(capture["devices"].items()):
        steps = _whole_steps(lines.get(MODULES_LINE, []))
        ops = steps and _clip(lines.get(OPS_LINE, []), steps[0], steps[1])
        if not ops:
            continue
        t0, t1, n = steps
        per_ms = 1e-6 / n
        unnamed: collections.Counter = collections.Counter()
        mixed_ns = self_ns = 0
        for name, ns in _self_times(ops):
            lab = labels.get(_EVENT.match(name).group(1), none)
            rows[chip, lab.scope or "unnamed", lab.pass_, lab.kernel] += (
                ns * per_ms)
            self_ns += ns
            mixed_ns += ns * lab.mixed
            if lab.scope is None:
                unnamed[name.split(", metadata=")[0]] += ns
        busy_ns, idle, end = 0, [], t0
        for s, e in sorted((s, s + d) for _, s, d in ops) + [(t1, t1)]:
            if s - end >= MIN_GAP_NS:
                idle.append((end, s))
            busy_ns += max(0, e - max(s, end))
            end = max(end, e)
        chips[chip] = {
            "steps": n, "t0_ns": t0, "t1_ns": t1,
            "window_ms": (t1 - t0) * per_ms, "busy_ms": busy_ns * per_ms,
            "idle_share": 1.0 - busy_ns / (t1 - t0),
            "op_self_ms": self_ns * per_ms, "mixed_ms": mixed_ns * per_ms,
            "unnamed": [(k, ns * per_ms) for k, ns in unnamed.most_common(10)],
            "idle": idle}
        seen: collections.Counter = collections.Counter()
        starts = _clip(lines.get(ASYNC_LINE, []), t0, t1)
        for line, started in ((ops, False), (starts, True)):
            for name, s, d in sorted(line, key=lambda ev: -ev[1]):
                instr = _EVENT.match(name).group(1)
                kind = labels.get(instr, none).collective
                if kind and kind.endswith("-start") == started:
                    met.setdefault((instr, seen[instr]), {})[chip] = (s, s + d)
                    seen[instr] += 1
    if not chips:
        raise ValueError(
            "no whole step on a '/device:TPU:<n>' plane: a CPU capture holds "
            "host threads alone, a window under two executions no whole one")
    collectives: Dict[Tuple, Dict[str, float]] = {}
    per_ms = 1e-6 / min(c["steps"] for c in chips.values())
    for (instr, _), at in met.items():
        if len(chips) < 2 or len(at) < len(chips):
            continue
        first, last = (f(s for s, _ in at.values()) for f in (min, max))
        gone, lab = max(e for _, e in at.values()), labels[instr]
        for chip, (s, e) in at.items():
            into = collectives.setdefault(
                (chip, lab.scope or "unnamed",
                 lab.collective.replace("-start", "")), dict.fromkeys(
                     ("calls", "late_ms", "wait_ms", "transfer_ms",
                      "early_ms"), 0.0))
            into["calls"] += 1e6 * per_ms
            into["late_ms"] += (s - first) * per_ms
            into["wait_ms"] += (min(last, e) - s) * per_ms
            into["transfer_ms"] += (e - min(last, e)) * per_ms
            into["early_ms"] += (gone - e) * per_ms
    return StepProfile(capture["profile_start_ns"], dict(rows), chips,
                       collectives)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="device time of a captured step")
    ap.add_argument("capture", help="an .xplane.pb, or a directory of them")
    ap.add_argument("--hlo", help="the executable's text; else the one kept "
                    "beside the capture, <cell>.hlo.txt or <dir>/step.hlo.txt")
    args = ap.parse_args()
    with open(args.hlo or (os.path.join(args.capture, "step.hlo.txt")
                           if os.path.isdir(args.capture) else
                           args.capture.replace(".xplane.pb", ".hlo.txt"))
              ) as fh:
        print(step_profile(load_capture(args.capture), fh.read()).table())
