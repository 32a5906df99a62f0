"""From the profiler's trace (`.xplane.pb`) to numbers: the busy union and
idle share of each device, device time by category of operation, how much of
the collectives' time no compute hides, and the longest idle gaps named by
what the host was doing.  Every PR computes these the same way, here.

What a trace of this machine looks like (jax 0.9.0, TPU v5 lite, read by hand
in PR 22; see README.md):

* one plane `/device:TPU:<n>` a chip.  Its line `XLA Ops` has one event for
  every executed HLO instruction of the TensorCore, named by the
  instruction's text (`%fusion.12 = bf16[...] fusion(...), kind=kOutput,
  ...`).  A `while` (the layer scan, the chunked loss) is an event that spans
  the events of its body, so durations are summed as SELF time: an event's
  duration less what its children cover.  `Async XLA Ops` has one event from
  the start to the done of each asynchronous instruction (copies, slices,
  collectives); they run beside the TensorCore's and are not "busy".
  `XLA Modules` has one event for every execution of a compiled program,
  `Steps` the same grouped by the profiler.
  The profiler may start in the middle of a step: the first `XLA Modules`
  event is then shorter than the others and its first operations are
  missing.  So everything is taken over WHOLE steps: from the start of the
  second execution of the step program to the end of the last.
* the host's planes are not recorded (`harness.Context.span` says why).  The
  device events count nanoseconds from the start of the capture, which the
  plane `Task Environment` gives as `profile_start_time` in nanoseconds of
  the host's `time.time_ns()`; the runners' own spans, kept on that clock,
  name the idle gaps.

`load()` turns the file into plain lists, `reduce()` works on those lists
alone, so the tests feed it a small recorded trace kept as JSON.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
ENVIRONMENT_PLANE = "Task Environment"
MIN_GAP_NS = 20_000         # shorter pauses between two ops are the device's own


# ------------------------------------------------------------------- loading

def load(path):
    """{"profile_start_ns": int, "devices": {plane: {line: [(name, start_ns,
    duration_ns)]}}} of one `.xplane.pb`; starts count from the capture's."""
    from jax.profiler import ProfileData

    out = {"profile_start_ns": 0, "devices": {}}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                line.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
        elif plane.name == ENVIRONMENT_PLANE:
            out["profile_start_ns"] = int(dict(plane.stats).get(
                "profile_start_time", 0))
    return out


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_dir(trace_dir, host_spans):
    """`reduce(load(...))` of the newest capture under `trace_dir`, or None
    where there is no capture or no operation ran on a device."""
    path = newest_xplane(trace_dir)
    return reduce(load(path), host_spans) if path else None


# ------------------------------------------------------------- categorising

def categorize(name):
    """Category of an `XLA Ops` event.  A copy of
    `torchmpi_tpu/utils/profiler.py:_categorize` (sound; listed in PERF.md
    for deletion there), with the Mosaic kernels and the plain matrix
    products told apart."""
    m = re.match(r"%?([a-zA-Z_\-]+)", name)
    base = m.group(1) if m else name[:24]
    if is_mosaic(name):     # named after the jax scope that called the kernel
        return "Mosaic kernel"
    if base.startswith("convolution"):
        return "convolution"
    if base in ("copy-start", "copy-done", "slice-start", "slice-done",
                "dynamic-slice-start", "dynamic-slice-done"):
        return "async DMA (copy/slice)"
    if is_collective(name):
        return "collective: " + base
    if base.startswith(("select-and-scatter", "select_and_scatter")):
        return "select-and-scatter (pool bwd)"
    if base.startswith("reduce-window"):
        return "reduce-window (pool fwd)"
    if "fusion" in base:
        kind = base.replace("_fusion", "").replace("fusion", "").strip("_.")
        if not kind:
            k = re.search(r"kind=k(\w+)", name)
            kind = k.group(1).lower() if k else "generic"
        return f"fusion: {kind}"
    return base


_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute", "collective-broadcast")


def is_collective(name):
    base = name.lstrip("%")
    return base.startswith(_COLLECTIVES)


def is_mosaic(name):
    return "tpu_custom_call" in name or "mosaic" in name.lower()


def is_matmul_or_conv(name):
    """Convolutions, and fusions whose root is a convolution or a dot (the
    TPU compiler names both `convolution`; `kind=kOutput` marks a fusion
    built round one)."""
    return categorize(name) in ("convolution", "fusion: output")


# ----------------------------------------------------------------- intervals

def union(intervals):
    """Sorted, disjoint [(start, end)] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of union `a` that union `b` does not cover (both disjoint
    and sorted)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, self_ns)] of events that may nest: an event's duration less
    the time its children cover."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []          # stack of [name, end, self_ns]
    for name, start, dur in order:
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out += [(name, self_ns) for name, _, self_ns in stack]
    return out


# ----------------------------------------------------------------- reduction

def whole_steps(modules):
    """(start, end, steps) of the whole steps on a device: the executions
    of the program that took most of the time, the first left out."""
    by_program = {}
    for name, s, d in modules:
        by_program.setdefault(name.split("(")[0], []).append((s, s + d))
    if not by_program:
        return None
    runs = sorted(max(by_program.values(),
                      key=lambda r: sum(e - s for s, e in r)))[1:]
    return (runs[0][0], runs[-1][1], len(runs)) if runs else None


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(s + d, t1) - max(s, t0))
            for n, s, d in events if s < t1 and s + d > t0]


def _reduce_device(lines, host_spans):
    steps = whole_steps(lines.get(MODULES_LINE, []))
    if steps is None:
        return None
    t0, t1, n_steps = steps
    ops = _clip(lines.get(OPS_LINE, []), t0, t1)
    if not ops:
        return None
    busy = union((s, s + d) for _, s, d in ops)
    by_category = {}
    matmul_ns = mosaic_ns = 0
    for name, ns in self_times(ops):
        c = categorize(name)
        by_category[c] = by_category.get(c, 0) + ns
        if is_matmul_or_conv(name):
            matmul_ns += ns
        if is_mosaic(name):
            mosaic_ns += ns
    # Collectives: the synchronous ones are ops of the TensorCore's line,
    # the asynchronous ones last from their start to their done.
    coll = union([(s, s + d) for n, s, d in ops if is_collective(n)]
                 + [(s, s + d) for n, s, d in
                    _clip(lines.get(ASYNC_LINE, []), t0, t1)
                    if is_collective(n)])
    compute = union((s, s + d) for n, s, d in ops if not is_collective(n)
                    and not n.lstrip("%").startswith(("while", "conditional")))
    # Idle gaps inside the window, each named by the host span that covers
    # most of it.
    gaps = {}
    for s, e in subtract([(t0, t1)], busy):
        if e - s < MIN_GAP_NS:
            continue
        best, best_ns = "no span of the runner", 0
        for name, hs, he in host_spans:
            ns = min(e, he) - max(s, hs)
            if ns > best_ns:
                best, best_ns = name, ns
        gaps[best] = gaps.get(best, 0) + (e - s)
    return {"window_ns": t1 - t0, "busy_ns": total(busy), "steps": n_steps,
            "by_category": by_category, "matmul_conv_ns": matmul_ns,
            "mosaic_ns": mosaic_ns, "collective_ns": total(coll),
            "collective_exposed_ns": total(subtract(coll, compute)),
            "gaps": gaps, "op_self_ns": sum(by_category.values())}


def reduce(trace, host_spans=()):
    """Means over the devices that ran a whole step.  Seconds, not shares:
    the readers under `layers/` divide.  `trace`: what `load()` returns.
    `host_spans`: (name, start_ns, end_ns) on the `time.time_ns()` clock."""
    origin = trace["profile_start_ns"]
    host_spans = [(n, s - origin, e - origin) for n, s, e in host_spans]
    per_device = [r for r in (_reduce_device(lines, host_spans)
                              for lines in trace["devices"].values()) if r]
    if not per_device:
        return None
    n = len(per_device)
    mean = lambda key: sum(r[key] for r in per_device) / n / 1e9
    merged = lambda key: _merge([r[key] for r in per_device], n)
    categories, gaps = merged("by_category"), merged("gaps")
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": n,
        "steps": max(r["steps"] for r in per_device),
        "window_s": mean("window_ns"), "busy_s": mean("busy_ns"),
        "op_self_s": mean("op_self_ns"),
        "matmul_conv_s": mean("matmul_conv_ns"), "mosaic_s": mean("mosaic_ns"),
        "collective_s": mean("collective_ns"),
        "collective_exposed_s": mean("collective_exposed_ns"),
        "categories": categories,
        "breakdown": {"device_ops": top(categories), "idle_gaps": top(gaps)},
    }


def _merge(dicts, n):
    out = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v / n / 1e9
    return out


# ------------------------------------------------------------ a look by hand

def describe(path, top=30):
    """Every plane and line of a capture with its event count and span, and
    of each device's `XLA Ops` and `Async XLA Ops` the events that took most
    time, by full name: what to read before trusting `reduce()`."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            row = {"line": line.name, "events": len(events)}
            if events:
                row["first_start_ns"] = events[0][1]
                row["last_end_ns"] = max(s + d for _, s, d in events)
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    by_name = {}
                    for name, ns in self_times(events):
                        n, t = by_name.get(name, (0, 0))
                        by_name[name] = (n + 1, t + ns)
                    row["top_by_self_ns"] = [
                        [name[:240], n, ns] for name, (n, ns) in
                        sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]]
            lines.append(row)
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    import json
    import sys

    for arg in sys.argv[1:]:
        print(json.dumps({"file": arg, "planes": describe(arg),
                          "reduced": reduce(load(arg))}, indent=1))
