"""What every runner shares: finding a cell's files by name, the device and
its peaks, the clock of the timed window, the count of compilations inside
it, warm-up, the profiler's window and the last line.

The harness is driven by data.  `BENCHMARK.json` names a cell's configuration
and traffic mix and the metrics; everything that belongs to one of them is a
file of its own under this directory, found by that name:

    configs/<config>.json    the sizes as run (names its runner)
    traffic/<mix>.json       the parameters the one generator (traffic.py) reads
    runners/<runner>.py      run(ctx) -> observations
    reference/<config>.py    the plain reference and its tolerances
    flops/<config>.py        required operations and bytes from shapes
    layers/<metric>.py       read(obs) -> value or None, one per-layer metric

A later PR adds a cell, a configuration or a metric as new files and new
entries in `BENCHMARK.json`, and edits nothing that is here.
"""

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The event JAX records round every compilation of a new program, whether
# the backend compiles it or the persistent cache supplies it.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class BenchmarkError(Exception):
    """The run cannot produce a result; the process exits non-zero and
    prints no result line."""


def load_json(*parts, base=HERE):
    path = os.path.join(base, *parts)
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchmarkError(f"no such file: {path}") from None


def load_module(kind, name, base=HERE):
    """The module `<base>/<kind>/<name>.py`, by path: names hold `-` and `.`
    and are no Python identifiers."""
    path = os.path.join(base, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(spec, workload):
    """(cell, configuration entry) of `workload` in BENCHMARK.json."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def metrics_of(spec, group, workload):
    """The metrics of `group` ("end_to_end" or "per_layer") that this cell
    reports: all that list no `workloads`, and those that list this one."""
    return [m for m in spec[group]
            if workload in m.get("workloads", [workload])]


def rehearsed(data):
    """A configuration or traffic file with its `rehearse` sizes laid over
    it: the CPU rehearsal scales sizes and changes nothing else."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    out.update(data.get("rehearse", {}))
    return out


def device_info(chips, rehearse):
    """The device as JAX reports it, and its row of peaks.json.  No TPU,
    fewer chips than the cell asks for, or a kind the table lacks: an error,
    never a default.  A rehearsal takes what it finds and has no peaks."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if rehearse:
        if len(devices) < chips:
            raise BenchmarkError(
                f"the rehearsal of a {chips}-chip cell needs {chips} devices "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count={chips})")
        info["count"] = chips
        return info, None
    if info["platform"] != "tpu":
        raise BenchmarkError(f"no accelerator: JAX reports platform "
                             f"{info['platform']!r}, not 'tpu'")
    if len(devices) != chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s), JAX "
                             f"reports {len(devices)}")
    peaks = load_json("peaks.json")
    if info["kind"] not in peaks:
        raise BenchmarkError(f"device kind {info['kind']!r} is not in "
                             f"peaks.json ({sorted(peaks)}): add its published "
                             f"peaks with their source")
    return info, peaks[info["kind"]]


def memory_peak_bytes(devices, program_bytes):
    """Peak bytes on the fullest chip.  On this machine the allocator's
    `peak_bytes_in_use` leaves out what a compiled program takes for its
    temporaries while it runs (PR 21, and the probe of PR 22: 0.64 GB
    reported beside 4.52 GB of temporaries), so the larger of that and the
    step program's own plan (arguments + outputs - aliased + temporaries,
    from `memory_analysis()` of the executable that ran) is reported."""
    seen = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in devices)
    return int(max(seen, program_bytes or 0))


def program_bytes(compiled):
    """Bytes one device holds while `compiled` runs, by the compiler's plan."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


class Context:
    """What a runner is given, and where it leaves what it observed."""

    def __init__(self, *, cell, cfg, traffic, seed, seconds, trace, t_start):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = cell["chips"]
        self.t_start = t_start
        self.setup_s = None
        self.counters = {}          # program counters and host-clock readings
        self.trace_dir = None
        self.spans = []             # (name, start_ns, end_ns), time.time_ns()
        self._tracing = False
        self._compiles = 0
        self._misses = 0
        self._in_window = False
        self.compiles_in_window = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    # ------------------------------------------------------- files by name

    def module(self, kind):
        """This cell's configuration's file of `kind` (reference, flops)."""
        return load_module(kind, self.cell["config"])

    # --------------------------------------------------------- compilations

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self._compiles += 1
            if self._in_window:
                self.compiles_in_window += 1

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self._misses += 1

    @contextlib.contextmanager
    def compiling(self, what):
        """Host clock round the first call of a shape: adds to the counter
        `compile_s` and logs how many programs it made and how many of them
        the persistent cache did not have."""
        t0, c0, m0 = time.perf_counter(), self._compiles, self._misses
        yield
        dt = time.perf_counter() - t0
        self.counters["compile_s"] = self.counters.get("compile_s", 0.0) + dt
        self.mark(f"{what}: first call {dt:.2f} s, {self._compiles - c0} "
                  f"program(s), {self._misses - m0} not in the cache")

    # ----------------------------------------------------------- the window

    @contextlib.contextmanager
    def window(self):
        """The timed window.  Set-up ends where it starts; a compilation
        inside it is counted and makes the run incorrect."""
        self.setup_s = time.perf_counter() - self.t_start
        self._in_window = True
        try:
            yield
        finally:
            self._in_window = False

    def mark(self, what):
        """A line of the set-up's timeline on standard error."""
        log(f"[{time.perf_counter() - self.t_start:7.2f} s] {what}")

    # ------------------------------------------------------------- profiler

    @contextlib.contextmanager
    def span(self, name):
        """A host span round a call of the runner into the program, kept in
        memory on the clock the capture's `profile_start_time` is given in
        (`time.time_ns()`), so that an idle gap of the device can be named by
        what the host was doing.  The profiler's own host tracer is off: one
        streamed ResNet step is 330,000 host events of the runtime's transfer
        threads, 14 MB of trace, and recording them slowed a traced run to 47
        steps in 45 s (PR 22)."""
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))

    def start_trace(self):
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        with self.span("bench.start_trace"):
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def stop_trace(self):
        """Close the profiler's window; does nothing where none is open."""
        import jax

        if self._tracing:
            self._tracing = False
            with self.span("bench.stop_trace"):
                jax.profiler.stop_trace()

    def drop_trace(self):
        if self.trace_dir:
            keep = os.environ.get("BENCHMARK_KEEP_TRACE")
            if keep:        # for a look by hand: see README.md
                import trace_reduce

                path = trace_reduce.newest_xplane(self.trace_dir)
                if path and os.path.getsize(path) < 48 * 2**20:
                    os.makedirs(keep, exist_ok=True)
                    shutil.copy(path, os.path.join(
                        keep, f"{self.cell['name']}.xplane.pb"))
            shutil.rmtree(self.trace_dir, ignore_errors=True)


WARM_UP_MAX_STEPS = 24


def warmed_up(step_times):
    """The warm-up rule: at least three fenced steps, and the last two agree
    within 5%."""
    t = step_times
    return len(t) >= 3 and abs(t[-1] - t[-2]) <= 0.05 * max(t[-1], t[-2])


def median_step_s(done):
    """Seconds a step, as the median of the intervals between the moments
    the host saw consecutive steps finished.  For a runner that keeps a step
    queued behind the one that runs: the device is never idle, so an interval
    is one step on the device, and the median over the window does not move
    when a few steps are slow (a host that stalls, a chip that slows for a
    moment), where steps over seconds does.  None with fewer than two
    stamps."""
    if len(done) < 2:
        return None
    gaps = sorted(b - a for a, b in zip(done, done[1:]))
    mid = len(gaps) // 2
    return gaps[mid] if len(gaps) % 2 else 0.5 * (gaps[mid - 1] + gaps[mid])


def mfu_percent(flops_per_sample, samples_per_s_chip, peaks):
    return 100.0 * flops_per_sample * samples_per_s_chip / peaks["bf16_flops_per_s"]


def finite(x):
    return isinstance(x, (int, float)) and math.isfinite(x)


def log(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
