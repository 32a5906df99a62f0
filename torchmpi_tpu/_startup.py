"""The start-up account: what this process spent before its first step, by
phase, kept by the program itself.  One :class:`Account` a process
(:data:`ACCOUNT`, reached as ``mpi.startup()``), as ``engine/sgdengine.py``
keeps one ``RunRecord`` a ``train()`` call: always on, no knob, independent
of ``obs_trace`` and the metrics feed; plain Python, a few dict operations
for every program the process makes.

This module imports the standard library alone, so that the package's first
statement can stamp through it before ``jax`` is imported.

Every stamp is ``time.monotonic_ns()``, the clock of ``obs/tracer.py`` and of
the run record; a stamp plus ``epoch_offset_ns`` is on the clock of
``time.time_ns()``, which a profiler capture's ``profile_start_time`` is
given in.  JAX reports its spans on ``time.time()``; they are moved onto the
same clock as they arrive.  The two questions asked from outside,
:meth:`Account.summary`'s ``until_ns`` and :meth:`Account.rows`, take and
give stamps on the capture's clock.

* ``t_import``, ``t_imported``: the first and the last statement of
  ``torchmpi_tpu/__init__.py``; ``jax_preloaded``: whether ``jax`` was in
  ``sys.modules`` at the first (under the benchmark's harness it is, and
  ``import_s`` is the package's own modules).
* ``starts``, ``stops``: the stamps of every ``mpi.start()`` and
  ``mpi.stop()`` that returned, written by ``runtime/lifecycle.py``, the
  newest :data:`CALLS_KEPT` of each (a benchmark's runner, a test suite and
  an elastic job start the runtime more than once a process); ``start`` and
  ``stop`` are the newest (``None`` before the first).  A start: ``t_enter``; ``t_group`` (the process
  group is up: ``jax.distributed.initialize``, where the deployment has
  one); ``t_backend`` (the first ``jax.devices()`` has answered;
  ``backend_was_up`` says whether a backend was initialised before it, in
  which case it cost nothing here); ``t_communicators`` (world communicator
  and the per-host split); ``t_selector`` (collective selector configured,
  the runtime is up: the ``runtime.start`` span ends here); ``t_return``
  (the planes started after that: ``obs.serve``, journal, history).
  A stop: ``t_enter``; ``t_down`` (drained and torn down: the
  ``runtime.stop`` span ends here); ``t_return`` (history, obsdump and the
  endpoint closed).
* ``programs``, the newest :data:`ROWS_KEPT`: a :class:`Row` for every
  program the process traces, lowers, or compiles or loads, on any thread
  (but for the rows under :data:`ROW_FLOOR_NS`, which are summed alone).
* ``sums``, which no ring forgets: ``trace_s``, ``lower_s``,
  ``backend_compile_s`` (seconds of the rows' OWN time, see :class:`Row`,
  so that the three add up to time spent and a jit traced inside a jit is
  not counted twice), ``programs`` (``compile`` rows), and of those rows'
  dealings with the persistent cache ``cache_requests``, ``cache_hits``,
  ``cache_misses`` (compiled and written: JAX counts no miss for a program
  too small or too quick to be worth writing), ``cache_load_s`` (fetching
  and deserialising hits, a part of ``backend_compile_s``) and
  ``compile_saved_s`` (what the hits' first compilations took, less their
  loads).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque, namedtuple
from typing import Any, Callable, Dict, List, Optional

ROWS_KEPT = 4096        # program rows an account keeps (the newest)
CALLS_KEPT = 16         # start() and stop() calls it keeps (the newest)
# A row shorter than this is summed and not kept: JAX records a `trace` of
# some microseconds for every call of a jitted function it has traced
# already, as a model's trace makes by the ten thousand, and a ring of those
# would hold nothing that could explain a gap or be one of the longest.
ROW_FLOOR_NS = 50_000

# The events JAX records round the three steps from a Python function to an
# executable.  It reports the backend's compile and the persistent cache's
# load alike under the third.
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_PHASE_SUM = {"trace": "trace_s", "lower": "lower_s",
              "compile": "backend_compile_s"}
# What the persistent cache says of the compile that is open on a thread.
_CACHE_OUTCOME = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": 1,
    "/jax/compilation_cache/compile_time_saved_sec": 2,
}

Row = namedtuple("Row", "fun_name phase t0 t1 own_ns cache")
Row.__doc__ = """One program in one phase.  ``fun_name`` as JAX gives it (the
function's name for ``trace``, the module's, ``jit_<name>``, for ``lower``
and ``compile``); ``phase`` one of ``trace``, ``lower`` (jaxpr to MLIR: a
Pallas kernel's body is lowered to Mosaic here), ``compile`` (the backend's
compile or the cache's load); ``t0 <= t1``; ``own_ns``: ``t1 - t0`` less the
rows that opened and ended inside it on the same thread (a jitted function
traced inside another's trace, a constant's little program compiled in the
middle of a trace); ``cache``, on a ``compile`` row that asked the
persistent cache: ``(outcome, load_s, saved_s)`` with ``outcome`` one of
``"hit"``, ``"miss"``, ``"asked"`` (neither: compiled and not written), else
``None``."""


def _count(sums: Dict[str, Any], row: Row) -> None:
    """The arithmetic of the sums, once: ``row`` added to ``sums``."""
    sums[_PHASE_SUM[row.phase]] += row.own_ns / 1e9
    if row.phase != "compile":
        return
    sums["programs"] += 1
    if row.cache is not None:
        outcome, load_s, saved_s = row.cache
        sums["cache_requests"] += 1
        sums["cache_hits"] += outcome == "hit"
        sums["cache_misses"] += outcome == "miss"
        sums["cache_load_s"] += load_s
        sums["compile_saved_s"] += saved_s


def _no_sums() -> Dict[str, Any]:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
            "cache_load_s": 0.0, "compile_saved_s": 0.0, "cache_hits": 0,
            "cache_misses": 0, "cache_requests": 0, "programs": 0}


class Account:
    """See the module's docstring for the fields.  ``compile_sinks``: called
    with every finished ``compile`` row, on the thread that compiled (the
    engine feeds its open ``RunRecord`` through one)."""

    def __init__(self) -> None:
        self.t_import = time.monotonic_ns()
        self.epoch_offset_ns = time.time_ns() - self.t_import
        self.jax_preloaded = "jax" in sys.modules
        self.t_imported: Optional[int] = None
        self.starts: deque = deque(maxlen=CALLS_KEPT)
        self.stops: deque = deque(maxlen=CALLS_KEPT)
        self.programs: deque = deque(maxlen=ROWS_KEPT)
        self.sums = _no_sums()
        self.compile_sinks: List[Callable[[Row], None]] = []
        self._lock = threading.Lock()
        # Of each thread: for every span that is open on it, the time of the
        # spans that ended inside; and the open compile's cache notes.
        self._thread = threading.local()
        self._spans_from_durations = False

    @property
    def start(self) -> Optional[Dict[str, Any]]:
        return self.starts[-1] if self.starts else None

    @property
    def stop(self) -> Optional[Dict[str, Any]]:
        return self.stops[-1] if self.stops else None

    # ----------------------------------------------------------- listening

    def imported(self) -> None:
        """The package's last statement: stamp it and register this
        account's listeners with ``jax.monitoring``, which is surely imported
        by now, while no program of the package exists yet.  Once: JAX keeps
        listeners for the life of the process."""
        if self.t_imported is not None:
            return
        self.t_imported = time.monotonic_ns()
        import jax.monitoring as monitoring

        register_spans = getattr(
            monitoring, "register_event_time_span_listener", None)
        if register_spans is not None:
            register_spans(self._on_span)
        else:       # an older JAX: the durations, ended on this clock
            self._spans_from_durations = True
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        # JAX records a span's start as a scalar when it opens: with it a
        # row knows which rows ended inside it (without, none: own = whole).
        register_scalars = getattr(monitoring, "register_scalar_listener",
                                   None)
        if register_scalars is not None:
            register_scalars(self._on_open)

    def _on_open(self, event: str, value: Any, **_: Any) -> None:
        if event in PHASES:
            mine = self._thread
            if not hasattr(mine, "open"):
                mine.open = []
            mine.open.append(0)

    def _on_span(self, event: str, start_time: float, end_time: float,
                 fun_name: str = "", **_: Any) -> None:
        phase = PHASES.get(event)
        if phase is not None:
            self._add(str(fun_name), phase,
                      int(start_time * 1e9) - self.epoch_offset_ns,
                      int(end_time * 1e9) - self.epoch_offset_ns)

    def _on_duration(self, event: str, duration: float, fun_name: str = "",
                     **_: Any) -> None:
        field = _CACHE_SECONDS.get(event)
        if field is not None:
            notes = getattr(self._thread, "cache", None)
            if notes is not None:
                notes[field] += float(duration)
        elif self._spans_from_durations and event in PHASES:
            t1 = time.monotonic_ns()
            self._add(str(fun_name), PHASES[event],
                      t1 - int(duration * 1e9), t1)

    def _on_event(self, event: str, **_: Any) -> None:
        outcome = _CACHE_OUTCOME.get(event)
        if outcome == "asked":      # a compile opens its dealings with it
            self._thread.cache = ["asked", 0.0, 0.0]
        elif outcome is not None:
            notes = getattr(self._thread, "cache", None)
            if notes is not None:
                notes[0] = outcome

    def _add(self, fun_name: str, phase: str, t0: int, t1: int) -> None:
        mine = self._thread
        open_ = getattr(mine, "open", None)
        inside = open_.pop() if open_ else 0
        if open_:
            open_[-1] += t1 - t0
        cache = None
        if phase == "compile":
            notes, mine.cache = getattr(mine, "cache", None), None
            cache = None if notes is None else tuple(notes)
        row = Row(fun_name, phase, t0, t1, max(t1 - t0 - inside, 0), cache)
        with self._lock:
            if t1 - t0 >= ROW_FLOOR_NS:
                self.programs.append(row)
            _count(self.sums, row)
        if phase == "compile":
            for sink in self.compile_sinks:
                sink(row)

    # ------------------------------------------------------------- reading

    def summary(self, until_ns: Optional[int] = None) -> Dict[str, Any]:
        """The arithmetic, once.  ``import_s``; ``start_s`` (``mpi.start()``
        entry to return, summed over the ``starts`` kept) and its parts
        ``start_group_s``, ``start_backend_s``, ``start_communicators_s``,
        ``start_selector_s``, ``start_planes_s``; ``stop_s`` likewise;
        ``jax_preloaded``; ``backend_was_up`` (of the oldest start kept);
        the sums; ``longest``: the three rows of most own
        time among those kept, as ``(fun_name, phase, seconds)``.  A span
        that has not happened is ``None``.  With ``until_ns``, a stamp on the
        capture's clock (``time.time_ns()``), the sums and ``longest`` are of
        the kept rows that had ended by then: what a process spent before its
        timed window, say."""
        with self._lock:
            rows, sums = list(self.programs), dict(self.sums)
        if until_ns is not None:
            rows = [r for r in rows if r.t1 + self.epoch_offset_ns <= until_ns]
            sums = _no_sums()
            for row in rows:
                _count(sums, row)
        starts, stops = list(self.starts), list(self.stops)

        def seconds(calls, a, b):
            return sum((c[b] - c[a]) / 1e9 for c in calls) if calls else None

        longest = sorted(rows, key=lambda r: r.own_ns, reverse=True)[:3]
        return {
            "import_s": None if self.t_imported is None else (
                self.t_imported - self.t_import) / 1e9,
            "jax_preloaded": self.jax_preloaded,
            "starts": len(starts),
            "start_s": seconds(starts, "t_enter", "t_return"),
            "start_group_s": seconds(starts, "t_enter", "t_group"),
            "start_backend_s": seconds(starts, "t_group", "t_backend"),
            "backend_was_up": starts[0]["backend_was_up"] if starts else None,
            "start_communicators_s": seconds(starts, "t_backend",
                                             "t_communicators"),
            "start_selector_s": seconds(starts, "t_communicators",
                                        "t_selector"),
            "start_planes_s": seconds(starts, "t_selector", "t_return"),
            "stop_s": seconds(stops, "t_enter", "t_return"),
            **sums,
            "longest": [(r.fun_name, r.phase, r.own_ns / 1e9)
                        for r in longest],
        }

    def rows(self, t0_ns: int, t1_ns: int) -> List[Row]:
        """The kept rows that overlap ``[t0_ns, t1_ns]``, an interval on the
        capture's clock, their stamps moved onto that clock: what the
        process was tracing, lowering, compiling or loading while a
        capture's device sat idle."""
        off = self.epoch_offset_ns
        with self._lock:
            kept = list(self.programs)
        return [r._replace(t0=r.t0 + off, t1=r.t1 + off) for r in kept
                if r.t0 + off <= t1_ns and r.t1 + off >= t0_ns]


ACCOUNT = Account()     # stamped by the package's first statement
