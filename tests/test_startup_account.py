"""The start-up account (``torchmpi_tpu/_startup.py``, ``mpi.startup()``;
docs/observability.md): the import's and the lifecycle's stamps, a row for
every program traced, lowered, compiled or loaded, the sums, the two
questions asked on a capture's clock, the run record's ``compiles`` and the
tracer's lifecycle spans fed from it, and the six readers
``benchmark/layers/*.py`` that hand its summary to the benchmark (tier-1 does
not collect ``benchmark/tests/``).
"""

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring     # the public module lists no listeners

import torchmpi_tpu as mpi
from torchmpi_tpu import _startup
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import mlp
from torchmpi_tpu.obs import tracer
from torchmpi_tpu.runtime import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, COMPILE = _startup.PHASES      # the events, in this order
READERS = {"import_s": 1.5, "runtime_start_s": 0.25, "trace_lower_s": 32.5,
           "backend_compile_s": 9.0, "cache_load_s": 4.0,
           "compile_cache_misses": 3}


def _rows_since(n, name):
    """The account's rows past its first ``n`` whose ``fun_name`` holds
    ``name`` (the ring is far from full in a test process... or says so)."""
    rows = list(mpi.startup().programs)
    assert len(rows) < _startup.ROWS_KEPT
    return [r for r in rows[n:] if name in r.fun_name]


@pytest.fixture(autouse=True)
def _room_in_the_ring():
    """These tests count the rows a call adds from the ring's length.  The
    account is the process's, and a worker that ran a file of kernel tests
    first hands it over full (4,096 rows are one such file): make room, as a
    long-lived process would have by forgetting."""
    rows = mpi.startup().programs
    if len(rows) > _startup.ROWS_KEPT // 2:
        rows.clear()


def _span(account, event, t0_s, t1_s, fun_name="f", inside=()):
    """Feed ``account`` a span as JAX would, seconds on ``time.time()``: its
    start as a scalar when it opens, the spans ``inside`` it, its two ends."""
    base = account.epoch_offset_ns / 1e9
    account._on_open(event, base + t0_s, fun_name=fun_name)
    for args in inside:
        _span(account, *args)
    account._on_span(event, base + t0_s, base + t1_s, fun_name=fun_name)


# ------------------------------------------------- rows of a real program

def test_a_jitted_function_leaves_a_row_for_each_phase_in_order():
    def startup_account_probe_a(x):
        return jnp.sin(x) * 2 + 1

    n = len(mpi.startup().programs)
    jax.jit(startup_account_probe_a)(jnp.ones(3)).block_until_ready()
    rows = _rows_since(n, "startup_account_probe_a")
    assert [r.phase for r in rows] == ["trace", "lower", "compile"]
    assert all(r.t0 <= r.t1 and 0 <= r.own_ns <= r.t1 - r.t0 for r in rows)
    assert rows[0].t1 <= rows[1].t0 and rows[1].t1 <= rows[2].t0
    assert rows[0].fun_name == "startup_account_probe_a"
    assert rows[1].fun_name == rows[2].fun_name     # the module's name


def test_the_sums_grow_by_the_rows_own_time_and_a_second_call_adds_none():
    account = mpi.startup()
    fn = jax.jit(lambda x: jnp.cos(x) @ x)
    n, before = len(account.programs), dict(account.sums)
    fn(jnp.ones((5, 5))).block_until_ready()
    new, after = list(account.programs)[n:], dict(account.sums)
    assert {r.phase for r in new} == {"trace", "lower", "compile"}
    for phase, key in _startup._PHASE_SUM.items():
        own = sum(r.own_ns for r in new if r.phase == phase) / 1e9
        assert own > 0 and after[key] - before[key] == pytest.approx(own)
    assert (after["programs"] - before["programs"]
            == sum(r.phase == "compile" for r in new) >= 1)
    # A row's own time leaves out the rows inside it: `cos` and `matmul`
    # are jitted functions traced inside the lambda's trace.
    outer = max((r for r in new if r.phase == "trace"),
                key=lambda r: r.t1 - r.t0)
    assert outer.own_ns < outer.t1 - outer.t0
    fn(jnp.ones((5, 5))).block_until_ready()         # the same shape
    assert len(account.programs) == n + len(new) and account.sums == after


def test_a_cold_compile_is_a_miss_and_a_cleared_and_warm_one_a_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    account = mpi.startup()
    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        def startup_account_probe_c(x):
            return jnp.tanh(x) * 7

        x = jnp.ones(6)         # a program of its own, made before
        before = dict(account.sums)
        jax.jit(startup_account_probe_c)(x).block_until_ready()
        cold = dict(account.sums)
        n = len(account.programs)
        jax.clear_caches()      # in memory; the directory keeps its entry
        jax.jit(startup_account_probe_c)(x).block_until_ready()
        warm = dict(account.sums)
        row = [r for r in _rows_since(n, "startup_account_probe_c")
               if r.phase == "compile"][-1]
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert cold["cache_misses"] - before["cache_misses"] == 1
    assert cold["cache_hits"] == before["cache_hits"]
    assert cold["cache_load_s"] == before["cache_load_s"]
    assert warm["cache_hits"] - cold["cache_hits"] == 1
    assert warm["cache_misses"] == cold["cache_misses"]
    assert warm["cache_requests"] - before["cache_requests"] == 2
    load_s = warm["cache_load_s"] - cold["cache_load_s"]
    assert 0 < load_s <= warm["backend_compile_s"] - cold["backend_compile_s"]
    assert row.cache[0] == "hit" and row.cache[1] == pytest.approx(load_s)


def test_two_bare_calls_of_a_kernel_are_lowered_in_place_a_jitted_one_once():
    """What cost PR 33 a round: a Pallas kernel called bare is traced and
    lowered where it stands, once for every call, inside its program's own
    rows; jitted, the program's calls share one trace, which has a row."""
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def startup_account_kernel(x):
        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    shared = jax.jit(startup_account_kernel)
    x = jnp.ones((8, 128))
    n = len(mpi.startup().programs)
    bare = jax.jit(lambda x: startup_account_kernel(
        startup_account_kernel(x)))(x)
    assert _rows_since(n, "startup_account_kernel") == []
    n = len(mpi.startup().programs)
    once = jax.jit(lambda x: shared(shared(x)))(x)
    rows = _rows_since(n, "startup_account_kernel")
    # Two calls, one trace (the second call's, a look-up of some
    # microseconds, is under the floor unless the machine stalls in it).
    assert rows and {r.phase for r in rows} == {"trace"}
    assert rows[0].t1 - rows[0].t0 > 20 * sum(r.t1 - r.t0 for r in rows[1:])
    np.testing.assert_allclose(bare, once)


# ----------------------------------------------- the arithmetic, by hand

def test_the_ring_keeps_its_rows_and_the_sums_keep_counting():
    account = _startup.Account()
    extra = 10
    for i in range(_startup.ROWS_KEPT + extra):
        _span(account, COMPILE, i, i + 0.5, fun_name=f"p{i}")
    assert len(account.programs) == _startup.ROWS_KEPT
    assert account.programs[0].fun_name == f"p{extra}"
    s = account.summary()
    assert s["programs"] == _startup.ROWS_KEPT + extra
    assert s["backend_compile_s"] == pytest.approx(
        0.5 * (_startup.ROWS_KEPT + extra))
    assert s["trace_s"] == s["lower_s"] == 0.0


def test_a_row_inside_a_row_is_counted_once():
    account = _startup.Account()
    _span(account, TRACE, 0.0, 5.0, "outer", inside=[
        (TRACE, 1.0, 2.0, "inner", [(TRACE, 1.2, 1.4, "innermost")]),
        (COMPILE, 3.0, 3.5, "jit_const")])
    _span(account, LOWER, 5.0, 6.0, "jit_outer")     # after it, not inside
    own = {r.fun_name: r.own_ns / 1e9 for r in account.programs}
    assert [r.fun_name for r in account.programs] == [
        "innermost", "inner", "jit_const", "outer", "jit_outer"]  # as ended
    assert own == pytest.approx({"innermost": 0.2, "inner": 0.8,
                                 "jit_const": 0.5, "outer": 3.5,
                                 "jit_outer": 1.0}, abs=1e-5)
    s = account.summary()
    assert s["trace_s"] + s["lower_s"] + s["backend_compile_s"] == \
        pytest.approx(6.0)
    assert s["longest"][0] == ("outer", "trace", pytest.approx(3.5, abs=1e-5))
    assert len(s["longest"]) == 3


def test_rows_of_another_thread_are_not_inside_this_one_s():
    account = _startup.Account()
    base = account.epoch_offset_ns / 1e9
    account._on_open(TRACE, base, fun_name="mine")
    worker = threading.Thread(
        target=_span, args=(account, COMPILE, 1.0, 2.0, "theirs"))
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive()
    account._on_span(TRACE, base, base + 3.0, fun_name="mine")
    assert [(r.fun_name, r.own_ns) for r in account.programs] == [
        ("theirs", pytest.approx(1e9, abs=1e4)),
        ("mine", pytest.approx(3e9, abs=1e4))]


def test_a_row_under_the_floor_is_summed_and_not_kept():
    account = _startup.Account()
    floor_s = _startup.ROW_FLOOR_NS / 1e9
    _span(account, TRACE, 0.0, 1.0, "outer", inside=[
        (TRACE, 0.5, 0.5 + floor_s / 5, "looked_up")] * 100)
    assert [r.fun_name for r in account.programs] == ["outer"]
    assert account.programs[0].own_ns == pytest.approx(
        1e9 - 20 * _startup.ROW_FLOOR_NS, abs=1e5)   # floats of time.time()
    assert account.summary()["trace_s"] == pytest.approx(1.0)


def test_a_stamp_and_the_offset_are_on_the_clock_of_time_time_ns():
    account = mpi.startup()
    assert abs(time.monotonic_ns() + account.epoch_offset_ns
               - time.time_ns()) < 5e6
    n = len(account.programs)
    t0 = time.time_ns()
    jax.jit(lambda x: x - 11)(1.0).block_until_ready()
    t1 = time.time_ns()
    rows = list(account.programs)[n:]
    assert rows and all(t0 - 5e6 <= r.t0 + account.epoch_offset_ns
                        and r.t1 + account.epoch_offset_ns <= t1 + 5e6
                        for r in rows)


def test_summary_until_leaves_out_later_rows():
    account = _startup.Account()
    _span(account, TRACE, 0.0, 1.0)
    _span(account, LOWER, 1.0, 3.0)
    account._on_event("/jax/compilation_cache/compile_requests_use_cache")
    account._on_event("/jax/compilation_cache/cache_misses")
    _span(account, COMPILE, 3.0, 7.0)
    off = account.epoch_offset_ns
    early = account.summary(until_ns=off + int(3.5e9))
    assert (early["trace_s"], early["lower_s"]) == pytest.approx(
        (1.0, 2.0), abs=1e-5)
    assert early["backend_compile_s"] == 0.0 and early["programs"] == 0
    assert early["cache_misses"] == early["cache_requests"] == 0
    assert [name for name, *_ in early["longest"]] == ["f", "f"]
    late = account.summary(until_ns=off + int(7e9) + 1000)
    assert late == account.summary()
    assert late["backend_compile_s"] == pytest.approx(4.0, abs=1e-5)
    assert late["cache_misses"] == late["cache_requests"] == 1


def test_rows_gives_what_overlaps_an_interval_on_the_capture_s_clock():
    account = _startup.Account()
    _span(account, TRACE, 0.0, 1.0, "a")
    _span(account, LOWER, 2.0, 3.0, "b")
    _span(account, COMPILE, 4.0, 6.0, "c")
    off = account.epoch_offset_ns

    def names(t0_s, t1_s):
        return [r.fun_name for r in account.rows(off + int(t0_s * 1e9),
                                                 off + int(t1_s * 1e9))]

    assert names(1.5, 1.9) == []                     # a gap no row explains
    assert names(2.5, 2.6) == ["b"]                  # inside a row
    assert names(0.5, 4.5) == ["a", "b", "c"]
    assert names(6.5, 9.0) == []
    row, = account.rows(off + int(2.5e9), off + int(2.6e9))
    assert row.t0 - off == pytest.approx(2e9, abs=1e3)


def test_an_older_jax_s_durations_become_rows_ended_on_this_clock():
    account = _startup.Account()
    account._spans_from_durations = True      # no time-span listener there
    t0 = time.monotonic_ns()
    account._on_duration(LOWER, 0.25, fun_name="jit_g")
    account._on_duration("/jax/some/other_duration", 9.0)
    row, = account.programs
    assert (row.fun_name, row.phase) == ("jit_g", "lower")
    assert row.t1 - row.t0 == 250_000_000 and t0 <= row.t1
    assert row.t1 <= time.monotonic_ns()
    assert account.summary()["lower_s"] == pytest.approx(0.25)


# ------------------------------------------------- import and lifecycle

def test_the_import_is_stamped():
    account = mpi.startup()
    assert account is _startup.ACCOUNT
    assert account.t_import < account.t_imported <= time.monotonic_ns()
    assert account.summary()["import_s"] == (
        account.t_imported - account.t_import) / 1e9 > 0
    assert account.jax_preloaded is True      # conftest imports jax first


def test_start_and_stop_are_stamped_in_order(world):
    account = mpi.startup()
    stamps = account.start
    order = ["t_enter", "t_group", "t_backend", "t_communicators",
             "t_selector", "t_return"]
    assert [stamps[k] for k in order] == sorted(stamps[k] for k in order)
    assert stamps["backend_was_up"] is True   # the devices fixture asked
    alone = _startup.Account()                # this start() and no other
    alone.starts.append(stamps)
    s = alone.summary()
    assert s["start_s"] == (stamps["t_return"] - stamps["t_enter"]) / 1e9 > 0
    parts = ["start_group_s", "start_backend_s", "start_communicators_s",
             "start_selector_s", "start_planes_s"]
    assert sum(s[p] for p in parts) == pytest.approx(s["start_s"])
    # The process's account sums every start() it keeps (a suite's many).
    kept = list(account.starts)
    assert kept[-1] is stamps and len(kept) <= _startup.CALLS_KEPT
    assert account.summary()["start_s"] == pytest.approx(
        sum(c["t_return"] - c["t_enter"] for c in kept) / 1e9)
    assert account.summary()["starts"] == len(kept)
    with pytest.raises(RuntimeError, match="twice"):
        mpi.start(with_tpu=False)
    assert account.start is stamps            # a start that raised wrote none
    mpi.stop()
    down = account.stop
    assert down["t_enter"] <= down["t_down"] <= down["t_return"]
    assert stamps["t_return"] <= down["t_enter"]
    assert account.summary()["stop_s"] == pytest.approx(sum(
        c["t_return"] - c["t_enter"] for c in account.stops) / 1e9)
    mpi.stop()                                # not started: no new stamps
    assert account.stop is down


def test_a_restart_keeps_one_account_and_one_set_of_listeners(devices):
    def mine():
        found = [
            monitoring.get_event_time_span_listeners(),
            monitoring.get_event_duration_listeners(),
            monitoring.get_event_listeners(),
            monitoring.get_scalar_listeners()]
        return [sum(getattr(cb, "__self__", None) is mpi.startup()
                    for cb in listeners) for listeners in found]

    account, first = mpi.startup(), mpi.startup().start
    assert mine() == [1, 1, 1, 1]
    for _ in range(2):
        if mpi.started():
            mpi.stop()
        mpi.start(with_tpu=False, devices=devices)
    mpi.stop()
    imported = account.t_imported
    account.imported()                        # again: nothing more
    assert account.t_imported == imported
    assert mpi.startup() is account and mine() == [1, 1, 1, 1]
    assert account.start is not first
    # Nothing else in the package listens to jax.monitoring.
    assert not any(
        getattr(cb, "__module__", "").startswith("torchmpi_tpu")
        and getattr(cb, "__self__", None) is not account
        for cb in monitoring.get_event_duration_listeners())


def test_with_obs_trace_on_the_lifecycle_spans_are_the_account_s(devices):
    if mpi.started():
        mpi.stop()
    config.reset(obs_trace=True)
    tracer.drain()
    tracer.set_clock_offset(1_000)
    try:
        mpi.start(with_tpu=False, devices=devices)
        mpi.stop()
        spans = {}
        for s in tracer.drain():
            if s["name"].startswith("runtime."):
                spans.setdefault(s["name"], []).append(s)
    finally:
        tracer.set_clock_offset(0)
        config.reset()
    account = mpi.startup()
    assert sorted(spans) == ["runtime.start", "runtime.stop"]
    (up,), (down,) = spans["runtime.start"], spans["runtime.stop"]
    assert (up["t0_ns"], up["t1_ns"]) == (
        account.start["t_enter"] - 1_000, account.start["t_selector"] - 1_000)
    assert (down["t0_ns"], down["t1_ns"]) == (
        account.stop["t_enter"] - 1_000, account.stop["t_down"] - 1_000)


def test_with_obs_trace_off_no_lifecycle_span_is_registered(devices):
    if mpi.started():
        mpi.stop()
    config.reset()
    tracer.drain()
    mpi.start(with_tpu=False, devices=devices)
    mpi.stop()
    assert not [s for s in tracer.drain() if s["name"].startswith("runtime.")]
    assert mpi.startup().start["t_return"] <= mpi.startup().stop["t_enter"]


# -------------------------------------------------------- the run record

def test_an_open_run_record_is_fed_from_the_account_s_compile_rows(world):
    rng = np.random.default_rng(0)
    batches = [(rng.standard_normal((8, 4, 16)).astype(np.float32),
                rng.integers(0, 4, (8, 4)).astype(np.int32))
               for _ in range(5)]
    batches[2] = (batches[2][0][:, :2], batches[2][1][:, :2])  # a new shape
    params = mlp.init(jax.random.PRNGKey(0), in_dim=16, hidden=(8,),
                      n_classes=4)
    account = mpi.startup()
    n = len(account.programs)
    engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, mode="compiled")
    rec = engine.train(params, batches)["run"]
    compiled = [r for r in list(account.programs)[n:]
                if r.phase == "compile" and rec.t_enter <= r.t1 <= rec.t_return]
    assert [seconds for _, seconds in rec.compiles] == [
        (r.t1 - r.t0) / 1e9 for r in compiled]
    steps = [step for step, _ in rec.compiles]
    assert 0 in steps and 2 in steps and set(steps) <= {0, 2}
    assert rec.summary()["recompiles"] == steps.count(2) >= 1
    before = len(rec.compiles)
    jax.jit(lambda x: x + 17)(1.0).block_until_ready()   # no record is open
    assert len(rec.compiles) == before


# ------------------------------------------------ the benchmark's readers

def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class _StubAccount:
    def summary(self):
        return {"import_s": 1.5, "start_s": 0.25, "trace_s": 20.0,
                "lower_s": 12.5, "backend_compile_s": 9.0,
                "cache_load_s": 4.0, "cache_misses": 3}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_the_account_and_none_without_one(name, monkeypatch):
    read = _reader(name)
    real = read({})
    assert isinstance(real, (int, float)) and real >= 0
    monkeypatch.setattr(mpi, "startup", _StubAccount)
    assert read({}) == READERS[name]
    monkeypatch.delattr(mpi, "startup")       # a parent of PR 34
    assert read({}) is None
