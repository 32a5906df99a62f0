"""The record the engine keeps of every ``train()`` call
(``engine/sgdengine.py:RunRecord``, docs/observability.md): its stamps, the
step completions, where a slow source, a slow hook and a recompilation show,
its bounds, and the three readers ``benchmark/layers/engine_*.py`` that hand
its summary to the benchmark (tier-1 does not collect ``benchmark/tests/``).
"""

import importlib.util
import os
import time
from collections import deque

import numpy as np
import pytest

import jax

from torchmpi_tpu.engine import AllReduceSGDEngine, sgdengine
from torchmpi_tpu.models import mlp
from torchmpi_tpu.collectives import eager
from torchmpi_tpu.runtime import config

P = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = {"engine_step_ms": "step_ms_p50", "engine_host_ms": "host_ms_p50",
           "engine_start_ms": "start_ms"}


def _batches(n, per_rank=4, seed=0):
    """``n`` rank-major batches ``(x:(P, b, 16), y:(P, b))``."""
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((P, per_rank, 16)).astype(np.float32),
             rng.integers(0, 4, (P, per_rank)).astype(np.int32))
            for _ in range(n)]


def _params(mode, comm):
    plain = mlp.init(jax.random.PRNGKey(0), in_dim=16, hidden=(8,), n_classes=4)
    if mode == "compiled":
        return plain
    stacked = jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a)[None], (P,) + a.shape).copy(),
        plain)
    return jax.tree.map(lambda a: eager.shard(comm, a), stacked)


def _engine(mode="compiled", hooks=None):
    return AllReduceSGDEngine(mlp.loss_fn, lr=0.1, mode=mode, hooks=hooks)


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("mode", ["compiled", "eager_sync", "eager_async"])
def test_stamps_are_ordered_and_steps_counted(world, mode):
    engine = _engine(mode)
    before = time.monotonic_ns()
    state = engine.train(_params(mode, world), _batches(5), epochs=2)
    after = time.monotonic_ns()
    rec = state["run"]
    assert rec is engine.last_run is sgdengine.runs()[-1]
    assert (rec.steps, rec.mode, rec.window) == (10, mode, 8)
    assert (before <= rec.t_enter <= rec.t_first_batch
            <= rec.t_first_dispatch <= rec.t_return <= after)
    stamps = list(rec.step_stamps)
    assert [s[0] for s in stamps] == list(range(10))
    last_end = rec.t_first_batch
    for _, t_batch, t_stepped, t_end, wait_ns, hook_ns, *own in stamps:
        assert last_end <= t_batch <= t_stepped <= t_end
        # The step function's own: entry, staged, dispatched, the two ends
        # of its wait, its last statement; then the async drain's blocked.
        assert [t_batch, *own[:6], t_stepped] == sorted(
            [t_batch, *own[:6], t_stepped])
        assert (own[6] is None) == (mode != "eager_async")
        assert own[6] is None or 0 <= own[6] <= own[4] - own[3]
        assert wait_ns >= 0 and hook_ns == 0
        assert t_end - t_batch >= wait_ns
        last_end = t_end
    assert last_end <= rec.t_return
    assert rec.t_first_dispatch <= stamps[0][2]
    s = rec.summary()
    assert s["steps"] == 10 and s["recompiles"] == 0
    assert s["start_ms"] >= s["first_batch_ms"] >= 0
    assert s["host_ms_p50"] > 0 and s["input_wait_ms_p50"] >= 0
    if mode == "compiled":
        # The host waited for steps 0 and 1; the last eight stay in flight.
        assert [c[0] for c in rec.completions] == [0, 1]
        assert any(step == 0 for step, _ in rec.compiles)
    else:
        # The eager modes fence within the step.
        assert not rec.completions and s["step_ms_p50"] is None
        assert s["intervals"] == 0


@pytest.mark.parametrize("mode", ["compiled", "eager_sync"])
def test_the_live_feed_is_computed_from_the_steps_own_stamps(world, mode):
    """``obs/serve.py:engine_step`` reads no clock: with the feed on, the
    step-time gauge and the phase gauges are functions of the last
    ``step_stamps`` entry, and the training is the one the feed-off run
    does."""
    from torchmpi_tpu.obs import alerts, native
    from torchmpi_tpu.obs.metrics import registry

    config.set("data_pipeline", "off")      # no pipeline wait_s: stamps only
    off = _engine(mode).train(_params(mode, world), _batches(4))
    config.set("obs_http", True)            # the feed, without the tracer
    on = _engine(mode).train(_params(mode, world), _batches(4))
    assert on["run"].steps == off["run"].steps == 4
    np.testing.assert_array_equal(np.asarray(on["loss"]),
                                  np.asarray(off["loss"]))
    jax.tree.map(np.testing.assert_array_equal, on["params"], off["params"])

    entry, staged, dispatched, sync, synced, done, _ = \
        on["run"].step_stamps[-1][6:]
    assert registry.gauge("tmpi_engine_step_seconds").value() == \
        (done - entry) / 1e9
    spans = {"data_wait": (entry, staged), "dispatch": (staged, dispatched),
             "collective": (sync, synced),
             "optimizer": (synced, done) if mode == "eager_sync" else (0, 0),
             # Hook time, where a test before this one loaded the plane.
             "ps": (synced, done) if mode == "compiled"
             and native.loaded("ps") else (0, 0)}
    assert tuple(spans) == alerts.PHASES
    phase = registry.gauge("tmpi_step_phase_seconds")
    for name, (t0, t1) in spans.items():
        assert phase.value(labels={"phase": name}) == (t1 - t0) / 1e9
    assert sum((t1 - t0) / 1e9 for t0, t1 in spans.values()) <= \
        (done - entry) / 1e9


def test_the_loop_imports_no_step_boundary_plane():
    """Resize, failure, election and retune attach through
    ``engine.step_boundaries`` from outside: the loop's module imports none
    of them, at its top or inside a function."""
    import ast

    barred = {"runtime.resize", "runtime.failure", "runtime.election",
              "collectives.retune"}
    with open(sgdengine.__file__) as f:
        tree = ast.parse(f.read())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            seen.add(module)
            seen.update(f"{module}.{alias.name}".lstrip(".")
                        for alias in node.names)
    assert seen and not {name for name in seen
                         if any(name.endswith(b) for b in barred)}


def test_completions_name_the_right_steps_with_a_window_of_two(world):
    config.set("engine_max_inflight_steps", 2)
    rec = _engine().train(_params("compiled", world), _batches(9))["run"]
    assert rec.window == 2
    done = list(rec.completions)
    assert [step for step, _ in done] == list(range(7))
    ends = {s[0]: s[3] for s in rec.step_stamps}
    stepped = {s[0]: s[2] for s in rec.step_stamps}
    for (step, at), (_, nxt) in zip(done, done[1:] + [(None, rec.t_return)]):
        # Seen finished after its own dispatch, inside step + 2's iteration.
        assert stepped[step] <= at <= ends[step + 2] and at <= nxt
    assert rec.summary()["intervals"] == 6


def test_completions_across_two_calls_on_one_engine(world):
    """``_inflight`` outlives a call: the second call's first waits are on
    the first call's last steps, which stamp nothing, and no record is
    written to after its call has returned."""
    config.set("engine_max_inflight_steps", 2)
    engine = _engine()
    first_state = engine.train(_params("compiled", world), _batches(5))
    first = first_state["run"]
    seen = list(first.completions)
    assert [step for step, _ in seen] == [0, 1, 2]
    second = engine.train(first_state["params"], _batches(6, seed=1),
                          start_step=5)["run"]
    assert list(first.completions) == seen
    assert [step for step, _ in second.completions] == [0, 1, 2, 3]
    assert second.start_step == 5 and second.steps == 6
    assert sgdengine.runs()[-2:] == [first, second]
    # The two waits on the first call's steps count as this call's waiting.
    assert sum(s[4] for s in second.step_stamps) > 0


def test_a_source_slow_to_start_shows_in_first_batch_ms(world):
    def source():
        time.sleep(0.2)
        yield from _batches(3)

    s = _engine().train(_params("compiled", world), source())["run"].summary()
    assert s["first_batch_ms"] >= 200
    assert s["start_ms"] >= s["first_batch_ms"]
    assert s["input_wait_ms_p50"] < 100


def test_a_slow_source_shows_in_input_wait_not_in_host_time(world):
    engine = _engine()
    state = engine.train(_params("compiled", world), _batches(2))  # compiles

    def source():
        for batch in _batches(6):
            time.sleep(0.05)
            yield batch

    s = engine.train(state["params"], source())["run"].summary()
    # The input pipeline's thread sleeps while the loop runs its step, so
    # the loop waits a little under the 50 ms.
    assert s["input_wait_ms_p50"] >= 35
    assert s["host_ms_p50"] < 25


def test_a_slow_hook_shows_in_neither(world):
    hooks = {"on_sample": lambda state: time.sleep(0.03),
             "on_update": lambda state: time.sleep(0.03)}
    engine = _engine(hooks=hooks)
    state = engine.train(_params("compiled", world), _batches(2))
    rec = engine.train(state["params"], _batches(6))["run"]
    s = rec.summary()
    assert all(stamp[5] >= 60e6 for stamp in rec.step_stamps)
    assert s["host_ms_p50"] < 25 and s["input_wait_ms_p50"] < 25


def test_a_hook_that_raises_still_closes_the_record(world):
    def boom(state):
        if state["t"] == 2 and not raised:
            raised.append(True)
            raise RuntimeError("boom")

    raised = []

    engine = _engine(hooks={"on_update": boom})
    with pytest.raises(RuntimeError, match="boom"):
        engine.train(_params("compiled", world), _batches(4))
    rec = engine.last_run
    assert rec.t_return is not None and rec.steps == 1
    assert engine._run is None and sgdengine._OPEN is None
    assert engine.train(_params("compiled", world), _batches(3))["run"].steps == 3


def test_a_batch_of_another_shape_is_a_recompile_at_its_step(world):
    batches = _batches(6)
    batches[3] = _batches(1, per_rank=2)[0]
    rec = _engine().train(_params("compiled", world), batches)["run"]
    steps = [step for step, _ in rec.compiles]
    assert 0 in steps and 3 in steps and set(steps) <= {0, 3}
    assert all(seconds > 0 for _, seconds in rec.compiles)
    assert rec.summary()["recompiles"] == steps.count(3) >= 1


def test_the_ring_and_runs_stay_bounded(world, monkeypatch):
    monkeypatch.setattr(sgdengine, "RUN_RING", 4)
    config.set("engine_max_inflight_steps", 1)
    engine = _engine()
    state = engine.train(_params("compiled", world), _batches(7))
    rec = state["run"]
    assert rec.steps == 7
    assert [s[0] for s in rec.step_stamps] == [3, 4, 5, 6]
    assert [c[0] for c in rec.completions] == [2, 3, 4, 5]
    assert rec.summary()["intervals"] == 3
    for _ in range(sgdengine.RUNS_KEPT + 2):
        state = engine.train(state["params"], _batches(1))
    kept = sgdengine.runs()
    assert len(kept) == sgdengine.RUNS_KEPT and rec not in kept
    assert kept[-1] is engine.last_run
    assert all(a.t_enter <= b.t_enter for a, b in zip(kept, kept[1:]))


def test_losses_are_bit_equal_with_a_bare_loop_over_the_same_step(world):
    """The record changes nothing the device computes: the engine's losses
    are those of its compiled step called in a plain loop (the parent's
    engine gave the same bits on this run; CHANGES.md, PR 24)."""
    batches = _batches(6, seed=7)
    losses = []
    engine = _engine(hooks={"on_update": lambda s: losses.append(s["loss"])})
    state = engine.train(_params("compiled", world), batches)
    params = jax.device_put(_params("compiled", world),
                            jax.tree.leaves(state["params"])[0].sharding)
    bare = []
    for x, y in batches:
        xb = jax.device_put(x.reshape(-1, 16), engine._batch_sh)
        yb = jax.device_put(y.reshape(-1), engine._batch_sh)
        params, _, loss = engine._compiled_step(params, None, xb, yb)
        bare.append(loss)
    assert [float(a).hex() for a in losses] == [float(b).hex() for b in bare]
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_stamp_plus_the_offset_is_on_the_epoch_clock(world):
    rec = _engine().train(_params("compiled", world), _batches(2))["run"]
    # The closest of five readings: the two clocks are read one after the
    # other, and the thread may lose the core between them.
    apart = min(abs(time.monotonic_ns() + rec.epoch_offset_ns - time.time_ns())
                for _ in range(5))
    assert apart < 1e6
    assert abs(rec.t_return + rec.epoch_offset_ns - time.time_ns()) < 5e9


def test_the_engine_test_loop_keeps_no_record_and_drains_the_window(world):
    config.set("engine_max_inflight_steps", 2)
    engine = _engine()
    state = engine.train(_params("compiled", world), _batches(4))
    kept, seen = sgdengine.runs(), list(state["run"].completions)
    value = engine.test(state["params"], _batches(5), mlp.loss_fn)
    assert np.isfinite(float(value))
    assert sgdengine.runs() == kept and list(state["run"].completions) == seen


def _record_with(intervals, gap_ns=2_000_000):
    rec = sgdengine.RunRecord("compiled", 8, 0)
    for step in range(intervals + 1):
        rec.completions.append((step, rec.t_enter + step * gap_ns))
    return rec


@pytest.mark.parametrize("field,least", [("step_ms_p50", 20),
                                         ("step_ms_p90", 100),
                                         ("step_ms_p95", 200)])
def test_a_percentile_needs_ten_samples_beyond_it(field, least):
    assert _record_with(least - 1).summary()[field] is None
    s = _record_with(least).summary()
    assert s[field] == pytest.approx(2.0) and s["intervals"] == least


def test_intervals_are_between_consecutive_steps_only():
    rec = _record_with(30)
    stamps = [c for c in rec.completions if c[0] != 10]   # step 10 fell out
    rec.completions = deque(stamps)
    rec.completions.append((31, stamps[-1][1] + 50_000_000))
    s = rec.summary()
    assert s["intervals"] == 29          # 10 -> 11 and 9 -> 11 are no interval
    assert s["step_ms_p50"] == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_returns_its_field_of_the_windows_run(world, monkeypatch,
                                                      name):
    """The window's record is the one that ran longest past its first
    dispatch: not the call that compiled, and not a shorter one that made
    more steps."""
    monkeypatch.setattr(sgdengine, "_RUNS", deque(maxlen=sgdengine.RUNS_KEPT))
    config.set("engine_max_inflight_steps", 1)
    engine = _engine()
    state = engine.train(_params("compiled", world), _batches(3))

    def slow(n):
        for batch in _batches(n):
            time.sleep(0.03)
            yield batch

    state = engine.train(state["params"], slow(25))
    window = state["run"]
    shorter = engine.train(state["params"], _batches(30))["run"]
    assert shorter.steps > window.steps
    assert (shorter.t_return - shorter.t_first_dispatch
            < window.t_return - window.t_first_dispatch)
    value = _reader(name)({})
    assert value == window.summary()[READERS[name]] and np.isfinite(value)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_returns_none_without_a_record(monkeypatch, name):
    monkeypatch.setattr(sgdengine, "_RUNS", deque(maxlen=sgdengine.RUNS_KEPT))
    assert _reader(name)({}) is None
    # Laid over a program that lacks the record (the parent commit).
    monkeypatch.delattr(sgdengine, "runs")
    assert _reader(name)({}) is None
