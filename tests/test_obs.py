"""Observability subsystem (torchmpi_tpu/obs): native trace-ring
semantics, span tracer, correlation join, metrics registry (including the
chaos-fault integration the retired peepholes gate on), exporters, and
the profiler-window satellite.

Ring-semantics tests drive the PS plane with raw ctypes calls because the
event algebra is exact there: every (failed or successful) ping emits
exactly two events (start + complete/error), so drop-oldest accounting
can be asserted to the event.  The hostcomm plane is covered end-to-end
by the join-rate tests (every native frame of a spanned collective must
carry the span's correlation id).
"""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchmpi_tpu.collectives.hostcomm import HostCommunicator, free_ports
from torchmpi_tpu.obs import export, metrics, tracer
from torchmpi_tpu.obs import native as obs_native
from torchmpi_tpu.parameterserver import native as ps_native
from torchmpi_tpu.runtime import chaos, config

pytestmark = pytest.mark.obs


@pytest.fixture()
def obs_on():
    """obs_trace on with fast-fail PS retries; buffers drained before and
    state fully restored after (the rings and the span buffer are
    process-global)."""
    config.reset(obs_trace=True, ps_retry_max=1, ps_retry_backoff_ms=1,
                 ps_retry_backoff_max_ms=2)
    ps_native.apply_config()
    obs_native.apply_config()
    tracer.drain()
    obs_native.drain_events("hostcomm")
    obs_native.drain_events("ps")
    yield
    config.reset()
    ps_native.apply_config()
    obs_native.apply_config()
    tracer.drain()
    obs_native.drain_events("hostcomm")
    obs_native.drain_events("ps")


def _failed_ping(L, corr):
    """One PS ping against a dead port under an explicit correlation id:
    emits exactly (start, error) — a deterministic 2-event generator."""
    peer = L.tmpi_ps_connect(b"127.0.0.1", 1)  # nothing listens on port 1
    L.tmpi_ps_set_correlation(corr)
    assert L.tmpi_ps_ping(peer) == 0
    L.tmpi_ps_set_correlation(0)
    L.tmpi_ps_disconnect(peer)


class TestNativeTraceRing:
    def test_overflow_drops_oldest_and_counts(self, obs_on):
        L = ps_native.lib()
        L.tmpi_ps_set_trace(1, 4)   # tiny ring for exact accounting
        try:
            dropped0 = obs_native.dropped("ps")
            for corr in range(1, 7):          # 6 pings = 12 events into 4
                _failed_ping(L, corr)
            ev = obs_native.drain_events("ps")
            assert len(ev) == 4
            # drop-oldest: the survivors are the NEWEST events (pings 5, 6)
            assert sorted(set(int(c) for c in ev["correlation"])) == [5, 6]
            assert obs_native.dropped("ps") - dropped0 == 8
        finally:
            obs_native.apply_config()          # restore configured capacity

    def test_drain_timestamps_monotonic(self, obs_on):
        L = ps_native.lib()
        for corr in range(1, 5):
            _failed_ping(L, corr)
        ev = obs_native.drain_events("ps")
        assert len(ev) == 8
        t = ev["t_ns"].astype(np.int64)
        assert (np.diff(t) >= 0).all()
        # and the clock is CLOCK_MONOTONIC — comparable to Python's
        now = time.monotonic_ns()
        assert 0 < int(t[-1]) <= now

    def test_trace_off_drains_empty(self, obs_on):
        L = ps_native.lib()
        L.tmpi_ps_set_trace(0, 0)
        _failed_ping(L, 9)
        assert len(obs_native.drain_events("ps")) == 0
        # hostcomm plane likewise: nothing traced, nothing drained
        assert len(obs_native.drain_events("hostcomm")) == 0
        obs_native.apply_config()

    def test_disable_discards_buffered_events(self, obs_on):
        """Disabling clears the ring: trace-off drains empty even when
        events were buffered but never drained, and a later re-enable
        starts from a clean ring (no stale tail from the prior run)."""
        L = ps_native.lib()
        _failed_ping(L, 11)               # 2 events buffered, undrained
        L.tmpi_ps_set_trace(0, 0)
        assert len(obs_native.drain_events("ps")) == 0
        L.tmpi_ps_set_trace(1, 0)
        assert len(obs_native.drain_events("ps")) == 0
        obs_native.apply_config()

    def test_concurrent_produce_drain_accounts_every_event(self, obs_on):
        """Producers (failed pings on 3 threads) race a drainer; at the
        end every emitted event is either drained or counted dropped —
        the invariant TSAN exercises under scripts/sanitize_drill.py."""
        L = ps_native.lib()
        L.tmpi_ps_set_trace(1, 64)
        try:
            dropped0 = obs_native.dropped("ps")
            per_thread, threads = 10, 3
            drained = []
            stop = threading.Event()

            def produce():
                for corr in range(1, per_thread + 1):
                    _failed_ping(L, corr)

            def drain_loop():
                while not stop.is_set():
                    drained.append(len(obs_native.drain_events("ps")))

            dr = threading.Thread(target=drain_loop)
            dr.start()
            with ThreadPoolExecutor(threads) as ex:
                list(ex.map(lambda _: produce(), range(threads)))
            stop.set()
            dr.join()
            total = (sum(drained) + len(obs_native.drain_events("ps"))
                     + (obs_native.dropped("ps") - dropped0))
            assert total == 2 * per_thread * threads
        finally:
            obs_native.apply_config()


class TestTracer:
    def test_disabled_span_is_noop(self):
        config.reset()            # obs_trace defaults off
        tracer.drain()
        with tracer.span("x") as corr:
            assert corr == 0
        assert tracer.drain() == []

    def test_nested_spans_share_correlation(self, obs_on):
        with tracer.span("outer") as corr:
            assert corr != 0
            assert tracer.current_correlation() == corr
            with tracer.span("inner") as inner_corr:
                assert inner_corr == corr
        spans = tracer.drain()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert {s["correlation"] for s in spans} == {corr}
        assert all(s["t1_ns"] >= s["t0_ns"] for s in spans)

    def test_threads_get_distinct_correlations(self, obs_on):
        def one(_):
            with tracer.span("t") as corr:
                return corr

        with ThreadPoolExecutor(4) as ex:
            corrs = list(ex.map(one, range(4)))
        assert len(set(corrs)) == 4

    def test_span_buffer_drops_oldest_and_counts(self, obs_on):
        tracer.configure(capacity=3)
        try:
            d0 = tracer.dropped()
            for i in range(5):
                with tracer.span(f"s{i}"):
                    pass
            spans = tracer.drain()
            assert [s["name"] for s in spans] == ["s2", "s3", "s4"]
            assert tracer.dropped() - d0 == 2
        finally:
            obs_native.apply_config()

    def test_exception_recorded_and_reraised(self, obs_on):
        with pytest.raises(ValueError):
            with tracer.span("boom"):
                raise ValueError("x")
        (s,) = tracer.drain()
        assert s["attrs"]["error"] == "ValueError"


def _ring(n=2):
    eps = [("127.0.0.1", p) for p in free_ports(n)]
    with ThreadPoolExecutor(n) as ex:
        return [f.result(timeout=120) for f in
                [ex.submit(HostCommunicator, r, n, eps, 60000)
                 for r in range(n)]]


class TestCorrelationJoin:
    def test_hostcomm_ops_join_their_spans(self, obs_on):
        comms = _ring()
        try:
            def work(r):
                a = np.full((512,), float(r + 1), np.float32)
                comms[r].allreduce(a)
                comms[r].broadcast(a, root=0)
                comms[r].barrier()
                h = comms[r].allreduce_async(np.ones((512,), np.float32))
                h.wait()
                return bool(np.allclose(a[:1], 3.0))

            with ThreadPoolExecutor(2) as ex:
                assert all(ex.map(work, range(2)))
        finally:
            for c in comms:
                c.close()
        spans = tracer.drain()
        ev = obs_native.drain_events("hostcomm")
        assert len(ev) > 0
        join = export.span_join_rate(spans, ev)
        assert join["rate"] == 1.0, join
        # the async wait path spanned with the dispatch's correlation
        names = [s["name"] for s in spans]
        assert "hostcomm.allreduce_async" in names
        assert "handle.wait" in names

    def test_ps_ops_join_their_spans(self, obs_on):
        import torchmpi_tpu.parameterserver as ps

        ps.init_cluster()
        try:
            data = np.arange(256, dtype=np.float32)
            t = ps.init(data)
            h, out = ps.receive(t)
            h.wait()
            assert np.array_equal(out, data)
            ps.send(t, np.ones(256, np.float32), rule="add").wait()
            ps.barrier()
        finally:
            ps.shutdown()
        spans = tracer.drain()
        ev = obs_native.drain_events("ps")
        assert len(ev) > 0
        join = export.span_join_rate(spans, ev)
        assert join["rate"] == 1.0, join


class TestMetricsRegistry:
    def test_counter_gauge_histogram_and_prometheus(self):
        reg = metrics.Registry()
        c = reg.counter("t_total", "help text")
        c.inc()
        c.inc(2, labels={"plane": "hc"})
        with pytest.raises(ValueError):
            c.inc(-1)
        reg.gauge("t_gauge").set(1.5)
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = reg.to_prometheus()
        assert "# TYPE t_total counter" in text
        assert 't_total{plane="hc"} 2.0' in text
        assert "t_gauge 1.5" in text
        assert 't_seconds_bucket{le="0.1"} 1' in text
        assert "t_seconds_count 2" in text
        # snapshot round-trips through json
        snap = json.loads(reg.to_json())
        assert snap["t_total"]["kind"] == "counter"
        # kind clash refuses
        with pytest.raises(ValueError):
            reg.gauge("t_total")

    def test_scraped_counters_match_native(self, obs_on):
        metrics.registry.scrape_native()
        assert (metrics.registry.counter("tmpi_ps_retry_total").value()
                >= ps_native.retry_count() - 1e-9)
        assert (metrics.registry.counter("tmpi_ps_crc_failure_total").value()
                >= ps_native.crc_failure_count() - 1e-9)

    def test_registry_increments_under_injected_faults(self, obs_on):
        """Satellite: the peepholes flow into the registry — a CRC-corrupted
        push through the chaos proxy must move the registry's retry and
        crc-failure counters (same fault shape as
        test_chaos.py::test_push_crc_nack_retries_to_success)."""
        config.set("ps_frame_crc", True)
        config.set("ps_retry_max", 4)
        config.set("ps_request_deadline_ms", 5000)
        ps_native.apply_config()
        metrics.registry.scrape_native()
        r0 = metrics.registry.counter("tmpi_ps_retry_total").value()
        c0 = metrics.registry.counter("tmpi_ps_crc_failure_total").value()
        L = ps_native.lib()
        sid = L.tmpi_ps_server_start(0)
        port = L.tmpi_ps_server_port(sid)
        spec = chaos.FaultSpec(corrupt_at_byte=300, fault_connections={0})
        try:
            with chaos.ChaosProxy(("127.0.0.1", port), spec, seed=3) as px:
                peer = L.tmpi_ps_connect(px.endpoint[0].encode(),
                                         px.endpoint[1])
                assert L.tmpi_ps_create(peer, 7, 1000, 0, 1) == 1
                data = np.arange(1000, dtype=np.float32)
                assert L.tmpi_ps_push(peer, 7, 1, 0, 0, 1000,
                                      data.ctypes.data) == 1
                L.tmpi_ps_disconnect(peer)
        finally:
            L.tmpi_ps_server_stop(sid)
        metrics.registry.scrape_native()
        assert metrics.registry.counter("tmpi_ps_retry_total").value() > r0
        assert (metrics.registry.counter("tmpi_ps_crc_failure_total").value()
                > c0)


class TestExport:
    def _fake(self):
        spans = [{"name": "op", "correlation": 7, "t0_ns": 1000,
                  "t1_ns": 5000, "thread": 1, "attrs": {"bytes": 64}}]
        ev = np.zeros((3,), obs_native.EVENT_DTYPE)
        ev["t_ns"] = [1500, 2500, 3500]
        ev["correlation"] = [7, 7, 0]       # last one unattributed
        ev["plane"] = [0, 0, 1]
        ev["op"] = [1, 1, 2]
        ev["phase"] = [1, 4, 1]             # start, complete, start
        ev["rank"] = [0, 0, -1]
        ev["bytes"] = [64, 64, 0]
        return spans, ev

    def test_join_rate_counts_unattributed_as_unjoined(self):
        spans, ev = self._fake()
        join = export.span_join_rate(spans, ev)
        assert join["native_events"] == 3 and join["joined"] == 2
        assert join["per_plane"]["hostcomm"]["joined"] == 2
        assert join["per_plane"]["ps"]["joined"] == 0

    def test_chrome_trace_structure(self, tmp_path):
        spans, ev = self._fake()
        trace = export.chrome_trace(spans, ev)
        events = trace["traceEvents"]
        # python span present as a complete event
        px = [e for e in events if e.get("cat") == "python"]
        assert len(px) == 1 and px[0]["ph"] == "X"
        # start..complete pair synthesized into ONE native X event
        nx = [e for e in events if e.get("cat") == "native"
              and e["ph"] == "X"]
        assert len(nx) == 1 and nx[0]["name"] == "allreduce"
        assert nx[0]["dur"] == pytest.approx(1.0)   # 1000 ns = 1 us
        # unpaired start stays an instant
        ni = [e for e in events if e.get("cat") == "native"
              and e["ph"] == "i"]
        assert len(ni) == 1 and ni[0]["name"] == "push.start"
        out = export.save(str(tmp_path / "t.json"), trace)
        assert json.load(open(out))["traceEvents"]


class TestEngineSpans:
    @pytest.mark.parametrize("mode, phases", [
        ("compiled",
         ("engine.stage", "engine.dispatch", "engine.inflight_wait")),
        ("eager_sync", ("engine.stage", "engine.grad", "engine.sync",
                        "engine.optimizer")),
        ("eager_async", ("engine.stage", "engine.grad", "engine.sync")),
    ])
    def test_step_phases_share_one_correlation(self, world, obs_on, mode,
                                               phases):
        import jax.numpy as jnp

        from torchmpi_tpu.collectives import eager
        from torchmpi_tpu.engine import AllReduceSGDEngine

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        engine = AllReduceSGDEngine(loss_fn, lr=0.01, mode=mode)
        params = {"w": jnp.zeros((3,), jnp.float32)}
        if mode != "compiled":
            params = {"w": eager.shard(world, np.zeros((8, 3), np.float32))}
        rng = np.random.default_rng(0)
        batches = [(rng.standard_normal((8, 4, 3)).astype(np.float32),
                    rng.standard_normal((8, 4)).astype(np.float32))]
        engine.train(params, batches, epochs=2)
        by_name = {}
        for s in tracer.drain():
            by_name.setdefault(s["name"], []).append(s)
        assert {n for n in by_name if n.startswith("engine.")} == {
            "engine.step", *phases}
        steps = {s["correlation"]: s for s in by_name["engine.step"]}
        assert len(steps) == 2
        for phase in phases:
            # One a step, under its step's correlation id and inside it:
            # registered from the step's own stamps (obs/serve.py).
            assert sorted(s["correlation"] for s in by_name[phase]) == \
                sorted(steps)
            for s in by_name[phase]:
                step = steps[s["correlation"]]
                assert (step["t0_ns"] <= s["t0_ns"] <= s["t1_ns"]
                        <= step["t1_ns"])
                assert s["thread"] == step["thread"]

    def test_profiler_hooks_compose_with_tracer_hooks(self):
        from torchmpi_tpu.utils.profiler import (StepWindowProfiler,
                                                 compose_hooks,
                                                 profiler_hooks)

        calls = []
        prof = StepWindowProfiler(enabled=False)
        hooks = compose_hooks(
            profiler_hooks(prof),
            tracer.hooks(),
            {"on_update": lambda state: calls.append(state["t"])},
        )
        hooks["on_update"]({"t": 3})
        hooks["on_end"]({"t": 3})
        assert calls == [3]


class TestProfilerTracePath:
    def test_trace_path_points_at_dumped_run_dir(self, tmp_path, obs_on):
        import jax

        from torchmpi_tpu.utils.profiler import StepWindowProfiler

        logdir = str(tmp_path / "trace")
        prof = StepWindowProfiler(logdir=logdir, start_step=0, end_step=1,
                                  enabled=True)
        prof.step(0)
        jax.block_until_ready(jax.numpy.ones((8,)) + 1)
        prof.step(1)
        assert prof.trace_path is not None
        import os

        assert os.path.isdir(prof.trace_path)
        # the actual run dir, not the logdir root (the satellite fix)
        assert os.path.join("plugins", "profile") in prof.trace_path
        # and the window registered as a span
        assert any(s["name"] == "profiler.window" for s in tracer.drain())


class TestTraceAbiCoverage:
    def test_abi_checker_sees_trace_fns_both_directions(self):
        """The new trace C ABI must be inside the checker's field of view:
        parsed from the extern "C" blocks AND declared in the binding
        modules — so future drift in either direction fails tmpi-analyze,
        not just this suite."""
        from pathlib import Path

        from torchmpi_tpu.analysis import abi

        repo = Path(__file__).resolve().parents[1]
        for cpp_rel, py_rel, prefix, fns in (
            ("torchmpi_tpu/_native/hostcomm.cpp",
             "torchmpi_tpu/collectives/hostcomm.py", "tmpi_hc_",
             {"tmpi_hc_set_trace", "tmpi_hc_trace_drain",
              "tmpi_hc_trace_dropped", "tmpi_hc_set_correlation"}),
            ("torchmpi_tpu/_native/ps.cpp",
             "torchmpi_tpu/parameterserver/native.py", "tmpi_ps_",
             {"tmpi_ps_set_trace", "tmpi_ps_trace_drain",
              "tmpi_ps_trace_dropped", "tmpi_ps_set_correlation"}),
        ):
            exported = abi.parse_c_exports(
                (repo / cpp_rel).read_text(), prefix)
            bound = abi.parse_ctypes_bindings(
                (repo / py_rel).read_text(), prefix)
            assert fns <= set(exported), cpp_rel
            assert fns <= set(bound), py_rel
            for fn in fns:
                assert bound[fn].argtypes is not None, fn
                assert bound[fn].restype_declared, fn


@pytest.mark.obs
class TestDrillQuick:
    def test_quick_drill_in_process(self, tmp_path):
        from torchmpi_tpu.obs.__main__ import run_drill

        artifact = run_drill(quick=True,
                             out_path=str(tmp_path / "OBS_test.json"),
                             trace_path=str(tmp_path / "trace.json"))
        assert artifact["verdict"] == "PASS", artifact
        assert artifact["span_join"]["rate"] >= 0.90
        assert artifact["ps_fault_cell"]["retries"] > 0
        assert artifact["ps_fault_cell"]["crc_failures"] > 0
        snap = artifact["metrics_snapshot"]
        assert snap["tmpi_ps_retry_total"]["values"][0]["value"] > 0
        trace = json.load(open(tmp_path / "trace.json"))
        assert len(trace["traceEvents"]) > 10
        # overhead A/B recorded
        key = [k for k in artifact if k.startswith("overhead_")][0]
        assert "delta_ms" in artifact[key]
