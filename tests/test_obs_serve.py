"""Live telemetry & health plane (torchmpi_tpu/obs/serve.py + cluster.py):
endpoint correctness against a live registry, the health state machine's
transitions, bounded-timeout aggregation with dead ranks, the merged
federation document, and the scrape-concurrent-with-native-emission shape
(TSAN-listed in scripts/sanitize_drill.py — a /metrics walk holds the
registry/metric locks while collective worker threads emit into the
native rings and scrape_native reads the C-ABI counters)."""

import json
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchmpi_tpu.collectives.hostcomm import HostCommunicator, free_ports
from torchmpi_tpu.obs import cluster, metrics, serve, tracer
from torchmpi_tpu.obs import native as obs_native
from torchmpi_tpu.runtime import config, failure

pytestmark = pytest.mark.obsserve


def _get(url, timeout=5.0):
    """GET keeping error-status bodies (healthz answers 503 for stalled)."""
    return cluster._get(url, timeout)


def _get_json(url, timeout=5.0):
    return json.loads(_get(url, timeout))


@pytest.fixture()
def fresh_server():
    """One endpoint over a PRIVATE registry + health (no scrape pass):
    the hermetic shape for route tests."""
    reg = metrics.Registry()
    hs = serve.HealthState()
    srv = serve.ObsHTTPServer(registry=reg, health=hs, scrape=False)
    yield srv, reg, hs
    srv.close()


@pytest.fixture()
def clean_health():
    """The process-global health singleton, reset around the test."""
    serve.health.reset()
    yield serve.health
    serve.health.reset()


class TestEndpoints:
    def test_metrics_serves_live_registry(self, fresh_server):
        srv, reg, _ = fresh_server
        reg.counter("tmpi_unit_total", "unit test counter").inc(
            3, labels={"a": "x"})
        text = _get(srv.url + "/metrics")
        assert "tmpi_unit_total{a=\"x\"} 3.0" in text
        assert text.count("# TYPE tmpi_unit_total counter") == 1
        # Live: a later inc is visible on the next scrape.
        reg.counter("tmpi_unit_total").inc(1, labels={"a": "x"})
        assert 'tmpi_unit_total{a="x"} 4.0' in _get(srv.url + "/metrics")

    def test_type_line_once_with_disjoint_label_sets(self, fresh_server):
        srv, reg, _ = fresh_server
        c = reg.counter("tmpi_disjoint_total", "h")
        c.inc(1, labels={"op": "allreduce"})
        c.inc(2, labels={"plane": "ps"})          # disjoint label set
        text = _get(srv.url + "/metrics")
        assert text.count("# TYPE tmpi_disjoint_total counter") == 1
        assert text.count("# HELP tmpi_disjoint_total") == 1

    def test_healthz_status_codes(self, fresh_server):
        srv, _, hs = fresh_server
        v = _get_json(srv.url + "/healthz")
        assert v["state"] == "healthy" and v["reasons"] == []
        # stalled -> 503 (body still carries the verdict; _get keeps it)
        hs.monitor("engine_step", degraded_after_s=0.001,
                   stalled_after_s=0.002)
        time.sleep(0.01)
        import urllib.error
        import urllib.request

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/healthz", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["state"] == "stalled"

    def test_spans_endpoint_peeks_bounded(self, fresh_server):
        srv, _, _ = fresh_server
        config.reset(obs_trace=True)
        obs_native.apply_config()
        try:
            tracer.drain()
            for i in range(10):
                tracer.record(f"unit.span{i}", 0, 1000)
            body = _get_json(srv.url + "/spans?limit=4")
            assert body["returned"] == 4
            assert [s["name"] for s in body["spans"]] == [
                f"unit.span{i}" for i in range(6, 10)]
            # Peek, not drain: a second read sees the same history.
            again = _get_json(srv.url + "/spans?limit=4")
            assert [s["name"] for s in again["spans"]] == [
                s["name"] for s in body["spans"]]
            assert len(tracer.peek()) == 10
        finally:
            tracer.drain()
            config.reset()
            obs_native.apply_config()

    def test_flight_post_writes_bundle(self, fresh_server, tmp_path):
        srv, _, _ = fresh_server
        config.reset(obs_flight_dir=str(tmp_path))
        try:
            import urllib.request

            req = urllib.request.Request(srv.url + "/flight", data=b"",
                                         method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                path = json.loads(r.read().decode())["path"]
            with open(path) as f:
                bundle = json.load(f)
            assert bundle["schema"] == "tmpi-flight-v1"
            assert bundle["reason"] == "http_request"
        finally:
            config.reset()

    def test_post_body_drained_on_keepalive_connection(self, fresh_server,
                                                       tmp_path):
        """POST with a body on a REUSED HTTP/1.1 connection: unread body
        bytes would be parsed as the next request line — the handler
        must drain them before responding."""
        import http.client

        srv, _, _ = fresh_server
        config.reset(obs_flight_dir=str(tmp_path))
        try:
            conn = http.client.HTTPConnection(*srv.address, timeout=10)
            conn.request("POST", "/flight", body=b'{"why": "drill"}',
                         headers={"Content-Type": "application/json"})
            r1 = conn.getresponse()
            assert r1.status == 200
            r1.read()
            # Same connection: the next request must parse cleanly.
            conn.request("GET", "/healthz")
            r2 = conn.getresponse()
            assert r2.status == 200
            assert json.loads(r2.read())["state"] == "healthy"
            conn.close()
        finally:
            config.reset()

    def test_healthz_does_not_plant_families_in_clean_registry(
            self, fresh_server):
        """The watched-counter scan reads via peek, never get-or-create:
        a registry that never scraped the PS counters must not grow
        empty tmpi_ps_* families just because /healthz looked."""
        srv, reg, _ = fresh_server
        assert _get_json(srv.url + "/healthz")["state"] == "healthy"
        assert "tmpi_ps_" not in _get(srv.url + "/metrics")
        assert reg.peek("tmpi_ps_client_fenced_total") is None

    def test_unknown_route_404(self, fresh_server):
        srv, _, _ = fresh_server
        import urllib.error
        import urllib.request

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope", timeout=5)
        assert ei.value.code == 404

    def test_default_binding_is_loopback(self, fresh_server):
        srv, _, _ = fresh_server
        assert srv.address[0] == "127.0.0.1"
        # And the knob-driven path (serve.start defaults) binds loopback
        # too — the security default the docs promise.
        assert config.get("obs_http_bind") == "127.0.0.1"
        srv2 = serve.start(port=0)
        try:
            assert srv2.address[0] == "127.0.0.1"
            assert serve.url() == srv2.url
            with pytest.raises(RuntimeError):
                serve.start(port=0)   # one endpoint per process
        finally:
            serve.stop()
        assert serve.url() is None

    def test_maybe_start_gated_on_knob(self):
        assert config.get("obs_http") is False
        assert serve.maybe_start() is None
        assert serve.url() is None


class TestHealthStateMachine:
    def test_fresh_is_healthy(self):
        hs = serve.HealthState()
        v = hs.evaluate(metrics.Registry())
        assert v["state"] == "healthy"
        assert v["reasons"] == []
        assert v["planes"].keys() == {"hostcomm", "ps"}

    def test_stale_step_degrades_then_stalls_then_recovers(self):
        hs = serve.HealthState()
        hs.monitor("engine_step", degraded_after_s=0.08,
                   stalled_after_s=0.2)
        reg = metrics.Registry()
        assert hs.evaluate(reg)["state"] == "healthy"
        time.sleep(0.1)
        v = hs.evaluate(reg)
        assert v["state"] == "degraded"
        assert [r["code"] for r in v["reasons"]] == ["degraded:engine_step"]
        time.sleep(0.15)
        v = hs.evaluate(reg)
        assert v["state"] == "stalled"
        assert [r["code"] for r in v["reasons"]] == ["stalled:engine_step"]
        hs.note("engine_step")            # progress returns
        assert hs.evaluate(reg)["state"] == "healthy"

    def test_drain_flag_and_precedence(self):
        hs = serve.HealthState()
        reg = metrics.Registry()
        hs.set_draining(True)
        v = hs.evaluate(reg)
        assert v["state"] == "draining"
        assert "draining" in [r["code"] for r in v["reasons"]]
        # stalled outranks draining: a wedged rank mid-drain is wedged.
        hs.monitor("engine_step", degraded_after_s=0.0, stalled_after_s=0.001)
        time.sleep(0.01)
        assert hs.evaluate(reg)["state"] == "stalled"
        hs.clear("engine_step")
        hs.set_draining(False)
        assert hs.evaluate(reg)["state"] == "healthy"

    def test_watchdog_derived_thresholds(self):
        hs = serve.HealthState()
        hs.register_watchdog(8.0)
        v = hs.evaluate(metrics.Registry())
        assert v["watchdog_timeout_s"] == 8.0
        assert v["marks"]["watchdog"]["degraded_after_s"] == pytest.approx(2.0)
        assert v["marks"]["watchdog"]["stalled_after_s"] == pytest.approx(4.0)
        hs.unregister_watchdog()
        assert "watchdog" not in hs.evaluate(metrics.Registry())["marks"]

    def test_counter_movement_degrades_within_window(self):
        reg = metrics.Registry()
        c = reg.counter("tmpi_ps_client_fenced_total", "fenced NACKs")
        c.inc(5)
        hs = serve.HealthState(error_window_s=0.3)
        # First evaluation BASELINES: pre-existing counts never flag.
        assert hs.evaluate(reg)["state"] == "healthy"
        c.inc()
        v = hs.evaluate(reg)
        assert v["state"] == "degraded"
        assert ["counter:tmpi_ps_client_fenced_total"] == [
            r["code"] for r in v["reasons"]]
        time.sleep(0.4)                   # movement ages out of the window
        assert hs.evaluate(reg)["state"] == "healthy"

    def test_real_watchdog_publishes_and_clears(self, clean_health):
        wd = failure.Watchdog(timeout=30.0, _on_expire=lambda: None)
        try:
            wd.kick()
            v = clean_health.evaluate(metrics.Registry())
            assert "watchdog" in v["marks"]
            assert v["watchdog_timeout_s"] == 30.0
        finally:
            wd.stop()
        assert "watchdog" not in clean_health.evaluate(
            metrics.Registry())["marks"]


class TestAggregator:
    def _servers(self, n, steps=None):
        regs = [metrics.Registry() for _ in range(n)]
        for r, reg in enumerate(regs):
            reg.counter("tmpi_engine_steps_total", "steps").inc(
                (steps or [10] * n)[r])
            reg.gauge("tmpi_engine_step_seconds", "step time").set(0.05)
        servers = [serve.ObsHTTPServer(registry=regs[r],
                                       health=serve.HealthState(),
                                       scrape=False, rank=r)
                   for r in range(n)]
        return servers, regs

    def test_federation_with_one_dead_rank_bounded(self):
        servers, _ = self._servers(2)
        dead = f"http://127.0.0.1:{free_ports(1)[0]}"   # nothing listens
        try:
            eps = [servers[0].url, servers[1].url, dead]
            t0 = time.monotonic()
            results = cluster.fetch(eps, timeout_s=0.5)
            elapsed = time.monotonic() - t0
            assert elapsed < 4.0, "a dead rank must not stall the sweep"
            view = cluster.job_view(results)
            assert [r["state"] for r in view["ranks"]] == [
                "healthy", "healthy", "unreachable"]
            assert view["verdict"] == "degraded"
            # The reachable ranks still merged into one federation doc.
            fed = cluster.federate(
                {r: res["metrics_text"] for r, res in enumerate(results)
                 if res.get("metrics_text")})
            assert fed.count("# TYPE tmpi_engine_steps_total counter") == 1
            assert 'tmpi_engine_steps_total{rank="0"} 10.0' in fed
            assert 'tmpi_engine_steps_total{rank="1"} 10.0' in fed
        finally:
            for s in servers:
                s.close()

    def test_accepted_but_silent_endpoint_times_out(self):
        """The blackhole shape: the kernel backlog accepts the connect,
        bytes never come — the probe must time out, not hang."""
        sil = socket.socket()
        sil.bind(("127.0.0.1", 0))
        sil.listen(1)
        try:
            url = f"http://127.0.0.1:{sil.getsockname()[1]}"
            t0 = time.monotonic()
            res = cluster.fetch_rank(url, timeout_s=0.5)
            assert time.monotonic() - t0 < 3.0
            assert res["reachable"] is False
            assert res["health"]["state"] == cluster.UNREACHABLE
        finally:
            sil.close()

    def test_trickling_endpoint_cannot_defeat_the_backstop(self):
        """An endpoint that keeps each socket op under the deadline by
        trickling a byte per interval defeats urllib's per-op timeout —
        the sweep's SHARED backstop window must still bound it, and the
        wedged probe must be abandoned (daemon), not joined."""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(4)
        stop_ev = threading.Event()

        def trickler():
            conns = []
            lst.settimeout(0.2)
            while not stop_ev.is_set():
                try:
                    c, _ = lst.accept()
                    conns.append(c)
                except OSError:
                    pass
                for c in conns:
                    try:
                        c.sendall(b"H")   # one byte, forever partial
                    except OSError:
                        pass
            for c in conns:
                c.close()

        th = threading.Thread(target=trickler, daemon=True)
        th.start()
        try:
            url = f"http://127.0.0.1:{lst.getsockname()[1]}"
            t0 = time.monotonic()
            results = cluster.fetch([url, url], timeout_s=0.4)
            elapsed = time.monotonic() - t0
            # One shared backstop (3*timeout + 1), not per rank.
            assert elapsed < 0.4 * 3 + 1 + 2, elapsed
            assert all(r["health"]["state"] == cluster.UNREACHABLE
                       for r in results)
        finally:
            stop_ev.set()
            th.join(timeout=5)
            lst.close()

    def test_straggler_named_from_live_gauges(self):
        servers, regs = self._servers(3)
        # Rank 0 (the lead) publishes the detector's verdicts; the skew
        # gauge's OWN rank label carries the attribution.
        g = regs[0].gauge("tmpi_rank_skew_attributed_seconds", "skew")
        g.set(0.02, labels={"rank": "0"})
        g.set(0.71, labels={"rank": "2"})
        try:
            view = cluster.job_view(
                cluster.fetch([s.url for s in servers], timeout_s=2.0))
            assert view["straggler"] == 2
            assert view["skew_attributed_s"][2] == pytest.approx(0.71)
        finally:
            for s in servers:
                s.close()

    def test_step_rate_from_consecutive_sweeps(self):
        servers, regs = self._servers(1, steps=[100])
        try:
            eps = [servers[0].url]
            v1 = cluster.job_view(cluster.fetch(eps, timeout_s=2.0))
            regs[0].counter("tmpi_engine_steps_total").inc(30)
            time.sleep(0.15)
            v2 = cluster.job_view(cluster.fetch(eps, timeout_s=2.0),
                                  prev=v1)
            rate = v2["ranks"][0]["step_rate"]
            # 30 steps over ~0.15-0.5s of wall: the rate must reflect the
            # counter delta, not the instantaneous gauge (1/0.05 = 20).
            assert rate > 50
            assert v2["ranks"][0]["step_ms"] == pytest.approx(50.0)
        finally:
            for s in servers:
                s.close()

    def test_render_table_mentions_every_rank(self):
        servers, _ = self._servers(2)
        try:
            view = cluster.job_view(
                cluster.fetch([s.url for s in servers], timeout_s=2.0))
            table = cluster.render_table(view)
            assert "job verdict: healthy" in table
            assert "\n   0 healthy" in table and "\n   1 healthy" in table
        finally:
            for s in servers:
                s.close()

    def test_endpoints_from_ring(self):
        ring = [("10.0.0.1", 7000), ("10.0.0.2", 7000)]
        assert cluster.endpoints_from_ring(ring, 8780, stride=0) == [
            "http://10.0.0.1:8780", "http://10.0.0.2:8780"]
        assert cluster.endpoints_from_ring(ring, 8780, stride=1) == [
            "http://10.0.0.1:8780", "http://10.0.0.2:8781"]

    def test_top_cli_once_json(self, capsys):
        from torchmpi_tpu.obs.__main__ import main as obs_main

        servers, _ = self._servers(2)
        try:
            rc = obs_main(["top", "--endpoints",
                           ",".join(s.url for s in servers),
                           "--once", "--json"])
            assert rc == 0
            out = capsys.readouterr().out
            view = json.loads(out[out.index("{"):])
            assert view["verdict"] == "healthy"
            assert len(view["ranks"]) == 2
        finally:
            for s in servers:
                s.close()


class TestScrapeConcurrentWithNativeEmission:
    """GET /metrics (scrape_native + full registry walk) racing live
    collective emission into the native trace rings — the TSAN shape."""

    def test_scrape_under_collective_load(self):
        config.reset(obs_trace=True)
        obs_native.apply_config()
        tracer.drain()
        obs_native.drain_events("hostcomm")
        eps = [("127.0.0.1", p) for p in free_ports(2)]
        with ThreadPoolExecutor(2) as ex:
            comms = list(ex.map(
                lambda r: HostCommunicator(r, 2, eps, 30000), range(2)))
        stop_ev = threading.Event()
        srv = serve.ObsHTTPServer(health=serve.HealthState())  # global reg
        try:
            def worker(r):
                # The stop flag rides in the reduced array: both ranks read
                # the same sum and leave after the same collective.  A rank
                # that acted on its own reading of `stop_ev` could leave its
                # peer blocked in one more allreduce, for good.
                a = np.empty((4096,), np.float32)
                n = 0
                while n < 60:
                    a.fill(1.0)
                    a[0] = float(stop_ev.is_set())
                    comms[r].allreduce(a)
                    n += 1
                    if a[0] > 0:
                        break
                return n

            with ThreadPoolExecutor(2) as ex:
                futs = [ex.submit(worker, r) for r in range(2)]
                bodies = []
                for _ in range(15):
                    bodies.append(_get(srv.url + "/metrics"))
                stop_ev.set()
                counts = [f.result(timeout=60) for f in futs]
            assert counts[0] == counts[1] > 0
            assert all("tmpi_trace_dropped_total" in b for b in bodies)
        finally:
            stop_ev.set()
            srv.close()
            for c in comms:
                c.close()
            config.reset()
            obs_native.apply_config()
            tracer.drain()
            obs_native.drain_events("hostcomm")


@pytest.mark.slow
class TestPsServerEndpoint:
    def test_ps_server_health_transitions(self, tmp_path):
        """scripts/ps_server.py --obs-http-port: healthy while serving,
        draining through the clean stop — the failover drills' server
        transition probe."""
        import os
        import signal
        import subprocess
        import sys as _sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ps_port, obs_port = free_ports(2)
        proc = subprocess.Popen(
            [_sys.executable, os.path.join(repo, "scripts", "ps_server.py"),
             "--port", str(ps_port), "--obs-http-port", str(obs_port)],
            stdout=subprocess.PIPE, text=True)
        url = f"http://127.0.0.1:{obs_port}"
        try:
            ready = json.loads(proc.stdout.readline())
            assert ready["event"] == "PS_READY"
            assert ready["obs_http"] == url
            assert _get_json(url + "/healthz")["state"] == "healthy"
            # /metrics scrapes THIS process's PS counters.
            assert "tmpi_ps_retry_total" in _get(url + "/metrics")
            proc.send_signal(signal.SIGTERM)
            # The endpoint answers draining through the clean stop.
            states = set()
            for _ in range(40):
                if proc.poll() is not None:
                    break
                try:
                    states.add(_get_json(url + "/healthz", 1.0)["state"])
                except Exception:
                    break
                time.sleep(0.05)
            assert "draining" in states, states
            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


class TestEngineFeed:
    def test_publish_step_gauges_and_health(self, clean_health):
        reg = metrics.Registry()
        serve.publish_step(step_s=0.2, examples=128, staged_bytes=4096,
                           overlap_fraction=0.9, step=7, registry=reg)
        assert reg.gauge("tmpi_engine_step_seconds").value() == \
            pytest.approx(0.2)
        assert reg.gauge("tmpi_engine_examples_per_sec").value() == \
            pytest.approx(640.0)
        assert reg.gauge("tmpi_engine_staged_bytes").value() == 4096
        assert reg.counter("tmpi_engine_steps_total").value() == 1
        assert reg.counter("tmpi_engine_examples_total").value() == 128
        assert "engine_step" in clean_health.evaluate(reg)["marks"]

    def test_overlap_fraction_clamped(self):
        reg = metrics.Registry()
        serve.publish_step(step_s=0.1, examples=1, staged_bytes=0,
                           overlap_fraction=1.7, registry=reg)
        assert reg.gauge("tmpi_engine_overlap_fraction").value() == 1.0
        serve.publish_step(step_s=0.1, examples=1, staged_bytes=0,
                           overlap_fraction=-0.3, registry=reg)
        assert reg.gauge("tmpi_engine_overlap_fraction").value() == 0.0

    def test_metrics_feed_gating(self):
        config.reset()
        assert serve.metrics_feed() is False
        config.set("obs_trace", True)
        assert serve.metrics_feed() is True
        config.reset(obs_http=True)
        assert serve.metrics_feed() is True
        config.reset()


class TestSharedCollectPass:
    def test_exporters_share_one_collect(self):
        reg = metrics.Registry()
        reg.counter("tmpi_shared_total", "h").inc(2)
        reg.histogram("tmpi_shared_seconds", "h").observe(0.01)
        fams = reg.collect()
        text = reg.to_prometheus(families=fams)
        snap = reg.snapshot(families=fams)
        # Both exporters derived from the SAME instant.
        assert "tmpi_shared_total 2.0" in text
        assert snap["tmpi_shared_total"]["values"][0]["value"] == 2.0
        # The collect result is a snapshot: later mutation is invisible.
        reg.counter("tmpi_shared_total").inc(5)
        assert "tmpi_shared_total 2.0" in reg.to_prometheus(families=fams)

    def test_concatenated_families_emit_type_once(self):
        a, b = metrics.Registry(), metrics.Registry()
        a.counter("tmpi_family_total", "h").inc(1, labels={"rank": "0"})
        b.counter("tmpi_family_total").inc(2, labels={"rank": "1"})
        merged = a.to_prometheus(families=a.collect() + b.collect())
        assert merged.count("# TYPE tmpi_family_total counter") == 1
        assert 'tmpi_family_total{rank="0"} 1.0' in merged
        assert 'tmpi_family_total{rank="1"} 2.0' in merged

    def test_parse_prometheus_roundtrip_with_escapes(self):
        reg = metrics.Registry()
        reg.counter("tmpi_escaped_total", "h").inc(
            1, labels={"msg": 'a"b\\c\nd'})
        parsed = cluster.parse_prometheus(reg.to_prometheus())
        [s] = [s for s in parsed["samples"]
               if s["name"] == "tmpi_escaped_total"]
        assert s["labels"]["msg"] == 'a"b\\c\nd'
        assert parsed["types"]["tmpi_escaped_total"] == "counter"
