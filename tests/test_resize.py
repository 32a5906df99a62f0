"""Elastic resize state machine (runtime/resize.py, ISSUE 14).

Pins the contracts the tentpole rests on:

* membership-epoch monotonicity: committed epochs strictly increase,
  concurrent/stale proposals serialize or reject — never fork;
* the join leg: state ships to the joiner behind the fence, the new
  ring wires at the committed membership, and the autotune winner cache
  is RE-KEYED at commit (a cache measured at N ranks never survives M);
* drain/evict legs: the departing rank leaves only AFTER the verdict,
  survivors renumber and keep collecting;
* chaos during the resize window aborts ATOMICALLY: a blackholed state
  ship aborts cleanly on the old ring (which never stopped), a member
  killed mid-quiesce aborts every survivor with the epoch unchanged —
  no rank ever reaches the new epoch, membership is never split;
* the autoscaler policy (scripts/elastic_launch.py) converts sustained
  gauge evidence into grow/drain/evict decisions and nothing less;
* the restart-rejoin path (StateServer + maybe_rejoin) and the
  POST /resize inbox.

Marker ``resize``; everything here is seconds-fast tier-1.  The file is
also on ``scripts/sanitize_drill.py``'s TSAN/ASan list
(joiner-state-ship vs engine-step is the new race class).
"""

import importlib.util
import json
import os
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax.numpy as jnp

from torchmpi_tpu.collectives import autotune
from torchmpi_tpu.collectives.hostcomm import HostCommunicator, free_ports
from torchmpi_tpu.obs import metrics as obs_metrics
from torchmpi_tpu.obs import rca, serve
from torchmpi_tpu.runtime import chaos, config, election, resize
from torchmpi_tpu.runtime.failure import HostcommError, InjectedFault

pytestmark = pytest.mark.resize

WALL = 90.0


@pytest.fixture(autouse=True)
def _fresh():
    config.reset()
    resize._clear_requests()
    election.reset()
    autotune.clear()
    yield
    resize._clear_requests()
    election.reset()
    autotune.clear()
    config.reset()


def _endpoints(n):
    return [("127.0.0.1", p) for p in free_ports(n)]


def _wire(eps, io_deadline_ms=0):
    n = len(eps)
    with ThreadPoolExecutor(n) as ex:
        futs = [ex.submit(HostCommunicator, r, n, eps, 30000, None,
                          io_deadline_ms) for r in range(n)]
        return [f.result(timeout=60) for f in futs]


def _controllers(eps, comms, **kw):
    m = resize.Membership(0, eps)
    return [resize.ResizeController(c, m, **kw) for c in comms]


def _boundaries(ctls, listeners=(), listener_kw=None):
    """Run one step boundary on every controller (and joiner waits)
    concurrently; returns (outcomes, join_results) where each element is
    the value or the raised exception."""
    listener_kw = listener_kw or {}
    with ThreadPoolExecutor(len(ctls) + len(listeners)) as ex:
        bf = [ex.submit(c.step_boundary) for c in ctls]
        jf = [ex.submit(li.wait, 30.0, **listener_kw) for li in listeners]
        outs, joins = [], []
        for f in bf:
            try:
                outs.append(f.result(timeout=WALL))
            except Exception as e:  # noqa: BLE001 — asserted by callers
                outs.append(e)
        for f in jf:
            try:
                joins.append(f.result(timeout=WALL))
            except Exception as e:  # noqa: BLE001
                joins.append(e)
    return outs, joins


def _close_all(ctls):
    for c in ctls:
        try:
            c.comm.close()
        except Exception:  # noqa: BLE001 — already-closed is fine here
            pass


def _allreduce_check(ctls):
    """Every live controller's ring agrees on a sum allreduce."""
    n = len(ctls)

    def work(c):
        a = np.full((16,), float(c.rank + 1), np.float32)
        c.comm.allreduce(a)
        return float(a[0])

    with ThreadPoolExecutor(n) as ex:
        vals = list(ex.map(work, ctls))
    expect = sum(range(1, n + 1))
    assert vals == [expect] * n


# ---------------------------------------------------------------- machine


class TestMembershipMachine:
    def test_propose_validation(self):
        eps = _endpoints(2)
        comms = _wire(eps)
        ctls = _controllers(eps, comms)
        try:
            with pytest.raises(resize.ResizeRejected):
                ctls[1].propose(drain=[1])          # not the leader
            with pytest.raises(resize.ResizeRejected):
                ctls[0].propose(drain=[0])          # the leader itself
            # ... unless the proposal is a leadership handoff
            # (runtime/election.py's planned path)
            assert ctls[0].propose(evict=[0], handoff=True)
            ctls[0]._pending.clear()
            with pytest.raises(resize.ResizeRejected):
                ctls[0].propose(drain=[5])          # unknown rank
            with pytest.raises(resize.ResizeRejected):
                ctls[0].propose(                    # already a member
                    join=[{"ring": eps[1], "sync": ("127.0.0.1", 1)}])
            with pytest.raises(resize.ResizeRejected):
                ctls[0].propose(drain=[1], target_epoch=0)  # stale epoch
        finally:
            _close_all(ctls)

    def test_no_proposal_is_continue(self):
        eps = _endpoints(2)
        ctls = _controllers(eps, _wire(eps))
        try:
            outs, _ = _boundaries(ctls)
            assert outs == [resize.CONTINUE, resize.CONTINUE]
            assert all(c.membership.epoch == 0 for c in ctls)
        finally:
            _close_all(ctls)

    def test_epochs_monotonic_under_queued_proposals(self):
        """Two queued grow proposals commit as epochs 1 then 2 — strictly
        monotonic, one membership change per boundary."""
        eps = _endpoints(2)
        ctls = _controllers(
            eps, _wire(eps), state_provider=lambda: {"w": np.arange(4.0)})
        joined = []
        try:
            for expect_epoch in (1, 2):
                ring_ep = _endpoints(1)[0]
                li = resize.JoinListener()
                ctls[0].propose(
                    join=[{"ring": ring_ep, "sync": li.endpoint}])
                outs, joins = _boundaries(ctls, [li])
                assert all(o == resize.COMMITTED for o in outs), outs
                ctl_new, state = joins[0]
                joined.append(ctl_new)
                ctls.append(ctl_new)
                assert list(state) == ["w"]
                epochs = {c.membership.epoch for c in ctls}
                assert epochs == {expect_epoch}
            assert len(ctls) == 4
            _allreduce_check(ctls)
        finally:
            _close_all(ctls)

    def test_stale_request_rejected_at_pop(self):
        """A queued request whose target rank left in the meantime is
        rejected at pop time and does NOT wedge the queue or the epoch."""
        eps = _endpoints(3)
        ctls = _controllers(eps, _wire(eps))
        try:
            ctls[0].propose(drain=[2])
            ctls[0].propose(drain=[2])   # stale after the first commits
            outs, _ = _boundaries(ctls)
            assert outs[2] == resize.DEPARTED
            survivors = ctls[:2]
            outs, _ = _boundaries(survivors)
            # the stale request was dropped: no proposal ran
            assert outs == [resize.CONTINUE, resize.CONTINUE]
            assert {c.membership.epoch for c in survivors} == {1}
        finally:
            _close_all(ctls)


# ------------------------------------------------------------------ legs


class TestJoinLeg:
    def test_join_ships_state_and_rekeys_autotune(self):
        eps = _endpoints(2)
        state = {"w": np.arange(8.0), "b": np.ones((2, 3), np.float32)}
        ctls = _controllers(eps, _wire(eps),
                            state_provider=lambda: dict(state))
        # A winner cache measured at the OLD membership size must not
        # survive the commit (fingerprint keys on process count).
        fp = autotune.fingerprint(process_count=2)
        autotune.activate({"version": autotune.CACHE_VERSION,
                           "fingerprint": fp,
                           "digest": autotune.fingerprint_digest(fp),
                           "cells": {}})
        assert autotune.active() is not None
        li = resize.JoinListener()
        ring_ep = _endpoints(1)[0]
        ctls[0].propose(join=[{"ring": ring_ep, "sync": li.endpoint}])
        try:
            outs, joins = _boundaries(ctls, [li])
            assert outs == [resize.COMMITTED, resize.COMMITTED]
            ctl3, shipped = joins[0]
            ctls.append(ctl3)
            assert ctl3.rank == 2 and ctl3.membership.size == 3
            assert not ctl3.fenced
            np.testing.assert_array_equal(shipped["w"], state["w"])
            np.testing.assert_array_equal(shipped["b"], state["b"])
            assert shipped["b"].dtype == np.float32
            _allreduce_check(ctls)
            # the commit re-keyed the cache: measured-at-2 is stale at 3
            assert autotune.active() is None
        finally:
            _close_all(ctls)

    def test_rekey_helper_directly(self):
        fp = autotune.fingerprint(process_count=2)
        doc = {"version": autotune.CACHE_VERSION, "fingerprint": fp,
               "digest": autotune.fingerprint_digest(fp), "cells": {}}
        autotune.activate(doc)
        stale = obs_metrics.registry.counter(
            "tmpi_autotune_cache_stale_total").value()
        assert autotune.rekey(process_count=2) is not None
        assert autotune.active() is not None     # digest still matches
        assert autotune.rekey(process_count=4) is None
        assert autotune.active() is None
        assert obs_metrics.registry.counter(
            "tmpi_autotune_cache_stale_total").value() == stale + 1


class TestDrainEvictLegs:
    def test_drain_renumbers_survivors(self):
        eps = _endpoints(3)
        ctls = _controllers(eps, _wire(eps))
        try:
            ctls[0].propose(drain=[1])
            outs, _ = _boundaries(ctls)
            assert outs == [resize.COMMITTED, resize.DEPARTED,
                            resize.COMMITTED]
            survivors = [ctls[0], ctls[2]]
            assert [c.rank for c in survivors] == [0, 1]
            assert {c.membership.epoch for c in survivors} == {1}
            assert ctls[1].membership.epoch == 1   # it heard the verdict
            _allreduce_check(survivors)
        finally:
            _close_all(ctls)

    def test_evict_via_request_queue(self):
        config.set("resize_enabled", True)
        eps = _endpoints(3)
        ctls = _controllers(eps, _wire(eps))
        try:
            assert resize.enqueue_request(
                {"action": "evict", "rank": 1}) == 1
            outs, _ = _boundaries(ctls)
            assert outs == [resize.COMMITTED, resize.DEPARTED,
                            resize.COMMITTED]
            assert resize.pending_requests() == 0
        finally:
            _close_all(ctls)

    def test_request_queue_requires_arming(self):
        with pytest.raises(resize.ResizeRejected):
            resize.enqueue_request({"action": "drain"})


# ----------------------------------------------------------------- chaos


class _DiesInQuiesce(resize.ResizeController):
    """Test seam: this member 'is killed' inside the resize window —
    after it learned the proposal, before the quiesce barrier — exactly
    the chaos-kill-mid-quiesce cell."""

    def _run_proposal(self, proposal, cfg):
        self.comm.close()
        raise InjectedFault("chaos kill mid-quiesce")


class TestChaosAbort:
    def test_blackholed_ship_aborts_cleanly(self):
        """Chaos (runtime/chaos.py blackhole) on the state-ship window:
        the ship times out, the verdict says ABORT, the joiner's fence
        discards the state, the OLD ring keeps training, and a clean
        retry commits."""
        config.set("resize_io_deadline_ms", 1500)
        eps = _endpoints(2)
        ctls = _controllers(eps, _wire(eps),
                            state_provider=lambda: {"w": np.zeros(4)})
        li = resize.JoinListener()
        proxy = chaos.ChaosProxy(li.endpoint,
                                 chaos.FaultSpec(blackhole_after_bytes=0),
                                 seed=7)
        ring_ep = _endpoints(1)[0]
        try:
            ctls[0].propose(join=[{"ring": ring_ep,
                                   "sync": proxy.endpoint}])
            outs, joins = _boundaries(ctls)
            assert outs == [resize.ABORTED, resize.ABORTED]
            assert {c.membership.epoch for c in ctls} == {0}
            assert proxy.stats["blackholes"] >= 1
            _allreduce_check(ctls)           # the old ring never stopped
            # clean retry commits at epoch 1
            li2 = resize.JoinListener()
            ctls[0].propose(join=[{"ring": ring_ep,
                                   "sync": li2.endpoint}])
            outs, joins = _boundaries(ctls, [li2])
            assert outs == [resize.COMMITTED, resize.COMMITTED]
            ctl3, _state = joins[0]
            ctls.append(ctl3)
            assert {c.membership.epoch for c in ctls} == {1}
            _allreduce_check(ctls)
        finally:
            proxy.close()
            li.close()
            _close_all(ctls)

    def test_member_killed_mid_quiesce_aborts_atomically(self):
        """A member dying inside the resize window (post-proposal,
        pre-barrier) aborts every survivor with the epoch UNCHANGED —
        no rank ever reaches the new epoch, membership is never split."""
        eps = _endpoints(3)
        comms = _wire(eps, io_deadline_ms=3000)
        m = resize.Membership(0, eps)
        ctls = [resize.ResizeController(comms[0], m),
                resize.ResizeController(comms[1], m),
                _DiesInQuiesce(comms[2], m)]
        li = resize.JoinListener()
        ring_ep = _endpoints(1)[0]
        try:
            ctls[0].propose(join=[{"ring": ring_ep, "sync": li.endpoint}])
            outs, _ = _boundaries(ctls)
            assert isinstance(outs[2], InjectedFault)
            for o in outs[:2]:
                assert isinstance(o, resize.ResizeAborted), outs
            assert {c.membership.epoch for c in ctls} == {0}
            assert not any(o == resize.COMMITTED for o in outs)
        finally:
            li.close()
            _close_all(ctls)


# ------------------------------------------------------------- rejoining


class TestRejoin:
    def test_state_server_roundtrip(self):
        state = {"w": np.arange(6.0), "step": np.asarray([7])}
        with resize.StateServer(lambda: dict(state),
                                meta={"epoch": 3}) as srv:
            meta, got = resize.rejoin_sync(srv.endpoint, timeout_s=5.0)
        assert meta["phase"] == "rejoin_state" and meta["epoch"] == 3
        np.testing.assert_array_equal(got["w"], state["w"])
        assert int(got["step"][0]) == 7

    def test_maybe_rejoin_env_gating(self, monkeypatch):
        monkeypatch.delenv(resize.REJOIN_ENV, raising=False)
        assert resize.maybe_rejoin() is None
        monkeypatch.setenv(resize.REJOIN_ENV, "1")
        monkeypatch.delenv(resize.REJOIN_PEER_ENV, raising=False)
        assert resize.maybe_rejoin() is None      # no peer configured
        with resize.StateServer(lambda: {"w": np.ones(3)}) as srv:
            monkeypatch.setenv(resize.REJOIN_PEER_ENV,
                               f"{srv.endpoint[0]}:{srv.endpoint[1]}")
            meta, got = resize.maybe_rejoin(timeout_s=5.0)
        np.testing.assert_array_equal(got["w"], np.ones(3))

    def test_unreachable_peer_is_recoverable(self):
        dead = _endpoints(1)[0]
        with pytest.raises(resize.ResizeAborted):
            resize.rejoin_sync(dead, timeout_s=1.0)

    def test_malformed_peer_env_is_recoverable(self, monkeypatch):
        # not host:port -> the promised recoverable ResizeAborted, never
        # an unclassified ValueError killing the restarted worker
        monkeypatch.setenv(resize.REJOIN_ENV, "1")
        monkeypatch.setenv(resize.REJOIN_PEER_ENV, "myhost")
        with pytest.raises(resize.ResizeAborted, match="host:port"):
            resize.maybe_rejoin(timeout_s=1.0)


# ------------------------------------------------------------- POST /resize


class TestServeResizeRoute:
    def _post(self, url, body: bytes):
        req = urllib.request.Request(
            url + "/resize", data=body, method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=5) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def test_route_queues_when_armed(self):
        srv = serve.ObsHTTPServer(registry=obs_metrics.Registry(),
                                  health=serve.HealthState(),
                                  scrape=False)
        try:
            code, doc = self._post(srv.url, b'{"action": "drain"}')
            assert code == 409                  # resize_enabled off
            config.set("resize_enabled", True)
            code, doc = self._post(srv.url, b'{"action": "drain"}')
            assert code == 200 and doc["queued"] == 1
            assert resize.pending_requests() == 1
            code, doc = self._post(srv.url, b"not json")
            assert code == 400
        finally:
            srv.close()


# ------------------------------------------------------------- RCA rules


def _rec(kind, wall, rank=0, **data):
    return {"v": 1, "wall": wall, "t_ns": 0, "rank": rank, "pid": 1,
            "seq": 0, "kind": kind, "corr": 0, "data": data}


def _rule(name):
    return next(r for r in rca.RULES if r.name == name)


class TestRcaRules:
    def test_aborted_resize_chain(self):
        tl = [
            _rec("resize.propose", 1.0, target_epoch=3, evict=[]),
            _rec("chaos.fault", 2.0, fault="blackhole"),
            _rec("resize.quiesce", 3.0, epoch=2),
            _rec("resize.abort", 4.0, epoch=2, reason="ship blackholed"),
            _rec("resize.commit", 9.0, epoch=3),
        ]
        v = _rule("aborted_resize").match(tl)
        assert v is not None and v["confidence"] == 1.0
        assert "epoch 2" in v["summary"]
        assert "blackhole" in v["summary"]
        assert _rule("aborted_resize").match(
            [_rec("resize.propose", 1.0)]) is None   # abort is required

    def test_straggler_evict_chain(self):
        tl = [
            _rec("chaos.fault", 1.0, fault="straggler", delay_ms=80),
            _rec("supervisor.scale", 2.0, rank=-1, action="evict"),
            _rec("resize.propose", 3.0, evict=[2], drain=[]),
            _rec("resize.commit", 4.0, epoch=1),
            _rec("resize.depart", 5.0, rank=2, evicted=True),
        ]
        v = _rule("straggler_evict").match(tl)
        assert v is not None and v["confidence"] == 1.0
        assert "[2]" in v["summary"]
        # a drain-only commit is NOT an eviction story
        tl2 = [_rec("resize.propose", 1.0, evict=[], drain=[1]),
               _rec("resize.commit", 2.0, epoch=1)]
        assert _rule("straggler_evict").match(tl2) is None

    def test_analyze_ranks_abort_over_transport_fallback(self, tmp_path):
        seg = tmp_path / "journal-r0-p1-0001.jsonl"
        recs = [
            _rec("chaos.fault", 1.0, fault="reset"),
            _rec("resize.propose", 2.0, evict=[]),
            _rec("resize.quiesce", 3.0, epoch=0),
            _rec("resize.abort", 4.0, epoch=0, reason="ring reset"),
        ]
        seg.write_text("".join(json.dumps(r) + "\n" for r in recs))
        report = rca.analyze(str(tmp_path))
        assert report["verdicts"]
        assert report["verdicts"][0]["rule"] == "aborted_resize"


# ------------------------------------------------------- autoscaler policy


def _load_elastic_launch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "elastic_launch.py")
    spec = importlib.util.spec_from_file_location("_elastic_launch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestScaleSensorDeltas:
    def test_skew_is_per_sweep_delta_not_absolute(self):
        """The cumulative gauge's labels survive a resize renumbering,
        so the sensor must feed DELTAS: a frozen row (its rank departed)
        stops being evidence; an absolute read would keep naming it."""
        el = _load_elastic_launch()
        import types as _types

        sensor = el.ScaleSensor(_types.SimpleNamespace(
            health_poll_port=1, health_poll_host="127.0.0.1",
            health_poll_stride=0, health_poll_timeout=0.1,
            autoscale_window=30.0))
        readings = iter([
            {2: 5.0},               # sweep 1: baseline only
            {2: 5.8},               # sweep 2: rank 2 moved
            {2: 5.8},               # sweep 3: frozen (rank departed)
            {2: 5.8, 1: 0.4},       # sweep 4: a new label baselines
        ])
        current = {}

        def fake_get(rank, path):
            if "/metrics" in path:
                return "\n".join(
                    f'tmpi_rank_skew_attributed_seconds{{rank="{r}"}} {v}'
                    for r, v in current.items()).encode()
            return None

        sensor._get = fake_get
        current = next(readings)
        assert sensor.sweep(3)[2]["skew_s"] == 0.0    # first sight
        current = next(readings)
        assert sensor.sweep(3)[2]["skew_s"] == pytest.approx(0.8)
        current = next(readings)
        assert sensor.sweep(3)[2]["skew_s"] == 0.0    # frozen row
        current = next(readings)
        out = sensor.sweep(3)
        assert out[1]["skew_s"] == 0.0                # new label baselines
        assert out[2]["skew_s"] == 0.0


class TestLeaderCache:
    """ROADMAP item-4 remainder: the supervisor primes its first resize
    dial from the majority ``tmpi_leader_rank`` the sweep already reads,
    instead of probing launch-time rank 0 and eating a 307 hop."""

    def _sensor(self, el):
        import types as _types

        return el.ScaleSensor(_types.SimpleNamespace(
            health_poll_port=9000, health_poll_host="127.0.0.1",
            health_poll_stride=2, health_poll_timeout=0.1,
            autoscale_window=30.0))

    def test_sweep_learns_majority_leader(self):
        el = _load_elastic_launch()
        sensor = self._sensor(el)
        votes = {0: 3, 1: 3, 2: 0}   # rank 2 lags behind the handoff

        def fake_get(rank, path):
            if path == "/metrics":
                return f"tmpi_leader_rank {votes[rank]}\n".encode()
            return None

        sensor._get = fake_get
        sensor.sweep(3)
        assert sensor.leader_rank == 3

    def test_tie_breaks_to_lowest_rank(self):
        el = _load_elastic_launch()
        sensor = self._sensor(el)
        votes = {0: 3, 1: 1}

        def fake_get(rank, path):
            if path == "/metrics":
                return f"tmpi_leader_rank {votes[rank]}\n".encode()
            return None

        sensor._get = fake_get
        sensor.sweep(2)
        assert sensor.leader_rank == 1

    def test_unreachable_ranks_leave_cache_unset(self):
        el = _load_elastic_launch()
        sensor = self._sensor(el)
        sensor._get = lambda rank, path: None
        sensor.sweep(3)
        assert sensor.leader_rank is None

    def test_sensed_url_dials_leader_inbox_first(self):
        el = _load_elastic_launch()
        auto = el.Autoscaler.__new__(el.Autoscaler)
        auto.sensor = self._sensor(el)
        auto._leader_url = None
        assert auto._sensed_leader_url() is None      # nothing sensed yet
        auto.sensor.leader_rank = 3
        assert auto._sensed_leader_url() == \
            "http://127.0.0.1:9006/resize"            # base 9000 + 3*2
        # a 307-proven endpoint outranks the gauge read
        auto._leader_url = "http://127.0.0.1:9002/resize"
        assert (auto._leader_url or auto._sensed_leader_url()) == \
            "http://127.0.0.1:9002/resize"


class TestAutoscalerPolicy:
    def test_evict_needs_sustained_attribution(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_share=0.5,
                                evict_sweeps=3)
        sweep = {0: {"drift": None, "skew_s": 0.01},
                 1: {"drift": None, "skew_s": 0.02},
                 2: {"drift": None, "skew_s": 0.9}}
        assert p.observe(sweep) is None
        assert p.observe(sweep) is None
        assert p.observe(sweep) == {"action": "evict", "rank": 2}
        # the decision reset the counters: fresh evidence required
        assert p.observe(sweep) is None

    def test_leader_is_evictable(self):
        # Leadership is a role, not immunity (runtime/election.py): a
        # straggling rank 0 is named like any other rank — the leader's
        # controller routes the request through the planned handoff at
        # the boundary (_shape_abstract flags handoff + replay).
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=1, max_nproc=4, evict_sweeps=2)
        sweep = {0: {"drift": None, "skew_s": 5.0},
                 1: {"drift": None, "skew_s": 0.0}}
        assert p.observe(sweep) is None
        assert p.observe(sweep) == {"action": "evict", "rank": 0}

    def test_interrupted_streak_resets(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_sweeps=3)
        bad = {0: {"drift": None, "skew_s": 0.0},
               1: {"drift": None, "skew_s": 0.0},
               2: {"drift": None, "skew_s": 1.0}}
        calm = {r: {"drift": None, "skew_s": 0.0} for r in range(3)}
        assert p.observe(bad) is None
        assert p.observe(bad) is None
        assert p.observe(calm) is None            # streak broken
        assert p.observe(bad) is None
        assert p.observe(bad) is None
        assert p.observe(bad) == {"action": "evict", "rank": 2}

    def test_grow_on_sustained_sag_and_drain_on_idle(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, up_drift=0.85,
                                up_sweeps=2, drain_drift=1.2,
                                drain_sweeps=2)
        sag = {r: {"drift": 0.7, "skew_s": 0.0} for r in range(3)}
        assert p.observe(sag) is None
        assert p.observe(sag) == {"action": "grow"}
        idle = {r: {"drift": 1.5, "skew_s": 0.0} for r in range(3)}
        assert p.observe(idle) is None
        assert p.observe(idle) == {"action": "drain", "rank": 2}
        # at max size, sag cannot grow
        p4 = el.AutoscalerPolicy(min_nproc=2, max_nproc=3, up_sweeps=1)
        full = {r: {"drift": 0.5, "skew_s": 0.0} for r in range(3)}
        assert p4.observe(full) is None


class TestAutoscalerAlertEvidence:
    """The alert plane (obs/alerts.py) as a second evidence channel:
    firing alerts from each rank's GET /alerts vote beside the drift and
    skew sensors — already debounced once by their for: duration, but
    the policy still demands ITS consecutive-sweep evidence."""

    @staticmethod
    def _alert(name, **annotation):
        return {"name": name, "severity": "warning",
                "annotation": annotation}

    def test_sag_alert_votes_grow_without_a_drift_probe(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, up_sweeps=2)
        sag = {r: {"drift": None, "skew_s": 0.0,
                   "alerts": ([self._alert("step_rate_sag")]
                              if r == 1 else [])}
               for r in range(3)}
        assert p.observe(sag) is None
        assert p.observe(sag) == {"action": "grow"}

    def test_straggler_alert_nominates_the_annotated_rank(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_sweeps=2)
        # The named rank accrues SOME skew this sweep (corroboration)
        # but below the sensor's own 0.5 evict share — only the alert
        # channel nominates.
        sweep = {r: {"drift": None, "skew_s": 0.1,
                     "alerts": [self._alert("straggler_skew", rank=2,
                                            value=0.9)]}
                 for r in range(3)}
        assert p.observe(sweep) is None
        assert p.observe(sweep) == {"action": "evict", "rank": 2}

    def test_stale_alert_rank_without_fresh_skew_never_evicts(self):
        # After a resize renumbers survivors, a stale straggler_skew
        # firing keeps naming the departed rank's OLD number from the
        # never-remapped gauge label — but that row's per-sweep delta
        # is zero, so the nomination must not corroborate (the innocent
        # rank now wearing the number is never evicted).
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_sweeps=1)
        sweep = {r: {"drift": None, "skew_s": 0.0,
                     "alerts": [self._alert("straggler_skew", rank=2,
                                            value=0.9)]}
                 for r in range(3)}
        for _ in range(4):
            assert p.observe(sweep) is None

    def test_alert_naming_the_leader_evicts_with_corroboration(self):
        # No leader immunity: a straggler_skew firing that names rank 0
        # nominates it exactly like any other rank, as long as the
        # per-sweep delta corroborates — eviction then rides the
        # planned-handoff path (runtime/election.py), not a restart.
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_sweeps=2)
        sweep = {r: {"drift": None, "skew_s": 0.2 if r == 0 else 0.0,
                     "alerts": [self._alert("straggler_skew", rank=0)]}
                 for r in range(3)}
        assert p.observe(sweep) is None
        assert p.observe(sweep) == {"action": "evict", "rank": 0}

    def test_alert_streak_interrupted_resets(self):
        el = _load_elastic_launch()
        p = el.AutoscalerPolicy(min_nproc=2, max_nproc=4, evict_sweeps=2)
        bad = {r: {"drift": None, "skew_s": 0.1,
                   "alerts": [self._alert("straggler_skew", rank=2)]}
               for r in range(3)}
        calm = {r: {"drift": None, "skew_s": 0.0, "alerts": []}
                for r in range(3)}
        assert p.observe(bad) is None
        assert p.observe(calm) is None           # streak broken
        assert p.observe(bad) is None
        assert p.observe(bad) == {"action": "evict", "rank": 2}


class TestGrowEndpoints:
    """--grow-endpoints: the static provisioner pool that turns advisory
    autoscaler grow requests into actionable joins."""

    def test_parse_forms(self):
        el = _load_elastic_launch()
        pool = el.parse_grow_endpoints("h1:7000, h2:7000:7100 ,")
        assert pool == [
            {"ring": ["h1", 7000], "sync": ["h1", 7001]},
            {"ring": ["h2", 7000], "sync": ["h2", 7100]},
        ]
        assert el.parse_grow_endpoints("") == []
        assert el.parse_grow_endpoints(None) == []

    def test_parse_rejects_malformed_entries(self):
        el = _load_elastic_launch()
        for bad in ("h1", ":7000", "h1:x", "h1:7000:y",
                    "h1:1:2:3"):
            with pytest.raises(ValueError):
                el.parse_grow_endpoints(bad)

    def _scaler(self, el, pool):
        import types as _types

        args = _types.SimpleNamespace(
            health_poll_port=1, health_poll_host="127.0.0.1",
            health_poll_stride=1, health_poll_timeout=0.2,
            autoscale_window=60.0, autoscale_min=2, autoscale_max=4,
            autoscale_interval=1.0, scale_up_drift=0.85,
            scale_up_sweeps=1, scale_evict_share=0.5,
            scale_evict_sweeps=1, scale_drain_drift=0.0,
            scale_drain_sweeps=1, grow_pool=pool)

        class _J:
            def __init__(self):
                self.records = []

            def emit(self, kind, **data):
                self.records.append((kind, data))

        a = el.Autoscaler(args, _J())
        a.sensor.sweep = lambda nproc: {}
        return a

    @staticmethod
    def _deliver(el, monkeypatch):
        """Stub a leader that accepts every POST (the real one rides
        urllib against --health-poll-port)."""
        import contextlib
        import io

        monkeypatch.setattr(
            el.urllib.request, "urlopen",
            lambda req, timeout=None: contextlib.closing(io.BytesIO(b"{}")))

    def test_grow_pops_one_slot_and_journals_the_endpoints(
            self, monkeypatch):
        el = _load_elastic_launch()
        self._deliver(el, monkeypatch)
        pool = el.parse_grow_endpoints("h1:7000,h2:8000")
        a = self._scaler(el, pool)
        a.policy.observe = lambda sweep: {"action": "grow"}
        d1 = a.maybe_scale(2)
        assert d1["join"] == [{"ring": ["h1", 7000],
                               "sync": ["h1", 7001]}]
        d2 = a.maybe_scale(3)
        assert d2["join"] == [{"ring": ["h2", 8000],
                               "sync": ["h2", 8001]}]
        # exhausted pool: the request falls back to advisory (no join)
        d3 = a.maybe_scale(4)
        assert "join" not in d3
        scale = [(k, d) for k, d in a.journal.records
                 if k == "supervisor.scale"]
        assert [("join" in d) for _k, d in scale] == [True, True, False]
        assert scale[0][1]["join"] == d1["join"]

    def test_undelivered_grow_restores_the_slot(self):
        # The leader is unreachable (port 1 refuses): the popped
        # standby slot must return to the FRONT of the pool — an
        # undelivered request never consumed the worker, and with a
        # 1-slot pool losing it would silently turn every future grow
        # advisory.
        el = _load_elastic_launch()
        pool = el.parse_grow_endpoints("h1:7000")
        a = self._scaler(el, pool)
        a.policy.observe = lambda sweep: {"action": "grow"}
        d = a.maybe_scale(2)
        assert d["join"] == [{"ring": ["h1", 7000],
                              "sync": ["h1", 7001]}]
        assert a.grow_pool == pool  # restored, not leaked
        kinds = [k for k, _d in a.journal.records]
        assert "supervisor.scale_undelivered" in kinds
        # The retry provisions the SAME slot again.
        d2 = a.maybe_scale(2)
        assert d2["join"] == d["join"]

    def test_non_grow_decisions_never_touch_the_pool(self):
        el = _load_elastic_launch()
        pool = el.parse_grow_endpoints("h1:7000")
        a = self._scaler(el, pool)
        a.policy.observe = lambda sweep: {"action": "evict", "rank": 2}
        d = a.maybe_scale(3)
        assert "join" not in d and len(a.grow_pool) == 1


# -------------------------------------------------------- engine boundary


class _StubController:
    def __init__(self, after, outcome=resize.DEPARTED):
        self.after = after
        self.outcome = outcome
        self.calls = 0
        self.membership = resize.Membership(7, [("127.0.0.1", 1)])

    def step_boundary(self):
        self.calls += 1
        return self.outcome if self.calls >= self.after else resize.CONTINUE


class TestEngineBoundary:
    def test_departed_ends_train_early(self, world):
        from torchmpi_tpu.engine import AllReduceSGDEngine

        def loss(params, batch):
            xb, yb = batch
            pred = xb @ params["w"]
            return jnp.mean((pred - yb) ** 2)

        eng = AllReduceSGDEngine(loss, lr=0.01, mode="compiled")
        stub = _StubController(after=3)
        eng.step_boundaries.append(resize.engine_boundary(stub))
        later = []                      # a boundary attached after it
        eng.step_boundaries.append(lambda state: later.append(state["t"]))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 2, 4)).astype(np.float32)
        y = rng.normal(size=(8, 2)).astype(np.float32)
        it = [(x, y)] * 6
        state = eng.train({"w": jnp.zeros((4,), jnp.float32)}, it)
        assert state.get("departed") is True
        assert stub.calls == 3
        assert state["t"] == 3          # three steps ran, then departure
        assert later == [1, 2]          # skipped on the step that ended it

    def test_committed_ends_train_for_rebuild(self, world):
        """A COMMITTED membership change ends train() with
        state["resized"] = the new epoch: the compiled world cannot
        follow a live world-size change — the elastic layer rebuilds
        the engine against the new membership."""
        from torchmpi_tpu.engine import AllReduceSGDEngine

        def loss(params, batch):
            xb, yb = batch
            return jnp.mean((xb @ params["w"] - yb) ** 2)

        eng = AllReduceSGDEngine(loss, lr=0.01, mode="compiled")
        eng.step_boundaries.append(resize.engine_boundary(
            _StubController(after=2, outcome=resize.COMMITTED)))
        rng = np.random.default_rng(0)
        it = [(rng.normal(size=(8, 2, 4)).astype(np.float32),
               rng.normal(size=(8, 2)).astype(np.float32))] * 5
        state = eng.train({"w": jnp.zeros((4,), jnp.float32)}, it)
        assert state.get("resized") == 7       # the stub's new epoch
        assert "departed" not in state
        assert state["t"] == 2

    @pytest.mark.parametrize("elects", [True, False])
    def test_a_boundary_fault_goes_to_the_election_when_there_is_one(
            self, elects):
        """``engine_boundary`` hands a transport fault of the controller's
        to the coordinator, whose outcome ends the loop as a commit does;
        with none attached, or for any other error, it propagates."""
        class Faulting(_StubController):
            def step_boundary(self):
                raise self.outcome

        class Coordinator:
            def on_boundary_fault(self, exc):
                assert isinstance(exc, HostcommError)
                return resize.COMMITTED

        boundary = resize.engine_boundary(
            Faulting(after=1, outcome=HostcommError("leader gone")),
            election=Coordinator() if elects else None)
        state = {}
        if elects:
            boundary(state)
            assert state == {"resized": 7}
        else:
            with pytest.raises(HostcommError):
                boundary(state)
        with pytest.raises(KeyError):       # not a transport fault
            resize.engine_boundary(
                Faulting(after=1, outcome=KeyError("bug")),
                election=Coordinator())(state)
