"""Inference serving plane (torchmpi_tpu/serving/): paged KV pool
accounting + deadline-aware eviction, the iteration-level scheduler's
join/leave (no head-of-line blocking), typed admission control and
deadline shedding, the router's drain cutover, the frontend→engine
correlation join, drain health precedence, the compiled llama runner's
equivalence with models/llama generation, and the
scheduler-vs-frontend concurrent shape (TSAN-listed in
scripts/sanitize_drill.py — frontend handler threads run admission
under the scheduler lock WHILE the engine's iteration thread
joins/decodes/sheds behind the same lock and the KV pool's own lock
interleaves with both)."""

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from torchmpi_tpu.obs import metrics, serve as obs_serve, tracer
from torchmpi_tpu.obs.history import flatten_families
from torchmpi_tpu.runtime import config
from torchmpi_tpu.serving import serve_config
from torchmpi_tpu.serving.engine import (
    AdmissionRejected, LlamaRunner, ServeEngine, StubRunner)
from torchmpi_tpu.serving.frontend import ServeFrontend
from torchmpi_tpu.serving.kvcache import BlockPool, PoolExhausted
from torchmpi_tpu.serving.router import NoReplicas, ServeRouter

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _fresh_config():
    config.reset()
    yield
    config.reset()


def _cfg(**over):
    """Engine cfg: fast defaults for in-process tests, explicit overrides."""
    cfg = serve_config()
    cfg.update({"block_size": 4, "kv_blocks": 64, "max_batch": 2,
                "max_queue": 8, "default_deadline_ms": 10000,
                "max_new_tokens": 8, "admission_headroom": 0.0,
                "runner": "stub", "stub_token_s": 0.0})
    cfg.update(over)
    return cfg


def _engine(registry=None, **over):
    cfg = _cfg(**over)
    reg = registry if registry is not None else metrics.Registry()
    pool = BlockPool(cfg["kv_blocks"], cfg["block_size"], registry=reg)
    return ServeEngine(runner=StubRunner(cfg["max_batch"]), pool=pool,
                       registry=reg, cfg=cfg), reg


def _flat(reg):
    return flatten_families(reg.collect())


def _drive(eng, reqs, max_iters=200):
    """Single-step the scheduler until every request settles."""
    for _ in range(max_iters):
        if all(r.done.is_set() for r in reqs):
            return
        eng.iteration()
    raise AssertionError(
        f"requests did not settle in {max_iters} iterations: "
        f"{[(r.id, r.state) for r in reqs]}")


# ------------------------------------------------------------------ pool

class TestKVPool:
    def test_lease_extend_release_accounting(self):
        pool = BlockPool(8, 4)
        got = pool.allocate("a", 10)          # ceil(10/4) = 3 blocks
        assert len(got) == 3
        assert pool.used_blocks() == 3 and pool.free_blocks() == 5
        assert pool.table("a") == got
        # growth inside the last block leases nothing new
        assert pool.extend("a", 2) == []      # 12 tokens = still 3 blocks
        new = pool.extend("a", 1)             # 13 tokens -> 4th block
        assert len(new) == 1
        assert pool.headroom() == pytest.approx(4 / 8)
        assert pool.release("a") == 4
        assert pool.free_blocks() == 8
        assert pool.release("a") == 0         # idempotent

    def test_exhaustion_is_atomic_no_partial_lease(self):
        pool = BlockPool(4, 4)
        pool.allocate("a", 8)                 # 2 blocks
        with pytest.raises(PoolExhausted):
            pool.allocate("b", 100)           # needs 25, only 2 free
        # the failed lease must not have leaked partial blocks
        assert pool.free_blocks() == 2
        assert pool.holders() == ["a"]

    def test_deadline_aware_eviction_oldest_deadline_first(self):
        pool = BlockPool(6, 4)
        now = 100.0
        pool.allocate("late", 8, deadline=now + 30)    # 2 blocks
        pool.allocate("soon", 8, deadline=now + 1)     # 2 blocks
        pool.allocate("mid", 8, deadline=now + 10)     # 2 blocks
        evicted = pool.evict_for(2, now, protect=("mid",))
        # closest-to-expiry victim first; the protected lease survives
        assert evicted == ["soon"]
        assert sorted(pool.holders()) == ["late", "mid"]

    def test_expiry_and_metrics(self):
        reg = metrics.Registry()
        pool = BlockPool(8, 4, registry=reg)
        pool.allocate("a", 8, deadline=10.0)
        pool.allocate("b", 8, deadline=99.0)
        assert _flat(reg)["tmpi_kv_blocks_used"] == 4.0
        assert pool.evict_expired(now=11.0) == ["a"]
        flat = _flat(reg)
        assert flat["tmpi_kv_blocks_used"] == 2.0
        assert flat["tmpi_kv_blocks_evicted_total"] == 2.0


# ------------------------------------------------------------- scheduler

class TestIterationScheduling:
    def test_join_leave_no_hol_blocking(self):
        eng, _ = _engine(max_batch=2)
        long = eng.submit([1, 2, 3], max_new=8)
        short = eng.submit([4, 5, 6], max_new=1)
        queued = eng.submit([7, 8, 9], max_new=1)
        # 2 slots: long+short join; short finishes first iteration and
        # leaves; queued joins the freed slot while long keeps decoding —
        # a long generation never blocks a short one behind it.
        eng.iteration()
        assert short.done.is_set() and short.state == "done"
        assert not long.done.is_set()
        eng.iteration()
        assert queued.done.is_set() and queued.state == "done"
        assert not long.done.is_set()
        _drive(eng, [long])
        assert long.state == "done" and len(long.tokens) == 8
        # all leases returned once everyone settled
        assert eng.pool.used_blocks() == 0

    def test_stub_tokens_deterministic(self):
        eng, _ = _engine()
        r1 = eng.submit([9, 9, 9], max_new=4)
        _drive(eng, [r1])
        eng2, _ = _engine()
        r2 = eng2.submit([9, 9, 9], max_new=4)
        _drive(eng2, [r2])
        assert r1.tokens == r2.tokens and len(r1.tokens) == 4


# ------------------------------------------------------------- admission

class TestAdmission:
    def test_queue_full_typed_rejection(self):
        eng, reg = _engine(max_queue=1)
        eng.submit([1], max_new=1)
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit([2], max_new=1)
        assert exc.value.reason == "queue_full"

    def test_kv_pressure_then_recovery(self):
        # 2 blocks of 4: one request's lease (prompt 3 + 1 = 1 block)
        # drops headroom to 0.5, under the 0.6 gate for the second.
        eng, reg = _engine(kv_blocks=2, block_size=4,
                           admission_headroom=0.6, max_queue=8)
        first = eng.submit([1, 2, 3], max_new=2)
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit([4, 5, 6], max_new=2)
        assert exc.value.reason == "kv_pressure"
        # finishing the first request frees its lease: admission recovers
        _drive(eng, [first])
        assert eng.pool.used_blocks() == 0
        second = eng.submit([4, 5, 6], max_new=2)
        _drive(eng, [second])
        assert second.state == "done"

    def test_negative_max_new_floored_to_one(self):
        # A client-supplied negative survives the `int(x) or default`
        # truthiness default; without the floor it would "complete"
        # after the first token (len(tokens) >= -3).
        eng, _ = _engine()
        req = eng.submit([1, 2], max_new=-3)
        _drive(eng, [req])
        assert req.state == "done"
        assert len(req.tokens) == 1

    def test_draining_typed_rejection(self):
        eng, _ = _engine()
        eng.drain(timeout=0.0)
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit([1], max_new=1)
        assert exc.value.reason == "draining"
        eng.undrain()
        assert eng.submit([1], max_new=1).state == "queued"


# ----------------------------------------------------------- deadline shed

class TestDeadlineShed:
    def test_shed_is_typed_counted_and_releases_blocks(self):
        eng, reg = _engine(default_deadline_ms=10)
        req = eng.submit([1, 2, 3], max_new=8)
        time.sleep(0.05)                      # blow the 10 ms deadline
        eng.iteration()
        assert req.done.is_set() and req.state == "shed"
        assert req.shed_reason == "deadline"
        flat = _flat(reg)
        assert flat['tmpi_serve_requests_total{outcome="shed_deadline"}'] \
            == 1.0
        assert eng.pool.used_blocks() == 0


# ------------------------------------------------------- kv-pressure shed

class TestKVPressureEviction:
    def test_evicted_victim_is_shed_and_scheduler_survives(self):
        # block_size=1: every generated token needs a fresh block, so
        # the pool exhausts mid-decode.  A's lease growth evicts B
        # (deadline-aware, A protected); B must leave the ENGINE too —
        # a still-running victim whose lease is gone would KeyError on
        # its own next extend and kill the scheduler thread.
        eng, reg = _engine(block_size=1, kv_blocks=5, max_batch=2,
                           max_new_tokens=8)
        a = eng.submit([1, 2], max_new=8, deadline_ms=60000)   # 3 blocks
        b = eng.submit([3], max_new=8, deadline_ms=120000)     # 2 blocks
        assert eng.pool.free_blocks() == 0
        eng.iteration()         # A's extend evicts B; must not raise
        assert b.done.is_set() and b.state == "shed"
        assert b.shed_reason == "kv_pressure"
        flat = _flat(reg)
        assert flat['tmpi_serve_requests_total{outcome="shed_kv_pressure"}'] \
            == 1.0
        # the scheduler keeps running: A decodes on, and when nothing
        # is left to evict it sheds TYPED instead of dying
        _drive(eng, [a, b])
        assert a.state == "shed" and a.shed_reason == "kv_pressure"
        assert eng.pool.used_blocks() == 0
        assert eng.stats()["queued"] == 0 and eng.stats()["active"] == 0

    def test_scheduler_thread_survives_iteration_error(self):
        # An unexpected exception inside an iteration must be counted
        # and survived — a dead daemon scheduler times out every
        # in-flight and future request with no signal.
        eng, reg = _engine()
        orig = eng.runner.decode
        state = {"failed": False}

        def flaky(tokens, pos, active):
            if not state["failed"]:
                state["failed"] = True
                raise RuntimeError("transient device error")
            return orig(tokens, pos, active)

        eng.runner.decode = flaky
        eng.start()
        try:
            req = eng.submit([1, 2], max_new=2, deadline_ms=10000)
            assert req.done.wait(5.0)
            assert req.state == "done"
            assert _flat(reg)["tmpi_serve_scheduler_errors_total"] == 1.0
        finally:
            eng.stop()


# ---------------------------------------------------------------- router

class TestRouterCutover:
    URLS = {0: "http://127.0.0.1:1", 1: "http://127.0.0.1:2"}

    def test_draining_moves_keys_and_cutover_back(self):
        router = ServeRouter(dict(self.URLS))
        keys = [f"client-{i}" for i in range(32)]
        before = {k: router.route(k) for k in keys}
        assert set(before.values()) == {0, 1}   # both replicas owning
        router.mark_draining(0)
        assert router.routable() == [1]
        assert all(router.route(k) == 1 for k in keys)
        router.unmark(0)
        # recovery restores the ORIGINAL placement — rendezvous hashing
        # moves only the keys it must, and moves them back
        assert {k: router.route(k) for k in keys} == before

    def test_all_draining_raises(self):
        router = ServeRouter(dict(self.URLS))
        router.mark_draining(0)
        router.mark_draining(1)
        with pytest.raises(NoReplicas):
            router.route("any")

    def test_membership_add_extends_ownership(self):
        router = ServeRouter(dict(self.URLS))
        router.add_replica(2, "http://127.0.0.1:3")
        keys = [f"client-{i}" for i in range(64)]
        owners = {router.route(k) for k in keys}
        assert owners == {0, 1, 2}

    def test_probe_falls_back_to_serving_url(self):
        # A router built WITHOUT probe_urls (the autoscaler-grow shape)
        # must still recover a dispatch-marked slot: probe() falls back
        # to the frontend's own GET /serve, so a briefly-crashed-then-
        # restarted replica is not routed around forever.
        eng, _ = _engine()
        eng.start()
        front = ServeFrontend(eng, health=obs_serve.HealthState(),
                              replica="pf0")
        try:
            router = ServeRouter({0: front.url})
            router.mark_draining(0)          # what dispatch() does on
            assert router.routable() == []   # a transport failure
            assert router.probe() == {0: "healthy"}
            assert router.routable() == [0]
            front.begin_drain()              # handoff window is visible
            assert router.probe() == {0: "draining"}
            assert router.routable() == []
            front.resume()
            assert router.probe() == {0: "healthy"}
            assert router.routable() == [0]
        finally:
            front.close()
            eng.stop()


# -------------------------------------------------- frontend integration

def _post_json(url, body, timeout=10.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


@pytest.fixture()
def live_replica():
    """Engine (background loop) + frontend over private registry/health."""
    reg = metrics.Registry()
    health = obs_serve.HealthState()
    eng, _ = _engine(registry=reg)
    eng.start()
    front = ServeFrontend(eng, health=health, replica="t0")
    yield front, eng, reg, health
    front.close()
    eng.stop()


class TestCorrelationJoin:
    def test_frontend_correlation_matches_engine_span(self, live_replica):
        front, _, _, _ = live_replica
        config.set("obs_trace", True)
        tracer.drain()                        # start from a clean buffer
        status, doc = _post_json(front.url + "/generate",
                                 {"prompt": [1, 2, 3], "max_new": 2})
        assert status == 200
        corr = doc["correlation"]
        assert corr != 0
        spans = {s["name"]: s for s in tracer.drain()
                 if s["correlation"] == corr}
        # the frontend's wait and the engine's work join on one id
        assert "serve.request" in spans
        assert "serve.generate" in spans
        assert spans["serve.generate"]["attrs"]["outcome"] == "done"

    def test_typed_backpressure_over_http(self):
        reg = metrics.Registry()
        eng, _ = _engine(registry=reg, max_queue=1)
        front = ServeFrontend(eng, replica="t1")  # engine NOT started
        try:
            eng.submit([1], max_new=1)            # fill the queue
            status, doc = _post_json(
                front.url + "/generate",
                {"prompt": [2], "max_new": 1, "deadline_ms": 50})
            assert status == 503
            assert doc["error"] == "admission"
            assert doc["reason"] == "queue_full"
        finally:
            front.close()
            eng.stop()


class TestHealthPrecedence:
    def test_drain_is_public_and_stall_outranks_it(self):
        reg = metrics.Registry()
        obs_serve.health.reset()
        try:
            obs_serve.begin_drain("test handoff")
            assert obs_serve.health.evaluate(registry=reg)["state"] \
                == "draining"
            # a wedged loop must outrank an intentional drain: the
            # supervisor's stall conversion wins the race
            obs_serve.health.monitor("engine_step",
                                     degraded_after_s=0.005,
                                     stalled_after_s=0.01)
            time.sleep(0.03)
            assert obs_serve.health.evaluate(registry=reg)["state"] \
                == "stalled"
            obs_serve.health.clear("engine_step")
            assert obs_serve.health.evaluate(registry=reg)["state"] \
                == "draining"
            obs_serve.end_drain()
            assert obs_serve.health.evaluate(registry=reg)["state"] \
                == "healthy"
        finally:
            obs_serve.health.reset()


# ------------------------------------------------------- compiled runner

class TestLlamaRunner:
    def test_prefill_bucket_is_bounded(self):
        # Prefill pads prompts to power-of-two buckets so the jitted
        # graph cache is O(log max_len), not one entry per distinct
        # prompt length (a compile storm under a real load mix).
        from torchmpi_tpu.serving.engine import _bucket_len

        assert _bucket_len(1, 512) == 8
        assert _bucket_len(8, 512) == 8
        assert _bucket_len(9, 512) == 16
        assert _bucket_len(300, 512) == 512
        assert _bucket_len(600, 512) == 512     # capped at cache length
        assert len({_bucket_len(n, 1 << 15) for n in range(1, 513)}) == 7

    @staticmethod
    def _served_and_generated(cfg, prompt, max_new=4):
        """(the tokens the engine serves, ``make_generate_fn``'s) for one
        prompt under one set of weights."""
        import jax
        import numpy as np

        from torchmpi_tpu.models import llama_decode

        runner = LlamaRunner(2, cfg=cfg, max_len=32)
        ecfg = _cfg(max_batch=2, max_new_tokens=max_new, block_size=4,
                    kv_blocks=32)
        pool = BlockPool(ecfg["kv_blocks"], ecfg["block_size"])
        eng = ServeEngine(runner=runner, pool=pool, cfg=ecfg)
        req = eng.submit(prompt, max_new=max_new)
        _drive(eng, [req])
        ref_fn = llama_decode.make_generate_fn(cfg, prompt_len=len(prompt),
                                               max_new=max_new)
        ref = ref_fn(runner.params, np.asarray([prompt], dtype=np.int32),
                     jax.random.PRNGKey(0))
        return req.tokens, [int(t) for t in np.asarray(ref)[0]]

    def test_matches_reference_generation(self):
        from torchmpi_tpu.models import llama

        served, generated = self._served_and_generated(llama.tiny(),
                                                       [1, 2, 3, 4, 5])
        assert served == generated

    def test_serves_experts_and_qk_norm(self):
        """The runner decodes through the model's own step, so what that
        step knows it serves: a mixture of experts with QK-norm, token for
        token (a whole bucket of prompt: an expert's capacity counts pads)."""
        import dataclasses

        from torchmpi_tpu.models import llama

        cfg = dataclasses.replace(llama.moe_tiny(), qk_norm=True,
                                  capacity_factor=8.0)
        served, generated = self._served_and_generated(
            cfg, [1, 2, 3, 4, 5, 6, 7, 8])
        assert served == generated
        plain = self._served_and_generated(
            dataclasses.replace(cfg, qk_norm=False), [1, 2, 3, 4, 5, 6, 7, 8])
        assert plain[0] == plain[1] and plain[0] != served

    @pytest.mark.parametrize("trait,said", [
        ("looped", "looped configuration"),
        ("runs", "stack of runs")])
    def test_refuses_what_decode_refuses(self, trait, said):
        """A configuration the decode path has no cache for is stopped by
        the model's table at the first prefill; nothing is decoded."""
        import dataclasses

        from torchmpi_tpu.models import llama

        kimi = llama.kimi_linear_48b_a3b()
        cfg = {
            "looped": dataclasses.replace(llama.tiny(), ut_steps=2),
            "runs": dataclasses.replace(
                kimi, vocab=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=32, dense_d_ff=96, max_seq=64,
                n_experts=8, expert_top_k=2, kda_heads=4, kda_head_dim=16,
                kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, layer_kinds=kimi.layer_kinds[:2]),
        }[trait]
        runner = LlamaRunner(2, cfg=cfg, max_len=32)
        with pytest.raises(NotImplementedError, match=said):
            runner.prefill(0, [1, 2, 3])
        with pytest.raises(NotImplementedError, match="the decode step"):
            runner.decode([1, 1], [3, 0], [True, False])


# ------------------------------------------------- concurrent race class

class TestSchedulerFrontendConcurrent:
    def test_submit_storm_against_live_scheduler(self, live_replica):
        # The sanitize drill's serving race class: frontend handler
        # threads run admission (engine lock + pool lock) WHILE the
        # iteration thread joins/decodes/sheds behind the same locks.
        front, eng, reg, _ = live_replica
        outcomes = []
        lock = threading.Lock()

        def client(i):
            for j in range(4):
                status, doc = _post_json(
                    front.url + "/generate",
                    {"prompt": [i, j, 7], "max_new": 2,
                     "deadline_ms": 5000})
                with lock:
                    outcomes.append((status, doc.get("error", "ok")))

        with ThreadPoolExecutor(max_workers=8) as pool:
            list(pool.map(client, range(8)))
        assert len(outcomes) == 32
        # every response is a TYPED verdict: done or a typed shed/503 —
        # never a hang, never an untyped error
        assert all(kind in ("ok", "admission", "shed")
                   for _, kind in outcomes)
        done = sum(1 for status, _ in outcomes if status == 200)
        flat = _flat(reg)
        assert flat['tmpi_serve_requests_total{outcome="done"}'] == done
        # the storm drained clean: no leaked leases or stuck slots
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and eng.pool.used_blocks():
            time.sleep(0.01)
        assert eng.pool.used_blocks() == 0
        assert eng.stats()["queued"] == 0
