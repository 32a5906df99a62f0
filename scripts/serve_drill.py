#!/usr/bin/env python
"""Serving-plane acceptance drill: continuous batching under load and
chaos, end to end over real HTTP.

Every leg stands real replicas up in-process (private ``Registry`` +
``HealthState`` per replica — the scale_drill idiom) and drives them
with ``scripts/loadgen.py``'s concurrent clients:

* ``baseline`` — 200+ concurrent clients against one replica; every
  request completes, zero hangs; p50/p99 + tokens/sec land in the
  artifact's ``serve`` section (perf-gated by ``scripts/perf_gate.py``).
* ``admission`` — a deliberately tiny queue/KV pool under a client
  storm: overload comes back as TYPED 503s (``queue_full`` /
  ``kv_pressure``), never unbounded buffering, and the replica serves
  normally again the moment the storm passes (every lease freed).
* ``deadline_shed`` — per-request deadlines against a slow decoder:
  past-deadline requests shed mid-generation with ``reason=deadline``,
  counted in ``tmpi_serve_requests_total{outcome="shed_deadline"}``.
* ``backpressure`` — chaos client personalities (slow / bursty /
  broken sockets via ``runtime/chaos.FaultSpec``): the server sheds
  broken connections without leaking handler threads and keeps
  answering.
* ``sigkill`` — a replica subprocess (``--replica`` mode) is
  SIGKILLed mid-decode (``chaos.kill_after``): the router detects the
  transport failure on dispatch, fails over to the ring's next owner
  (``tmpi_serve_router_failover_total``), and no client hangs.
* ``rolling_restart`` — two replicas behind the router restarted
  one-at-a-time by ``elastic_launch.RollRestarter`` (drain via
  ``POST /drain`` → ``/healthz`` reads ``draining`` → the router's
  probe routes around it → restart → ready): background load keeps
  succeeding through the whole roll.
* ``slo_autoscale`` — the authored ``serve_p99_over_deadline`` alert
  rule (``obs/alerts.py`` rules-path JSON over ``tmpi_serve_p99_ms``)
  fires under overload; ``elastic_launch``'s ScaleSensor reads the
  firing over real HTTP, AutoscalerPolicy converts it into a grow
  decision (GROW_ALERTS), and the ``--grow-endpoints`` pool
  (``parse_grow_endpoints``) names the endpoint the new replica is
  provisioned on — detection turned into capacity.
* ``llama_runner`` — the compiled path: two requests of different
  lengths decoded CONCURRENTLY by ``LlamaRunner``'s per-slot-position
  step match ``models/llama_decode.make_generate_fn`` token for token.

    python scripts/serve_drill.py --quick     # seconds-scale smoke
    python scripts/serve_drill.py             # full drill

Writes ``SERVE_r19.json``: per-leg outcome, the ``serve`` latency /
throughput section, a journal audit, and the PASS/FAIL verdict.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import types
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from torchmpi_tpu.collectives.hostcomm import free_ports  # noqa: E402
from torchmpi_tpu.obs import alerts as obs_alerts  # noqa: E402
from torchmpi_tpu.obs import history as obs_history  # noqa: E402
from torchmpi_tpu.obs import journal as obs_journal  # noqa: E402
from torchmpi_tpu.obs import metrics as obs_metrics  # noqa: E402
from torchmpi_tpu.obs import serve as obs_serve  # noqa: E402
from torchmpi_tpu.obs.export import atomic_write_json  # noqa: E402
from torchmpi_tpu.runtime import chaos, config  # noqa: E402
from torchmpi_tpu.serving.engine import (  # noqa: E402
    LlamaRunner, ServeEngine, StubRunner)
from torchmpi_tpu.serving.frontend import ServeFrontend  # noqa: E402
from torchmpi_tpu.serving.kvcache import BlockPool  # noqa: E402
from torchmpi_tpu.serving.router import ServeRouter  # noqa: E402

# The supervisor halves (RollRestarter, ScaleSensor, AutoscalerPolicy,
# parse_grow_endpoints) live in the stdlib-only launch script; the drill
# drives the SAME classes ``--roll-restart`` / ``--autoscale`` run.
import importlib.util as _ilu  # noqa: E402


def _load_script(name):
    spec = _ilu.spec_from_file_location(
        f"_{name}", os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_elastic_launch = _load_script("elastic_launch")
_loadgen = _load_script("loadgen")


def _serve_cfg(**over):
    """An explicit engine config dict (the ``serve_*`` knob shape) so
    legs tune replicas without mutating global config."""
    cfg = {
        "block_size": 16,
        "kv_blocks": 256,
        "max_batch": 8,
        "max_queue": 64,
        "default_deadline_ms": 10000,
        "max_new_tokens": 32,
        "admission_headroom": 0.02,
        "runner": "stub",
        "stub_token_s": 0.0,
        "drain_timeout_s": 5.0,
    }
    cfg.update(over)
    return cfg


class Replica:
    """One serving replica: private registry + health, engine, frontend,
    and (optionally) the obs endpoint the router/autoscaler probe."""

    def __init__(self, name, port=0, obs_port=None, cfg=None, runner=None,
                 history=None, alerts_engine=None):
        self.name = name
        self.cfg = cfg or _serve_cfg()
        self.registry = obs_metrics.Registry()
        self.health = obs_serve.HealthState(name=name)
        pool = BlockPool(self.cfg["kv_blocks"], self.cfg["block_size"],
                         registry=self.registry)
        if runner is None:
            runner = StubRunner(self.cfg["max_batch"],
                                token_s=self.cfg["stub_token_s"])
        self.engine = ServeEngine(runner=runner, pool=pool,
                                  registry=self.registry,
                                  cfg=self.cfg).start()
        self.front = ServeFrontend(self.engine, port=port,
                                   health=self.health, replica=name)
        self.obs = None
        if obs_port is not None:
            self.obs = obs_serve.ObsHTTPServer(
                port=obs_port, registry=self.registry, health=self.health,
                scrape=False, history=history, alerts=alerts_engine)

    @property
    def url(self):
        return self.front.url

    def metrics(self):
        return obs_history.flatten_families(self.registry.collect())

    def close(self):
        self.front.close()
        self.engine.stop()
        if self.obs is not None:
            self.obs.close()


def _get_json(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _post_json(url, body, timeout=10.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode() or "{}")
        except Exception:  # noqa: BLE001 - body need not be JSON
            return e.code, {}


def _wait_for(fn, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if fn():
                return True
        except Exception:  # noqa: BLE001 - probe until live
            pass
        time.sleep(interval)
    return False


# ------------------------------------------------------------- the legs

def leg_baseline(workdir, quick):
    """200+ concurrent clients, one replica: zero hangs, every request
    completes, latency/throughput recorded for the perf gate."""
    clients = 40 if quick else 220
    rep = Replica("base0", cfg=_serve_cfg(
        stub_token_s=0.002, max_queue=512, kv_blocks=512,
        admission_headroom=0.005))
    try:
        report = _loadgen.run_load(
            [rep.url], clients=clients, requests_per_client=5,
            max_new=8, prompt_tokens=8, deadline_ms=20000, timeout=60.0)
        flat = rep.metrics()
        ok = (report["hung_clients"] == 0
              and report["ok"] == report["requests"]
              and report["requests"] >= clients * 5
              and report["p99_ms"] > 0.0
              and flat.get('tmpi_serve_requests_total{outcome="done"}',
                           0.0) >= report["ok"])
        return {"ok": ok, "clients": clients, "ok_requests": report["ok"],
                **{k: report[k] for k in ("requests", "p50_ms", "p99_ms",
                                          "tokens_per_sec", "hung_clients",
                                          "outcomes")}}
    finally:
        rep.close()


def leg_admission(workdir, quick):
    """Overload a tiny queue/pool: typed 503s, then full recovery."""
    rep = Replica("adm0", cfg=_serve_cfg(
        max_batch=2, max_queue=4, kv_blocks=8, stub_token_s=0.01,
        admission_headroom=0.05))
    try:
        clients = 12 if quick else 30
        report = _loadgen.run_load(
            [rep.url], clients=clients, requests_per_client=2,
            max_new=4, prompt_tokens=4, deadline_ms=8000, timeout=30.0)
        rejected = sum(n for o, n in report["outcomes"].items()
                       if o.startswith("admission:"))
        typed_only = all(o == "ok" or o.startswith(("admission:", "shed:"))
                         for o in report["outcomes"])
        # Recovery: the storm passed — one clean request must succeed
        # and every lease must be back in the pool.
        recovered = _wait_for(
            lambda: _post_json(f"{rep.url}/generate",
                               {"prompt": [1, 2, 3], "max_new": 2},
                               timeout=10.0)[0] == 200, timeout=10.0)
        drained = _wait_for(lambda: rep.engine.pool.stats()["used"] == 0,
                            timeout=5.0)
        return {"ok": (report["hung_clients"] == 0 and report["ok"] > 0
                       and rejected > 0 and typed_only and recovered
                       and drained),
                "rejected": rejected, "outcomes": report["outcomes"],
                "recovered": recovered, "pool_drained": drained}
    finally:
        rep.close()


def leg_deadline_shed(workdir, quick):
    """Deadlines against a slow decoder: typed, counted mid-decode sheds."""
    rep = Replica("dl0", cfg=_serve_cfg(
        max_batch=4, max_queue=8, kv_blocks=32, stub_token_s=0.05))
    try:
        report = _loadgen.run_load(
            [rep.url], clients=6, requests_per_client=2, max_new=16,
            prompt_tokens=4, deadline_ms=200, timeout=30.0)
        sheds = report["outcomes"].get("shed:deadline", 0)
        flat = rep.metrics()
        counted = flat.get(
            'tmpi_serve_requests_total{outcome="shed_deadline"}', 0.0)
        drained = _wait_for(lambda: rep.engine.pool.stats()["used"] == 0,
                            timeout=5.0)
        return {"ok": (report["hung_clients"] == 0 and sheds > 0
                       and counted >= sheds and drained),
                "sheds": sheds, "counted": counted,
                "outcomes": report["outcomes"]}
    finally:
        rep.close()


def leg_backpressure(workdir, quick):
    """Chaos personalities: slow, bursty and broken-socket clients — the
    server sheds the broken ones without leaking handler threads."""
    rep = Replica("bp0", cfg=_serve_cfg(
        max_batch=4, max_queue=24, kv_blocks=128, stub_token_s=0.005))
    threads_before = threading.active_count()
    try:
        clients = 20 if quick else 60
        report = _loadgen.run_load(
            [rep.url], clients=clients, requests_per_client=3,
            max_new=4, prompt_tokens=4, deadline_ms=10000, timeout=30.0,
            slow_frac=0.2, bursty_frac=0.2, broken_frac=0.1,
            slow_spec=chaos.FaultSpec(delay_ms=20.0, jitter_ms=40.0))
        typed_only = all(
            o in ("ok", "broken_probe")
            or o.startswith(("admission:", "shed:"))
            for o in report["outcomes"])
        # Broken sockets must not leak handler threads: after a short
        # settle the thread census returns to (near) the baseline.
        time.sleep(2.0)
        threads_after = threading.active_count()
        alive = _post_json(f"{rep.url}/generate",
                           {"prompt": [5], "max_new": 2})[0] == 200
        return {"ok": (report["hung_clients"] == 0 and report["ok"] > 0
                       and typed_only and alive
                       and threads_after <= threads_before + 8),
                "outcomes": report["outcomes"],
                "threads_before": threads_before,
                "threads_after": threads_after, "alive_after": alive}
    finally:
        rep.close()


def _spawn_replica_proc(port, token_s):
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--replica",
         "--replica-name", "victim", "--replica-port", str(port),
         "--replica-token-s", str(token_s)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    url = f"http://127.0.0.1:{port}"
    if not _wait_for(lambda: _get_json(f"{url}/serve")["slots"] > 0,
                     timeout=20.0):
        proc.kill()
        raise RuntimeError("replica subprocess never became ready")
    return proc, url


def leg_sigkill(workdir, quick):
    """SIGKILL a replica subprocess mid-decode: the router fails the
    transport error over to the surviving replica; nothing hangs."""
    port = free_ports(1)[0]
    proc, victim_url = _spawn_replica_proc(port, token_s=0.02)
    survivor = Replica("surv1", cfg=_serve_cfg(
        max_queue=128, kv_blocks=256, stub_token_s=0.002))
    router_reg = obs_metrics.Registry()
    router = ServeRouter({0: victim_url, 1: survivor.url},
                         registry=router_reg, timeout=15.0)
    results = {"ok": 0, "typed": 0, "transport": 0}
    lock = threading.Lock()
    rounds = 8 if quick else 24

    def _dispatcher(widx):
        for n in range(rounds):
            try:
                status, doc = router.dispatch(
                    f"w{widx}k{n}", {"prompt": [widx, n], "max_new": 4,
                                     "deadline_ms": 10000})
                with lock:
                    if status == 200:
                        results["ok"] += 1
                    else:
                        results["typed"] += 1
            except Exception:  # noqa: BLE001 - a hang/raise fails the leg
                with lock:
                    results["transport"] += 1
            time.sleep(0.01)

    timer = chaos.kill_after(proc.pid, 0.4)
    workers = [threading.Thread(target=_dispatcher, args=(i,), daemon=True)
               for i in range(4)]
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120.0)
        hung = sum(1 for w in workers if w.is_alive())
        proc.wait(timeout=10.0)
        flat = obs_history.flatten_families(router_reg.collect())
        failovers = flat.get("tmpi_serve_router_failover_total", 0.0)
        # After the failure is detected every key routes to the survivor.
        post_status, post_doc = router.dispatch(
            "post-kill", {"prompt": [9], "max_new": 2})
        return {"ok": (hung == 0 and results["transport"] == 0
                       and results["ok"] > 0 and failovers >= 1
                       and router.routable() == [1]
                       and post_status == 200
                       and post_doc.get("replica") == "surv1"),
                "results": results, "failovers": failovers,
                "routable": router.routable(), "hung_workers": hung}
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
        survivor.close()


def leg_rolling_restart(workdir, quick):
    """Roll two replicas behind the router with elastic_launch's
    RollRestarter while background load keeps flowing."""
    cfg = dict(max_queue=64, kv_blocks=128, stub_token_s=0.002,
               drain_timeout_s=3.0)
    reps = {0: Replica("rr0", obs_port=0, cfg=_serve_cfg(**cfg)),
            1: Replica("rr1", obs_port=0, cfg=_serve_cfg(**cfg))}
    ports = {s: (r.front.port, r.obs.port) for s, r in reps.items()}
    router_reg = obs_metrics.Registry()
    router = ServeRouter({s: r.url for s, r in reps.items()},
                         probe_urls={s: r.obs.url for s, r in reps.items()},
                         registry=router_reg, timeout=10.0)
    stop = threading.Event()
    results = {"ok": 0, "typed": 0, "transport": 0}

    def _loader():
        n = 0
        while not stop.is_set():
            router.probe()
            n += 1
            try:
                status, _doc = router.dispatch(
                    f"sess{n % 8}", {"prompt": [n % 256], "max_new": 4,
                                     "deadline_ms": 5000})
                results["ok" if status == 200 else "typed"] += 1
            except Exception:  # noqa: BLE001 - transport = leg failure
                results["transport"] += 1
            time.sleep(0.02)

    loader = threading.Thread(target=_loader, daemon=True)
    loader.start()

    def _drain(slot):
        return _post_json(f"{reps[slot].url}/drain", {})[0] == 200

    def _wait_drained(slot):
        eng = reps[slot].engine
        return _wait_for(lambda: (eng.draining
                                  and eng.stats()["active"] == 0
                                  and eng.stats()["queued"] == 0),
                         timeout=15.0)

    def _restart(slot):
        fport, oport = ports[slot]
        reps[slot].close()
        reps[slot] = Replica(f"rr{slot}", port=fport, obs_port=oport,
                             cfg=_serve_cfg(**cfg))
        return True

    def _wait_ready(slot):
        url = reps[slot].url
        return _wait_for(
            lambda: _post_json(f"{url}/generate",
                               {"prompt": [7], "max_new": 2})[0] == 200,
            timeout=15.0)

    roller = _elastic_launch.RollRestarter(
        [0, 1], _drain, _wait_drained, _restart, _wait_ready,
        journal=_elastic_launch.SupervisorJournal(workdir), settle_s=0.2)
    try:
        res = roller.run()
        time.sleep(0.3)
        stop.set()
        loader.join(timeout=30.0)
        fresh = all(_get_json(f"{r.url}/serve")["iterations"] >= 0
                    and not _get_json(f"{r.url}/serve")["draining"]
                    for r in reps.values())
        return {"ok": (res["ok"] and res["rolled"] == ["0", "1"]
                       and results["transport"] == 0
                       and results["ok"] > 0 and not loader.is_alive()
                       and fresh),
                "roll": res, "load": dict(results)}
    finally:
        stop.set()
        for r in reps.values():
            r.close()


def leg_slo_autoscale(workdir, quick):
    """The SLO loop closed end to end: authored alert rule fires under
    overload → ScaleSensor reads it over HTTP → AutoscalerPolicy votes
    grow (GROW_ALERTS) → the --grow-endpoints pool names the endpoint
    the new replica is provisioned on → the router serves from it."""
    slo_ms = 150.0
    rules_path = os.path.join(workdir, "serve_slo_rules.json")
    with open(rules_path, "w") as f:
        json.dump({"rules": [{
            "name": "serve_p99_over_deadline",
            "kind": "threshold",
            "metric": "tmpi_serve_p99_ms",
            "op": "ge",
            "value": slo_ms,
            "window_s": 60.0,
            "for_s": 0.0,
            "severity": "critical",
            "summary": "serving p99 latency breached the deadline SLO",
        }]}, f, indent=1)

    store = obs_history.HistoryStore()
    rep = Replica("slo0", cfg=_serve_cfg(
        max_batch=4, max_queue=64, kv_blocks=128, stub_token_s=0.03))
    aeng = obs_alerts.build_engine(
        store=store, health=rep.health, registry=rep.registry,
        cfg={"enabled": True, "default_pack": False,
             "rules_path": rules_path, "eval_every": 1, "for_s": 2.0,
             "flight": False})
    rep.obs = obs_serve.ObsHTTPServer(
        port=0, registry=rep.registry, health=rep.health, scrape=False,
        history=store, alerts=aeng)
    grown = None
    try:
        # Overload: queueing on 4 slow slots pushes p99 well over SLO.
        _loadgen.run_load([rep.url], clients=8 if quick else 16,
                          requests_per_client=2, max_new=8,
                          prompt_tokens=4, deadline_ms=20000, timeout=60.0)

        def _evaluated_firing():
            store.record(time.time(), rep.metrics())
            aeng.evaluate(now=time.time())
            return any(a["name"] == "serve_p99_over_deadline"
                       for a in aeng.firing())

        fired = _wait_for(_evaluated_firing, timeout=10.0, interval=0.2)

        sensor = _elastic_launch.ScaleSensor(types.SimpleNamespace(
            health_poll_port=rep.obs.port, health_poll_host="127.0.0.1",
            health_poll_stride=0, health_poll_timeout=3.0,
            autoscale_window=30.0))
        policy = _elastic_launch.AutoscalerPolicy(
            min_nproc=1, max_nproc=2, up_drift=0.0, up_sweeps=2)
        decision = None
        for _ in range(4):
            decision = policy.observe(sensor.sweep(1))
            if decision is not None:
                break
        grow = bool(decision and decision.get("action") == "grow")

        # The provisioner pool: --grow-endpoints names WHERE capacity
        # comes from; the grow decision pops one slot and the new
        # replica is stood up at exactly that endpoint.
        new_port = free_ports(1)[0]
        pool = _elastic_launch.parse_grow_endpoints(
            f"127.0.0.1:{new_port}")
        served = False
        if grow:
            entry = pool.pop(0)
            host, ring_port = entry["ring"]
            grown = Replica("g1", port=ring_port, cfg=_serve_cfg(
                max_queue=64, kv_blocks=128))
            router = ServeRouter({0: rep.url, 1: grown.url})
            key = next(f"k{i}" for i in range(64)
                       if router.route(f"k{i}") == 1)
            status, doc = router.dispatch(
                key, {"prompt": [3, 1, 4], "max_new": 4})
            served = status == 200 and doc.get("replica") == "g1"
        return {"ok": (fired and grow and served and not pool),
                "fired": fired,
                "decision": decision,
                "pool_consumed": not pool,
                "grown_replica_served": served,
                "slo_ms": slo_ms,
                "p99_ms": rep.engine.percentile(99.0)}
    finally:
        rep.close()
        if grown is not None:
            grown.close()


def leg_llama_runner(workdir, quick):
    """Continuous-batching decode on the COMPILED path matches the
    reference generate token for token — two concurrent requests of
    different budgets (they join and leave on different iterations)."""
    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama, llama_decode

    cfg = llama.tiny()
    runner = LlamaRunner(slots=2, max_len=64)
    eng = ServeEngine(
        runner=runner, pool=BlockPool(64, 8),
        cfg=_serve_cfg(max_batch=2, max_new_tokens=8,
                       default_deadline_ms=300000)).start()
    try:
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9, 10, 11]]
        reqs = [eng.submit(prompts[0], max_new=6, deadline_ms=300000),
                eng.submit(prompts[1], max_new=3, deadline_ms=300000)]
        done = all(r.done.wait(timeout=300.0) for r in reqs)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=5, max_new=6)
        ref = gen(runner.params, jnp.asarray(prompts, jnp.int32),
                  jax.random.PRNGKey(0))
        ref0 = [int(t) for t in ref[0]]
        ref1 = [int(t) for t in ref[1]][:3]
        match = (reqs[0].tokens == ref0 and reqs[1].tokens == ref1)
        return {"ok": (done and match
                       and all(r.state == "done" for r in reqs)),
                "match": match,
                "tokens": [list(r.tokens) for r in reqs],
                "reference": [ref0, ref1]}
    finally:
        eng.stop()


# ------------------------------------------------------------ replica mode

def _replica_main(args):
    """``--replica``: one stub replica in its own process — the SIGKILL
    leg's victim.  Serves until killed."""
    rep = Replica(args.replica_name, port=args.replica_port,
                  cfg=_serve_cfg(max_queue=128, kv_blocks=256,
                                 stub_token_s=args.replica_token_s))
    print(f"READY {rep.url}", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        rep.close()
    return 0


def _journal_audit(workdir):
    """Count the serving journal kinds actually written this run."""
    kinds = {}
    for name in sorted(os.listdir(workdir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(workdir, name), encoding="utf-8") as f:
            for line in f:
                try:
                    kind = json.loads(line).get("kind", "")
                except ValueError:
                    continue
                if kind.startswith("serve.") or kind.startswith(
                        "supervisor.roll_restart"):
                    kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(_REPO, "SERVE_r19.json"))
    ap.add_argument("--workdir", default="")
    ap.add_argument("--replica", action="store_true",
                    help="internal: run one replica subprocess")
    ap.add_argument("--replica-name", default="victim")
    ap.add_argument("--replica-port", type=int, default=0)
    ap.add_argument("--replica-token-s", type=float, default=0.01)
    args = ap.parse_args(argv)

    if args.replica:
        return _replica_main(args)

    workdir = args.workdir or tempfile.mkdtemp(prefix="serve_drill_")
    config.reset()
    config.set("journal_enabled", True)
    config.set("journal_dir", workdir)
    config.set("obs_trace", True)
    obs_journal.reset()

    t0 = time.time()
    legs = {}
    legs["baseline"] = leg_baseline(workdir, args.quick)
    legs["admission"] = leg_admission(workdir, args.quick)
    legs["deadline_shed"] = leg_deadline_shed(workdir, args.quick)
    legs["backpressure"] = leg_backpressure(workdir, args.quick)
    legs["sigkill"] = leg_sigkill(workdir, args.quick)
    legs["rolling_restart"] = leg_rolling_restart(workdir, args.quick)
    legs["slo_autoscale"] = leg_slo_autoscale(workdir, args.quick)
    if not args.quick:
        legs["llama_runner"] = leg_llama_runner(workdir, args.quick)

    obs_journal.reset()   # flush segments before the audit
    journal_kinds = _journal_audit(workdir)
    # The lifecycle kinds the legs above must have exercised.
    journal_ok = {"serve.shed", "serve.drain",
                  "supervisor.roll_restart"} <= set(journal_kinds)

    verdict = ("PASS" if journal_ok and all(
        leg["ok"] for leg in legs.values()) else "FAIL")
    doc = {
        "verdict": verdict,
        "quick": bool(args.quick),
        "elapsed_s": round(time.time() - t0, 1),
        "workdir": workdir,
        "legs": legs,
        "serve": {
            "clients": legs["baseline"]["clients"],
            "requests": legs["baseline"]["requests"],
            "p50_ms": legs["baseline"]["p50_ms"],
            "p99_ms": legs["baseline"]["p99_ms"],
            "tokens_per_sec": legs["baseline"]["tokens_per_sec"],
        },
        "journal": {"ok": journal_ok, "kinds": journal_kinds},
    }
    atomic_write_json(args.out, doc, indent=1)
    print(json.dumps({k: doc[k] for k in ("verdict", "elapsed_s")},
                     indent=1))
    print(f"artifact: {args.out}")
    return 0 if verdict == "PASS" else 1


if __name__ == "__main__":
    sys.exit(main())
