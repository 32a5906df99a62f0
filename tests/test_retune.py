"""Self-driving performance (ISSUE 16): the compiled-mode autotune pass
and the alert-triggered :class:`RetuneController`.

Pins the contracts the tentpole rests on:

* the controller's firing -> evidence -> probe -> apply lifecycle, with
  journal events at every transition and knob flips derived from the
  measured overlap verdict;
* flap suppression (evidence that resolves inside the debounce never
  probes) and the post-apply cooldown (a still-firing alert cannot
  thrash the knobs);
* revert-on-regression: flips whose post-apply step rate sags below
  ``retune_revert_drift`` x the pre-probe baseline are restored, and a
  window that closes clean keeps them;
* compiled-pass winner-cache roundtrip through the atomic per-fabric
  store, base-digest matching (the pass's OWN varied knobs must not
  self-invalidate the doc) and fingerprint invalidation for everything
  else;
* ``autotune_mode=off`` bit-for-bit: a contrary compiled doc is never
  consulted by ``tp.resolve_wire_dtype`` or the selector;
* the ``rekey()`` memo-resurrection fix: an in-flight ``decide()``
  verdict computed against the pre-rekey cache cannot write into the
  post-rekey memo (generation stamp), even when the doc object survives.

Marker ``retune``.  ``TestControllerConcurrent`` is on
``scripts/sanitize_drill.py``'s TSAN/ASan list: the probe bench thread
runs native hostcomm collectives while the train-loop thread keeps
hitting ``step_boundary``.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from torchmpi_tpu.collectives import autotune, retune, selector
from torchmpi_tpu.obs import alerts, journal, metrics as obs_metrics
from torchmpi_tpu.obs import history
from torchmpi_tpu.parallel import tp
from torchmpi_tpu.runtime import config

pytestmark = pytest.mark.retune


@pytest.fixture(autouse=True)
def _fresh():
    """Every test starts with no caches, no controller, default knobs."""
    autotune.clear()
    autotune.clear_compiled()
    retune.uninstall()
    selector.configure()
    yield
    retune.uninstall()
    autotune.clear()
    autotune.clear_compiled()
    config.reset()
    selector.configure()
    journal.reset()


# ------------------------------------------------------------- test doubles

class StubAlertEngine:
    def __init__(self):
        self.rules = []

    def fire(self, *names):
        self.rules = [{"name": n, "severity": "warning", "since": 0.0,
                       "phase": "engine", "annotation": "stub"} for n in names]

    def firing(self):
        return self.rules


class StubStore:
    def __init__(self, r=10.0):
        self.r = r

    def rate(self, name, window, now=None):
        return self.r


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _controller(bench=None, rate=10.0, **cfg_over):
    eng, store, clock = StubAlertEngine(), StubStore(rate), Clock()
    cfg = retune.retune_config()
    cfg.update({"enabled": True, "debounce_s": 5.0, "cooldown_s": 60.0,
                "revert_window_s": 30.0, "revert_drift": 0.9,
                "poll_interval_steps": 1}, **cfg_over)
    ctl = retune.RetuneController(
        alert_engine=eng, store=store, now_fn=clock,
        bench_fn=bench or (lambda: {"overlap": {"win": 0.3}}), cfg=cfg)
    return ctl, eng, store, clock


def _drive_to_apply(ctl, eng, clock, rule="step_rate_sag"):
    """Fire -> debounce -> probe -> join -> apply; leaves ctl in COOLDOWN."""
    eng.fire(rule)
    ctl.step_boundary()
    assert ctl.state == retune.EVIDENCE
    clock.t += 6.0
    ctl.step_boundary()
    assert ctl.state == retune.PROBING
    ctl.join()
    ctl.step_boundary()
    assert ctl.state == retune.COOLDOWN


# ---------------------------------------------------------------- lifecycle

class TestLifecycle:
    def test_firing_probe_apply_flips_knobs_and_journals(self, tmp_path):
        config.set("journal_enabled", True)
        config.set("journal_dir", str(tmp_path))
        journal.reset()
        prior_bucket = int(config.get("gradient_bucket_bytes"))
        ctl, eng, _, clock = _controller()
        _drive_to_apply(ctl, eng, clock)
        # ready won by 0.3: buckets halve so more transfers are in
        # flight to hide updates behind; drain already "ready" stays.
        assert int(config.get("gradient_bucket_bytes")) == prior_bucket // 2
        assert str(config.get("engine_async_drain")) == "ready"
        assert ctl.retunes == 1
        kinds = [e["kind"] for e in journal.tail(64)
                 if e["kind"].startswith("retune.")]
        assert kinds == ["retune.probe", "retune.decision",
                         "retune.apply", "retune.cooldown"]

    def test_barrier_win_flips_drain_and_doubles_buckets(self):
        ctl, eng, _, clock = _controller(
            bench=lambda: {"overlap": {"win": -0.2}})
        prior_bucket = int(config.get("gradient_bucket_bytes"))
        _drive_to_apply(ctl, eng, clock, rule="overlap_collapse")
        assert str(config.get("engine_async_drain")) == "barrier"
        assert int(config.get("gradient_bucket_bytes")) == prior_bucket * 2

    def test_wash_margin_applies_nothing(self):
        ctl, eng, _, clock = _controller(
            bench=lambda: {"overlap": {"win": 0.01}})
        prior = (str(config.get("engine_async_drain")),
                 int(config.get("gradient_bucket_bytes")))
        _drive_to_apply(ctl, eng, clock)
        assert (str(config.get("engine_async_drain")),
                int(config.get("gradient_bucket_bytes"))) == prior
        assert ctl.retunes == 0
        assert ctl.snapshot()["applied"] is None

    def test_flap_inside_debounce_returns_to_idle_without_probe(self):
        probes = []
        ctl, eng, _, clock = _controller(
            bench=lambda: probes.append(1) or {})
        eng.fire("step_rate_sag")
        ctl.step_boundary()
        assert ctl.state == retune.EVIDENCE
        eng.fire()                       # resolves before the debounce
        clock.t += 2.0
        ctl.step_boundary()
        assert ctl.state == retune.IDLE
        clock.t += 10.0
        ctl.step_boundary()
        assert ctl.state == retune.IDLE and not probes

    def test_cooldown_suppresses_a_still_firing_alert(self):
        calls = []
        ctl, eng, _, clock = _controller(
            bench=lambda: calls.append(1) or {"overlap": {"win": 0.3}})
        _drive_to_apply(ctl, eng, clock)
        assert len(calls) == 1
        # still firing through the whole cooldown: no second probe
        for _ in range(5):
            clock.t += 10.0
            ctl.step_boundary()
        assert len(calls) == 1
        # cooldown expired (60 s) -> idle -> evidence -> second probe
        clock.t += 15.0
        ctl.step_boundary()
        assert ctl.state in (retune.IDLE, retune.EVIDENCE)
        ctl.step_boundary()
        clock.t += 6.0
        ctl.step_boundary()
        ctl.join()
        ctl.step_boundary()
        assert len(calls) == 2

    def test_bench_error_is_a_verdict_not_a_crash(self, tmp_path):
        config.set("journal_enabled", True)
        config.set("journal_dir", str(tmp_path))
        journal.reset()

        def boom():
            raise RuntimeError("wire fell over")

        ctl, eng, _, clock = _controller(bench=boom)
        _drive_to_apply(ctl, eng, clock)
        assert ctl.retunes == 0
        [dec] = [e for e in journal.tail(64)
                 if e["kind"] == "retune.decision"]
        assert "wire fell over" in dec["data"]["error"]

    def test_frozen_config_refusal_is_journaled(self, tmp_path, monkeypatch):
        config.set("journal_enabled", True)
        config.set("journal_dir", str(tmp_path))
        journal.reset()
        ctl, eng, _, clock = _controller()

        def frozen_set(k, v):
            raise RuntimeError("constants are frozen")

        monkeypatch.setattr(retune.config, "set", frozen_set)
        _drive_to_apply(ctl, eng, clock)
        [ap] = [e for e in journal.tail(64) if e["kind"] == "retune.apply"]
        assert "frozen" in ap["data"]["refused"]
        assert ap["data"]["applied"] == {}

    def test_step_boundary_never_raises(self):
        ctl, eng, _, _ = _controller()
        ctl._tick = None                 # force an internal failure
        assert ctl.step_boundary() == retune.IDLE


# ------------------------------------------------------------------ revert

class TestRevert:
    def test_regression_inside_window_restores_priors(self):
        prior_bucket = int(config.get("gradient_bucket_bytes"))
        ctl, eng, store, clock = _controller()
        _drive_to_apply(ctl, eng, clock)
        assert int(config.get("gradient_bucket_bytes")) == prior_bucket // 2
        store.r = 5.0                    # rate sagged to 0.5x baseline
        clock.t += 10.0                  # inside the 30 s revert window
        ctl.step_boundary()
        assert ctl.reverts == 1
        assert int(config.get("gradient_bucket_bytes")) == prior_bucket

    def test_clean_window_keeps_the_flips(self):
        prior_bucket = int(config.get("gradient_bucket_bytes"))
        ctl, eng, store, clock = _controller()
        _drive_to_apply(ctl, eng, clock)
        store.r = 11.0                   # post-apply rate is fine
        clock.t += 31.0                  # revert window closed
        ctl.step_boundary()
        assert ctl.reverts == 0
        assert int(config.get("gradient_bucket_bytes")) == prior_bucket // 2
        # the window is closed: a later sag can no longer revert
        store.r = 1.0
        clock.t += 5.0
        ctl.step_boundary()
        assert ctl.reverts == 0

    def test_rate_at_drift_boundary_reverts(self):
        ctl, eng, store, clock = _controller()
        _drive_to_apply(ctl, eng, clock)
        store.r = 9.0                    # exactly 0.9x the 10.0 baseline
        clock.t += 10.0
        ctl.step_boundary()
        assert ctl.reverts == 1


# ------------------------------------------------------------ installation

class TestInstall:
    def test_maybe_install_gated_on_knob(self):
        assert retune.maybe_install() is None
        assert retune.installed() is None
        config.set("retune_enabled", True)

        class Eng:
            step_boundaries = []

        eng = Eng()
        ctl = retune.maybe_install(
            engine=eng, alert_engine=StubAlertEngine(), store=StubStore())
        assert ctl is not None
        assert eng.step_boundaries == [ctl.step_boundary]
        assert retune.installed() is ctl

    def test_engine_consults_at_step_boundary(self, world):
        from torchmpi_tpu.engine import AllReduceSGDEngine

        calls = []

        class Probe:
            def step_boundary(self, state):
                calls.append(state["t"])

        def loss(params, batch):
            x, y = batch
            return jnp.mean((x @ params - y) ** 2)

        eng = AllReduceSGDEngine(loss, lr=0.1, comm=world, mode="compiled")
        eng.step_boundaries.append(Probe().step_boundary)
        params = jnp.zeros((4, 2), jnp.float32)
        xs = np.ones((world.size, 2, 4), np.float32)
        ys = np.zeros((world.size, 2, 2), np.float32)
        eng.train(params, [(xs, ys)] * 3)
        assert calls == [1, 2, 3]       # once a step, after its on_update


# ----------------------------------------------------- the mix-drift alert

class TestMixDriftAlert:
    def test_default_pack_rule_threshold_comes_from_the_knob(self):
        config.set("retune_mix_threshold", 0.7)
        [rule] = [r for r in alerts.default_rules()
                  if r.name == "autotune_mix_drift"]
        assert rule.value == 0.7

    def test_seeded_drift_fires_the_real_rule(self):
        st = history.HistoryStore(interval_s=1.0)
        eng = alerts.build_engine(
            store=st, cfg={"enabled": True, "default_pack": True,
                           "rules_path": "", "eval_every": 1, "for_s": 3.0,
                           "flight": False})
        for i in range(10):
            st.record(1000.0 + i, {"tmpi_autotune_mix_drift": 0.8})
            eng.evaluate(now=1000.0 + i)
        assert "autotune_mix_drift" in [f["name"] for f in eng.firing()]

    def test_mix_drift_gauge_counts_uncovered_samples(self, world,
                                                      monkeypatch):
        # A private registry: the process-global tmpi_collective_seconds
        # histogram carries samples from every other test in the run.
        reg = obs_metrics.Registry()
        monkeypatch.setattr(autotune, "_registry", lambda: reg)
        fp = autotune.fingerprint(world)
        doc = {"version": autotune.CACHE_VERSION, "fingerprint": fp,
               "digest": autotune.fingerprint_digest(fp),
               "cells": {autotune.cell_key(
                   "allreduce", "float32", "1KiB", "cpu", "singlenode"): {
                   "op": "allreduce", "dtype": "float32", "bytes": 1024,
                   "bucket": "1KiB", "placement": "cpu",
                   "scope": "singlenode", "winner": "xla",
                   "default": "hostcomm", "ms": {"xla": 1.0}}}}
        autotune.activate(doc)
        h = reg.histogram("tmpi_collective_seconds", "test feed")
        for _ in range(3):               # covered cell
            h.observe(1e-4, labels={"op": "allreduce", "plane": "hostcomm",
                                    "bytes_bucket": "1KiB"})
        for _ in range(9):               # traffic the cache never measured
            h.observe(1e-4, labels={"op": "allgather", "plane": "hostcomm",
                                    "bytes_bucket": "8MiB"})
        assert autotune.mix_drift(min_samples=1) == pytest.approx(0.75)
        g = reg.peek("tmpi_autotune_mix_drift")
        assert g is not None

    def test_below_min_samples_reports_zero(self, world, monkeypatch):
        reg = obs_metrics.Registry()
        monkeypatch.setattr(autotune, "_registry", lambda: reg)
        fp = autotune.fingerprint(world)
        autotune.activate({"version": autotune.CACHE_VERSION,
                           "fingerprint": fp,
                           "digest": autotune.fingerprint_digest(fp),
                           "cells": {}})
        h = reg.histogram("tmpi_collective_seconds", "test feed")
        h.observe(1e-4, labels={"op": "allreduce", "plane": "hostcomm",
                                "bytes_bucket": "1KiB"})
        assert autotune.mix_drift(min_samples=50, publish=False) == 0.0

    def test_no_cache_installed_is_zero_drift(self):
        h = obs_metrics.registry.histogram(
            "tmpi_collective_seconds", "test feed")
        h.observe(1e-4, labels={"op": "allreduce", "plane": "hostcomm",
                                "bytes_bucket": "1KiB"})
        assert autotune.mix_drift(min_samples=1, publish=False) == 0.0


# --------------------------------------------------- compiled-pass caching

def _compiled_doc(knob_winners=None, fp=None):
    fp = fp or autotune.fingerprint()
    return {"version": autotune.CACHE_VERSION, "kind": "compiled",
            "topology": "test", "fingerprint": fp,
            "digest": autotune.fingerprint_digest(fp),
            "base_digest": autotune.base_digest(fp),
            "created_unix": 0.0, "timed": False,
            "programs": {}, "knob_winners": dict(knob_winners or {})}


class TestCompiledCache:
    def test_roundtrip_and_wire_dtype_consult(self, tmp_path):
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        doc = _compiled_doc({"manual_wire_dtype": "bfloat16"})
        autotune.save_compiled(doc)
        autotune.clear_compiled()
        assert autotune.compiled_wire_dtype() is None    # mode off
        config.set("autotune_mode", "cache")
        assert autotune.compiled_wire_dtype() == "bfloat16"
        # the consult reaches tp.resolve_wire_dtype's auto branch
        assert tp.resolve_wire_dtype() == jnp.bfloat16

    def test_off_mode_never_consults_the_doc(self, tmp_path):
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        autotune.save_compiled(_compiled_doc(
            {"manual_wire_dtype": "bfloat16"}))
        autotune.clear_compiled()
        assert config.get("autotune_mode") == "off"      # the default
        # off on a cpu host: auto resolves f32, the doc is dead weight
        assert tp.resolve_wire_dtype() == jnp.float32
        assert autotune.compiled_active() is None        # never even loaded

    def test_explicit_knob_outranks_the_measurement(self, tmp_path):
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        autotune.save_compiled(_compiled_doc(
            {"manual_wire_dtype": "bfloat16"}))
        autotune.clear_compiled()
        config.set("autotune_mode", "cache")
        config.set("manual_wire_dtype", "float32")
        assert tp.resolve_wire_dtype() == jnp.float32

    def test_varied_knob_does_not_self_invalidate(self, tmp_path):
        """The doc's match identity excludes the knobs the pass varies:
        installing its own wire verdict must not make it stale."""
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        autotune.save_compiled(_compiled_doc(
            {"manual_wire_dtype": "bfloat16"}))
        autotune.clear_compiled()
        config.set("manual_wire_dtype", "bfloat16")      # apply the verdict
        config.set("autotune_mode", "cache")
        assert autotune.load_compiled() is not None
        assert autotune.compiled_wire_dtype() == "bfloat16"

    def test_foreign_fingerprint_is_stale_and_never_applied(self, tmp_path):
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        autotune.save_compiled(_compiled_doc(
            {"manual_wire_dtype": "bfloat16"}))
        autotune.clear_compiled()
        stale0 = obs_metrics.registry.counter(
            "tmpi_autotune_cache_stale_total").value()
        config.set("hc_frame_crc", True)                 # base identity moved
        assert autotune.load_compiled() is None
        assert obs_metrics.registry.counter(
            "tmpi_autotune_cache_stale_total").value() > stale0
        config.set("autotune_mode", "cache")
        assert autotune.compiled_wire_dtype() is None

    def test_activate_validate_refuses_foreign_doc(self, tmp_path):
        doc = _compiled_doc({"manual_wire_dtype": "bfloat16"})
        config.set("hc_frame_crc", True)                 # running fabric moved
        assert autotune.activate_compiled(doc) is None
        assert autotune.compiled_active() is None
        # the drill/test escape hatch installs it anyway
        assert autotune.activate_compiled(doc, validate=False) is doc
        assert autotune.compiled_active() is doc

    def test_store_merges_fabrics(self, tmp_path):
        config.set("autotune_cache_path", str(tmp_path / "autotune.json"))
        d1 = _compiled_doc({"manual_wire_dtype": "bfloat16"})
        config.set("hc_frame_crc", True)
        d2 = _compiled_doc({"manual_wire_dtype": "float32"})
        config.set("hc_frame_crc", False)
        autotune.save_compiled(d1)
        autotune.save_compiled(d2)
        loaded = autotune.load_compiled()
        assert loaded is not None
        assert loaded["base_digest"] == d1["base_digest"]

    def test_compiled_preference_maps_namespace_winners(self):
        autotune.activate_compiled(_compiled_doc(
            {"use_pallas_collectives": True}), validate=False)
        config.set("autotune_mode", "cache")
        assert autotune.compiled_preference(
            "allreduce", "tpu", "singlenode") == "pallas"
        assert autotune.compiled_preference(
            "allreduce", "cpu", "singlenode") is None    # device plane only
        autotune.activate_compiled(_compiled_doc(
            {"use_hierarchical_collectives": True}), validate=False)
        assert autotune.compiled_preference(
            "allreduce", "tpu", "multinode") == "hierarchical"


class TestCompiledPass:
    """The real AOT pass over a cheap program.  manual_psum_bf16 pins its
    wire dtype internally, so the wire variants compile to identical HLO
    — the pass must record the tie as NO verdict, not a first-in-dict
    win."""

    def test_tie_is_no_verdict(self):
        doc = autotune.compiled_pass(
            "v5e-8", programs=["manual_psum_bf16"])
        rec = doc["programs"]["manual_psum_bf16"]
        assert all(v.get("compile_ok")
                   for v in rec["variants"].values())
        assert rec["winner"] is None
        assert doc["knob_winners"] == {}
        assert doc["base_digest"] == autotune.base_digest(
            autotune.fingerprint(topology="v5e-8"))

    def test_scoring_prefers_fewer_collective_bytes(self):
        lo = {"compile_ok": True,
              "collectives": {"operand_bytes": {"all-reduce:bf16": 100}},
              "memory": {"peak_hbm_bytes": 10}}
        hi = {"compile_ok": True,
              "collectives": {"operand_bytes": {"all-reduce:f32": 200}},
              "memory": {"peak_hbm_bytes": 10}}
        bad = {"compile_ok": False}
        assert autotune._compiled_score(lo) < autotune._compiled_score(hi)
        assert autotune._compiled_score(hi) < autotune._compiled_score(bad)
        timed = {"compile_ok": True, "wall_s": 0.5}
        assert autotune._compiled_score(timed) == (0.5, 0.0)


# --------------------------------------------- the memo-generation fix

class TestMemoGeneration:
    def _doc(self, world):
        fp = autotune.fingerprint(world)
        return {"version": autotune.CACHE_VERSION, "fingerprint": fp,
                "digest": autotune.fingerprint_digest(fp),
                "cells": {autotune.cell_key(
                    "allreduce", "float32", "1KiB", "cpu", "singlenode"): {
                    "op": "allreduce", "dtype": "float32", "bytes": 1024,
                    "bucket": "1KiB", "placement": "cpu",
                    "scope": "singlenode", "winner": "xla",
                    "default": "hostcomm",
                    "ms": {"hostcomm": 9.0, "xla": 1.0}}}}

    def test_rekey_same_doc_clears_memos_and_bumps_generation(self, world):
        autotune.activate(self._doc(world))
        config.set("autotune_mode", "cache")
        payload = np.ones((256,), np.float32)
        assert autotune.decide("allreduce", "cpu", "singlenode", "sync",
                               payload, ["hostcomm", "xla"]) == "xla"
        assert autotune._decisions
        gen0 = autotune._generation
        # matching digest: the doc SURVIVES rekey, the memos must not
        assert autotune.rekey() is autotune.active()
        assert autotune._decisions == {}
        assert autotune._generation != gen0

    def test_stale_verdict_cannot_resurrect_after_rekey(self, world):
        """The regression: decide() snapshots (doc, generation); rekey()
        with a MATCHING digest keeps the doc object, so an identity-only
        write-back guard would let a verdict computed from pre-rekey
        histograms land in the post-rekey memo.  Replays the exact
        write-back sequence with a snapshot taken before rekey."""
        autotune.activate(self._doc(world))
        config.set("autotune_mode", "cache")
        with autotune._lock:
            doc, gen = autotune._active, autotune._generation
        autotune.rekey()                 # same digest: same doc object
        assert autotune.active() is doc
        # the in-flight verdict now tries to write back
        with autotune._lock:
            if autotune._active is doc and autotune._generation == gen:
                autotune._decisions["stale"] = ["pallas", 1]
        assert "stale" not in autotune._decisions

    def test_activate_and_clear_bump_generation(self, world):
        g0 = autotune._generation
        autotune.activate(self._doc(world))
        g1 = autotune._generation
        autotune.clear()
        g2 = autotune._generation
        assert g0 < g1 < g2


# ------------------------------------------------------------- concurrency

class TestControllerConcurrent:
    def test_probe_races_step_boundaries(self, world):
        """The sanitizer drill's race class: the probe thread runs REAL
        native hostcomm collectives (overlap A/B over a loopback ring)
        while train-loop threads hammer step_boundary and a reader
        snapshots — controller state, config flips and metrics must stay
        coherent throughout."""
        eng, store, clock = StubAlertEngine(), StubStore(), Clock()
        lock = threading.Lock()

        def bench():
            return {"overlap": autotune.overlap_ab(
                n_buckets=3, bucket_elements=1 << 12, reps=1,
                update_passes=10)}

        cfg = retune.retune_config()
        cfg.update({"enabled": True, "debounce_s": 0.0, "cooldown_s": 0.5,
                    "revert_window_s": 0.0, "poll_interval_steps": 1})
        ctl = retune.RetuneController(alert_engine=eng, store=store,
                                      bench_fn=bench,
                                      now_fn=lambda: clock.t, cfg=cfg)
        eng.fire("overlap_collapse")
        stop = threading.Event()
        errors = []

        def stepper():
            while not stop.is_set():
                try:
                    ctl.step_boundary()
                    with lock:
                        clock.t += 0.05
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                time.sleep(0.001)

        def reader():
            while not stop.is_set():
                try:
                    ctl.snapshot()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                time.sleep(0.002)

        threads = [threading.Thread(target=stepper) for _ in range(2)]
        threads.append(threading.Thread(target=reader))
        for t in threads:
            t.start()
        deadline = time.time() + 20.0
        while ctl.retunes < 1 and time.time() < deadline:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(10.0)
        ctl.join()
        assert not errors
        assert ctl.retunes >= 1
