"""Alert-triggered retune controller: the alert->decision->action loop for
PERFORMANCE knobs, the same pattern the autoscaler proved for membership.

The alert plane (obs/alerts.py) *detects* a sagging step rate, a collapsed
async overlap, or live traffic drifting off the autotune cache's measured
cells — and, before this module, nothing *acted* on a firing.  The
:class:`RetuneController` closes the loop.  It attaches to
``engine.step_boundaries`` and is consulted at the same step boundary as
the resize controller (the only place no collective is in flight); a
consult is a few dict reads and NEVER blocks or breaks the train loop.

Lifecycle (mirroring the autoscaler's two-debounce discipline — the alert
plane's ``for_s`` already debounced once, the controller still demands its
own sustained evidence):

* **idle -> evidence**: a trigger rule (``step_rate_sag``,
  ``overlap_collapse``, ``autotune_mix_drift``) is firing.  A flap that
  resolves inside ``retune_debounce_s`` returns to idle unjournaled.
* **evidence -> probing** (``retune.probe`` journaled): the firing
  persisted through the debounce.  The probe — an overlap A/B re-bench and
  a fresh eager autotune pass — runs on its OWN daemon thread, off the hot
  path; steps keep flowing while it measures.
* **probing -> apply** (``retune.decision`` + ``retune.apply`` journaled):
  the probe's verdict maps onto knob flips — the measured overlap winner
  picks the ``engine_async_drain`` discipline and steers the gradient
  bucket geometry (a winning ready discipline halves buckets so more
  transfers are in flight to hide updates behind, floor 4 MiB; a winning
  barrier doubles them to amortize dispatch, cap 64 MiB), and a fresh pass
  doc reinstalls the winner cache, which clears every decision memo.  A
  frozen config records the refusal instead of crashing the loop.
* **apply -> cooldown** (``retune.cooldown`` journaled): no new probe for
  ``retune_cooldown_s`` — a flapping alert must not thrash the knobs.
  Inside ``retune_revert_window_s`` the post-apply step rate is watched:
  at or below ``retune_revert_drift`` x the pre-probe baseline the flips
  REVERT to their recorded priors (``retune.revert`` journaled) — a
  retune must never make a sagging job worse and stay.

Every ``retune_*`` knob is read through :func:`retune_config` — the single
touchpoint ``analysis/knobs.py``'s plumb check keys on.  The controller
also publishes the ``tmpi_autotune_mix_drift`` gauge each poll (via
``autotune.mix_drift``), which is the series the default-pack
``autotune_mix_drift`` alert watches — the controller feeds the very
detector that triggers it, one closed loop.

Evidence trail: ``obs/rca.py``'s ``perf_retune`` rule chains the journaled
``alert.firing -> retune.probe -> retune.decision -> retune.apply``
sequence, so ``tmpi-trace why`` names a mid-job retune from journals
alone.  Drill: ``scripts/retune_drill.py`` -> ``RETUNE_r16.json``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..obs import journal as _journal
from ..runtime import config
from . import autotune

#: default-pack rules whose firing counts as retune evidence.
TRIGGER_RULES = ("step_rate_sag", "overlap_collapse", "autotune_mix_drift")

#: controller states (exported: tests and /retune assert on them).
IDLE = "idle"
EVIDENCE = "evidence"
PROBING = "probing"
COOLDOWN = "cooldown"

#: gradient bucket geometry rails for measured flips.
_BUCKET_FLOOR = 4 << 20
_BUCKET_CAP = 64 << 20
#: overlap-fraction margin below which the A/B is a wash — no flip.
_OVERLAP_MARGIN = 0.05

# The installed controller (serve.py's GET /retune reads it; the engine
# holds its own reference for the step-boundary consult).
_installed: Optional["RetuneController"] = None
_lock = threading.Lock()


def retune_config() -> Dict[str, Any]:
    """Every ``retune_*`` knob in one read — the single config touchpoint
    (the pattern ``resize.scale_config``/``alerts_config`` set, and the
    one ``analysis/knobs.py``'s plumb check verifies)."""
    return {
        "enabled": bool(config.get("retune_enabled")),
        "poll_interval_steps": max(
            1, int(config.get("retune_poll_interval_steps"))),
        "debounce_s": float(config.get("retune_debounce_s")),
        "cooldown_s": float(config.get("retune_cooldown_s")),
        "revert_window_s": float(config.get("retune_revert_window_s")),
        "revert_drift": float(config.get("retune_revert_drift")),
        "mix_threshold": float(config.get("retune_mix_threshold")),
        "mix_min_samples": int(config.get("retune_mix_min_samples")),
    }


class RetuneController:
    """The step-boundary perf controller.  Dependency-injected for drills
    and tests: ``alert_engine``/``store`` default to the process
    singletons, ``bench_fn`` to the real off-hot-path probe
    (:meth:`_default_bench`), ``now_fn`` to wall time (the clock the
    history store and alert engine share)."""

    def __init__(self, alert_engine=None, store=None,
                 bench_fn: Optional[Callable[[], Dict[str, Any]]] = None,
                 now_fn: Callable[[], float] = time.time,
                 cfg: Optional[Dict[str, Any]] = None):
        # Merge over the knob defaults: a PARTIAL override dict must not
        # strip the keys it doesn't name — step_boundary swallows every
        # internal error by contract, so a missing key would otherwise
        # read as a controller that silently never arms.
        self.cfg = {**retune_config(), **(cfg or {})}
        self._alert_engine = alert_engine
        self._store = store
        self._bench_fn = bench_fn or self._default_bench
        self._now = now_fn
        self.state = IDLE
        self.retunes = 0
        self.reverts = 0
        self._steps = 0
        self._evidence_since: Optional[float] = None
        self._evidence_rules: List[str] = []
        self._probe_lock = threading.Lock()
        self._probe_result: Optional[Dict[str, Any]] = None
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_baseline_rate: Optional[float] = None
        self._cooldown_until = 0.0
        # Last apply: {"t", "flips", "priors", "baseline_rate"} — the
        # revert path's evidence.  None once reverted or window closed.
        self._applied: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------- wiring

    def _engine(self):
        if self._alert_engine is not None:
            return self._alert_engine
        from ..obs import alerts

        return alerts.engine()

    def _history(self):
        if self._store is not None:
            return self._store
        from ..obs import history

        return history.store()

    def _firing(self) -> List[str]:
        eng = self._engine()
        if eng is None:
            return []
        try:
            return [f["name"] for f in eng.firing()
                    if f["name"] in TRIGGER_RULES]
        except Exception:  # noqa: BLE001 — a broken engine is no evidence
            return []

    def _step_rate(self, now: float) -> Optional[float]:
        st = self._history()
        if st is None:
            return None
        try:
            return st.rate("tmpi_engine_steps_total", 30.0, now=now)
        except Exception:  # noqa: BLE001
            return None

    # -------------------------------------------------- the step hook

    def step_boundary(self, state=None) -> str:
        """Consulted by the engine once per step (``state`` is the engine's,
        unused); returns the controller state.  MUST never raise and never
        block: probes run on their own thread, and any internal failure
        leaves the loop training."""
        self._steps += 1
        if self._steps % self.cfg["poll_interval_steps"]:
            return self.state
        try:
            self._tick(self._now())
        except Exception:  # noqa: BLE001 — the train loop outranks us
            pass
        return self.state

    def _tick(self, now: float) -> None:
        # Feed the detector every poll: the mix-drift gauge is the
        # autotune_mix_drift alert's series (cheap: one histogram walk).
        autotune.mix_drift(min_samples=self.cfg["mix_min_samples"])
        if self.state == COOLDOWN:
            self._tick_cooldown(now)
            return
        if self.state == PROBING:
            self._tick_probe(now)
            return
        firing = self._firing()
        if self.state == IDLE:
            if firing:
                self.state = EVIDENCE
                self._evidence_since = now
                self._evidence_rules = list(firing)
            return
        # EVIDENCE: hold through the debounce; a flap returns to idle
        # silently (the alert plane journals its own resolve).
        if not firing:
            self.state = IDLE
            self._evidence_since = None
            self._evidence_rules = []
            return
        self._evidence_rules = sorted(set(self._evidence_rules) | set(firing))
        if now - self._evidence_since >= self.cfg["debounce_s"]:
            self._start_probe(now)

    # ------------------------------------------------------ the probe

    def _start_probe(self, now: float) -> None:
        self.state = PROBING
        self._probe_baseline_rate = self._step_rate(now)
        _journal.emit("retune.probe", rules=list(self._evidence_rules),
                      debounce_s=self.cfg["debounce_s"],
                      baseline_rate=self._probe_baseline_rate)
        _counter("tmpi_retune_probes_total",
                 "retune probes launched (sustained alert evidence "
                 "survived the controller's debounce)")

        def run() -> None:
            try:
                res = self._bench_fn()
            except Exception as e:  # noqa: BLE001 — verdict, not crash
                res = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            with self._probe_lock:
                self._probe_result = res

        t = threading.Thread(target=run, name="tmpi-retune-probe",
                             daemon=True)
        self._probe_thread = t
        t.start()

    def _default_bench(self) -> Dict[str, Any]:
        """The real off-hot-path probe: the overlap A/B (measured drain
        disciplines over a chaos-delayed loopback ring — no device
        involvement, safe beside a live step loop) plus a fresh eager
        autotune pass when a communicator is up (refreshed cell winners
        for the drifted byte mix)."""
        out: Dict[str, Any] = {}
        try:
            out["overlap"] = autotune.overlap_ab(reps=1, update_passes=30)
        except Exception as e:  # noqa: BLE001
            out["overlap_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        try:
            from ..runtime import communicator as _comm_mod

            comm = _comm_mod.stack.current()
            out["pass_doc"] = autotune.run_pass(comm=comm, install=False)
        except Exception as e:  # noqa: BLE001
            out["pass_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        return out

    def _tick_probe(self, now: float) -> None:
        with self._probe_lock:
            res, self._probe_result = self._probe_result, None
        if res is None:
            return  # still measuring off the hot path; steps keep flowing
        self._probe_thread = None
        self._apply(now, res)

    # ------------------------------------------------------ the apply

    def _apply(self, now: float, res: Dict[str, Any]) -> None:
        flips: Dict[str, Any] = {}
        basis: Dict[str, Any] = {}
        ov = (res or {}).get("overlap")
        if isinstance(ov, dict) and "win" in ov:
            basis["overlap_win"] = ov["win"]
            want = "ready" if float(ov["win"]) > 0 else "barrier"
            if str(config.get("engine_async_drain")) != want and (
                    abs(float(ov["win"])) >= _OVERLAP_MARGIN):
                flips["engine_async_drain"] = want
            cur = int(config.get("gradient_bucket_bytes"))
            if float(ov["win"]) >= _OVERLAP_MARGIN and cur > _BUCKET_FLOOR:
                flips["gradient_bucket_bytes"] = max(_BUCKET_FLOOR, cur // 2)
            elif float(ov["win"]) <= -_OVERLAP_MARGIN and cur < _BUCKET_CAP:
                flips["gradient_bucket_bytes"] = min(_BUCKET_CAP, cur * 2)
        doc = (res or {}).get("pass_doc")
        install_doc = isinstance(doc, dict) and doc.get("cells")
        if install_doc:
            basis["pass_digest"] = doc.get("digest")
            basis["pass_cells"] = len(doc.get("cells", {}))
        action = ("apply" if (flips or install_doc)
                  else "none")
        _journal.emit("retune.decision", rules=list(self._evidence_rules),
                      action=action, flips=dict(flips), basis=basis,
                      error=(res or {}).get("error"))
        applied: Dict[str, Any] = {}
        priors: Dict[str, Any] = {}
        refused = None
        if action == "apply":
            try:
                for k, v in flips.items():
                    prior = config.get(k)
                    config.set(k, v)
                    priors[k] = prior
                    applied[k] = v
            except RuntimeError as e:
                # Frozen config: the refusal is the record — knobs the
                # compiled world was built against must not move under it.
                # (set() raises before mutating, so nothing partial needs
                # unwinding: applied holds exactly the flips that landed.)
                refused = str(e)[:200]
            if install_doc:
                # Fresh winners in, every decision memo cleared — the
                # drifted byte mix resolves against measurements again.
                autotune.activate(doc)
            else:
                autotune.rekey()
            self.retunes += 1
            _counter("tmpi_retune_applies_total",
                     "retune decisions applied (knob flips and/or a "
                     "reinstalled winner cache)")
        _journal.emit("retune.apply", applied=applied, priors=priors,
                      reinstalled_cache=bool(install_doc),
                      refused=refused)
        self._applied = ({"t": now, "flips": applied, "priors": priors,
                          "baseline_rate": self._probe_baseline_rate}
                         if applied else None)
        self._enter_cooldown(now)

    def _enter_cooldown(self, now: float) -> None:
        self.state = COOLDOWN
        self._cooldown_until = now + self.cfg["cooldown_s"]
        self._evidence_since = None
        _journal.emit("retune.cooldown", until_s=self.cfg["cooldown_s"],
                      revert_window_s=self.cfg["revert_window_s"])

    # ----------------------------------------------- cooldown / revert

    def _tick_cooldown(self, now: float) -> None:
        ap = self._applied
        if ap is not None:
            age = now - ap["t"]
            if age > self.cfg["revert_window_s"]:
                self._applied = None  # window closed clean; flips stay
            elif self._regressed(now, ap):
                self._revert(now, ap)
        if now >= self._cooldown_until:
            self.state = IDLE
            self._evidence_rules = []

    def _regressed(self, now: float, ap: Dict[str, Any]) -> bool:
        base = ap.get("baseline_rate")
        if not base or base <= 0:
            return False
        rate = self._step_rate(now)
        if rate is None:
            return False
        return (rate / base) <= self.cfg["revert_drift"]

    def _revert(self, now: float, ap: Dict[str, Any]) -> None:
        restored: Dict[str, Any] = {}
        try:
            for k, v in ap["priors"].items():
                config.set(k, v)
                restored[k] = v
        except RuntimeError:
            pass  # frozen mid-window: journal what happened, keep going
        autotune.rekey()  # memos must not keep serving the reverted world
        self.reverts += 1
        self._applied = None
        _counter("tmpi_retune_reverts_total",
                 "retunes reverted inside the post-apply window (the "
                 "post-retune step rate regressed vs the pre-probe "
                 "baseline)")
        _journal.emit("retune.revert", restored=restored,
                      baseline_rate=ap.get("baseline_rate"),
                      rate=self._step_rate(now),
                      revert_drift=self.cfg["revert_drift"])

    # ----------------------------------------------------- inspection

    def probe_in_flight(self) -> bool:
        t = self._probe_thread
        return t is not None and t.is_alive()

    def join(self, timeout: float = 30.0) -> None:
        """Test/drill hook: wait for an in-flight probe thread."""
        t = self._probe_thread
        if t is not None:
            t.join(timeout)

    def snapshot(self) -> Dict[str, Any]:
        """The live state GET /retune serves."""
        return {
            "state": self.state,
            "steps": self._steps,
            "retunes": self.retunes,
            "reverts": self.reverts,
            "evidence_rules": list(self._evidence_rules),
            "probe_in_flight": self.probe_in_flight(),
            "cooldown_until": self._cooldown_until,
            "applied": ({k: v for k, v in self._applied.items()
                         if k != "priors"}
                        if self._applied else None),
            "cfg": dict(self.cfg),
        }


def maybe_install(engine=None, **kwargs) -> Optional[RetuneController]:
    """Arm the controller when ``retune_enabled`` is set: construct it,
    append its ``step_boundary`` to ``engine.step_boundaries`` (after a
    resize boundary, which skips it on the step that ends the loop), and
    register it for GET /retune.  Off = one config read, None, nothing
    installed."""
    global _installed
    if not bool(config.get("retune_enabled")):
        return None
    ctl = RetuneController(**kwargs)
    if engine is not None:
        engine.step_boundaries.append(ctl.step_boundary)
    with _lock:
        _installed = ctl
    return ctl


def installed() -> Optional[RetuneController]:
    with _lock:
        return _installed


def uninstall() -> None:
    """Drop the registered controller (test hook)."""
    global _installed
    with _lock:
        _installed = None


def _counter(name: str, help_: str) -> None:
    from ..obs import metrics

    metrics.registry.counter(name, help_).inc()
