"""Training-health & numerics observability: the plane that watches the
*values*, not the wall-clock.

Everything the obs stack built so far (PRs 4/7/8) answers "is this rank
*moving*" — spans, stragglers, `/healthz` liveness.  None of it can
answer "is this rank computing the *right numbers*": a one-byte wire
corruption with ``hc_frame_crc`` off, a non-deterministic kernel, or a
missed bucket sync silently forks the replicas and the job trains to
garbage while every health probe reads green.  The OPT/PaLM-class
logbooks name silent numeric divergence and loss blow-ups as the
dominant *undetected* failure family; replica-consistent synchronous SGD
is this repo's whole value proposition, so the numerics plane watches it
directly:

* **In-step sentinels** (:func:`sentinel_stats`): cheap fused statistics
  computed INSIDE the compiled step — per-bucket gradient L2 norms (the
  same bucket granularity the collectives ride,
  ``nn.bucketing.bucket_sq_norms``), the global nonfinite count, and the
  update/param norm ratio — surfaced per step as ``tmpi_numerics_*``
  gauges/histograms through ``obs/serve.publish_step`` and kept in a
  bounded history ring the flight recorder snapshots.  Gated by the
  ``numerics_mode`` knob; ``off`` (the default) leaves the compiled step
  bit-for-bit the pre-numerics step.
* **Cross-rank consistency auditor** (:class:`Auditor`): every
  ``numerics_audit_interval`` steps each rank folds a deterministic
  blake2b fingerprint over its parameter leaves (per-leaf digests folded
  into one tree digest) and allgathers the 16-byte fold over the
  hostcomm plane.  On mismatch it binary-searches the leaf tree —
  O(log n) further 16-byte allgathers — to name the **first divergent
  leaf**, majority-votes the **outlier rank**, bumps
  ``tmpi_numerics_divergence_total``, trips the ``diverged`` state in
  the ``/healthz`` machine (precedence below ``stalled``, HTTP 503) and
  dumps a flight-recorder bundle carrying the divergent leaf path, the
  per-rank digests and the recent sentinel history.
* **Compute-efficiency gauges** (:func:`probe_step_flops` /
  :func:`publish_flops`): the per-program analytical FLOPs XLA's cost
  model already knows at compile time, published as ``tmpi_step_flops``
  and ``tmpi_mfu_estimate`` on ``/metrics`` so MFU stops being a number
  every bench re-derives by hand (``tmpi-trace top`` shows it per rank).

Proof by drill: ``tmpi-trace drill --numerics`` (``obs/__main__.py``)
runs the chaos proxy's one-byte silent-corruption negative control
against the auditor and an injected-NaN leg against the sentinels —
the ``NUMERICS_r12.json`` artifact.  See docs/numerics.md.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "Auditor",
    "AuditResult",
    "DIGEST_BYTES",
    "MODES",
    "device_peak_flops",
    "fold_digests",
    "history",
    "leaf_digests",
    "majority_vote",
    "numerics_config",
    "probe_step_flops",
    "publish_flops",
    "record_sentinels",
    "reset",
    "sentinel_stats",
    "sentinels_enabled",
    "snapshot",
    "tree_digest",
]

#: per-leaf / folded digest width (blake2b truncated): 128 bits is far
#: beyond accidental-collision range while keeping every audit exchange
#: a 16-byte allgather.
DIGEST_BYTES = 16

MODES = ("off", "sentinel", "audit")
#: the modes that carry in-graph sentinels (audit = sentinel + the
#: cross-rank digest exchange).  THE mode predicate — the engine and
#: serve.metrics_feed consult this tuple so the three sites can never
#: drift on what counts as "on".
SENTINEL_MODES = ("sentinel", "audit")

#: histogram buckets for gradient norms: powers of ten — a healthy run's
#: bucket norms sit within a decade or two; a blow-up walks the tail.
NORM_BUCKETS = (1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0, 1000.0)


def numerics_config() -> Dict[str, Any]:
    """The ``numerics_*`` knobs in one read — the single config
    touchpoint for the namespace (the knob checker's plumb target),
    consumed by the engine, the auditor and the sentinel history."""
    from ..runtime import config

    return {
        "mode": str(config.get("numerics_mode")),
        "audit_interval": int(config.get("numerics_audit_interval")),
        "history": int(config.get("numerics_history")),
    }


def sentinels_enabled() -> bool:
    """Whether the compiled step should carry in-graph sentinels —
    ``sentinel`` and ``audit`` both do (audit is sentinel + the
    cross-rank digest exchange)."""
    return numerics_config()["mode"] in SENTINEL_MODES


# ------------------------------------------------------------- sentinels

def sentinel_stats(params: Any, grads: Any,
                   updates: Optional[Any] = None) -> Dict[str, Any]:
    """In-graph sentinel statistics — traced INSIDE the compiled step, so
    the whole bundle fuses with the backward pass it observes:

    * ``bucket_grad_norms`` — per-bucket gradient L2 norms at the
      collective-bucket granularity (``nn.bucketing``): the shape a
      missed/forked bucket sync shows up in.
    * ``grad_norm`` — global gradient L2 norm (the loss-blow-up leading
      indicator every large-run logbook plots).
    * ``nonfinite_count`` — total non-finite gradient entries; a single
      NaN/inf flags the step it happened, not epochs later.
    * ``update_ratio`` — ||update|| / ||param|| (when ``updates`` given):
      the LR-sanity signal (healthy ~1e-3; ~1 means the optimizer is
      rewriting the network every step).

    Everything accumulates in f32 regardless of compute dtype.
    """
    import jax
    import jax.numpy as jnp

    from ..nn import bucketing

    plan = bucketing.plan_buckets(grads)
    bucket_sq = bucketing.bucket_sq_norms(grads, plan)
    total_sq = (jnp.sum(bucket_sq) if plan.specs
                else jnp.zeros((), jnp.float32))
    leaves = jax.tree.leaves(grads)
    nonfinite = (
        jnp.sum(jnp.stack([
            jnp.sum(jnp.logical_not(jnp.isfinite(leaf)).astype(jnp.int32))
            for leaf in leaves]))
        if leaves else jnp.zeros((), jnp.int32))
    stats: Dict[str, Any] = {
        "bucket_grad_norms": jnp.sqrt(bucket_sq),
        "grad_norm": jnp.sqrt(total_sq),
        "nonfinite_count": nonfinite,
    }
    if updates is not None:
        upd_sq = jnp.sum(jnp.stack([
            jnp.sum(jnp.square(u.astype(jnp.float32)))
            for u in jax.tree.leaves(updates)]))
        par_sq = jnp.sum(jnp.stack([
            jnp.sum(jnp.square(p.astype(jnp.float32)))
            for p in jax.tree.leaves(params)]))
        stats["update_ratio"] = (jnp.sqrt(upd_sq)
                                 / jnp.maximum(jnp.sqrt(par_sq), 1e-12))
    return stats


_lock = threading.Lock()
_history: collections.deque = collections.deque(maxlen=64)
_last_audit: Optional[Dict[str, Any]] = None


def record_sentinels(step: Optional[int], stats: Dict[str, Any],
                     registry=None) -> Dict[str, Any]:
    """Host side of one step's sentinels: read the device scalars (this
    is the sentinel read point — the cost the bench's
    ``sentinel_overhead_ms`` series prices), publish the
    ``tmpi_numerics_*`` gauges/histograms, and append to the bounded
    history ring the flight recorder snapshots."""
    if registry is None:
        from .metrics import registry as registry_
        registry = registry_
    rec: Dict[str, Any] = {
        "step": None if step is None else int(step),
        "grad_norm": float(stats["grad_norm"]),
        "nonfinite": int(stats["nonfinite_count"]),
        "bucket_grad_norms": [round(float(v), 6) for v in
                              np.asarray(stats["bucket_grad_norms"])],
        "wall_time": time.time(),
    }
    if "update_ratio" in stats:
        rec["update_ratio"] = float(stats["update_ratio"])
    registry.gauge(
        "tmpi_numerics_grad_norm",
        "global gradient L2 norm of the most recent engine step").set(
            rec["grad_norm"])
    registry.gauge(
        "tmpi_numerics_nonfinite",
        "non-finite gradient entries in the most recent engine step").set(
            float(rec["nonfinite"]))
    if rec["nonfinite"]:
        registry.counter(
            "tmpi_numerics_nonfinite_total",
            "non-finite gradient entries the in-step sentinels caught",
        ).inc(float(rec["nonfinite"]))
    if "update_ratio" in rec:
        registry.gauge(
            "tmpi_numerics_update_ratio",
            "update/param L2 norm ratio of the most recent engine step",
        ).set(rec["update_ratio"])
    h = registry.histogram(
        "tmpi_numerics_bucket_grad_norm",
        "per-collective-bucket gradient L2 norms from the in-step "
        "sentinels", buckets=NORM_BUCKETS)
    for v in rec["bucket_grad_norms"]:
        if np.isfinite(v):
            h.observe(v)
    cap = max(1, numerics_config()["history"])
    with _lock:
        global _history
        if _history.maxlen != cap:
            _history = collections.deque(_history, maxlen=cap)
        _history.append(rec)
    return rec


def history(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The most recent ``n`` sentinel records (all when None), oldest
    first — the divergence bundle's recent-numerics evidence."""
    with _lock:
        out = list(_history)
    return out[-n:] if n else out


def snapshot() -> Dict[str, Any]:
    """What the flight recorder embeds in every bundle: the sentinel
    history tail and the last audit verdict (either may be empty)."""
    with _lock:
        return {"history": list(_history), "last_audit": _last_audit}


def reset() -> None:
    """Forget history + last audit (tests; the ring is process-global)."""
    global _last_audit
    with _lock:
        _history.clear()
        _last_audit = None


def _set_last_audit(doc: Dict[str, Any]) -> None:
    global _last_audit
    with _lock:
        _last_audit = doc


# --------------------------------------------------------------- digests

def leaf_digests(tree: Any) -> Tuple[List[str], List[bytes]]:
    """Deterministic per-leaf fingerprints: for each leaf (pytree
    traversal order), blake2b over its path, dtype, shape and raw byte
    view.  Path/dtype/shape join the hash so a reshape or a re-keyed
    tree can never alias a value corruption — the digest speaks for the
    *named tensor*, not just its bytes."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    paths: List[str] = []
    digests: List[bytes] = []
    for path, leaf in flat:
        a = np.ascontiguousarray(np.asarray(leaf))
        h = hashlib.blake2b(digest_size=DIGEST_BYTES)
        key = jax.tree_util.keystr(path)
        h.update(key.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
        paths.append(key)
        digests.append(h.digest())
    return paths, digests


def fold_digests(digests: Sequence[bytes], lo: int = 0,
                 hi: Optional[int] = None) -> bytes:
    """Fold a contiguous run of per-leaf digests into one 16-byte
    digest — the tree-level fingerprint (full range) and the binary
    drill-down's probe (sub-ranges)."""
    h = hashlib.blake2b(digest_size=DIGEST_BYTES)
    for d in digests[lo:len(digests) if hi is None else hi]:
        h.update(d)
    return h.digest()


def tree_digest(tree: Any) -> str:
    """Hex of the folded whole-tree fingerprint (convenience)."""
    return fold_digests(leaf_digests(tree)[1]).hex()


def majority_vote(digests: Sequence[bytes],
                  reference: Optional[bytes] = None,
                  ) -> Tuple[Optional[bytes], Optional[List[int]]]:
    """Name the outliers among per-rank digests: the strict-majority
    value is the consensus; ranks holding anything else are outliers.
    ``reference`` (a known-good digest — a golden checkpoint's, or the
    drill's deterministic replay) joins as one extra vote, which is what
    breaks the 1-vs-1 tie a two-replica deployment otherwise cannot
    attribute.  Returns ``(None, None)`` when no strict majority exists."""
    counts = collections.Counter(digests)
    if reference is not None:
        counts[reference] += 1
    total = len(digests) + (1 if reference is not None else 0)
    top, c = counts.most_common(1)[0]
    if c * 2 <= total:
        return None, None
    return top, [r for r, d in enumerate(digests) if d != top]


# --------------------------------------------------------------- auditor

@dataclasses.dataclass
class AuditResult:
    """One audit's verdict (identical on every rank — every decision is
    derived from allgathered data alone)."""

    ok: bool
    step: Optional[int]
    rank: int
    size: int
    tree_digest: str
    tree_digests_by_rank: Dict[int, str]
    first_divergent_leaf: Optional[str] = None
    first_divergent_index: Optional[int] = None
    leaf_digests_by_rank: Optional[Dict[int, str]] = None
    outlier_ranks: Optional[List[int]] = None
    consensus: Optional[str] = None
    exchanges: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class Auditor:
    """Cross-rank parameter-consistency auditor over a hostcomm-plane
    communicator (anything with ``rank``/``size``/``allgather``).

    Protocol (every rank runs it identically, so the collective schedule
    can never desync): allgather the 16-byte tree fold; all-equal = the
    replicas agree, done — one tiny collective per audit.  On mismatch,
    binary-search the leaf range with one 16-byte fold allgather per
    round (the invariant: the prefix before ``lo`` agrees everywhere,
    the first divergence lives in ``[lo, hi)``), landing on the FIRST
    divergent leaf in O(log n_leaves) exchanges; a final allgather of
    that leaf's per-rank digests feeds :func:`majority_vote`.

    Effects on divergence: ``tmpi_numerics_divergence_total`` bumps (its
    movement marks every observing rank ``degraded`` via the watched
    counters), the OUTLIER rank's ``/healthz`` trips ``diverged`` (503;
    every rank trips when the vote is inconclusive — fail safe), and a
    flight bundle lands with the leaf path, per-rank digests and recent
    sentinel history.  A later clean audit clears the state — recovery
    is observable, not sticky.
    """

    def __init__(self, comm, interval: Optional[int] = None,
                 health=None, registry=None):
        self.comm = comm
        self.interval = interval
        self._health = health
        self._registry = registry
        self.last_result: Optional[AuditResult] = None
        # Register the divergence counter AT ZERO now: /healthz's
        # watched-counter scan baselines families at first sight, so a
        # counter born at 1 during the first divergence would read as
        # pre-existing and never flag movement on the observer ranks.
        self._reg().counter(
            "tmpi_numerics_divergence_total",
            "cross-rank parameter-divergence events the auditor caught")

    def _health_state(self):
        if self._health is not None:
            return self._health
        from . import serve

        return serve.health

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from .metrics import registry

        return registry

    def _exchange(self, digest: bytes) -> List[bytes]:
        # int8 wire view: the hostcomm dtype table carries int8, and a
        # digest is opaque bytes — reduction semantics never apply.
        arr = np.frombuffer(digest, dtype=np.int8).copy()
        out = self.comm.allgather(arr)
        raw = out.tobytes()
        parts = [raw[i * DIGEST_BYTES:(i + 1) * DIGEST_BYTES]
                 for i in range(self.comm.size)]
        # HierarchicalHostCommunicator.allgather returns (group,
        # intra-rank) order — global rank order only when the groups are
        # contiguous.  The vote indexes digests BY GLOBAL RANK, so map
        # positions back through the group layout when the comm exposes
        # one (a flat ring has no .groups and passes through).
        groups = getattr(self.comm, "groups", None)
        if groups is not None:
            by_rank: List[bytes] = [b""] * self.comm.size
            for pos, r in enumerate(r for g in groups for r in g):
                by_rank[r] = parts[pos]
            parts = by_rank
        return parts

    def maybe_audit(self, params: Any, step: int,
                    reference: Any = None) -> Optional[AuditResult]:
        """The engine's per-step entry point: audits only in ``audit``
        mode, on the ``numerics_audit_interval`` cadence; anything else
        is two config reads."""
        cfg = numerics_config()
        if cfg["mode"] != "audit":
            return None
        interval = self.interval if self.interval else cfg["audit_interval"]
        if interval <= 0 or int(step) % interval != 0:
            return None
        return self.audit(params, step=step, reference=reference)

    def step_boundary(self, state: Dict[str, Any]) -> None:
        """:meth:`maybe_audit` as a step boundary of the engine:
        ``engine.step_boundaries.append(auditor.step_boundary)``."""
        self.maybe_audit(state["params"], state["t"])

    def audit(self, params: Any, step: Optional[int] = None,
              reference: Any = None) -> AuditResult:
        """Run one audit now.  ``reference``: an optional known-good
        params tree (or a precomputed ``(paths, digests)`` pair) that
        joins the outlier vote as one extra voter — the two-replica
        tie-breaker (see :func:`majority_vote`)."""
        from . import tracer

        with tracer.span("numerics.audit", step=step, rank=self.comm.rank):
            return self._audit(params, step, reference)

    def _audit(self, params: Any, step: Optional[int],
               reference: Any) -> AuditResult:
        reg = self._reg()
        health = self._health_state()
        paths, digests = leaf_digests(params)
        reg.counter(
            "tmpi_numerics_audit_total",
            "cross-rank parameter-consistency audits run").inc()
        tree = fold_digests(digests)
        got = self._exchange(tree)
        exchanges = 1
        tree_by_rank = {r: d.hex() for r, d in enumerate(got)}
        if all(d == got[0] for d in got):
            recovered = (self.last_result is not None
                         and not self.last_result.ok)
            result = AuditResult(
                ok=True, step=step, rank=self.comm.rank,
                size=self.comm.size, tree_digest=tree.hex(),
                tree_digests_by_rank=tree_by_rank, exchanges=exchanges)
            self.last_result = result
            _set_last_audit(result.to_dict())
            if recovered:
                # Journal the RECOVERY edge (obs/journal.py): a
                # divergence that cleared is a state change the live
                # surface forgets within one audit interval.
                from . import journal as _journal

                _journal.emit("numerics.audit", rank=self.comm.rank,
                              ok=True, recovered=True, step=step)
            reg.gauge(
                "tmpi_numerics_diverged",
                "1 while the last cross-rank audit found divergence").set(0.0)
            health.clear_diverged()
            return result

        # Drill-down: find the FIRST divergent leaf.  Invariant: the
        # prefix [0, lo) folds equal on every rank; [lo, hi) contains the
        # first divergence (established by the tree-level mismatch).
        lo, hi = 0, len(digests)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            seg = self._exchange(fold_digests(digests, lo, mid))
            exchanges += 1
            if all(d == seg[0] for d in seg):
                lo = mid
            else:
                hi = mid
        leaf_got = self._exchange(digests[lo])
        exchanges += 1

        ref_digest = None
        if reference is not None:
            if (isinstance(reference, tuple) and len(reference) == 2
                    and isinstance(reference[1], (list, tuple))):
                ref_digest = reference[1][lo]
            else:
                ref_digest = leaf_digests(reference)[1][lo]
        consensus, outliers = majority_vote(leaf_got, ref_digest)

        result = AuditResult(
            ok=False, step=step, rank=self.comm.rank, size=self.comm.size,
            tree_digest=tree.hex(), tree_digests_by_rank=tree_by_rank,
            first_divergent_leaf=paths[lo], first_divergent_index=lo,
            leaf_digests_by_rank={r: d.hex()
                                  for r, d in enumerate(leaf_got)},
            outlier_ranks=outliers,
            consensus=consensus.hex() if consensus else None,
            exchanges=exchanges)
        self.last_result = result
        _set_last_audit(result.to_dict())

        reg.counter(
            "tmpi_numerics_divergence_total",
            "cross-rank parameter-divergence events the auditor caught",
        ).inc()
        reg.gauge(
            "tmpi_numerics_diverged",
            "1 while the last cross-rank audit found divergence").set(1.0)
        # The OUTLIER reads diverged (it holds the wrong numbers); an
        # inconclusive vote trips everyone — fail safe, never silent.
        if outliers is None or self.comm.rank in outliers:
            health.set_diverged(leaf=paths[lo], step=step,
                                outlier_ranks=outliers)
        from . import journal as _journal

        _journal.emit("numerics.audit", rank=self.comm.rank, ok=False,
                      step=step, first_divergent_leaf=paths[lo],
                      outlier_ranks=outliers,
                      tree_digests_by_rank=tree_by_rank)
        from . import flight

        flight.on_failure(
            "numerics_divergence", step=step, rank=self.comm.rank,
            first_divergent_leaf=paths[lo],
            leaf_digests_by_rank=result.leaf_digests_by_rank,
            tree_digests_by_rank=tree_by_rank,
            outlier_ranks=outliers,
            sentinel_history=history(16))
        return result


# ------------------------------------------------ compute-efficiency feed

#: bf16 peak FLOP/s by TPU generation (public spec sheets).  The ONE
#: copy — bench.py's roofline imports this table, so a new generation
#: lands in the bench MFU and the live tmpi_mfu_estimate gauge together.
_PEAK_BF16 = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


_default_peak: Optional[Tuple[Optional[float]]] = None


def device_peak_flops(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of ``device`` (default: the first visible
    device); None off-TPU — an MFU against an unknown peak is noise.
    The default-device answer is cached: ``publish_flops`` runs per
    engine step and the device kind cannot change mid-process."""
    global _default_peak
    if device is None:
        if _default_peak is not None:
            return _default_peak[0]
        import jax

        device = jax.devices()[0]
        _default_peak = (device_peak_flops(device),)
        return _default_peak[0]
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind:
        return None
    for key in ("v5 lite", "v5e", "v5p", "v6 lite", "v6e",
                "v4", "v3", "v2", "v5"):
        if key in kind:
            return _PEAK_BF16[key]
    return None


def probe_step_flops(jitted, args: Tuple[Any, ...]) -> Optional[float]:
    """Analytical FLOPs of one compiled step from XLA's own cost model,
    via ``lower()`` — a TRACE, not a compile or an execution, so the
    probe costs one re-trace and never touches the donated buffers.
    None when the backend exposes no cost analysis."""
    try:
        ca = jitted.lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:  # noqa: BLE001 — backend-dependent surface
        return None


def publish_flops(step_flops: float, step_s: float, registry=None) -> None:
    """Publish the compute-efficiency gauges: ``tmpi_step_flops`` (the
    compiled step's analytical FLOPs) and — where the device peak is
    known — ``tmpi_mfu_estimate`` (achieved FLOP/s per chip over bf16
    peak), the number the ROADMAP's MFU work kept re-deriving by hand."""
    if registry is None:
        from .metrics import registry as registry_
        registry = registry_
    registry.gauge(
        "tmpi_step_flops",
        "analytical FLOPs of one compiled engine step (XLA cost model)",
    ).set(float(step_flops))
    peak = device_peak_flops()
    if not peak:
        return
    import jax

    n = max(1, jax.device_count())
    achieved = float(step_flops) / max(float(step_s), 1e-12) / n
    registry.gauge(
        "tmpi_mfu_estimate",
        "model FLOPs utilization estimate: achieved FLOP/s per chip over "
        "bf16 peak, from tmpi_step_flops and the live step time",
    ).set(achieved / peak)
