"""Jaxpr collective linter over the registered multi-chip programs.

SPMD correctness is a cross-rank agreement property no unit test on one
process can see: every rank must execute the same collectives in the same
order with the same operand layout, and every manual-region gradient wire
must actually ride the dtype the ``manual_wire_dtype`` gate promises (an
accidental ``.astype(f32)`` upstream of a psum silently doubles the bytes
of every gradient hand-off — the regression PR 1's TOPOLOGY artifact
exists to prevent).  This pass traces programs to jaxprs (no compile, no
devices touched) and walks them with three checks:

* **axis binding** — collective axis names must be bound by an enclosing
  ``shard_map`` (trace-time NameErrors are caught and classified; the
  static walk double-checks eqn axes against the binder stack).
* **manual wire dtype** — non-scalar floating ``psum`` operands inside
  manual regions must equal the resolved wire dtype
  (``parallel.tp.resolve_wire_dtype`` under the pinned knob).  Scalar
  psums are exempt (loss/metric scalars are latency-, not volume-bound);
  integer psums are exempt (token counts, routing).
* **collectives under cond/while** — a collective beneath value-dependent
  control flow executes only if the predicate agrees on every rank; a
  divergent predicate is a cross-rank deadlock, not an error message.
  Flagged unless suppressed with a written uniformity argument.

Suppressions are code, reviewed like code: entries in
:data:`SUPPRESSIONS` carry a rationale string, and a suppression that
matches nothing in a linted program is itself a finding (stale
suppressions rot into blanket ignores otherwise).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from . import Finding, Note

#: collective primitives (cross-rank semantics; must agree on every rank).
COLLECTIVE_PRIMITIVES: Set[str] = {
    "psum", "psum2", "pmax", "pmin", "pbroadcast", "ppermute", "pgather",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
}
#: the all-reduce class the wire-dtype gate governs (gradient/activation
#: volume wires; pmax/pmin/ppermute ride their own numerics contracts).
_WIRE_CHECKED: Set[str] = {"psum", "psum2"}
_CONTROL_PRIMITIVES: Set[str] = {"cond", "while"}

#: registered programs whose builders pin an explicit wire override —
#: linted against that pin, not the knob (runtime/topology.py builds a
#: _f32 twin of each probe precisely to keep the f32 path compiling).
PROGRAM_WIRE_OVERRIDES: Dict[str, str] = {
    "manual_psum_f32": "float32",
    "pallas_ring_allreduce_f32": "float32",
}


@dataclasses.dataclass
class Suppression:
    """One accepted hazard.  ``axes``/``dtype`` of ``None`` match any;
    ``rationale`` is mandatory — it is the review record."""

    program: str
    code: str                      # finding code this silences
    rationale: str
    axes: Optional[Tuple[str, ...]] = None
    dtype: Optional[str] = None
    hits: int = dataclasses.field(default=0, compare=False)

    def matches(self, program: str, code: str, axes: Tuple[str, ...],
                dtype: str) -> bool:
        return (self.program == program and self.code == code
                and (self.axes is None or self.axes == tuple(axes))
                and (self.dtype is None or self.dtype == dtype))


#: The tree's accepted hazards.  Keep this SHORT; every entry is a debt.
SUPPRESSIONS: List[Suppression] = [
    Suppression(
        program=p, code="jaxpr-collective-under-cond",
        rationale="1F1B tick/stage predicates depend only on "
                  "(tick, stage, microbatch count) — identical constants "
                  "on every rank of the group, so every rank takes the "
                  "same branch (llama_pipeline._make_tp_ce_sum docstring; "
                  "the alternating schedule is cond-gated by design)")
    for p in ("1f1b_manual_tp_combined", "1f1b_manual_tp_alternating")
] + [
    Suppression(
        program=p, code="jaxpr-manual-psum-wire-dtype",
        axes=("tp",), dtype="float32",
        rationale="tp-sharded CE forward psums (softmax normalization sum "
                  "+ cross-shard target-logit pick): intentional f32 "
                  "numerics whose operands are already vocab-reduced "
                  "(B, C) — bytes are B*C, not the B*C*V a gradient wire "
                  "carries; the CE *gradient* psum rides the gate "
                  "(llama_pipeline._make_tp_ce_sum bwd)")
    for p in ("1f1b_manual_tp_combined", "1f1b_manual_tp_alternating")
]


# ----------------------------------------------------------------- walker


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    from jax.extend import core

    out = []
    for k, v in eqn.params.items():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for i, b in enumerate(vs):
            if isinstance(b, core.ClosedJaxpr):
                out.append((f"{k}[{i}]", b.jaxpr))
            elif isinstance(b, core.Jaxpr):
                out.append((f"{k}[{i}]", b))
    return out


def _shard_map_bound_axes(eqn) -> Set[str]:
    mesh = eqn.params.get("mesh")
    axes = set(getattr(mesh, "axis_names", ()) or ())
    auto = eqn.params.get("auto") or frozenset()
    return axes - set(auto)


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list, frozenset, set)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


def lint_jaxpr(jaxpr, label: str, expected_wire: Optional[str],
               suppressions: Sequence[Suppression],
               findings: List[Finding], notes: List[Note]) -> None:
    """Walk one (traced) jaxpr, appending findings/notes.

    ``expected_wire``: dtype name every non-scalar float manual-region
    psum must carry, or None to skip the wire check.
    """

    def _emit(code: str, axes: Tuple[str, ...], dtype: str, msg: str) -> None:
        for s in suppressions:
            if s.matches(label, code, axes, dtype):
                s.hits += 1
                notes.append(Note("jaxpr", f"suppressed:{code}", label,
                                  f"{msg} — suppressed: {s.rationale}"))
                return
        findings.append(Finding("jaxpr", code, label, msg))

    def walk(jx, bound: Set[str], manual_depth: int, ctrl: List[str]) -> None:
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMITIVES:
                axes = _eqn_axes(eqn)
                avals = [v.aval for v in eqn.invars
                         if hasattr(v.aval, "dtype")]
                dtype = str(avals[0].dtype) if avals else "?"
                unbound = [a for a in axes if a not in bound]
                if unbound:
                    _emit("jaxpr-unbound-axis", axes, dtype,
                          f"{prim} over axes {axes} but {unbound} not bound "
                          f"by any enclosing shard_map (bound: "
                          f"{sorted(bound) or 'none'})")
                if ctrl:
                    _emit("jaxpr-collective-under-cond", axes, dtype,
                          f"{prim} over {axes} under {'/'.join(ctrl)}: ranks "
                          "disagreeing on the predicate would desync the "
                          "collective schedule (deadlock, not an error)")
                if (expected_wire is not None and prim in _WIRE_CHECKED
                        and manual_depth > 0):
                    for aval in avals:
                        import jax.numpy as jnp

                        if (jnp.issubdtype(aval.dtype, jnp.floating)
                                and aval.ndim >= 1
                                and str(aval.dtype) != expected_wire):
                            _emit("jaxpr-manual-psum-wire-dtype", axes,
                                  str(aval.dtype),
                                  f"manual-region {prim} over {axes} rides "
                                  f"{aval.dtype} (shape "
                                  f"{tuple(aval.shape)}); the "
                                  f"manual_wire_dtype gate resolves "
                                  f"{expected_wire} — an upstream upcast "
                                  "is inflating wire bytes")
                            break
            sub_bound = bound | (_shard_map_bound_axes(eqn)
                                 if prim == "shard_map" else set())
            sub_manual = manual_depth + (1 if prim == "shard_map" else 0)
            sub_ctrl = ctrl + ([prim] if prim in _CONTROL_PRIMITIVES else [])
            for _, sub in _sub_jaxprs(eqn):
                walk(sub, sub_bound, sub_manual, sub_ctrl)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr,
         set(), 0, [])


def lint_callable(fn: Callable, args: Tuple, label: str,
                  expected_wire: Optional[str] = None,
                  suppressions: Optional[Sequence[Suppression]] = None,
                  ) -> Tuple[List[Finding], List[Note]]:
    """Trace ``fn(*args)`` and lint the jaxpr.  Trace failures are
    findings, not crashes: an unbound axis name raises at bind time."""
    import jax

    findings: List[Finding] = []
    notes: List[Note] = []
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — the failure IS the verdict
        text = f"{type(e).__name__}: {str(e)[:300]}"
        code = ("jaxpr-unbound-axis"
                if "axis name" in str(e) or "unbound" in str(e).lower()
                else "jaxpr-trace-error")
        findings.append(Finding("jaxpr", code, label,
                                f"tracing failed: {text}"))
        return findings, notes
    lint_jaxpr(jaxpr, label, expected_wire,
               list(suppressions or ()), findings, notes)
    return findings, notes


# ------------------------------------------------------------ repo runner


def lint_registered_programs(topology: str = "v5e-8",
                             programs: Optional[Sequence[str]] = None,
                             wire_dtype: str = "bfloat16",
                             ) -> Tuple[List[Finding], List[Note]]:
    """Trace + lint ``runtime/topology.py:PROGRAMS`` against a named TPU
    topology with the ``manual_wire_dtype`` knob pinned to ``wire_dtype``
    (the TPU resolution — how the byte-halving is proven; tracing needs no
    chips, same as the AOT dry run)."""
    from ..parallel import tp as _tp
    from ..runtime import config
    from ..runtime import topology as topo

    labels = list(topo.PROGRAMS) if programs is None else list(programs)
    unknown = [l for l in labels if l not in topo.PROGRAMS]
    if unknown:
        raise KeyError(f"unknown programs {unknown}; "
                       f"known: {list(topo.PROGRAMS)}")
    if config.frozen():
        raise RuntimeError(
            "jaxpr lint needs a writable config to pin manual_wire_dtype "
            "(constants are frozen; run before start() or after reset())")

    findings: List[Finding] = []
    notes: List[Note] = []
    prior = config.get("manual_wire_dtype")
    config.set("manual_wire_dtype", wire_dtype)
    try:
        resolved = str(__import__("jax.numpy", fromlist=["dtype"]
                                  ).dtype(_tp.resolve_wire_dtype()))
        active = [s for s in SUPPRESSIONS if s.program in labels]
        for s in active:
            s.hits = 0
        for label in labels:
            expected = PROGRAM_WIRE_OVERRIDES.get(label, resolved)
            try:
                fn, args = topo.PROGRAMS[label](topology)
            except Exception as e:  # noqa: BLE001 — record, don't abort
                findings.append(Finding(
                    "jaxpr", "jaxpr-build-error", label,
                    f"program builder failed: {type(e).__name__}: "
                    f"{str(e)[:300]}"))
                continue
            f, n = lint_callable(fn, args, label, expected_wire=expected,
                                 suppressions=active)
            findings += f
            notes += n
        for s in active:
            if s.hits == 0:
                findings.append(Finding(
                    "jaxpr", "jaxpr-stale-suppression", s.program,
                    f"suppression for {s.code!r} matched nothing — the "
                    "hazard it documented is gone; delete the entry "
                    f"(rationale was: {s.rationale[:120]})"))
    finally:
        config.set("manual_wire_dtype", prior)
    return findings, notes
