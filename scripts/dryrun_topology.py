#!/usr/bin/env python
"""AOT topology validation sweep: compile every registered multi-chip
program against named TPU topologies (zero chips needed) and write the
TOPOLOGY artifact.

    python scripts/dryrun_topology.py                 # v5e-8 + v4-32
    python scripts/dryrun_topology.py --topologies v5e-8
    python scripts/dryrun_topology.py --out TOPOLOGY_r06.json

Per topology the sweep runs twice where it matters: every program with
bf16 manual wires (what the TPU backend's ``manual_wire_dtype="auto"``
resolves to), plus the 1F1B manual-tp stage and the isolated psum probe
with f32 wires — the A/B that proves the bf16 gate halves the manual
stage's gradient wire bytes, asserted from the compiled HLO's collective
operand sizes rather than from faith.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def wire_comparison(bf16_run: dict, f32_run: dict) -> dict:
    """Extract the all-reduce wire-byte A/B between the bf16- and
    f32-wire compiles of the same programs."""
    out = {}
    for label, rec_f32 in f32_run["programs"].items():
        rec_bf16 = bf16_run["programs"].get(label)
        if not (rec_bf16 and rec_bf16.get("compile_ok")
                and rec_f32.get("compile_ok")):
            continue

        def ar_bytes(rec):
            ob = rec.get("collectives", {}).get("operand_bytes", {})
            return {k: v for k, v in ob.items() if k.startswith("all-reduce")}

        out[label] = {
            "all_reduce_operand_bytes_bf16_wire": ar_bytes(rec_bf16),
            "all_reduce_operand_bytes_f32_wire": ar_bytes(rec_f32),
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topologies", nargs="*", default=["v5e-8", "v4-32"])
    ap.add_argument("--out", default=os.path.join(_REPO, "TOPOLOGY_r06.json"))
    ap.add_argument("--programs", nargs="*", default=None,
                    help="subset of runtime.topology.PROGRAMS labels")
    args = ap.parse_args()

    # The compile-only path must not take hold of a real TPU —
    # everything here is host-side compilation.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

    from torchmpi_tpu.runtime import topology

    artifact = {
        "artifact": "topology-aot-dryrun",
        "jax": __import__("jax").__version__,
        "topologies": {},
    }
    ok_total = 0
    for topo in args.topologies:
        print(f"== {topo}", file=sys.stderr, flush=True)
        bf16_run = topology.dryrun_topology(topo, programs=args.programs,
                                            wire_dtype="bfloat16")
        # f32-wire comparison pass: the isolated probe pair already covers
        # both wires; recompile the real manual-tp 1F1B stage with f32
        # wires so the halving is shown on the production program.
        f32_labels = [l for l in ("1f1b_manual_tp_combined",)
                      if args.programs is None or l in args.programs]
        f32_run = (topology.dryrun_topology(topo, programs=f32_labels,
                                            wire_dtype="float32")
                   if f32_labels else {"programs": {}})
        entry = dict(bf16_run)
        entry["f32_wire_programs"] = f32_run["programs"]
        entry["wire_comparison"] = wire_comparison(bf16_run, f32_run)
        artifact["topologies"][topo] = entry
        ok_total += entry["compile_ok_count"]
        for label, rec in entry["programs"].items():
            status = "ok" if rec.get("compile_ok") else "FAIL"
            print(f"   {label:32s} {status}", file=sys.stderr, flush=True)

    artifact["compile_ok_total"] = ok_total
    # The bf16-psum-in-manual-region question, answered from the records:
    # supported iff the bf16-wire probe compiled on every swept topology
    # that RAN it.  A sweep that never ran the probe (a --programs subset)
    # must say "unanswered" (null), not "unsupported" — the same
    # evidence-honesty rule as dryrun_topology's frozen-config guard.
    probes = [t["programs"]["manual_psum_bf16"]
              for t in artifact["topologies"].values()
              if "manual_psum_bf16" in t["programs"]]
    artifact["bf16_psum_in_manual_region"] = {
        "supported": (all(p.get("compile_ok") for p in probes)
                      if probes else None),
        "evidence": ("manual_psum_bf16 compile records per topology"
                     if probes else "probe not run in this sweep"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps({"out": args.out, "compile_ok_total": ok_total,
                      "bf16_manual_psum_supported":
                          artifact["bf16_psum_in_manual_region"]["supported"]}),
          flush=True)


if __name__ == "__main__":
    main()
