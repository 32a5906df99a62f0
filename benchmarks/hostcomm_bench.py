"""Host-plane (TCP ring) bandwidth sweep across REAL processes on loopback —
the reference's benchmark-as-tuner protocol (torchmpi/tester.lua:103-126)
applied to hostcomm: sizes 2^8..2^23 f32, chunk_bytes in {64k..4M}, bus
bandwidth modeled as 2n(p-1)/p bytes per rank for the ring allreduce.

    python benchmarks/hostcomm_bench.py --nproc 4
    python benchmarks/hostcomm_bench.py --nproc 2 --quick

Rank 0 prints one JSON line per (chunk_bytes, size) and a winner summary;
the chosen default feeds runtime/config.py's buffer knobs (BASELINE.md
round-4 table).
"""

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np


def worker(rank, nproc, ports, sizes, chunks, reps_cap, out_path, hier=None,
           crc=False):
    from torchmpi_tpu.collectives.hostcomm import (HierarchicalHostCommunicator,
                                                   HostCommunicator)
    from torchmpi_tpu.runtime import config

    # CRC A/B: the frame-integrity trailers are a per-comm wire-format
    # choice (every rank agrees via config), so the flag must be set
    # BEFORE wiring.  crc=False is the seed fast path.
    config.reset(hc_frame_crc=bool(crc))
    if hier:
        # Two-level plane: ports = nproc intra ports then one per group.
        groups = [[int(r) for r in g.split(",")] for g in hier.split(";")]
        intra = [("127.0.0.1", p) for p in ports[:nproc]]
        inter = [("127.0.0.1", p) for p in ports[nproc:]]
        comm = HierarchicalHostCommunicator(rank, groups, intra, inter,
                                            timeout_ms=30000)
    else:
        endpoints = [("127.0.0.1", p) for p in ports]
        comm = HostCommunicator(rank, nproc, endpoints, timeout_ms=30000)
    rows = []
    for cb in chunks:
        config.reset(hc_frame_crc=bool(crc))
        config.set("min_buffer_size_cpu", cb)
        config.set("max_buffer_size_cpu", cb)
        for n in sizes:
            a = np.zeros((n,), np.float32)
            # Warmup + sync.
            comm.allreduce(a)
            comm.barrier()
            # Budget ~20 MB of payload bytes per cell, 3..reps_cap reps.
            reps = int(min(reps_cap, max(3, (20 << 20) // max(n * 4, 1))))
            t0 = time.perf_counter()
            for _ in range(reps):
                comm.allreduce(a)
            dt = (time.perf_counter() - t0) / reps
            comm.barrier()
            if rank == 0:
                row = {"plane": f"hier[{hier}]" if hier else "flat",
                       "chunk_bytes": cb, "elements": n,
                       "crc": bool(crc),
                       "ms": round(dt * 1e3, 3)}
                if not hier:
                    # Ring bus model only describes the FLAT ring; the
                    # two-level algebra moves different per-rank bytes, so
                    # hier rows compare on ms alone.
                    bus = 2 * n * 4 * (nproc - 1) / nproc
                    row["bus_gb_s"] = round(bus / dt / 1e9, 3)
                rows.append(row)
    # Observability satellite (new keys; every timed row above ran with
    # obs_trace at its configured value — off by default, so the default
    # sweep numbers are untouched): one instrumented
    # pass at a mid size yields a per-op collective-time breakdown from
    # the span tracer, and the metrics registry contributes a native
    # counter snapshot.  All ranks run the ops (collective semantics);
    # rank 0 records the summary row.
    # Only the SETUP is guarded (e.g. the PS .so that apply_config loads
    # won't build): that failure is identical on every rank, so all ranks
    # skip together and the sweep rows above still land.  The probe
    # collectives themselves run unguarded — swallowing a rank-local
    # transport fault there would desync the ring for the final barrier.
    obs_ready = False
    try:
        from torchmpi_tpu.obs import metrics as obs_metrics
        from torchmpi_tpu.obs import native as obs_native
        from torchmpi_tpu.obs import tracer as obs_tracer

        prior_trace = bool(config.get("obs_trace"))
        config.set("obs_trace", True)
        obs_native.apply_config()
        obs_ready = True
    except Exception as e:  # noqa: BLE001 — the sweep rows must still land
        print(f"hostcomm_bench: obs summary unavailable ({e!r})",
              file=sys.stderr, flush=True)
    if obs_ready:
        try:
            obs_tracer.drain()
            probe = np.zeros((sizes[len(sizes) // 2],), np.float32)
            for _ in range(3):
                comm.allreduce(probe)
            comm.barrier()
            spans = obs_tracer.drain()
        finally:
            config.set("obs_trace", prior_trace)
            obs_native.apply_config()
        if rank == 0:
            obs_metrics.registry.scrape_native()
            rows.append({
                "summary": True,
                "probe_elements": int(probe.size),
                "collective_breakdown": obs_tracer.breakdown(spans),
                "metrics_snapshot": obs_metrics.registry.snapshot(),
            })

    comm.barrier()
    comm.close()
    if rank == 0:
        with open(out_path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=4)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--worker", nargs=2, type=int, metavar=("RANK", "NPROC"))
    ap.add_argument("--ports", type=str, default="")
    ap.add_argument("--out", type=str, default="/tmp/hostcomm_bench.jsonl")
    ap.add_argument("--hier", type=str, default=None,
                    help="semicolon-separated rank groups (e.g. '0,1,2;3,4,5')"
                         ": bench the two-level intra x roots plane instead "
                         "of the flat ring (flat-vs-hier A/B at equal nproc)")
    ap.add_argument("--crc", action="store_true",
                    help="enable hc_frame_crc (CRC32 frame trailers) so the "
                         "integrity check's cost is measurable against the "
                         "default crc-off seed fast path")
    args = ap.parse_args()

    sizes = ([1 << 12, 1 << 18, 1 << 22] if args.quick else
             [1 << k for k in range(8, 24, 2)] + [(1 << 20) + 7919])
    chunks = ([1 << 18] if args.quick else
              [1 << 16, 1 << 18, 1 << 20, 1 << 22])

    if args.worker:
        rank, nproc = args.worker
        ports = [int(p) for p in args.ports.split(",")]
        worker(rank, nproc, ports, sizes, chunks, reps_cap=50,
               out_path=args.out, hier=args.hier, crc=args.crc)
        return

    from torchmpi_tpu.collectives.hostcomm import free_ports

    n_groups = len(args.hier.split(";")) if args.hier else 0
    if args.hier:
        nranks = sum(len(g.split(",")) for g in args.hier.split(";"))
        if nranks != args.nproc:
            raise SystemExit(f"--hier names {nranks} ranks, --nproc is "
                             f"{args.nproc}")
    ports = ",".join(map(str, free_ports(args.nproc + n_groups)))
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--worker", str(r), str(args.nproc), "--ports", ports,
         "--out", args.out]
        + (["--quick"] if args.quick else [])
        + (["--hier", args.hier] if args.hier else [])
        + (["--crc"] if args.crc else []),
        # Host plane only (numpy over TCP): the workers stay off any
        # accelerator, which one process at a time may hold.
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
        for r in range(args.nproc)]
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"worker rcs: {rc}")
    # One winner table PER PLANE, scored within ONE unit (-ms: lower wall
    # time wins).  Mixing units — bus_gb_s for flat rows vs -ms for hier
    # rows — made any flat row (positive GB/s) beat any hier row (negative
    # ms) at the same element count regardless of actual wall time; wall
    # time is the comparable both planes report.
    best = {}
    for line in open(args.out):
        row = json.loads(line)
        print(json.dumps({"nproc": args.nproc, **row}), flush=True)
        if row.get("summary"):      # obs breakdown row, not a sweep cell
            continue
        key = (row["plane"], row["elements"])
        score = -row["ms"]
        if key not in best or score > best[key][0]:
            best[key] = (score, row)
    by_plane = {}
    for _, row in best.values():
        chunks = by_plane.setdefault(row["plane"], {})
        chunks[row["chunk_bytes"]] = chunks.get(row["chunk_bytes"], 0) + 1
    for plane, by_chunk in sorted(by_plane.items()):
        print(json.dumps({"plane": plane,
                          "winner_chunk_by_size_count": by_chunk}),
              flush=True)


if __name__ == "__main__":
    main()
