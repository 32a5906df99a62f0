#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, and as the last line of standard output one JSON
object: `correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` `breakdown`.  `--trace 0` reports the cell's end-to-end metrics
with the profiler off; `--trace 1` reports its per-layer metrics, from the
profiler's trace of a short steady window and from the program's counters.
Without a TPU, with fewer chips than the cell asks for, or on a device kind
that `peaks.json` lacks, the run exits non-zero and prints no result.

`--rehearse` is for the CPU (see README.md): it lays each file's `rehearse`
sizes over it, runs the same code end to end, and prints `"correct": false`,
the CPU in `device`, and the names of the metrics it could have read but
none of their values, since a number from a CPU run is no device metric.
"""

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import json
import sys

import harness                      # beside this file, as every module here
from harness import ROOT, BenchmarkError, log

sys.path.insert(0, ROOT)            # the program the runners import


def run_cell(args, spec):
    cell, config = harness.find_cell(spec, args.workload)
    cfg = harness.load_json(config["file"], base=ROOT)
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse:
        cfg, mix = harness.rehearsed(cfg), harness.rehearsed(mix)

    device, peaks = harness.device_info(cell["chips"], args.rehearse)
    ctx = harness.Context(cell=cell, cfg=cfg, traffic=mix, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START)
    runner = harness.load_module("runners", cfg["runner"])
    try:
        seen = runner.run(ctx)
        obs = {"cell": cell, "cfg": cfg, "traffic": mix, "peaks": peaks,
               "counters": ctx.counters, "run": seen, "trace": None,
               "flops": ctx.module("flops")}
        if ctx.trace and ctx.trace_dir:
            import trace_reduce

            obs["trace"] = trace_reduce.reduce_dir(ctx.trace_dir, ctx.spans)
    finally:
        ctx.stop_trace()
        ctx.drop_trace()

    per_s_chip = seen["samples_per_s"] / cell["chips"]
    flops = obs["flops"].required_flops_per_sample(cfg, mix)
    check = ctx.counters["reference_check"]
    dp = ctx.counters.get("dp_check")
    correct = (check["ok"] and seen["failed"] == 0 and seen["attempted"] > 0
               and ctx.compiles_in_window == 0 and (dp is None or dp["ok"]))
    log(f"{seen['attempted']} steps in {seen['window_s']:.3f} s, "
        f"{per_s_chip:.1f} {cfg['throughput_metric']}, loss "
        f"{seen['first_loss']:.4f} -> {seen['last_loss']:.4f}, "
        f"{ctx.compiles_in_window} compilation(s) in the window, set-up "
        f"{ctx.setup_s:.2f} s")
    log(f"reference check: {check}")
    if obs["trace"] is not None:
        log(f"trace: {obs['trace']['steps']} whole steps on "
            f"{obs['trace']['devices']} device(s)")
    if dp is not None:
        log(f"one device against {cell['chips']}: {dp}")

    if args.trace:
        values = {}
        for m in harness.metrics_of(spec, "per_layer", cell["name"]):
            value = harness.load_module("layers", m["name"]).read(obs)
            if harness.finite(value):
                values[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            cfg["throughput_metric"]: per_s_chip,
            "setup_s": ctx.setup_s,
        }
        if peaks is not None:
            values["mfu"] = harness.mfu_percent(flops, per_s_chip, peaks)
        units = {m["name"]: m["unit"]
                 for m in harness.metrics_of(spec, "end_to_end", cell["name"])}
        values = {k: {"value": v, "unit": units[k]}
                  for k, v in values.items() if k in units}

    result = {"correct": bool(correct), "attempted": seen["attempted"],
              "failed": seen["failed"], "metrics": values, "device": device}
    if args.rehearse:
        # A CPU run has no device metric: names only, and never correct.
        result["correct"] = False
        result["rehearsal"] = {"would_report": sorted(values),
                               "checks_passed": bool(correct)}
        result["metrics"] = {}
        return result
    device["memory_peak_bytes"] = harness.memory_peak_bytes(
        seen["devices"], seen["program_bytes"])
    if args.trace and obs["trace"] is not None:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = obs["trace"]["breakdown"]
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' `rehearse` sizes")
    args = ap.parse_args(argv)
    try:
        spec = harness.load_json("BENCHMARK.json", base=ROOT)
        result = run_cell(args, spec)
    except BenchmarkError as e:
        log(f"error: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
