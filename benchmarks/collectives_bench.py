"""Collective benchmark CLI — the reference's ``collectives_all.lua
-benchmark`` entry point (sizes 2^8..2^max with jitter, 10 warmup + 10 timed,
GB/s through the per-collective volume models).

    # 8-device virtual CPU mesh (cluster stand-in):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/collectives_bench.py --max-pow 20

    # real chips: no env overrides.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import torchmpi_tpu as mpi
from torchmpi_tpu.utils import tester


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--collectives", default=None,
                    help="comma list; default depends on --impl")
    ap.add_argument("--min-pow", type=int, default=8)
    ap.add_argument("--max-pow", type=int, default=23)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line per config instead of the table")
    ap.add_argument("--fence", default="block", choices=["block", "value"],
                    help="completion fence: 'value' (device->host read) "
                         "where block_until_ready does not fence")
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"],
                    help="pallas = device-plane ring kernels (allreduce/"
                         "reduce_scatter/allgather only).  Meaningful on "
                         "real multi-chip TPU; on the CPU mesh the kernels "
                         "run the Pallas *interpreter* (correct but ~1000x "
                         "slow — use tiny --min/max-pow, or pytest "
                         "tests/test_pallas_ring.py for correctness)")
    args = ap.parse_args()
    if args.collectives is None:
        args.collectives = ("allreduce,reduce_scatter,allgather"
                            if args.impl == "pallas" else
                            "allreduce,broadcast,allgather,"
                            "reduce_scatter,alltoall")
    colls = [c.strip() for c in args.collectives.split(",") if c.strip()]
    if args.impl == "pallas":
        bad = [c for c in colls if c not in tester.PALLAS_COLLECTIVES]
        if bad:
            ap.error(f"--impl pallas supports {tester.PALLAS_COLLECTIVES}; "
                     f"drop {bad}")

    import jax.numpy as jnp

    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    if args.impl == "pallas":
        # The selector's pallas namespace falls back to xla at or below the
        # small-message cutoff (the reference's nElement switch); zero it so
        # the sweep measures the rings themselves at every size.
        from torchmpi_tpu.runtime import config
        config.set("small_allreduce_size_gpu", 0)
    mpi.start(with_tpu=jax.default_backend() == "tpu")
    comm = mpi.stack.world()
    print(f"# backend={jax.default_backend()} p={comm.size}")

    report = None if args.json else print
    results = tester.sweep(
        comm,
        collectives=colls,
        min_pow=args.min_pow, max_pow=args.max_pow,
        dtype=dtype, warmup=args.warmup, iters=args.iters,
        report=report, fence=args.fence, impl=args.impl,
    )

    if args.json:
        for r in results:
            print(json.dumps({
                "impl": args.impl,
                "collective": r.collective, "elements": r.elements,
                "dtype": r.dtype, "p": r.p,
                "mean_us": round(r.mean_seconds * 1e6, 2),
                "bus_gbs": round(r.bus_gbs, 4),
                "peak_hbm_bytes": r.peak_hbm_bytes,
            }))
    mpi.stop()


if __name__ == "__main__":
    main()
