"""The passes' float32 scatter-add alone, on one chip: XLA's ``at[].add``
against ``ops.scatter_add_rows``, 16 calls in a ``fori_loop`` that carries the
sums as the expert layer's loops do, at the Mellum2 cell's pass (65,536 sums,
8,192 rows of 2,304, two or three of 16 experts a pass) and at the GLM cell's
(16,384 sums, 32,768 rows of 2,048 of which a quarter arrived, 8 experts).
Prints ns a row (on the chip alone) and how far the kernel's sums are from
XLA's (0.0: to the bit); PERF.md section 6 (PR 52) has the readings this was
written for.

    chiprun --chips 1 --timeout 900 -- python3 benchmarks/scatter_add_rows_bench.py
    JAX_PLATFORMS=cpu python3 benchmarks/scatter_add_rows_bench.py   # tiny, interpreted

A change to the kernel's DMA waits is rehearsed HERE before a step runs it:
the interpreter's waits are no-ops, so a wrong count passes every CPU test and
hangs the chip (hence the call's ``--timeout``).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from torchmpi_tpu.ops.scatter_add_rows import row_tile, scatter_add_rows

CALLS = 16
ON_CHIP = jax.devices()[0].platform == "tpu"
SHAPES = {
    "mellum2": dict(N=65536, R=8192, D=2304, experts=16, each=8192, passes=16),
    "glm": dict(N=16384, R=32768, D=2048, experts=8, each=1024, passes=1),
} if ON_CHIP else {
    "mellum2": dict(N=512, R=64, D=256, experts=4, each=64, passes=4),
    "glm": dict(N=128, R=256, D=128, experts=4, each=16, passes=1),
}


def passes_of(N, R, experts, each, passes, seed=7):
    """``(index (passes, R), kept (passes, experts), valid rows)``: the sorted
    order of ``experts`` segments of about ``each`` distinct tokens of N, cut
    into passes of R rows as ``llama._held_pass`` cuts it; a row past the
    order's end names N."""
    rng = np.random.default_rng(seed)
    segments = [np.sort(rng.choice(N, size=min(N, rng.binomial(N, each / N)),
                                   replace=False)) for _ in range(experts)]
    order = np.concatenate(segments)
    arrived = np.array([len(s) for s in segments])
    ends = np.cumsum(arrived)
    index = np.full((passes, R), N, np.int32)
    kept = np.zeros((passes, experts), np.int32)
    for p in range(passes):
        part = order[p * R:(p + 1) * R]
        index[p, :len(part)] = part
        kept[p] = (np.clip(ends - p * R, 0, R)
                   - np.clip(ends - arrived - p * R, 0, R))
    return jnp.asarray(index), jnp.asarray(kept), min(len(order), passes * R)


def best_of_three(loop, sums, *args):
    out = loop(sums, *args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = loop(out, *args)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def xla_loop(sums, index, kept, rows):
    def one(i, s):
        return s.at[index[i % index.shape[0]]].add(
            rows[i % rows.shape[0]].astype(jnp.float32), mode="drop")
    return lax.fori_loop(0, CALLS, one, sums)


def kernel_loop(tile):
    def loop(sums, index, kept, rows):
        def one(i, s):
            p = i % index.shape[0]
            return scatter_add_rows(s, index[p], rows[i % rows.shape[0]],
                                    kept[p], tile=tile, interpret=not ON_CHIP)
        return lax.fori_loop(0, CALLS, one, sums)
    return loop


def main():
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for name, shape in SHAPES.items():
        N, R, D = shape["N"], shape["R"], shape["D"]
        index, kept, valid = passes_of(
            N, R, shape["experts"], shape["each"], shape["passes"])
        a_loop = valid * CALLS // shape["passes"]
        rows = jax.random.normal(jax.random.PRNGKey(1), (2, R, D),
                                 jnp.bfloat16)
        def line(what, seconds, **more):
            # a time off the chip is the interpreter's: a rehearsal prints none
            times = dict(ms_a_loop=seconds * 1e3,
                         ns_a_valid_row=seconds * 1e9 / a_loop,
                         ns_a_pass_row=seconds * 1e9 / (R * CALLS))
            print(json.dumps(dict(shape=name, what=what, **(
                times if ON_CHIP else {"rehearsal": True}), **more)),
                  flush=True)

        xla = jax.jit(xla_loop, donate_argnums=0)
        want = np.asarray(xla(jnp.zeros((N, D), jnp.float32), index, kept,
                              rows))
        line("xla", best_of_three(xla, jnp.zeros((N, D), jnp.float32), index,
                                  kept, rows))
        for tile in (16,) if not ON_CHIP else sorted({64, row_tile(D)}):
            loop = jax.jit(kernel_loop(tile), donate_argnums=0)
            got = np.asarray(loop(jnp.zeros((N, 1, D), jnp.float32), index,
                                  kept, rows))[:, 0]
            line(f"kernel, tile {tile}", best_of_three(
                loop, jnp.zeros((N, 1, D), jnp.float32), index, kept, rows),
                 max_abs_difference=float(np.abs(got - want).max()))


if __name__ == "__main__":
    main()
