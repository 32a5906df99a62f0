"""Pallas-conv experiment on the ResNet MXU-underfill shapes (VERDICT r04
weak item 1 / next-round item 6): the r03 trace pinned the single-chip
ResNet plateau on conv fusions at ~46% MXU efficiency, dominated by the
deep-stage shapes whose spatial tiles underfill the 128x128 MXU —
7x7x512 k3 (2.64 ms fwd+bwd chain) and the 14x14x256 band.  This bench
runs the one untried lever: a hand-tiled Pallas conv (shifted-window
accumulation — im2col as nine MXU dots over a VMEM-resident input block,
no patch matrix materialized) against XLA's conv on exactly those shapes,
interleaved A/B, slope-timed (fori_loop-chained iterations inside one jit,
fenced by a value read — the r04 isolated-shape protocol).

    python benchmarks/pallas_conv_bench.py            # real chip
    JAX_PLATFORMS=cpu python benchmarks/pallas_conv_bench.py --check
        # correctness only (interpreter)

One JSON line per (shape, impl, direction); a final verdict line feeds
BASELINE.md's accept/reject table.  Reference: the custom-kernel-beats-
vendor stance this framework inherits (reference README.md:106).
"""

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------- the kernel
#
# NHWC k3 s1 same-pad conv as shifted-window MXU dots: grid over
# (batch blocks, out-channel blocks); each instance holds a (bn, H+2, W+2,
# C) input block and a (9, C, bc) filter block in VMEM and accumulates
#   o[:, i, j, :] += x[:, i+di, j+dj, :] @ w[di*3+dj]
# as nine (bn*H*W, C) @ (C, bc) dots — the im2col contraction without ever
# materializing the (N*H*W, 9C) patch matrix in HBM (its write+read is pure
# bandwidth at these shapes).  f32 accumulation, cast on store.


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref):
    bn, Hp, Wp, C = x_ref.shape
    H, W = Hp - 2, Wp - 2
    bc = o_ref.shape[-1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for di in range(3):
        for dj in range(3):
            win = x_ref[:, di:di + H, dj:dj + W, :].reshape(bn * H * W, C)
            acc_ref[...] += jnp.dot(
                win.astype(jnp.float32),
                w_ref[di * 3 + dj].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[...] = acc_ref[...].reshape(bn, H, W, bc).astype(o_ref.dtype)


def pallas_conv3x3(x, w, bn=8, bc=256, interpret=False):
    """x (N, H, W, C) NHWC, w (3, 3, C, Cout) -> (N, H, W, Cout); k3 s1
    same-pad.  ``bn`` batches x ``bc`` output channels per grid cell."""
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    if N % bn or Cout % bc:
        raise ValueError(f"bn={bn} must divide N={N}, bc={bc} Cout={Cout}")
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wf = w.reshape(9, C, Cout)
    return pl.pallas_call(
        _conv_kernel,
        grid=(N // bn, Cout // bc),
        in_specs=[
            pl.BlockSpec((bn, H + 2, W + 2, C), lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((9, C, bc), lambda b, c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((bn, H, W, bc), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn * H * W, bc), jnp.float32)],
        interpret=interpret,
    )(xp, wf)


def xla_conv3x3(x, w):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def im2col_conv3x3(x, w):
    """Explicit patch extraction + one dot — the materialized-im2col
    contrast arm (XLA fuses what it can; the patch matrix may still hit
    HBM)."""
    N, H, W, C = x.shape
    patches = lax.conv_general_dilated_patches(
        x, (3, 3), (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # patches: (N, H, W, C*9) with channel-major feature order -> matches
    # w.transpose(2,0,1,3).reshape(C*9, Cout).
    wf = w.transpose(2, 0, 1, 3).reshape(C * 9, w.shape[-1])
    return (patches.reshape(N * H * W, C * 9) @ wf).reshape(
        N, H, W, w.shape[-1])


# ------------------------------------------------------------- measurement

def chain(fn, n):
    """fori_loop-chain n applications (output feeds input through a cast)
    so the whole run is one dispatch; returns a jitted thunk."""

    def run(x, w):
        def body(_, xc):
            return fn(xc, w).astype(xc.dtype)

        return lax.fori_loop(0, n, body, x)

    return jax.jit(run)


def grad_chain(fn, n):
    """fori_loop-chained fwd+bwd: each iteration takes d/d(x,w) of one conv
    (the r04 rejection-table protocol) — where the training-step cost
    actually lives (dx needs the transposed-filter conv, dw the
    activation-cotangent correlation)."""

    def one(x, w):
        return jnp.sum(fn(x, w).astype(jnp.float32) ** 2)

    g = jax.grad(one, argnums=(0, 1))

    def run(x, w):
        def body(_, c):
            xc, wc = c
            dx, dw = g(xc, wc)
            return (dx.astype(xc.dtype) * 1e-3 + xc,
                    dw.astype(wc.dtype) * 1e-3 + wc)

        x2, w2 = lax.fori_loop(0, n, body, (x, w))
        return x2

    return jax.jit(run)


def slope_time(fn, x, w, n1=50, n2=200, make_chain=None):
    """Two-point slope over LONG chains: where a dispatch carries a
    drifting fixed latency (~30-60 ms on the rounds 2-5 set-up) the chain
    difference must dwarf it — 150 chained convs at ~0.5-3 ms each gives a
    75-450 ms differential signal."""
    mk = make_chain or chain
    c1, c2 = mk(fn, n1), mk(fn, n2)
    float(jnp.sum(c1(x, w)))            # compile + warm
    float(jnp.sum(c2(x, w)))
    t0 = time.perf_counter()
    float(jnp.sum(c1(x, w)))
    ta = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(jnp.sum(c2(x, w)))
    tb = time.perf_counter() - t0
    return (tb - ta) / (n2 - n1)


SHAPES = [
    ("7x7x512 k3", (128, 7, 7, 512), 512, dict(bn=8, bc=256)),
    ("14x14x256 k3", (128, 14, 14, 256), 256, dict(bn=8, bc=256)),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="correctness only (interpreter off-TPU)")
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    rng = np.random.RandomState(0)

    if args.check or not on_tpu:
        for name, xshape, cout, kw in SHAPES:
            N, H, W, C = xshape
            # Tiny check geometry: same structure, interpreter-speed sizes.
            xs = (8, H, W, 64)
            x = jnp.asarray(rng.randn(*xs), jnp.float32)
            w = jnp.asarray(rng.randn(3, 3, 64, 128) * 0.1, jnp.float32)
            want = xla_conv3x3(x, w)
            got = pallas_conv3x3(x, w, bn=4, bc=128, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)
            got2 = im2col_conv3x3(x, w)
            np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)
            print(json.dumps({"shape": name, "check": "ok"}), flush=True)
        return

    dtype = jnp.bfloat16
    for name, xshape, cout, kw in SHAPES:
        N, H, W, C = xshape
        x = jnp.asarray(rng.randn(*xshape), dtype)
        w = jnp.asarray(rng.randn(3, 3, C, cout) * 0.05, dtype)
        flops = 2 * N * H * W * 9 * C * cout
        impls = {
            "xla": xla_conv3x3,
            "im2col": im2col_conv3x3,
            "pallas": lambda x, w, kw=kw: pallas_conv3x3(x, w, **kw),
        }
        # Where the step cost actually lives: the fwd+bwd chain (XLA only —
        # the pallas kernel is fwd-only; a win here would motivate the
        # dx/dw kernels, a loss closes the question).
        ms_g = sorted(slope_time(xla_conv3x3, x, w, make_chain=grad_chain)
                      for _ in range(args.trials))[args.trials // 2]
        print(json.dumps({
            "shape": name, "impl": "xla fwd+bwd",
            "ms": round(ms_g * 1e3, 3),
            "mxu_eff": round(3 * flops / ms_g / 197e12, 3),
        }), flush=True)
        # Interleaved trials: impl order rotates so drift hits all alike.
        times = {k: [] for k in impls}
        for t in range(args.trials):
            for k in list(impls)[t % len(impls):] + list(impls)[:t % len(impls)]:
                times[k].append(slope_time(impls[k], x, w))
        for k, ts in times.items():
            ms = sorted(ts)[len(ts) // 2]
            print(json.dumps({
                "shape": name, "impl": k,
                "ms": round(ms * 1e3, 3),
                "trials_ms": [round(s * 1e3, 3) for s in ts],
                "mxu_eff": round(flops / ms / 197e12, 3),
            }), flush=True)


if __name__ == "__main__":
    main()
