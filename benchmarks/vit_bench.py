"""ViT benchmark CLI: training step time by the two-point-slope protocol
of BASELINE.md (rounds 2-5), one JSON line per config.

    # real chip (defaults: ViT-B/16, 224x224, bf16):
    python benchmarks/vit_bench.py
    python benchmarks/vit_bench.py --batch 128 --attn flash

    # CPU smoke (tiny config):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/vit_bench.py --preset tiny --steps 3
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _autotune_section():
    """The acceptance A/B on THIS bench's model family, not just resnet
    (collectives/autotune.guarded_bench_section — shared with
    llama_bench; never raises, the headline row must land regardless)."""
    from torchmpi_tpu.collectives import autotune

    return autotune.guarded_bench_section(
        log=lambda m: log(f"vit_bench: {m}"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="b16", choices=["b16", "tiny"])
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--attn", default="full", choices=["full", "flash"])
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--registers", type=int, default=None,
                    help="learned register tokens appended to the patch "
                         "sequence; default 60 for --attn flash on b16 "
                         "(196+60=256 admits the Pallas tiles), else 0")
    ap.add_argument("--layer-loop", default="unroll",
                    choices=["unroll", "scan"],
                    help="unroll kills the scan's residual-stacking DUS "
                         "copies (+44%% on v5e, BASELINE.md)")
    ap.add_argument("--steps", type=int, default=10, help="timed steps (min 3)")
    args = ap.parse_args()
    args.steps = max(args.steps, 3)

    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import vit

    import dataclasses

    if args.registers is None:
        args.registers = 60 if (args.attn == "flash"
                                and args.preset == "b16") else 0
    if args.preset == "tiny":
        cfg = dataclasses.replace(vit.tiny(), n_registers=args.registers)
        args.batch = min(args.batch, 8)
    else:
        cfg = vit.vit_b16(n_registers=args.registers)
    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    params = vit.init(jax.random.PRNGKey(0), cfg, dtype=dtype)
    n = vit.num_params(params)
    log(f"vit_bench: preset={args.preset} params={n/1e6:.1f}M "
        f"batch={args.batch} backend={jax.default_backend()}")

    B = args.batch
    x = jnp.asarray(rng.randn(B, cfg.image, cfg.image, cfg.in_channels),
                    dtype)
    y = jnp.asarray(rng.randint(0, cfg.n_classes, (B,)), jnp.int32)
    loss_fn = vit.make_loss_fn(cfg, attn=args.attn, remat=args.remat,
                               layer_loop=args.layer_loop)

    def step_fn(p, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, (x, y))
        return jax.tree.map(lambda a, b: a - 1e-3 * b.astype(a.dtype),
                            p, g), loss

    step = jax.jit(step_fn, donate_argnums=(0,))
    p, loss = step(params, x, y)

    def run(p, nsteps):
        t0 = time.perf_counter()
        for _ in range(nsteps):
            p, loss = step(p, x, y)
        float(loss)
        return time.perf_counter() - t0, p

    n1 = min(max(2, args.steps // 3), args.steps - 1)
    _, p = run(p, 2)
    t1, p = run(p, n1)
    t2, p = run(p, args.steps)
    st = (t2 - t1) / (args.steps - n1)
    if st <= 0:
        log("vit_bench: slope non-positive, using plain average")
        st = t2 / args.steps
    # Dense layers apply PER TOKEN: 6 * matmul-params * tokens (fwd+bwd,
    # MAC=2), + the non-causal attention term 12 * layers * N^2 * d_model
    # per image.  The head runs once per image (post-pool), so it is
    # counted per image, not per token (per-token would overcount ~0.9%
    # on b16).
    # Registers are real tokens: they ride every encoder matmul and the
    # N^2 attention — but NOT patch_embed (they are concatenated after
    # it), which like the head is counted at its own token count.
    N = cfg.seq_len
    head = cfg.d_model * cfg.n_classes
    patch_mm = (cfg.patch * cfg.patch * cfg.in_channels) * cfg.d_model
    n_mm = (n - cfg.n_patches * cfg.d_model - head - patch_mm
            - cfg.n_registers * cfg.d_model)  # pos/register embeds: no matmul
    fl = (6 * n_mm * B * N + 6 * head * B + 6 * patch_mm * B * cfg.n_patches
          + 12 * cfg.n_layers * B * N * N * cfg.d_model)
    print(json.dumps({
        "metric": (f"vit-{args.preset} train ({args.attn}"
                   + (f"+{cfg.n_registers}reg" if cfg.n_registers else "")
                   + (f", remat={args.remat}" if args.remat != "none" else "")
                   + (", scan" if args.layer_loop == "scan" else "")
                   + f", {cfg.image}px)"),
        "value": round(B / st, 1), "unit": "images/sec",
        "ms_per_step": round(st * 1e3, 2),
        "approx_tflops": round(fl / st / 1e12, 1),
    }), flush=True)
    # Autotune section as its OWN line, AFTER the headline lands: a
    # wedged collective in the pass must not cost the measurement that
    # already completed.
    print(json.dumps({
        "metric": f"vit-{args.preset} autotune",
        "autotune": _autotune_section(),
    }), flush=True)


if __name__ == "__main__":
    main()
