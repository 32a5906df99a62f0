"""Llama benchmark CLI: training step time (two-point slope, value-read
fence — the protocol of BASELINE.md, rounds 2-5) and
KV-cache decode throughput, one JSON line per config.

    # real chip (defaults: 8B-width 4-layer slice, bf16):
    python benchmarks/llama_bench.py
    python benchmarks/llama_bench.py --train-seq 8192 --attn flash
    python benchmarks/llama_bench.py --decode-batch 32

    # CPU smoke (tiny config):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/llama_bench.py --preset tiny --steps 3

Reproduces the numbers recorded in BASELINE.md §Llama.
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
    (collectives/autotune.guarded_bench_section — shared with vit_bench;
    never raises, the headline rows must land regardless)."""
    from torchmpi_tpu.collectives import autotune

    return autotune.guarded_bench_section(
        log=lambda m: log(f"llama_bench: {m}"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="8b-slice",
                    choices=["8b-slice", "8b", "tiny"],
                    help="8b-slice = full 8B width, 4 layers (fits 1 chip)")
    ap.add_argument("--attn", default="flash",
                choices=["full", "flash", "ring", "ring-zigzag"],
                help="ring = the flash-composed ring over an sp mesh of ALL visible devices (sp=1 single-chip measures the composition overhead against plain flash)")
    ap.add_argument("--train-batch", type=int, default=1)
    ap.add_argument("--train-seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=10,
                    help="timed steps for the slope (plus warmup; min 3)")
    ap.add_argument("--decode-batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--skip-train", action="store_true")
    ap.add_argument("--skip-decode", action="store_true")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="turn the FFN into this many routed experts "
                         "(Mixtral-style MoE; 0 = dense)")
    ap.add_argument("--moe-top-k", type=int, default=2)
    ap.add_argument("--layer-loop", default="scan",
                    choices=["scan", "unroll"],
                    help="unroll inlines the decoder layers (kills the "
                         "scan's residual-stacking DUS copies; A/B in "
                         "BASELINE.md)")
    args = ap.parse_args()
    args.steps = max(args.steps, 3)

    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama, llama_decode

    if args.preset == "tiny":
        cfg = llama.tiny()
        args.train_seq = min(args.train_seq, 64)
        args.prompt_len = min(args.prompt_len, 16)
        args.max_new = min(args.max_new, 8)
    elif args.preset == "8b":
        cfg = llama.llama3_8b()
    else:
        full = llama.llama3_8b()
        cfg = llama.Config(vocab=full.vocab, d_model=full.d_model,
                           n_layers=4, n_heads=full.n_heads,
                           n_kv_heads=full.n_kv_heads, d_ff=full.d_ff,
                           max_seq=full.max_seq)
    if args.moe_experts:
        import dataclasses

        cfg = dataclasses.replace(cfg, n_experts=args.moe_experts,
                                  expert_top_k=min(args.moe_top_k,
                                                   args.moe_experts))
    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    params = llama.init(jax.random.PRNGKey(0), cfg, dtype=dtype)
    nparams = llama.num_params(params)
    log(f"llama_bench: preset={args.preset} params={nparams/1e9:.2f}B "
        f"moe={cfg.n_experts or 'off'} backend={jax.default_backend()}")

    if not args.skip_train:
        B, L = args.train_batch, args.train_seq
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
        targets = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
        lc = min(512, L)
        while lc > 1 and L % lc:
            lc -= 1
        mesh = None
        if args.attn.startswith("ring"):
            from torchmpi_tpu import parallel as _par

            mesh = _par.make_mesh({"dp": 1, "sp": len(jax.devices())})
        loss_fn = llama.make_loss_fn(cfg, mesh=mesh, attn=args.attn,
                                     remat="dots",
                                     loss_chunk=lc if lc >= 64 else 0,
                                     layer_loop=args.layer_loop)
        def step_fn(p, t, tg):
            loss, g = jax.value_and_grad(loss_fn)(p, (t, tg))
            return jax.tree.map(lambda a, b: a - 3e-4 * b.astype(a.dtype),
                                p, g), loss
        step = jax.jit(step_fn, donate_argnums=(0,))
        p, loss = step(params, tokens, targets)

        def run(p, n):
            t0 = time.perf_counter()
            for _ in range(n):
                p, loss = step(p, tokens, targets)
            float(loss)
            return time.perf_counter() - t0, p

        n1 = min(max(2, args.steps // 3), args.steps - 1)
        _, p = run(p, 2)
        t1, p = run(p, n1)
        t2, p = run(p, args.steps)
        st = (t2 - t1) / (args.steps - n1)
        if st <= 0:
            # Timing noise beat the slope (tiny configs / CPU smoke): fall
            # back to the plain average, which only over-counts the fixed
            # dispatch overhead.
            log("llama_bench: slope non-positive, using plain average")
            st = t2 / args.steps
        n_mm = nparams - cfg.vocab * cfg.d_model
        if cfg.n_experts:
            # Only top-k of the E expert FFNs run per token.
            ffn = 3 * cfg.n_layers * cfg.d_model * cfg.d_ff
            n_mm = n_mm - ffn * cfg.n_experts + ffn * cfg.expert_top_k
        fl = 6 * n_mm * B * L + 12 * cfg.n_layers * B * L * L * cfg.d_model
        moe_tag = f", moe={cfg.n_experts}x top{cfg.expert_top_k}" \
            if cfg.n_experts else ""
        print(json.dumps({
            "metric": (f"llama-{args.preset} train ({args.attn}, L={L}"
                       + (", unroll" if args.layer_loop == "unroll" else "")
                       + f"{moe_tag})"),
            "value": round(B * L / st, 1), "unit": "tokens/sec",
            "ms_per_step": round(st * 1e3, 1),
            "approx_tflops": round(fl / st / 1e12, 1),
        }), flush=True)
        # Autotune section as its OWN line, AFTER the headline lands: a
        # wedged collective in the pass must not cost the measurement
        # that already completed.
        print(json.dumps({
            "metric": f"llama-{args.preset} autotune",
            "autotune": _autotune_section(),
        }), flush=True)

    if not args.skip_decode:
        if not args.skip_train:
            # The training loop donated the parameter buffers; rebuild.
            params = llama.init(jax.random.PRNGKey(0), cfg, dtype=dtype)
        B, Lp, N = args.decode_batch, args.prompt_len, args.max_new
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, Lp)), jnp.int32)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=Lp, max_new=N)
        np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))  # compile

        def run_gen():
            t0 = time.perf_counter()
            np.asarray(gen(params, prompt, jax.random.PRNGKey(2)))
            return time.perf_counter() - t0

        run_gen()
        ts = min(run_gen() for _ in range(3))
        print(json.dumps({
            "metric": f"llama-{args.preset} generate, prefill+decode "
                      f"(B={B}, prompt={Lp}, new={N})",
            "value": round(B * N / ts, 1), "unit": "tokens/sec",
            "ms_per_new_token_e2e": round(ts / N * 1e3, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
