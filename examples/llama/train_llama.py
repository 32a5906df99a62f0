"""Llama-family data+model-parallel training — BASELINE config 5
("Llama-3-8B hierarchical comm (intra-host ICI x inter-host DCN)
data+model parallel").

The mesh is dp x tp (x sp with --sp>1): `parallel.make_mesh` orders slow
(cross-host) axes above fast ICI axes, the parameter pytree is
Megatron-sharded by `llama.param_specs`, and one pjit'd step carries
forward, backward, the tp activation psums, and the dp gradient psums —
XLA's overlap replaces the reference's hand-pipelined per-layer sync
(reference: torchmpi/nn.lua:112-213).

8B-scale memory controls are on by default: per-layer rematerialization
(`--remat dots`) always, and for `--preset 8b` the chunked vocab loss
(`--loss-chunk`, auto 512) that never materializes the (B, L, V) f32
logits (`--loss-chunk 0` forces the dense loss).

Run on the virtual CPU mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama/train_llama.py --dp 2 --tp 4
(or on real TPU chips with no env overrides; --preset 8b for the full
Llama-3-8B geometry).  `--moe-experts E --ep N` switches the FFN to E
routed experts sharded over an expert-parallel axis (Mixtral-style);
`--sp` adds ring-attention sequence parallelism, with heads tp-sharded
when the mesh also has tp (Megatron-SP composition).
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

import torchmpi_tpu as mpi
from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama, llama_decode, llama_pipeline


def synthetic_tokens(cfg, n_seq, seq_len, seed=0):
    """A learnable synthetic corpus: order-1 Markov chains over the vocab so
    next-token loss genuinely falls below ln(vocab) (zero-egress stand-in
    for a tokenized dataset).  Returns ``(tokens, table)``; the transition
    table doubles as a generation-quality oracle (--generate)."""
    rng = np.random.RandomState(seed)
    # Each token deterministically maps to a small candidate set; sequences
    # random-walk through it.
    fanout = 4
    table = rng.randint(0, cfg.vocab, (cfg.vocab, fanout))
    toks = np.empty((n_seq, seq_len + 1), np.int64)
    toks[:, 0] = rng.randint(0, cfg.vocab, n_seq)
    for t in range(seq_len):
        pick = rng.randint(0, fanout, n_seq)
        toks[:, t + 1] = table[toks[:, t], pick]
    return toks.astype(np.int32), table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "8b"])
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel axis size (default 2; 1 when --pp "
                         "is given — pass explicitly to compose 3-D)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel axis size (default 4, or 1 when "
                         "--ep > 1 so the documented MoE invocation fits "
                         "the device count)")
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline stages; >0 switches to the GPipe step "
                         "(layers as stages); combine with explicit "
                         "--dp/--tp for the 3-D composed mesh (--sp does "
                         "not compose with pp)")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8, help="global sequences/step")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--attn", default="full",
                choices=["full", "flash", "ring", "ring-zigzag"])
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--loss-chunk", type=int, default=-1,
                    help="sequence chunk for the vocab loss (0 = dense; "
                         "default: auto — dense for tiny, 512 for 8b)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for --generate (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits when sampling")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass when sampling (0 = off)")
    ap.add_argument("--generate", type=int, default=0, metavar="N",
                    help="after training, generate N tokens per prompt and "
                         "score what fraction of transitions are legal "
                         "under the synthetic Markov corpus")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="Mixtral-style MoE FFN with this many routed "
                         "experts, sharded over an ep mesh axis (--ep)")
    ap.add_argument("--moe-top-k", type=int, default=2)
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel mesh axis size (with --moe-experts)")
    args = ap.parse_args()
    if args.loss_chunk < 0:
        args.loss_chunk = 512 if args.preset == "8b" else 0

    # Ring attention needs an sp mesh axis even at sp=1 (the shard_map
    # names it); sp=1 measures the composition against plain flash.
    needs_sp = args.sp > 1 or (args.attn.startswith("ring")
                               and args.pp == 0)
    if args.dp is None:
        # -1 = fill the remaining devices, so --sp/--tp choices always
        # multiply out to the visible device count without hand-tuning.
        args.dp = 1 if args.pp > 0 else (-1 if needs_sp else 2)
    if args.tp is None:
        args.tp = 1 if (args.ep > 1 or args.pp > 0 or needs_sp) else 4
    mpi.start()
    if args.moe_experts and args.pp > 0:
        raise SystemExit("--moe-experts does not compose with --pp "
                         "(make_pp_train_step rejects MoE configs)")
    if args.ep > 1 and not args.moe_experts:
        raise SystemExit("--ep without --moe-experts would only replicate "
                         "dense compute over the ep axis; add --moe-experts")
    if args.moe_experts:
        if args.moe_experts % max(args.ep, 1):
            raise SystemExit("--moe-experts must be divisible by --ep")
        if args.moe_top_k < 1:
            raise SystemExit("--moe-top-k must be >= 1")
    if args.pp > 0:
        if args.attn.startswith("ring"):
            raise SystemExit(f"--attn {args.attn} does not compose with "
                             "--pp (the sp ring and the GPipe carrier "
                             "conflict); use full or flash")
        # 3-D composition: dp and tp ride along with the pipeline (GSPMD
        # shards micro-batches over dp and stage weights over tp inside
        # every stage tick — make_pp_train_step's auto_other_axes path).
        axes = {"pp": args.pp,
                **({"dp": args.dp} if args.dp > 1 else {}),
                **({"tp": args.tp} if args.tp > 1 else {})}
    elif needs_sp:
        axes = {"dp": args.dp, "sp": args.sp,
                **({"tp": args.tp} if args.tp > 1 else {})}
    else:
        axes = {"dp": args.dp, "tp": args.tp}
    if args.ep > 1:
        if args.pp > 0 or needs_sp:
            raise SystemExit("--ep composes with dp x tp here; "
                             "drop --pp/--sp and ring attention")
        axes = {"dp": args.dp, "ep": args.ep,
                **({"tp": args.tp} if args.tp > 1 else {})}
    if args.pp > 0:
        # Mesh over exactly the devices the requested axes use (pp alone, or
        # the dp x pp x tp product when composing).
        n_pp = args.pp * max(args.dp, 1) * max(args.tp, 1) \
            if len(axes) > 1 else args.pp
        mesh = parallel.make_mesh(axes, devices=jax.devices()[:n_pp])
    else:
        mesh = parallel.make_mesh(axes)
    print(f"[{mpi.process_rank()}/{mpi.process_count()}] mesh {dict(mesh.shape)} "
          f"attn={args.attn} remat={args.remat} loss_chunk={args.loss_chunk}")

    cfg = llama.llama3_8b() if args.preset == "8b" else llama.tiny(
        vocab=512, seq=args.seq)
    if args.moe_experts:
        import dataclasses

        cfg = dataclasses.replace(
            cfg, n_experts=args.moe_experts,
            expert_top_k=min(args.moe_top_k, args.moe_experts))
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    if args.pp > 0:
        pp_step, V = llama_pipeline.make_pp_train_step(
            cfg, mesh, n_microbatches=args.microbatches, lr=args.lr,
            attn=args.attn, remat=args.remat, loss_chunk=args.loss_chunk)
        params = llama_pipeline.shard_params_pp(
            llama.init(jax.random.PRNGKey(0), cfg, dtype=dtype), mesh, cfg)
        def step(p, o, t, tg):
            p2, loss = pp_step(p, t, tg)
            return p2, o, loss
        print(f"pipeline: {args.pp} stages x {V} layers/stage, "
              f"{args.microbatches} micro-batches")
    else:
        params = llama.shard_params(
            llama.init(jax.random.PRNGKey(0), cfg, dtype=dtype), mesh, cfg)
        step = llama.make_train_step(cfg, mesh, lr=args.lr, attn=args.attn,
                                     remat=args.remat,
                                     loss_chunk=args.loss_chunk)
    n = llama.num_params(params)
    print(f"params: {n/1e6:.1f}M")

    if args.steps < 1:
        raise SystemExit("--steps must be >= 1")
    if args.generate < 0:
        raise SystemExit("--generate must be >= 0")
    data, table = synthetic_tokens(cfg, n_seq=max(args.batch * 8, 64),
                                   seq_len=args.seq)
    rng = np.random.RandomState(1)
    opt_state = None
    losses = []
    try:
        t0 = time.perf_counter()
        for it in range(args.steps):
            idx = rng.randint(0, len(data), args.batch)
            batch = data[idx]
            tokens = jnp.asarray(batch[:, :-1])
            targets = jnp.asarray(batch[:, 1:])
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            losses.append(float(loss))
            if it % 10 == 0 or it == args.steps - 1:
                print(f"step {it}: loss {losses[-1]:.4f}")
        dt = time.perf_counter() - t0
        tok_s = args.batch * args.seq * args.steps / dt
        print(f"trained {args.steps} steps in {dt:.1f}s ({tok_s:,.0f} tok/s); "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        assert losses[-1] < losses[0], "loss did not decrease"

        if args.generate:
            # Train -> generate -> score: fraction of generated transitions
            # that are legal under the corpus' Markov table.  Chance level
            # is fanout/vocab; a trained model should be far above it.
            pl = min(16, args.seq)
            prompts = data[:4, :pl]
            gen = llama_decode.make_generate_fn(
                cfg, prompt_len=pl, max_new=args.generate,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p)
            out = np.asarray(gen(params, jnp.asarray(prompts),
                                 jax.random.PRNGKey(7)))
            seqs = np.concatenate([prompts, out], axis=1)
            legal = total = 0
            for row in seqs:
                for t in range(pl - 1, seqs.shape[1] - 1):
                    legal += int(row[t + 1] in table[row[t]])
                    total += 1
            chance = 100.0 * table.shape[1] / cfg.vocab
            print(f"generation legality: {100.0 * legal / total:.1f}% of "
                  f"transitions in the Markov table (chance {chance:.1f}%)")
    finally:
        mpi.stop()


if __name__ == "__main__":
    main()
