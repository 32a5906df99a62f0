"""Collective-volume accounting for the 3-D dp x pp x tp llama step,
counted from the COMPILED program on the virtual 8-mesh (the moe_volume.py
HLO technique): per-kind bytes of collective-permute (the pp hand-offs),
all-reduce (tp activation psums + dp grad reductions), and the ZeRO-1
reduce-scatter / all-gather pair when enabled.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/pp3d_volume.py

Emits one JSON line per mesh layout so the 3-D composition's exchange cost
can be compared against its pairwise ingredients (BASELINE.md table;
VERDICT r03 item 2's "count its collective volume" requirement).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama, llama_pipeline
from moe_volume import collective_bytes, _flops


def build_pp_step(cfg, axes, zero1=False):
    mesh = parallel.make_mesh(axes)
    params = llama_pipeline.shard_params_pp(
        llama.init(jax.random.PRNGKey(0), cfg), mesh, cfg)
    B, L = 8, cfg.max_seq
    tokens = jnp.zeros((B, L), jnp.int32)
    if zero1:
        import optax

        opt = optax.adam(1e-3)
        step, _ = llama_pipeline.make_pp_train_step(
            cfg, mesh, n_microbatches=2, optimizer=opt,
            opt_state_example=jax.eval_shape(opt.init, params), zero1=True)
        opt_state = opt.init(params)
        lowered = step.lower(params, opt_state, tokens, tokens)
    else:
        step, _ = llama_pipeline.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                           lr=1e-3)
        lowered = step.lower(params, tokens, tokens)
    compiled = lowered.compile()
    return _flops(compiled), compiled.as_text()


def build_dptp_step(cfg, axes):
    mesh = parallel.make_mesh(axes)
    params = llama.shard_params(
        llama.init(jax.random.PRNGKey(0), cfg), mesh, cfg)
    step = llama.make_train_step(cfg, mesh, lr=1e-3)
    tokens = jnp.zeros((8, cfg.max_seq), jnp.int32)
    compiled = step.lower(params, None, tokens, tokens).compile()
    return _flops(compiled), compiled.as_text()


def eight_b_slice():
    """Compile the composed step at TRUE 8B width (4-layer slice) via
    abstract inputs — nothing materializes; prints volume + memory
    (BASELINE.md round-4 "3-D step at true 8B width")."""
    import dataclasses
    import time

    from jax.sharding import NamedSharding

    from torchmpi_tpu.models.llama_pipeline import param_specs_pp
    from torchmpi_tpu.models._common import mesh_spec

    cfg = dataclasses.replace(llama.llama3_8b(), n_layers=4)
    mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2})
    pshapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                                dtype=jnp.bfloat16))
    abstract = jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype,
            sharding=NamedSharding(mesh, mesh_spec(sp, mesh, sh.shape))),
        pshapes, param_specs_pp(cfg))
    builds = [
        ("gpipe", "auto", 2, llama_pipeline.make_pp_train_step),
        ("gpipe", "manual", 2, llama_pipeline.make_pp_train_step),
        # 1F1B x manual stage: the S-bounded (2S-1 stash) schedule hosting
        # the hand-sharded flash stage — the long-context config-5 form
        # that previously ran GPipe-only (VERDICT r04 item 1).
        ("1f1b", "manual", 2, llama_pipeline.make_1f1b_train_step),
        # The stash bound itself: at M=8 GPipe's per-stage activation
        # stash is M-deep and its temp memory grows with it; 1F1B's stays
        # at the 2S-1 level (measured 18.37 vs 10.21 GB, BASELINE.md
        # round-5 table).
        ("gpipe", "manual", 8, llama_pipeline.make_pp_train_step),
        ("1f1b", "manual", 8, llama_pipeline.make_1f1b_train_step),
    ]
    for sched, stage_tp, M, make in builds:
        tok = jax.ShapeDtypeStruct((2 * M, 4096), jnp.int32)
        step, _ = make(cfg, mesh, n_microbatches=M,
                       lr=1e-4, remat="dots",
                       loss_chunk=512, attn="flash",
                       stage_tp=stage_tp)
        t0 = time.perf_counter()
        compiled = step.lower(abstract, tok, tok).compile()
        cb = collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "config": (f"8b-width dp2 x pp2 x tp2 {sched} "
                       f"stage_tp={stage_tp} (4-layer slice, B={2 * M}, "
                       f"M={M}, L=4096)"),
            "compile_s": round(time.perf_counter() - t0, 1),
            "flops_tf": round(_flops(compiled) / 1e12, 2),
            "collective_gb": {k: round(v / 1e9, 2)
                              for k, v in cb.items() if v},
            "arg_gb": round(getattr(mem, "argument_size_in_bytes", 0) / 1e9,
                            2) if mem else None,
            "temp_gb": round(getattr(mem, "temp_size_in_bytes", 0) / 1e9, 2)
            if mem else None,
        }), flush=True)


def schedule_8b_rows():
    """combined vs alternating manual-1F1B stash bound at pp4 x tp2, 8B
    width (S=4: 2S-1=7 vs S+1=5 stashed carriers — the BASELINE round-5
    'alternating' paragraph's protocol)."""
    import dataclasses
    import time

    from jax.sharding import NamedSharding

    from torchmpi_tpu.models.llama_pipeline import param_specs_pp
    from torchmpi_tpu.models._common import mesh_spec

    cfg = dataclasses.replace(llama.llama3_8b(), n_layers=4)
    mesh = parallel.make_mesh({"pp": 4, "tp": 2})
    pshapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                                dtype=jnp.bfloat16))
    abstract = jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype,
            sharding=NamedSharding(mesh, mesh_spec(sp, mesh, sh.shape))),
        pshapes, param_specs_pp(cfg))
    tok = jax.ShapeDtypeStruct((8, 4096), jnp.int32)
    for sched in ("combined", "alternating"):
        step, _ = llama_pipeline.make_1f1b_train_step(
            cfg, mesh, n_microbatches=8, lr=1e-4, remat="dots",
            loss_chunk=512, attn="flash", stage_tp="manual",
            manual_schedule=sched)
        t0 = time.perf_counter()
        compiled = step.lower(abstract, tok, tok).compile()
        cb = collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "config": (f"8b-width pp4 x tp2 1f1b manual_schedule={sched} "
                       "(4-layer slice, B=8, M=8, L=4096)"),
            "compile_s": round(time.perf_counter() - t0, 1),
            "collective_gb": {k: round(v / 1e9, 2)
                              for k, v in cb.items() if v},
            "temp_gb": round(getattr(mem, "temp_size_in_bytes", 0) / 1e9, 2)
            if mem else None,
        }), flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--width-8b", action="store_true",
                    help="compile-check the composed step at true 8B width "
                         "(abstract inputs; ~15 s) instead of the tiny sweep")
    ap.add_argument("--schedule-8b", action="store_true",
                    help="combined vs alternating manual-1F1B stash A/B at "
                         "pp4 x tp2, 8B width")
    args = ap.parse_args()
    if args.width_8b:
        eight_b_slice()
        return
    if args.schedule_8b:
        schedule_8b_rows()
        return

    cfg = llama.tiny(vocab=512, seq=128)

    rows = []
    for name, build, axes, kw in [
        ("dp8 (pure data parallel)", build_dptp_step, {"dp": 8}, {}),
        ("dp4 x tp2", build_dptp_step, {"dp": 4, "tp": 2}, {}),
        # NOTE: make_pp_train_step composes dp via GSPMD whenever the mesh
        # has dp > 1, so this row is the 2-D composed pipeline (dp-sharded
        # micro-batches), not a replicated-dp baseline.
        ("dp4 x pp2 (2-D composed)", build_pp_step, {"pp": 2, "dp": 4}, {}),
        ("dp2 x pp2 x tp2", build_pp_step, {"dp": 2, "pp": 2, "tp": 2}, {}),
        ("dp2 x pp2 x tp2 + zero1", build_pp_step,
         {"dp": 2, "pp": 2, "tp": 2}, {"zero1": True}),
    ]:
        flops, hlo = build(cfg, axes, **kw)
        cb = collective_bytes(hlo)
        rows.append({
            "config": name, "flops": flops,
            "collective_total_mb": round(sum(cb.values()) / 1e6, 3),
            "permute_mb": round(cb["collective-permute"] / 1e6, 3),
            "allreduce_mb": round(cb["all-reduce"] / 1e6, 3),
            "collective_bytes": {k: v for k, v in cb.items() if v},
        })
    for r in rows:
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
