"""Distributed-generation memory check at FULL 8B width (round 5): can the
flagship be SAMPLED?  16.1 GB of bf16 params exceed one 16 GB chip
(BASELINE.md projection), so decode must run tp-sharded with per-shard KV
caches — ``make_generate_fn(mesh=...)``.  This bench compiles the whole
prefill+decode program at true Llama-3-8B width via abstract inputs
(nothing materializes) and prints the per-device argument/temp footprint
per mesh shape.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/gen_volume.py

Caveat recorded in BASELINE.md: XLA-CPU's memory analysis shows a
weight-proportional temp term (~2x the argument bytes) that is an
artifact of the virtual backend — RESOLVED by a same-program A/B on the
real chip (BASELINE.md round-5 table: temp/arg 2.37 on CPU vs 0.17 on
TPU v5e; CPU materializes layout copies of weights for its dot kernels,
TPU reads them in place).  Read this bench's temp_gb column as a CPU
upper bound only: tp4 fits even under it, and the tp2 "no" is CPU
pessimism — chip-backed scaling puts tp2 at ~8.7 GB/device.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama, llama_decode
from torchmpi_tpu.models.llama import param_specs
from torchmpi_tpu.models._common import mesh_spec


def main():
    cfg = llama.llama3_8b()      # full 32 layers — generation only
    pshapes = jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    for axes in ({"tp": 2}, {"tp": 4}, {"dp": 2, "tp": 4}):
        n = int(np.prod(list(axes.values())))
        mesh = parallel.make_mesh(axes, devices=jax.devices()[:n])
        abstract = jax.tree.map(
            lambda sh, sp: jax.ShapeDtypeStruct(
                sh.shape, sh.dtype,
                sharding=NamedSharding(mesh, mesh_spec(sp, mesh, sh.shape))),
            pshapes, param_specs(cfg))
        B = 2 * dict(axes).get("dp", 1)
        prompt = jax.ShapeDtypeStruct((B, 512), jnp.int32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=512, max_new=512,
                                     mesh=mesh)
        t0 = time.perf_counter()
        compiled = gen.lower(abstract, prompt, rng).compile()
        mem = compiled.memory_analysis()
        arg = getattr(mem, "argument_size_in_bytes", 0) / 1e9
        tmp = getattr(mem, "temp_size_in_bytes", 0) / 1e9
        print(json.dumps({
            "config": f"8B generate {axes} B={B} prompt=512 max_new=512",
            "compile_s": round(time.perf_counter() - t0, 1),
            "arg_gb": round(arg, 2),
            "temp_gb": round(tmp, 2),
            "fits_16gb_chip": bool(arg + tmp < 16.0),
        }), flush=True)


if __name__ == "__main__":
    main()
