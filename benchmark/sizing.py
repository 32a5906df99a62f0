#!/usr/bin/env python3
"""Size a cell without the chip: compile its step program at the real sizes
for a DESCRIBED `v5e:2x2` (the TPU compiler is installed here; no chip is
attached) and print the compiler's memory plan, the Mosaic kernels and the
collectives it holds.

    JAX_PLATFORMS=cpu python3 benchmark/sizing.py --workload mixtral-8x7b-l4096
    JAX_PLATFORMS=cpu python3 benchmark/sizing.py --workload mixtral-8x7b-l4096 \\
        --set num_hidden_layers=2 --set batch=1

`--set key=value` overrides a number of the configuration or the traffic
file, to ask what else would fit.  What the compiler refuses here (a program
too large for 16 GB, a kernel it cannot tile) costs no chip time.  A compile
that passes is not a chip run: nothing here is a time, and nothing is
written to `PERF.md` as measured.  The program asks `jax.default_backend()`
whether to run its kernels in interpret mode; it is answered "tpu" here, as
`tests/test_aot_compile.py` does.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import harness
from harness import ROOT

sys.path.insert(0, ROOT)


def _described(chips):
    import jax
    from jax.experimental import topologies

    # Such a compile cannot be read back from the persistent cache.
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return list(topo.devices)[:chips]


def _shapes(tree, sharding_of):
    import jax

    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                          sharding=s),
                        tree, sharding_of)


def step_tokens(cfg, mix, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchmpi_tpu.models import llama
    from torchmpi_tpu.models._common import mesh_spec
    from torchmpi_tpu.parallel import make_mesh

    runner = harness.load_module("runners", "step_tokens")
    model, how = runner._model(cfg), cfg["run"]
    mesh = make_mesh(mix["mesh"], devices=devices)
    shapes = jax.eval_shape(lambda: llama.init(
        jax.random.PRNGKey(0), model, dtype=jnp.dtype(how["dtype"])))
    shardings = jax.tree.map(
        lambda a, s: NamedSharding(mesh, mesh_spec(s, mesh, a.shape)),
        shapes, llama.param_specs(model))
    tokens = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    step = llama.make_train_step(model, mesh, lr=how["lr"], attn=how["attn"],
                                 remat=how["remat"], loss_chunk=how["loss_chunk"])
    return step.lower(_shapes(shapes, shardings), None, tokens, tokens).compile()


def engine_images(cfg, mix, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import resnet
    from torchmpi_tpu.runtime.communicator import RANK_AXIS, Communicator

    runner = harness.load_module("runners", "engine_images")
    model = runner._model(cfg)
    comm = Communicator(devices, name="described")
    mesh = comm.mesh()
    engine = AllReduceSGDEngine(resnet.make_loss_fn(model), lr=cfg["lr"],
                                comm=comm, mode="compiled")
    step = engine._build_compiled_step(comm)
    dtype = jnp.dtype(cfg["dtype"])
    shapes = jax.eval_shape(lambda: resnet.init(jax.random.PRNGKey(0), model,
                                                dtype=dtype)[0])
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(RANK_AXIS))
    n = mix["per_chip_batch"] * len(devices)
    size = cfg["image_size"]
    x = jax.ShapeDtypeStruct((n, size, size, cfg["in_channels"]), dtype,
                             sharding=rows)
    y = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows)
    params = _shapes(shapes, jax.tree.map(lambda _: repl, shapes))
    return step.lower(params, None, x, y).compile()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    args = ap.parse_args(argv)

    spec = harness.load_json("BENCHMARK.json", base=ROOT)
    cell, config = harness.find_cell(spec, args.workload)
    cfg = harness.load_json(config["file"], base=ROOT)
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    for item in args.set:
        key, value = item.split("=", 1)
        target = cfg if key in cfg else mix
        if key not in target:
            raise SystemExit(f"--set {key}: neither file has that key")
        target[key] = json.loads(value)

    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    compiled = globals()[cfg["runner"]](cfg, mix, _described(cell["chips"]))
    m = compiled.memory_analysis()
    hlo = compiled.as_text()
    held = harness.program_bytes(compiled)
    print(json.dumps({
        "workload": args.workload, "set": args.set, "chips": cell["chips"],
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "temporaries_gb": m.temp_size_in_bytes / 1e9,
        "held_while_running_gb": held / 1e9,
        "share_of_hbm": held / harness.load_json("peaks.json")[
            "TPU v5 lite"]["hbm_bytes"],
        "tpu_custom_call": hlo.count("tpu_custom_call"),
        "collectives": hlo_collective_stats(hlo)["counts"],
        "compiled_for": "described v5e:2x2, no chip: not a measurement",
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
