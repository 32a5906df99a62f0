"""The benchmark's token cells' step programs are pinned.  For every token
cell of `BENCHMARK.json` the step its runner builds from its configuration
and traffic files, at their REHEARSAL sizes (`harness.rehearsed`), is traced
on the CPU mesh the cell's `chips` asks for (`jax.make_jaxpr` of
`llama.make_train_step`: no compile), the text's `0x...` addresses taken
out, and its SHA-256 held against `tests/step_programs.json`.

Why: an edit to shared code (`models/llama.py`, `ops/`) for one
configuration must leave every other cell's program what it was, and "the
JAXPR is byte for byte the parent's" is otherwise a builder's word from a
scratch script.  A trace holds every operation, shape, type, scope-free
parameter and kernel body of the step; it holds no file name and no line
number, so a checkout elsewhere reads the same.  It is not the chip's
program (the widths are toys and the compiler has not run), which the
driver's measurement of every cell is for.

A PR that MEANS to change a cell's program writes the file anew and says so:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python tests/test_step_programs.py > tests/step_programs.json

(`python tests/test_step_programs.py <root>` prints the hashes of the tree at
`<root>`, a parent's checkout, with this file's code.)  The hashes of the
eight token cells older than PR 48 were written from PR 47's tree.
"""

import hashlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STORED = os.path.join(ROOT, "tests", "step_programs.json")


def _harness(root):
    sys.path[:0] = [p for p in (root, os.path.join(root, "benchmark"))
                    if p not in sys.path]
    import harness
    return harness


def token_cells(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    return [cell["name"] for cell in spec["workloads"]
            if cell["config"] != "resnet50"]


def step_program(name, root=ROOT):
    """SHA-256 of the traced step of cell `name` of the tree at `root`."""
    import jax
    import jax.numpy as jnp

    harness = _harness(root)
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import make_mesh

    spec = harness.load_json("BENCHMARK.json", base=root)
    cell, config = harness.find_cell(spec, name)
    cfg = harness.rehearsed(harness.load_json(config["file"], base=root))
    mix = harness.rehearsed(harness.load_json(
        "traffic", cell["traffic"] + ".json"))
    load = lambda runner: harness.load_module("runners", runner)
    model, how = load(cfg["runner"])._model(cfg), cfg["run"]
    mesh = make_mesh(mix["mesh"], devices=jax.devices()[:cell["chips"]])
    more, state = {"lr": how["lr"]} if "lr" in how else {}, None
    params = jax.eval_shape(lambda: llama.init(
        jax.random.PRNGKey(0), model, dtype=jnp.dtype(how["dtype"])))
    if "optimizer" in how:
        more["optimizer"] = load("step_tokens_adamw")._optimizer(
            how["optimizer"])
        state = jax.eval_shape(more["optimizer"].init, params)
    if cell["chips"] > 1:       # `step_tokens_ep.py` asks for the units
        more["with_delivered"] = True
    step = llama.make_train_step(
        model, mesh, attn=how["attn"], remat=how["remat"],
        loss_chunk=how["loss_chunk"], **more)
    tokens = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), jnp.int32)
    text = str(jax.make_jaxpr(step)(params, state, tokens, tokens))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()


@pytest.mark.parametrize("name", token_cells())
def test_step_program_is_the_stored_one(name):
    with open(STORED) as fh:
        stored = json.load(fh)
    assert name in stored, f"a new cell: write {STORED} anew (see above)"
    assert step_program(name) == stored[name], (
        f"the step of {name} is not the program it was: if that is meant, "
        f"write {STORED} anew and say so (see this file's docstring)")


if __name__ == "__main__":
    root = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else ROOT
    print(json.dumps({name: step_program(name, root)
                      for name in token_cells(root)}, indent=1))
