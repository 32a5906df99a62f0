"""Laguna-S-2.1-style stacks, the second half (``tests/test_laguna.py`` holds
the kernels, the rotations, the layers against the reference, and the
fixtures and helpers these share; the driver hands a worker a file at a time,
and the two together were the suite's fifth-longest unit of work): a chip's
shares of the experts add up, four devices against one, the remat policies,
the published 48 layers, the stacks of runs as they were, the names in the
device program.  Small widths, float32, the CPU."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.parallel import mesh as pmesh

from test_laguna import (PUBLISHED, _kernels, file_of, five, laguna_tiny,
                         layer_of, reference, rel,
                         sample)  # noqa: F401

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


@pytest.mark.parametrize("n_experts,held", [(256, 8), (16, 4), (16, 2)])
def test_the_shares_add_up(reference, n_experts, held):
    """Over all ``n_experts / held`` shares of a layer (thirty-two of eight,
    as the deployment's chips, and fewer), the held experts' parts, with the
    shared expert counted once, sum to the uncut reference's layer output; a
    share's weights are the uncut layer's experts."""
    whole = laguna_tiny(n_layers=2, n_experts=n_experts, held=None, k=10)
    full = layer_of(llama.init(jax.random.PRNGKey(0), whole), 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, whole.d_model))
    xt = x.reshape(-1, whole.d_model)
    want = reference.experts_ffn(file_of(whole), full, xt)
    shared = reference.swiglu(xt, full["shared_gate"], full["shared_up"],
                              full["shared_down"])
    total = 0.0
    for first in range(0, n_experts, held):
        cfg = laguna_tiny(n_layers=2, n_experts=n_experts,
                          held=(first, held), k=10)
        lp = dict(full, **{name: full[name][first:first + held]
                           for name in ("w_gate", "w_up", "w_down")})
        if first in (0, n_experts - held):
            mine = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
            np.testing.assert_array_equal(mine["w_up"], lp["w_up"])
        part, _ = llama._moe_ffn(cfg, lp, x)
        total = total + part.reshape(xt.shape) - shared
    assert rel(total + shared, want) < 1e-5


# ------------------------------------------------------- mesh, remat, names

def test_four_devices_against_one():
    """Under GSPMD on dp x tp the stack gives one device's loss and
    gradients, the windowed flash kernels in a ``shard_map`` over the batch
    and the heads (6 and 4 of them over tp 2), ``wg`` sharded by head."""
    cfg = laguna_tiny(n_layers=2)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    sample = (tokens, jnp.roll(tokens, -1, 1))
    loss_of = lambda mesh: jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, mesh, attn="flash", loss_chunk=32)))
    alone = loss_of(None)(params, sample)
    mesh = pmesh.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    specs = llama.param_specs(cfg)
    for run in specs["layers"]:
        assert run["wg"] == run["wq"] == jax.sharding.PartitionSpec(
            None, None, "tp")
    sharded = llama.shard_params(params, mesh, cfg)
    assert sharded["layers"][1]["wg"].sharding.shard_shape((1, 48, 6)) == (
        1, 48, 3)
    loss, grads = loss_of(mesh)(sharded, sample)
    np.testing.assert_allclose(loss, alone[0], rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(alone[1])):
        assert rel(a, b) < 1e-4 or float(jnp.max(jnp.abs(b))) == 0.0, \
            jax.tree_util.keystr(path)


_GRADS = {}


def _grads(cfg, params, sample, remat):
    """The five-layer cut's gradients under a remat policy, taken once."""
    if remat not in _GRADS:
        _GRADS[remat] = jax.jit(jax.grad(llama.make_loss_fn(
            cfg, attn="flash", remat=remat, loss_chunk=32)))(params, sample)
    return _GRADS[remat]


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_remat_gives_the_gradients_and_replays_no_kernel(five, sample, remat):
    """``"dots"`` and ``"full"`` give ``"none"``'s gradients, and the step
    holds each of the five layers' flash kernels once forward and once
    backward."""
    cfg, params = five
    for g, w in zip(jax.tree.leaves(_grads(cfg, params, sample, remat)),
                    jax.tree.leaves(_grads(cfg, params, sample, "none"))):
        assert rel(g, w) < 1e-4 or float(jnp.max(jnp.abs(w))) == 0.0
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat,
                                 loss_chunk=32)
    tokens = jnp.zeros((1, 160), jnp.int32)
    shapes = jax.eval_shape(lambda: params)
    flash = [n for n in _kernels(jax.make_jaxpr(step)(
        shapes, None, tokens, tokens).jaxpr, []) if n and "flash" in n]
    assert flash == ["flash_fwd"] * 5 + ["flash_bwd"] * 5


def test_the_published_48_layers_build():
    runs = llama.layer_runs(PUBLISHED)
    assert len(runs) == 24
    assert runs[:3] == (("attn", "dense", 1), ("swa", "moe", 3),
                        ("attn", "moe", 1))
    assert (PUBLISHED.head_dim, PUBLISHED.n_heads, PUBLISHED.swa_heads) == (
        128, 48, 72)
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                               PUBLISHED, jnp.bfloat16))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 117.5e9 < count < 117.6e9
    full, sliding = shapes["layers"][0], shapes["layers"][1]
    assert full["wq"].shape == (1, 3072, 48 * 128)
    assert full["wg"].shape == (1, 3072, 48)
    assert full["w_gate"].shape == (1, 3072, 12288)
    assert sliding["wq"].shape == (3, 3072, 72 * 128)
    assert sliding["wk"].shape == (3, 3072, 8 * 128)
    assert sliding["wo"].shape == (3, 72 * 128, 3072)
    assert sliding["router"].shape == (3, 3072, 256)
    assert sliding["w_gate"].shape == (3, 256, 3072, 1024)
    mixer = lambda run: sum(int(np.prod(run[k].shape[1:])) for k in (
        "wq", "wk", "wv", "wg", "wo"))
    assert (mixer(full), mixer(sliding)) == (44_187_648, 63_135_744)
    # the benchmark's cut: five layers, experts 0-7, an eighth of the rows
    cut = dataclasses.replace(
        PUBLISHED, n_layers=5, vocab=12544, experts_held=(0, 8),
        layer_kinds=PUBLISHED.layer_kinds[:5])
    held = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(held)) == 811_017_216
    specs = llama.param_specs(PUBLISHED)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, shapes)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs, is_leaf=is_spec))
    # all 48 layers at toy widths
    cfg = laguna_tiny(n_layers=48)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab)
    loss = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="full",
                                      loss_chunk=32))(params, (tokens, tokens))
    assert np.isfinite(float(loss))


def test_the_kinds_come_from_the_files_lists():
    kinds = llama.window_layer_kinds(
        ["full_attention", "sliding_attention"], ["dense", "sparse"])
    assert kinds == (("attn", "dense"), ("swa", "moe"))
    with pytest.raises(ValueError, match="chunked_attention"):
        llama.window_layer_kinds(["chunked_attention"], ["dense"])
    with pytest.raises(ValueError, match="1 layer types for 2"):
        llama.window_layer_kinds(["full_attention"], ["dense", "sparse"])


@pytest.mark.parametrize("preset,leaves,checksum,loss", [
    ("glm", 53, 733133.2083365738, 7.012062072753906)])
@pytest.mark.usefixtures("full_optimisation")
def test_a_stack_of_runs_is_what_it_was(preset, leaves, checksum, loss):
    """With the new fields at their defaults the GLM-4.7-Flash preset builds
    the parameter tree and the weights for a seed that the commit before
    this model gave, and its loss (numbers taken from that commit; the Kimi
    Linear preset's are in ``test_glm_flash.py``, the homogeneous
    configurations' in ``test_kimi_linear.py``)."""
    glm = llama.glm_4_7_flash()
    assert (glm.head_dim, glm.swa_window, glm.attn_gate, glm.rope_yarn,
            glm.rope_fraction) == (102, 0, False, None, 1.0)
    cfg = dataclasses.replace(
        glm, vocab=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=4,
        d_ff=32, dense_d_ff=96, max_seq=256, n_experts=8, expert_top_k=2,
        q_lora_rank=40, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, head_dim=0,
        layer_kinds=glm.layer_kinds[:5], experts_held=(0, 2))
    params = llama.init(jax.random.PRNGKey(7), cfg)
    flat = jax.tree.leaves(params)
    assert len(flat) == leaves
    total = sum(np.sum(np.abs(np.asarray(a, np.float64))) * (i + 1)
                for i, a in enumerate(flat))
    assert total == pytest.approx(checksum, rel=1e-12)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    got = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="dots",
                                     loss_chunk=16))(params, (tokens, tokens))
    assert float(got) == pytest.approx(loss, rel=1e-6)


def test_the_head_width_is_a_field():
    """0: derived, as every configuration before had it; given, q is
    ``n_heads * head_dim`` wide on any state."""
    assert llama.tiny().head_dim == 16 and llama.llama3_8b().head_dim == 128
    wide = dataclasses.replace(llama.tiny(), head_dim=32)
    params = llama.init(jax.random.PRNGKey(0), wide)
    assert params["layers"]["wq"].shape == (2, 64, 4 * 32)
    assert params["layers"]["wo"].shape == (2, 4 * 32, 64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    flash, full = (jax.jit(llama.make_loss_fn(wide, attn=a))(
        params, (tokens, tokens)) for a in ("flash", "full"))
    assert float(flash) == pytest.approx(float(full), rel=1e-5)


def test_the_programs_names(five, sample):
    """``swa`` inside ``attn`` round a sliding layer's kernels alone, forward
    and backward; ``attn.gate`` round the gate; the full layers' kernels
    under ``attn`` outside ``swa``."""
    cfg, params = five
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                 loss_chunk=32)
    shapes = jax.eval_shape(lambda: params)
    names = set(re.findall(r'loc\("([^"]+)"', step.lower(
        shapes, None, *sample).as_text(debug_info=True)))
    part = lambda scope: re.compile(
        r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    for scope in ("embed", "attn", "swa", "attn.gate", "ffn", "moe.router",
                  "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
                  "final_norm", "head_loss", "optimizer"):
        assert any(part(scope).search(n) for n in names), scope
    inside = [n for n in names if part("swa").search(n)]
    assert all(part("attn").search(n) for n in inside)
    assert any("flash_fwd" in n for n in inside)
    assert any("flash_bwd" in n for n in inside)
    # the projections, the rotation and the gate lie outside it
    assert not any(part("attn.gate").search(n) for n in inside)
    assert not any("dot_general" in n and "flash" not in n and
                   "pallas" not in n for n in inside)
    outside = [n for n in names if part("attn").search(n)
               and not part("swa").search(n)]
    assert any("flash_fwd" in n for n in outside)
    assert any("flash_bwd" in n for n in outside)
    gate = [n for n in names if part("attn.gate").search(n)]
    assert any("logistic" in n for n in gate)
    assert any("transpose(" in n for n in gate)
