"""Kimi-Linear-style stacks whole (``tests/test_kimi_linear.py`` holds the
blocks against the reference, the published pattern, and the fixtures and
helpers these share; ``tests/test_kimi_linear_kernels.py`` the kernels; the
driver hands a worker a file at a time, and the three together were the
suite's longest unit of work): five layers against the plain reference, four
layers on a mesh, the frozen selection bias, the names in the device program.
Small widths, float32, the CPU."""

import dataclasses
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.parallel import mesh as pmesh

from test_kimi_linear import file_of, kimi_tiny, model, reference  # noqa: F401
from test_kimi_linear_kernels import rel

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


@pytest.fixture(scope="module")
def sample():
    return (jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 128),
            jax.random.randint(jax.random.PRNGKey(2), (2, 96), 0, 128))


@pytest.fixture(scope="module")
def plain(model, reference, sample):
    cfg, params = model
    return jax.jit(lambda p, s: reference.loss_and_grads(file_of(cfg), p, s))(
        params, sample)


def test_five_layers_against_the_reference(model, reference, sample, plain):
    """Loss, logits and every leaf's gradient of the five-layer model (all
    three layer kinds, a share of the experts, the chunked head) against the
    plain reference."""
    cfg, params = model
    want_loss, want_logits, want = plain
    loss_fn = llama.make_loss_fn(cfg, attn="flash", loss_chunk=32)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, sample)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    logits = llama.apply(cfg, params, sample[0], attn="flash")
    assert rel(logits, want_logits) < 1e-4
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) > 80
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        if path[-1].key == "router_bias":   # moves the choice alone
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(w))
        else:
            assert rel(g, w) < 2e-3, jax.tree_util.keystr(path)


def test_four_layers_on_a_mesh():
    """Under GSPMD on dp x tp the hybrid stack (KDA, KDA, KDA, MLA; heads of
    128 channels, so the kernels) gives one device's loss and gradients, the
    flash kernels and the KDA recurrence each in a ``shard_map`` of its own
    over the batch and the heads."""
    cfg = dataclasses.replace(kimi_tiny(n_layers=4), kda_head_dim=128)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 96), 0, cfg.vocab)
    sample = (tokens, jnp.roll(tokens, -1, 1))
    loss_of = lambda mesh: jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, mesh, attn="flash", loss_chunk=32)))
    alone = loss_of(None)(params, sample)
    mesh = pmesh.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    loss, grads = loss_of(mesh)(llama.shard_params(params, mesh, cfg), sample)
    np.testing.assert_allclose(loss, alone[0], rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(alone[1])):
        assert rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def _names(jaxpr):
    """The names ``checkpoint_name`` left in a jaxpr, sub-jaxprs included."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _names(sub)
    return found


def test_adamw_leaves_the_selection_bias_alone(model, sample):
    """Weight decay would move a bias whose gradient is zero: the step hands
    every ``router_bias`` back to the bit and steps the router beside it."""
    cfg, params = model
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    optimizer = optax.adamw(1e-2, weight_decay=0.1)
    step = llama.make_train_step(cfg, mesh, optimizer=optimizer, attn="flash",
                                 remat="full", loss_chunk=32)
    stepped, _, loss = step(jax.tree.map(jnp.copy, params),
                            optimizer.init(params), *sample)
    assert np.isfinite(float(loss))
    for new, old in zip(stepped["layers"], params["layers"]):
        if "router_bias" in old:
            np.testing.assert_array_equal(new["router_bias"],
                                          old["router_bias"])
            assert float(jnp.max(jnp.abs(new["router"] - old["router"]))) > 0
    assert sum("router_bias" in run for run in params["layers"]) == 3


def test_the_programs_names(model, sample):
    """``kda`` (the recurrence alone) and ``mla`` (the whole latent mixer)
    inside ``attn``, ``moe.shared`` beside the four ``moe.`` scopes, ``ffn``
    for the dense first layer, forward and backward."""
    cfg, params = model
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                 loss_chunk=32)
    shapes = jax.eval_shape(lambda: params)
    names = set(re.findall(r'loc\("([^"]+)"', step.lower(
        shapes, None, *sample).as_text(debug_info=True)))
    part = lambda scope: re.compile(
        r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    for scope in ("embed", "attn", "kda", "mla", "ffn", "moe.router",
                  "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
                  "final_norm", "head_loss", "optimizer"):
        assert any(part(scope).search(n) for n in names), scope
    ops = [n for n in names if n.startswith("jit(step)")]
    for inner in ("kda", "mla"):
        assert all(re.search(r"attn\)*/(.*/)?" + inner, n)
                   for n in ops if part(inner).search(n)), inner
    assert any(part("kda").search(n) and "transpose(" in n for n in names)
    assert any("mla" in n and "flash_fwd" in n for n in names)
    assert any("mla" in n and "flash_bwd" in n for n in names)
    assert not any("rope" in n for n in names)
