"""Kimi-Linear-style stacks under the remat policies (the stack's other tests
are in ``tests/test_kimi_linear_stack.py`` and ``tests/test_kimi_linear.py``,
whose fixtures and helpers these share; a file of their own because the
driver hands a worker a file at a time): ``"dots"`` and ``"full"`` give the
gradients of no remat and run no kernel twice, and both keep the KDA tiles'
inverse by name.  Small widths, float32, the CPU."""

import dataclasses

import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import kda as kda_ops
from torchmpi_tpu.parallel import mesh as pmesh

from test_kimi_linear import model  # noqa: F401
from test_kimi_linear_kernels import (_scans_and_kernels, kda_inputs,
                                      kernel_inputs, rel)
from test_kimi_linear_stack import _names, sample  # noqa: F401

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


@pytest.mark.parametrize("head_dim", [16, 128], ids=["plain", "kernels"])
@pytest.mark.parametrize("remat", ["dots", "full"])
def test_remat_gives_the_gradients_and_runs_nothing_twice(model, sample,
                                                          remat, head_dim):
    """``"dots"`` and ``"full"`` give ``"none"``'s gradients, and the step's
    jaxpr holds each flash kernel and each KDA scan once forward and once
    backward: neither policy replays a kernel or the recurrence.  At a head
    width that takes the KDA kernels there is no scan: a KDA layer holds
    ``kda_fwd`` once and ``kda_bwd`` once, and the forward pass a policy
    replays, whose output and states it kept, adds none."""
    cfg, params = model
    if head_dim == 16:
        grads = lambda r: jax.jit(jax.grad(llama.make_loss_fn(
            cfg, attn="flash", remat=r, loss_chunk=32)))(params, sample)
    else:       # the recurrence alone, checkpointed as a layer is
        cfg = dataclasses.replace(cfg, kda_heads=1, kda_head_dim=head_dim)
        params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                                   cfg))
        x = kda_inputs(130, 1.0, B=1, H=1, D=head_dim)
        grads = lambda r: jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(llama._wrap_remat(kda_ops.kda, r)(
                *a))), argnums=(0, 1, 2, 3, 4)))(*x)
    for g, w in zip(jax.tree.leaves(grads(remat)),
                    jax.tree.leaves(grads("none"))):
        assert rel(g, w) < 1e-4
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat,
                                 loss_chunk=32)
    tokens = jnp.zeros((1, 160), jnp.int32)
    found = _scans_and_kernels(jax.make_jaxpr(step)(
        params, None, tokens, tokens).jaxpr)
    # 160 tokens are 3 chunks: a scan of that length is the recurrence's (the
    # head's has 5, the grouped matmuls' metadata 2 experts), one forward and
    # one backward for each of the four KDA layers; the one latent layer's two
    # flash kernels.
    chunks = kda_ops.n_chunks(160)
    scans = 4 if head_dim == 16 else 0
    assert found.count(("scan", chunks, False)) == scans
    assert found.count(("scan", chunks, True)) == scans
    assert [f for f in found if f[0] == "pallas_call"
            and "flash" in (f[1] or "")] == [("pallas_call", "flash_fwd"),
                                              ("pallas_call", "flash_bwd")]
    kda_kernels = [f[1] for f in found if f[0] == "pallas_call"
                   and "kda" in (f[1] or "")]
    # the way in and the way out keep their inputs alone and are formed
    # again in the backward pass; the recurrence between them is not
    forward = ["kda_pre", "kda_fwd", "kda_post"]
    backward = ["kda_post", "kda_post_bwd", "kda_bwd", "kda_pre",
                "kda_pre_bwd"]
    assert sorted(kda_kernels) == ([] if head_dim == 16 else
                                   sorted(4 * (forward + backward)))
    assert (kda_kernels.count("kda_fwd"), kda_kernels.count("kda_bwd")) == (
        (0, 0) if head_dim == 16 else (4, 4))
    # every forward kernel, then every backward one: nothing replayed between
    assert [n for n in kda_kernels if n in ("kda_fwd", "kda_bwd")] == (
        [] if head_dim == 16 else 4 * ["kda_fwd"] + 4 * ["kda_bwd"])


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_both_policies_keep_the_inverse_by_name(remat):
    """The recurrence checkpointed as a layer is: the forward kernel's four
    results each carry their name, ``_wrap_remat``'s policies keep all four
    and the backward pass holds ``kda_fwd`` once and ``kda_bwd`` once; a
    policy that keeps the names the plain form has, or all but the last,
    runs ``kda_fwd`` again for what it lacks."""
    x = kernel_inputs(130, 1.0, H=2)

    def program(wrapped):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.sin(wrapped(*a))),
            argnums=(0, 1, 2, 3, 4)))(*x).jaxpr
        return ([name for kind, name in _scans_and_kernels(jaxpr)
                 if kind == "pallas_call"], _names(jaxpr))

    kernels, names = program(llama._wrap_remat(kda_ops.kda, remat))
    assert kernels == ["kda_fwd", "kda_bwd"]
    assert kda_ops.KDA_RESIDUAL_NAMES == ("kda_o", "kda_state", "kda_inverse",
                                          "kda_p")
    assert set(kda_ops.KDA_RESIDUAL_NAMES) <= names
    for n in (2, 3):
        some = jax.checkpoint_policies.save_only_these_names(
            *kda_ops.KDA_RESIDUAL_NAMES[:n])
        kernels, _ = program(jax.checkpoint(kda_ops.kda, policy=some))
        assert kernels == ["kda_fwd", "kda_fwd", "kda_bwd"]
