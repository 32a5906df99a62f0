"""``ops.kda_mixer``: a KDA layer's passes round its recurrence.  The fused
kernels (a head of 128 channels; off the TPU through the Pallas interpreter)
against the plain ``jax.numpy`` form they stand for: values and every
gradient, in float32 and in bfloat16; the convolution's reach over a row
block's edge, forward and backward, and the zeros before the sequence; which
form a head width takes; the layer under the remat policies.  Small shapes,
the CPU."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import kda_mixer as km

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

PRE_INPUTS = "xq xk xv f conv_q conv_k conv_v a_log dt_bias".split()
PRE_OUTPUTS = "q k v g".split()
POST_INPUTS = "o z o_norm".split()
EPS = 1e-5


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def pre_inputs(L, B=2, H=2, D=128, dtype=jnp.float32, seed=0):
    """The way in's nine inputs as a layer has them: projections' outputs of
    unit scale, filters drawn as ``llama.init`` draws them, the decay's
    parameters in the ranges it seeds."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    C = H * D
    x = [jax.random.normal(k, (B, L, C)).astype(dtype) for k in ks[:4]]
    conv = [(jax.random.normal(k, (4, C)) * 0.5).astype(dtype)
            for k in ks[4:7]]
    a_log = jnp.log(jax.random.uniform(ks[7], (H,), jnp.float32, 1.0, 16.0))
    return (*x, *conv, a_log, jax.random.normal(ks[8], (C,)))


def post_inputs(L, B=2, H=2, D=128, dtype=jnp.float32, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, L, H * D)).astype(dtype),
            jax.random.normal(ks[1], (B, L, H * D)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(ks[2], (D,)))


def weights(outs, seed=7):
    """A cotangent for each output, in its type."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(outs))
    return [jax.random.normal(k, o.shape).astype(o.dtype)
            for k, o in zip(ks, outs)]


def values_and_grads(fn, args, cotangents=None):
    outs, vjp = jax.vjp(fn, *args)
    outs = outs if isinstance(outs, tuple) else (outs,)
    cotangents = cotangents or weights(outs)
    return outs, vjp(tuple(cotangents) if len(outs) > 1 else cotangents[0])


post = lambda fn: (lambda o, z, w: fn(o, z, w, eps=EPS))
f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# --------------------------------------------- the kernels are the plain form

@pytest.mark.parametrize("L", [40, 256, 600],
                         ids=["under-a-block", "whole-blocks", "ragged-blocks"])
def test_the_way_in_is_the_plain_form(L):
    """q, k, v, g and the gradient of all nine inputs (the three filters,
    ``a_log``, ``dt_bias`` among them), float32, to 1e-5; g's lanes are a
    head's own scale, so it is the plain form's to the bit."""
    args = pre_inputs(L)
    got, grads = values_and_grads(km.kda_pre, args)
    want, want_grads = values_and_grads(km.pre_plain, args)
    for name, a, b in zip(PRE_OUTPUTS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 1e-5, name
    for name, a, b in zip(PRE_INPUTS, grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 1e-5, name


@pytest.mark.parametrize("L", [40, 256, 600],
                         ids=["under-a-block", "whole-blocks", "ragged-blocks"])
def test_the_way_out_is_the_plain_form(L):
    """The gated output and the gradient of o, the gate's pre-activation
    (whose row sum is the bias's) and ``o_norm``, float32, to 1e-5."""
    args = post_inputs(L)
    got, grads = values_and_grads(post(km.kda_post), args)
    want, want_grads = values_and_grads(post(km.post_plain), args)
    assert rel(got[0], want[0]) < 1e-5
    for name, a, b in zip(POST_INPUTS, grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 1e-5, name


def test_the_way_in_in_bfloat16_is_as_near_float32_as_the_plain_form():
    """In the compute type of the cell: q, k, v and the inputs' gradients
    are bfloat16, g and the float32 parameters' gradients float32, and each
    is as near the float32 result as the plain form's is (the same roundings
    in the same places: within a fifth more of its error, and never a
    bfloat16 step apart from it)."""
    low = pre_inputs(600, dtype=jnp.bfloat16)
    cot = weights(km.pre_plain(*low))
    exact, exact_grads = values_and_grads(km.pre_plain, f32(low), f32(cot))
    got, grads = values_and_grads(km.kda_pre, low, cot)
    want, want_grads = values_and_grads(km.pre_plain, low, cot)
    assert [a.dtype for a in got] == [jnp.bfloat16] * 3 + [jnp.float32]
    assert [a.dtype for a in grads] == ([jnp.bfloat16] * 7
                                        + [jnp.float32] * 2)
    for name, a, b, e in zip(PRE_OUTPUTS + PRE_INPUTS, got + grads,
                             want + want_grads, exact + exact_grads):
        assert rel(a, e) < 1.2 * rel(b, e) + 1e-5, name
        assert rel(a, b) < 2 ** -7, name


def test_the_way_out_in_bfloat16_is_as_near_float32_as_the_plain_form():
    low = post_inputs(600, dtype=jnp.bfloat16)
    cot = weights([post(km.post_plain)(*low)])
    exact, exact_grads = values_and_grads(post(km.post_plain), f32(low),
                                          f32(cot))
    got, grads = values_and_grads(post(km.kda_post), low, cot)
    want, want_grads = values_and_grads(post(km.post_plain), low, cot)
    assert got[0].dtype == jnp.bfloat16
    assert [a.dtype for a in grads] == [jnp.bfloat16] * 2 + [jnp.float32]
    for name, a, b, e in zip(["out"] + POST_INPUTS, got + grads,
                             want + want_grads, exact + exact_grads):
        assert rel(a, e) < 1.2 * rel(b, e) + 1e-5, name
        assert rel(a, b) < 2 ** -7, name


# ------------------------------------------------------ the convolution's halo

def _impulse(L, rows, C=128):
    x = jnp.zeros((1, L, C))
    return x.at[0, jnp.asarray(rows)].set(
        jax.random.normal(jax.random.PRNGKey(3), (len(rows), C)) + 2.0)


R = km._PRE_TILE[0]          # rows a grid step of the way in takes


@pytest.mark.parametrize("rows", [(R - 3, R - 2, R - 1), (2 * R - 1,),
                                  (0, 1, 2), (R, 3 * R - 1)],
                         ids=["a-blocks-last-rows", "second-blocks-last-row",
                              "the-first-three-rows", "a-blocks-first-row"])
def test_the_convolution_reaches_over_a_blocks_edge(rows):
    """Three row blocks.  An impulse on a block's last rows reaches the next
    block's first three rows through the halo view, and only those; its
    gradient comes back over the edge through the carried scratch; the
    first rows of the sequence see zeros before them.  v is the branch
    without a norm: the convolution and the SiLU alone."""
    L = 3 * R
    assert km._row_block(L, km._PRE_TILE) == (R, L)
    args = list(pre_inputs(L, B=1, H=1))
    args[2] = _impulse(L, rows)                         # xv
    pick = lambda outs: outs[2]
    got = pick(km.kda_pre(*args))
    want = pick(km.pre_plain(*args))
    assert rel(got, want) < 1e-6
    touched = sorted({t for r in rows for t in range(r, min(r + 4, L))})
    nonzero = np.flatnonzero(np.abs(np.asarray(got[0])).sum(-1))
    assert nonzero.tolist() == touched
    # backward: a cotangent on the rows the impulse reaches
    cot = jnp.zeros((1, L, 128)).at[0, jnp.asarray(touched)].set(1.0)
    grad = lambda fn: jax.grad(
        lambda xv, w: jnp.sum(pick(fn(*args[:2], xv, *args[3:6], w,
                                      *args[7:])) * cot), (0, 1))(
        args[2], args[6])
    (dx, dw), (want_dx, want_dw) = grad(km.kda_pre), grad(km.pre_plain)
    assert rel(dx, want_dx) < 1e-6 and rel(dw, want_dw) < 1e-6
    reached = sorted({t for r in touched for t in range(max(r - 3, 0), r + 1)})
    nonzero = np.flatnonzero(np.abs(np.asarray(dx[0])).sum(-1))
    assert nonzero.tolist() == reached


def test_every_tap_crosses_every_edge():
    """A ramp over the rows through filters that pick one tap each: row t of
    the result is ``silu`` of row t - 3 + i, so any block edge that loses or
    shifts a row shows, forward; and the filter's gradient sums each tap's
    products over all rows of all blocks."""
    L, C = 3 * R, 128
    ramp = jnp.broadcast_to(jnp.arange(1.0, L + 1)[None, :, None] / L,
                            (1, L, C))
    args = list(pre_inputs(L, B=1, H=1))
    args[2] = ramp
    for i in range(4):
        args[6] = jnp.zeros((4, C)).at[i].set(1.0)
        v = km.kda_pre(*args)[2]
        shifted = jnp.pad(ramp, ((0, 0), (3 - i, 0), (0, 0)))[:, :L]
        assert rel(v, jax.nn.silu(shifted)) < 1e-6, i
    dw = jax.grad(lambda w: jnp.sum(km.kda_pre(*args[:6], w, *args[7:])[2]))(
        args[6])
    want = jax.grad(lambda w: jnp.sum(km.pre_plain(*args[:6], w,
                                                   *args[7:])[2]))(args[6])
    assert rel(dw, want) < 1e-6


# ------------------------------------------------------- which form runs when

def _kernels(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("D,kernels", [(16, False), (64, False), (128, True),
                                       (256, True)])
def test_the_head_width_chooses_the_form(D, kernels):
    """A head that fills whole lanes takes the kernels, forward and
    backward; any other width the plain form, which then is autodiff's."""
    args = pre_inputs(48, B=1, H=2, D=D)
    loss = lambda *a: sum(jnp.sum(o) for o in km.kda_pre(*a))
    assert _kernels(km.kda_pre, *args) == (["kda_pre"] if kernels else [])
    assert _kernels(jax.grad(loss), *args) == (
        ["kda_pre", "kda_pre_bwd"] if kernels else [])
    if not kernels:
        assert all(bool(jnp.all(a == b)) for a, b in zip(
            km.kda_pre(*args), km.pre_plain(*args)))
    args = post_inputs(48, B=1, H=2, D=D)
    loss = lambda *a: jnp.sum(post(km.kda_post)(*a))
    assert _kernels(post(km.kda_post), *args) == (
        ["kda_post"] if kernels else [])
    assert _kernels(jax.grad(loss), *args) == (
        ["kda_post", "kda_post_bwd"] if kernels else [])


def test_a_head_of_256_channels_norms_over_both_its_lane_tiles():
    """Two vregs of lanes a head: the norms sum over all of a head's."""
    args = pre_inputs(80, B=1, H=2, D=256)
    got, grads = values_and_grads(km.kda_pre, args)
    want, want_grads = values_and_grads(km.pre_plain, args)
    for a, b in zip(got + grads, want + want_grads):
        assert rel(a, b) < 1e-5
    args = post_inputs(80, B=1, H=2, D=256)
    got, grads = values_and_grads(post(km.kda_post), args)
    want, want_grads = values_and_grads(post(km.post_plain), args)
    for a, b in zip(got + grads, want + want_grads):
        assert rel(a, b) < 1e-5


def test_the_layers_share_one_trace_of_each_kernel():
    """The four calls are jitted on static shapes: a second layer, the pass
    a checkpoint replays and a second program trace no kernel body anew."""
    args = pre_inputs(64, B=1, H=2, seed=11)
    km.kda_pre(*args)
    before = km._pre_call._cache_size()
    jax.jit(lambda *a: km.kda_pre(*km.kda_pre(*a)[:3], *a[3:]))(*args)
    assert km._pre_call._cache_size() == before


# ------------------------------------------------------------------ the layer

def _mixer_inputs(B, L, H, D, seed=0):
    """``kda_mixer``'s twelve inputs."""
    xq, xk, xv, f, cq, ck, cv, a_log, dt_bias = pre_inputs(
        L, B=B, H=H, D=D, seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[0], (B, L, H)))
    z = jax.random.normal(ks[1], (B, L, H * D))
    o_norm = 1.0 + 0.1 * jax.random.normal(ks[2], (D,))
    return xq, xk, xv, f, beta, z, cq, ck, cv, a_log, dt_bias, o_norm


@pytest.mark.parametrize("remat", ["full", "dots"])
def test_the_layer_under_a_remat_policy_gives_nones_gradient(remat):
    """``kda_mixer`` (way in, recurrence, way out; kernels) checkpointed as
    a layer is: ``"full"`` and ``"dots"`` give ``"none"``'s gradient of all
    twelve inputs, the recurrence runs once each way, and the way in and the
    way out, whose rule keeps their inputs alone, are formed again."""
    args = _mixer_inputs(1, 130, 2, 128)
    # an output projection after it, as a layer has: its gradient reads the
    # gated output, so a policy that kept only inputs forms that again too
    args += (jax.random.normal(jax.random.PRNGKey(5), (256, 8)),)
    mixer = llama._kda_sharded(None, 2, EPS)
    layer = lambda *a: mixer(*a[:-1]) @ a[-1]

    def grads(r):
        fn = llama._wrap_remat(layer, r)
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=tuple(range(13)))
    got, want = jax.jit(grads(remat))(*args), jax.jit(grads("none"))(*args)
    for i, (a, b) in enumerate(zip(got, want)):
        assert rel(a, b) < 1e-4, i
    names = _kernels(grads(remat), *args)
    assert sorted(names) == sorted(
        ["kda_pre", "kda_post"] * 2 + ["kda_fwd"]
        + ["kda_pre_bwd", "kda_bwd", "kda_post_bwd"])
    assert sorted(_kernels(grads("none"), *args)) == sorted(
        ["kda_pre", "kda_fwd", "kda_post", "kda_pre_bwd", "kda_bwd",
         "kda_post_bwd"])


@pytest.mark.parametrize("head_dim", [16, 128], ids=["plain", "kernels"])
def test_the_block_is_the_plain_forms_block(head_dim):
    """``llama._kda_block`` with the mixer the width takes against the same
    block with the plain form between the projections: a layer's output and
    the gradient of every leaf of the layer and of its input."""
    from torchmpi_tpu.ops.kda import kda

    cfg = dataclasses.replace(
        llama.kimi_linear_48b_a3b(), vocab=64, d_model=32, n_layers=1,
        n_heads=2, n_kv_heads=2, d_ff=16, dense_d_ff=48, kda_heads=2,
        kda_head_dim=head_dim, layer_kinds=(("kda", "dense"),),
        experts_held=None)
    lp = jax.tree.map(lambda a: a[0], llama.init(
        jax.random.PRNGKey(0), cfg)["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, cfg.d_model))

    def plain(xq, xk, xv, f, beta, z, cq, ck, cv, a_log, dt_bias, o_norm):
        heads = lambda a: a.reshape(*a.shape[:2], a_log.shape[0], -1)
        q, k, v, g = km.pre_plain(xq, xk, xv, f, cq, ck, cv, a_log, dt_bias)
        o = kda(heads(q), heads(k), heads(v), heads(g), beta)
        return km.post_plain(o.reshape(xq.shape), z, o_norm,
                             eps=cfg.norm_eps)

    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    both = lambda mixer: jax.jit(jax.value_and_grad(
        lambda lp, x: jnp.sum(llama._kda_block(cfg, lp, x, mixer) * w),
        argnums=(0, 1)))(lp, x)
    (got, grads), (want, want_grads) = (
        both(llama._kda_sharded(None, 2, cfg.norm_eps)), both(plain))
    assert rel(got, want) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert rel(a, b) < 2e-5, jax.tree_util.keystr(path)
