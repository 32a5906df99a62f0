"""The kernels under a Kimi-Linear-style stack, apart from the stack
(``tests/test_kimi_linear.py``; the driver hands a worker a file at a time,
and the two together were the suite's longest unit of work): the chunked KDA
recurrence against the token-by-token one, its Mosaic kernels against the
plain form, on a mesh against one device, and flash attention with keys and
values of different widths.  Small widths, float32, the CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import flash_attention
from torchmpi_tpu.ops import kda as kda_ops
from torchmpi_tpu.ops.flash_attention import _flash_bh, _flash_bh_bwd
from torchmpi_tpu.parallel import mesh as pmesh

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _scans_and_kernels(jaxpr):
    """``("scan", length, reverse)`` and ``("pallas_call", name)`` of a
    jaxpr's equations, in order, sub-jaxprs (checkpoint, custom_vjp, pjit)
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(("pallas_call", eqn.params["name"]))
            continue
        if eqn.primitive.name == "scan":
            found.append(("scan", eqn.params["length"],
                          eqn.params["reverse"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scans_and_kernels(sub)
    return found


# ----------------------------------------------------------- the recurrence

def kda_inputs(L, decay, B=2, H=2, D=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, L, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, L, H, D)))
    v = jax.random.normal(ks[2], (B, L, H, D))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, L, H, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    return q, k, v, g, beta


def correlated_inputs(spread, L, H, D):
    """Keys of a chunk nearly (``spread`` 0.3) or wholly (0) one direction,
    beta near 1, hardly any decay: what one optimizer step made of seeded
    keys on the chip."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    k = unit(jax.random.normal(ks[5], (1, 1, H, D))
             + spread * jax.random.normal(ks[1], (1, L, H, D)))
    return (unit(jax.random.normal(ks[0], (1, L, H, D))) * D ** -0.5, k,
            jax.random.normal(ks[2], (1, L, H, D)),
            -1e-3 * jax.nn.softplus(jax.random.normal(ks[3], (1, L, H, D))),
            jax.nn.sigmoid(4.0 + jax.random.normal(ks[4], (1, L, H))))


@pytest.mark.parametrize("L,decay", [
    (64, 0.1), (200, 1.0), (130, 30.0), (256, 1e-3), (40, 80.0)],
    ids=["one-chunk", "ragged-200", "decay-to-0", "decay-near-1",
         "short-and-strong"])
def test_chunked_recurrence_is_the_token_recurrence(L, decay):
    """Values and all five gradients, at lengths that are and are not whole
    chunks, with decays near 1 (g about -1e-3) and near 0 (g to -100 a token:
    ``e^{-G}`` of one chunk would be e^6000) and nothing overflowing."""
    x = kda_inputs(L, decay)
    o, want = jax.jit(kda_ops.kda)(*x), kda_ops.kda_recurrent(*x)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert rel(o, want) < 2e-6
    w = jax.random.normal(jax.random.PRNGKey(9), o.shape)
    grads = lambda fn: jax.jit(lambda *a: all_grads(fn, a, w))(*x)
    for name, got, ref in zip("q k v g beta".split(), grads(kda_ops.kda),
                              grads(kda_ops.kda_recurrent)):
        assert bool(jnp.all(jnp.isfinite(got))), name
        # (the log-decay's gradient under strong decay is what float32 leaves
        # of terms near 1e-9)
        assert rel(got, ref) < (1e-3 if name == "g" else 2e-4), name


@pytest.mark.parametrize("spread", [0.3, 0.0], ids=["correlated", "collinear"])
def test_correlated_keys_and_strong_writes_stay_stable(spread):
    """Keys of a chunk nearly (or wholly) one direction, beta near 1, hardly
    any decay: what one optimizer step made of seeded keys on the chip.  The
    unit triangular inverse by the powers of N (``(I + N)(I + N^2) ...``)
    cancels 1e17 down to 1 there and the state grows without bound; by
    substitution in blocks no entry passes 1."""
    x = correlated_inputs(spread, L=512, H=2, D=32)
    want = kda_ops.kda_recurrent(*x)
    assert rel(jax.jit(kda_ops.kda)(*x), want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = lambda fn: jax.jit(lambda *a: all_grads(fn, a, w))(*x)
    for got, ref in zip(grads(kda_ops.kda), grads(kda_ops.kda_recurrent)):
        assert rel(got, ref) < 1e-4
    N = -0.9 * jnp.tril(jnp.ones((64, 64)), -1)
    inverse = kda_ops._unit_lower_inverse(N)
    assert float(jnp.max(jnp.abs(inverse))) <= 1.0
    np.testing.assert_allclose(inverse, jnp.linalg.inv(jnp.eye(64) - N),
                               atol=1e-6)


def test_recurrence_in_bfloat16_keeps_a_float32_state():
    """bfloat16 operands, float32 state and decay sums: the output stays
    within bfloat16's rounding of the float32 recurrence over 8 chunks."""
    x = kda_inputs(512, 0.05, B=1)
    cast = lambda a: a.astype(jnp.bfloat16)
    o = kda_ops.kda(cast(x[0]), cast(x[1]), cast(x[2]), x[3], x[4])
    assert o.dtype == jnp.bfloat16
    assert rel(o.astype(jnp.float32), kda_ops.kda_recurrent(*x)) < 2e-2
    assert kda_ops.n_chunks(512) == 8 and kda_ops.n_chunks(130) == 3


# ------------------------------------------- the chunk-local kernels
#
# A head of 128 channels takes ``kda_fwd`` and ``kda_bwd`` (the Pallas
# interpreter here): the chunk-local part and the recurrence over the chunks
# in one kernel each way, the state in VMEM from chunk to chunk.  The plain
# XLA form, which narrower heads keep, is their oracle.

def kernel_inputs(L, decay, H=2, seed=0):
    return kda_inputs(L, decay, B=1, H=H, D=128, seed=seed)


def plain_form(monkeypatch, fn, *args):
    """``fn(*args)`` traced with the plain XLA form at every head width."""
    with monkeypatch.context() as m:
        m.setattr(kda_ops, "_takes_kernel", lambda head_dim: False)
        return jax.jit(lambda *a: fn(*a))(*args)


def all_grads(fn, x, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                    argnums=(0, 1, 2, 3, 4))(*x)


KERNEL_CASES = pytest.mark.parametrize("L,decay", [
    (130, 1.0), (130, 30.0), (192, 1e-3), (40, 80.0)],
    ids=["ragged-130", "decay-to-0", "decay-near-1", "short-and-strong"])


@KERNEL_CASES
def test_kernel_forward_is_the_token_recurrence_and_the_plain_form(
        monkeypatch, L, decay):
    """The forward kernel against the token-by-token recurrence and against
    the plain form, at one to three chunks of which the last is padded,
    decays near 1 and near 0 (``e^{-G}`` of a chunk would be e^6000): the
    output, and the state that enters each chunk, which the kernel keeps
    transposed."""
    assert kda_ops._takes_kernel(128) and not kda_ops._takes_kernel(32)
    x = kernel_inputs(L, decay)
    o = jax.jit(kda_ops.kda)(*x)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert rel(o, kda_ops.kda_recurrent(*x)) < 2e-6
    assert rel(o, plain_form(monkeypatch, kda_ops.kda, *x)) < 1e-6
    pad = lambda a: jnp.pad(a, ((0, 0), (0, -L % 64)) + ((0, 0),)
                            * (a.ndim - 2))
    padded = tuple(map(pad, x))
    # the residuals: the five inputs, then what each form keeps, the states
    # first
    got, kept = jax.jit(kda_ops._kda_chunks_fwd)(*padded)
    want, kept_plain = plain_form(monkeypatch, kda_ops._kda_chunks_fwd,
                                  *padded)
    assert (len(kept), len(kept_plain)) == (8, 6)
    states, plain = kept[5], kept_plain[5]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert states.shape == plain.shape == (kda_ops.n_chunks(L), 1, 2, 128,
                                           128)
    assert states.dtype == plain.dtype == jnp.float32
    assert not bool(jnp.any(states[0])) and bool(jnp.all(jnp.isfinite(states)))
    if len(states) > 1:
        assert rel(states[1:], jnp.swapaxes(plain, -1, -2)[1:]) < 1e-6


@KERNEL_CASES
def test_kernel_gradients_are_the_plain_forms(monkeypatch, L, decay):
    """All five gradients through ``kda_bwd`` (the chunk-local part formed
    again in VMEM, the recurrence run backward and the chunk-local gradient,
    a chunk at a time from the last) against the plain form's: autodiff of
    ``_intra`` and of ``_inter`` in the scan, nothing of it written by
    hand."""
    x = kernel_inputs(L, decay, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), x[0].shape)
    got = jax.jit(lambda *a: all_grads(kda_ops.kda, a, w))(*x)
    want = plain_form(monkeypatch,
                      lambda *a: all_grads(kda_ops.kda, a, w), *x)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        # (the log-decay's gradient under strong decay is what float32 leaves
        # of terms near 1e-9, in either form)
        assert rel(a, b) < (1e-3 if name == "g" else 1e-5), name


@pytest.mark.parametrize("spread", [0.3, 0.0], ids=["correlated", "collinear"])
def test_kernels_stay_stable_on_correlated_keys(spread):
    """``test_correlated_keys_and_strong_writes_stay_stable``'s inputs
    through the kernels: the tile's inverse is substitution in blocks and
    merges too, no entry past 1."""
    x = correlated_inputs(spread, L=192, H=2, D=128)
    want = kda_ops.kda_recurrent(*x)
    assert rel(jax.jit(kda_ops.kda)(*x), want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    for got, ref in zip(jax.jit(lambda *a: all_grads(kda_ops.kda, a, w))(*x),
                        all_grads(kda_ops.kda_recurrent, x, w)):
        assert rel(got, ref) < 1e-4
    N = -0.9 * jnp.tril(jnp.ones((64, 64)), -1)
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (64, 64), i)
                for i in (0, 1))
    inverse = kda_ops._tile_inverse(N, row, col)
    assert float(jnp.max(jnp.abs(inverse))) <= 1.0
    np.testing.assert_allclose(inverse, jnp.linalg.inv(jnp.eye(64) - N),
                               atol=1e-6)
    np.testing.assert_allclose(inverse, kda_ops._unit_lower_inverse(N),
                               atol=1e-6)


def plain_n(k, g, beta):
    """``N = -Diag(beta) tril(M, -1)`` of every tile, (B, H, N, 64, 64), as
    the plain form's algebra has it, written out here in float32: ``M_ij =
    sum_c k_ic k_jc exp(G_ic - G_jc)``."""
    k, g, beta = map(kda_ops._chunked, (k, g, beta))
    G = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(jnp.minimum(G[..., :, None, :] - G[..., None, :, :], 0.0))
    M = jnp.einsum("...ic,...jc,...ijc->...ij", k, k, decay,
                   precision=jax.lax.Precision.HIGHEST)
    return -beta[..., :, None] * jnp.tril(M, -1)


def by_head(tiles, H):
    """The kernels' kept tiles, (N, B, H / 2, 64, 2 * 64): a grid step's two
    heads side by side on the lanes -> (B, H, N, 64, 64)."""
    n, b = tiles.shape[:2]
    tiles = tiles.reshape(n, b, H // 2, 64, 2, 64)      # (n, b, h2, r, i, c)
    return jnp.einsum("nbhric->bhinrc", tiles).reshape(b, H, n, 64, 64)


@pytest.mark.parametrize("keys", ["seeded", "correlated", "collinear"])
def test_the_kernel_keeps_every_tiles_inverse(monkeypatch, keys):
    """The third residual ``kda_fwd`` writes is ``(I - N)^-1`` of every
    tile, float32, the tiles of a grid step's two heads side by side on the
    lanes ((N, B, H / 2, 64, 2 * 64)): against ``jnp.linalg.inv`` of the
    plain algebra's N, on seeded keys and on the keys that break an inverse
    by powers.  The fourth is the tile's ``P`` in the inputs' type, laid out
    the same: the plain form's, which ``_intra`` returns."""
    H = 4
    x = (kernel_inputs(192, 1.0, H=H) if keys == "seeded" else
         correlated_inputs({"correlated": 0.3, "collinear": 0.0}[keys],
                           L=192, H=H, D=128))
    _, (*_, inverse, pairs) = jax.jit(kda_ops._kda_chunks_fwd)(*x)
    assert (inverse.dtype, pairs.dtype) == (jnp.float32, x[0].dtype)
    assert inverse.shape == pairs.shape == (3, 1, H // 2, 64, 2 * 64)
    tiles = by_head(inverse, H)
    want = jnp.linalg.inv(jnp.eye(64) - plain_n(x[1], x[3], x[4]))
    np.testing.assert_allclose(tiles, want, atol=2e-6)
    assert float(jnp.max(jnp.abs(tiles))) <= 1.0 + 1e-6
    assert not bool(jnp.any(jnp.triu(tiles, 1)))
    plain_p = jax.jit(kda_ops._intra)(*map(kda_ops._chunked, x))[3]
    np.testing.assert_allclose(by_head(pairs, H), plain_p, atol=1e-6)
    # an odd number of heads: one head a grid step, a tile a block
    odd = tuple(a[:, :, :1] for a in x)
    _, (*_, alone, alone_p) = jax.jit(kda_ops._kda_chunks_fwd)(*odd)
    assert alone.shape == alone_p.shape == (3, 1, 1, 64, 64)
    np.testing.assert_array_equal(alone[:, 0, 0], tiles[0, 0])
    np.testing.assert_array_equal(alone_p[:, 0, 0], by_head(pairs, H)[0, 0])


def test_the_backward_kernel_inverts_no_tile(monkeypatch):
    """``kda_bwd`` reads the inverse ``kda_fwd`` made: tracing the backward
    kernel calls ``_tile_inverse`` for no tile and the forward kernel once a
    head.  Both kernel calls are traced anew (what ``ops.kda`` jits,
    unjitted), so the patched helper is the one traced and no other trace is
    touched."""
    calls = []
    inverse_of = kda_ops._tile_inverse
    monkeypatch.setattr(kda_ops, "_tile_inverse", lambda *a: (
        calls.append(1), inverse_of(*a))[1])
    H = 4
    x = kernel_inputs(128, 1.0, H=H)
    flat = (*map(kda_ops._flat, x[:4]), x[4])
    o, *kept = jax.eval_shape(lambda *a: kda_ops._kda_kernel.__wrapped__(
        *a, H=H, interpret=True), *flat)
    # (a grid step's two heads are two tiles of one traced body)
    assert len(calls) == kda_ops._heads_a_step(H) == 2
    del calls[:]
    grads = jax.eval_shape(lambda *a: kda_ops._kda_kernel_bwd.__wrapped__(
        *a, H=H, interpret=True), *flat, *kept, o)
    assert calls == []
    assert [a.shape for a in grads] == [a.shape for a in flat]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("L,H,D", [(16384, 32, 128), (130, 3, 128),
                                   (200, 2, 32)],
                         ids=["kimi-linear", "odd-heads", "plain-form"])
def test_residual_bytes_are_the_arrays(monkeypatch, L, H, D, dtype):
    """``residual_bytes`` (shapes alone) against what the forward rule hands
    the backward one beside the inputs: ``o``, the states and, from the
    kernels, the inverses and ``P``; at Kimi Linear's shapes 134, 537, 134
    and 67 MB."""
    dtype = jnp.dtype(dtype)
    B = 1
    padded = kda_ops.n_chunks(L) * 64
    x = [jax.ShapeDtypeStruct((B, padded, H, D), t)
         for t in (dtype, dtype, dtype, jnp.float32)]
    beta = jax.ShapeDtypeStruct((B, padded, H), jnp.float32)
    monkeypatch.setattr(kda_ops, "_off_tpu", lambda: True)
    o, kept = jax.eval_shape(kda_ops._kda_chunks_fwd, *x, beta)
    nbytes = lambda a: a.size * a.dtype.itemsize
    want = dict(zip(kda_ops.KDA_RESIDUAL_NAMES, map(nbytes, (o, *kept[5:]))))
    got = kda_ops.residual_bytes(B, L, H, D, dtype)
    assert got == want
    assert ("kda_inverse" in got) == ("kda_p" in got) == (D == 128)
    if (L, dtype) == (16384, jnp.bfloat16):
        assert got == {"kda_o": 2**27, "kda_state": 2**29,
                       "kda_inverse": 2**27, "kda_p": 2**26}


@pytest.mark.parametrize("decay_sums", ["float32", "bfloat16"])
def test_kernels_in_bfloat16_keep_float32_decay_sums_and_state(
        monkeypatch, decay_sums):
    """bfloat16 q, k and v through the forward kernel, three chunks of decay
    near 1/2 a token (``G`` to -45 a chunk): the output stays within 1e-2 of
    the float32 recurrence on the same inputs, bfloat16's rounding, and the
    chunk-entry states and the log-decay's gradient are float32.  With the
    decay sums rounded to bfloat16 inside the tile it is more than 3e-2 off:
    this is the case that sees what the benchmark's ``correct`` cannot
    (PERF.md section 6, PR 32 (3)).  Both cases trace the kernel's call anew
    (what ``ops.kda`` jits, unjitted), so the tile helper as patched is the
    one traced and no other trace is touched."""
    x = kernel_inputs(192, 1.0)
    x = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    want = kda_ops.kda_recurrent(*x)
    if decay_sums == "bfloat16":
        exact = kda_ops._decay_sums
        monkeypatch.setattr(kda_ops, "_decay_sums", lambda g, row, col: exact(
            g, row, col).astype(jnp.bfloat16).astype(jnp.float32))
    o, states, inverse, pairs = jax.jit(
        lambda *a: kda_ops._kda_kernel.__wrapped__(
            *map(kda_ops._flat, a[:4]), a[4], H=2, interpret=True))(*x)
    off = rel(o.reshape(want.shape).astype(jnp.float32), want)
    assert o.dtype == jnp.bfloat16
    assert states.dtype == jnp.float32 and states.shape[0] == 3
    assert inverse.dtype == jnp.float32 and inverse.shape[0] == 3
    assert pairs.dtype == jnp.bfloat16 and pairs.shape == inverse.shape
    if decay_sums == "bfloat16":
        assert off > 3e-2
        return
    assert off < 1e-2
    assert rel(o.reshape(want.shape).astype(jnp.float32),
               kda_ops.kda(*x).astype(jnp.float32)) == 0.0
    grads = jax.eval_shape(lambda *a: all_grads(kda_ops.kda, a, 1.0), *x)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2


# ------------------------------------------------ on more than one device
#
# The compiler partitions no Mosaic kernel (tests/test_aot_compile.py asks the
# chip's), so on a mesh the recurrence runs in a ``shard_map`` over the batch
# and the heads, whichever form the head width takes.

def mixer_inputs(L, B, H, D, seed=0):
    """``ops.kda_mixer.kda_mixer``'s twelve inputs: the projections' outputs,
    beta, the gate's pre-activation and the layer's per-channel leaves."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    C = H * D
    xq, xk, xv, f, z = (jax.random.normal(k, (B, L, C)) for k in ks[:5])
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, L, H)))
    conv = [jax.random.normal(k, (4, C)) * 0.5 for k in ks[6:9]]
    a_log = jnp.log(jax.random.uniform(ks[9], (H,), jnp.float32, 1.0, 16.0))
    return (xq, xk, xv, f, beta, z, *conv, a_log,
            jax.random.normal(ks[10], (C,)),
            1.0 + 0.1 * jax.random.normal(ks[11], (D,)))


MIXER_INPUTS = ("xq xk xv f beta z conv_q conv_k conv_v a_log dt_bias "
                "o_norm").split()


@pytest.mark.parametrize("axes", [{"dp": 2, "tp": 2}, {"tp": 4}, {"dp": 4}],
                         ids=["dp2-tp2", "tp4", "dp4"])
@pytest.mark.parametrize("head_dim", [16, 128], ids=["plain", "kernels"])
def test_the_recurrence_on_a_mesh_is_one_devices(head_dim, axes):
    """``llama._kda_sharded``: a KDA layer between its projections, the way
    in, the recurrence and the way out.  Values and all twelve gradients on a
    mesh are one device's, the batch split over ``dp`` and the heads over
    ``tp`` (the filters and ``dt_bias`` with their channels, ``a_log`` with
    its heads, ``o_norm`` whole; a leaf's gradient summed over ``dp``); the
    call is ONE ``shard_map`` and each device's kernels, where the width
    takes them, stand inside it on its own rows and heads."""
    from torchmpi_tpu.ops import kda_mixer

    mesh = pmesh.make_mesh(axes, devices=jax.devices()[:4])
    x = mixer_inputs(130, B=4, H=4, D=head_dim, seed=2)
    w = jax.random.normal(jax.random.PRNGKey(9), x[0].shape)
    sharded = llama._kda_sharded(mesh, 4, 1e-5)
    alone = llama._kda_sharded(None, 4, 1e-5)
    assert alone.func is kda_mixer.kda_mixer and alone.keywords == {
        "eps": 1e-5}
    both = lambda fn: jax.jit(lambda *a: (fn(*a), all_grads(fn, a, w)))
    (o, grads), (want, want_grads) = both(sharded)(*x), both(alone)(*x)
    assert rel(o, want) < 1e-6
    for name, a, b in zip(MIXER_INPUTS, grads, want_grads):
        assert a.shape == b.shape and rel(a, b) < 1e-5, name
    (outer,) = [e for e in jax.make_jaxpr(sharded)(*x).jaxpr.eqns]
    assert outer.primitive.name == "shard_map"
    local = (4 // axes.get("dp", 1), 4 // axes.get("tp", 1) * head_dim)
    inside = _scans_and_kernels(outer.params["jaxpr"])
    if head_dim == 128:
        assert inside == [("pallas_call", "kda_pre"),
                          ("pallas_call", "kda_fwd"),
                          ("pallas_call", "kda_post")]
        kernel = _find(outer.params["jaxpr"], "pallas_call")
        B, L, C = kernel.invars[0].aval.shape       # the rows in whole blocks
        assert (B, C) == local and L >= 130
    else:
        assert inside == [("scan", 3, False)]
    assert _scans_and_kernels(jax.make_jaxpr(alone)(*x).jaxpr) == inside


def _find(jaxpr, primitive):
    """The first equation of ``primitive`` in a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            return eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if (found := _find(sub, primitive)) is not None:
                return found
    return None


# ------------------------------------------------- flash with Dk != Dv

def _qkv(L, H, Dk, Dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, L, H, Dk)),
            jax.random.normal(ks[1], (1, L, H, Dk)),
            jax.random.normal(ks[2], (1, L, H, Dv)),
            jax.random.normal(ks[3], (1, L, H, Dv)))


@pytest.mark.parametrize("Dk,Dv", [(48, 32), (24, 32), (32, 32)])
def test_flash_with_values_of_their_own_width(Dk, Dv):
    """q and k ``Dk`` wide, v and o ``Dv``: output and all three gradients
    against full attention, several blocks a side; the equal case too."""
    q, k, v, w = _qkv(256, 2, Dk, Dv)
    flash = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128) * w)
    full = lambda q, k, v: jnp.sum(
        llama._causal_attention(q, k, v, Dk ** -0.5) * w)
    assert flash_attention(q, k, v, causal=True).shape == (1, 256, 2, Dv)
    assert abs(float(flash(q, k, v)) - float(full(q, k, v))) < 1e-3
    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(full, (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape and rel(got, want) < 1e-5


def test_flash_streaming_backward_with_values_of_their_own_width():
    """The two streaming kernels give what the one kernel gives."""
    q, k, v, do = (a.transpose(0, 2, 1, 3).reshape(2, 128, -1)
                   for a in _qkv(128, 2, 48, 32))
    kw = dict(causal=True, block_q=32, block_k=64, interpret=True)
    o, lse = _flash_bh(q, k, v, **kw)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    one = _flash_bh_bwd(q, k, v, do, lse, delta, **kw)
    two = _flash_bh_bwd(q, k, v, do, lse, delta, vmem_budget=0, **kw)
    assert [a.shape for a in one] == [q.shape, k.shape, v.shape]
    for a, b in zip(one, two):
        assert rel(a, b) < 1e-6
