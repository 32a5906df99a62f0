"""Kimi-Linear-style stacks (``llama.kimi_linear_48b_a3b``): the chunked KDA
recurrence against the token-by-token one, flash attention with keys and
values of different widths, the KDA and latent-attention blocks, the sigmoid
router and a chip's share of the experts against the plain reference the
benchmark keeps (``benchmark/reference/kimi-linear-48b-a3b.py``, which imports
nothing of the program), the runs a stack is built from, the remat policies,
the frozen selection bias, the refusals and the names in the device program.
Small widths, float32, the CPU."""

import dataclasses
import importlib.util
import os
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import flash_attention
from torchmpi_tpu.ops import kda as kda_ops
from torchmpi_tpu.ops.flash_attention import _flash_bh, _flash_bh_bwd
from torchmpi_tpu.parallel import mesh as pmesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.kimi_linear_48b_a3b()


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "kimi-linear-48b-a3b.py")
    spec = importlib.util.spec_from_file_location("kimi_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kimi_tiny(n_layers=5, n_experts=8, held=(0, 2), k=2):
    """The published pattern's first ``n_layers`` layers at toy widths."""
    return dataclasses.replace(
        PUBLISHED, vocab=128, d_model=64, n_layers=n_layers, n_heads=4,
        n_kv_heads=4, d_ff=32, dense_d_ff=96, max_seq=256,
        n_experts=n_experts, expert_top_k=k, kda_heads=4, kda_head_dim=16,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, layer_kinds=PUBLISHED.layer_kinds[:n_layers],
        experts_held=held)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    kda = [i + 1 for i, (m, _) in enumerate(PUBLISHED.layer_kinds)
           if m == "kda"]
    mla = [i + 1 for i, (m, _) in enumerate(PUBLISHED.layer_kinds)
           if m == "mla"]
    first, held = cfg.experts_held or (0, cfg.n_experts)
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "first_k_dense_replace": 1,
        "linear_attn_config": {
            "kda_layers": kda, "full_attn_layers": mla,
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv},
        "published": {"num_experts": cfg.n_experts},
        "num_experts": held, "experts_held_first": first,
        "num_experts_per_token": cfg.expert_top_k,
        "num_shared_experts": cfg.n_shared_experts,
        "moe_renormalize": cfg.moe_renormalize,
        "routed_scaling_factor": cfg.routed_scale,
    }


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer_of(params, run, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][run])


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


# ------------------------------------------------- blocks against the reference

@pytest.fixture(scope="module")
def model():
    cfg = kimi_tiny()
    return cfg, llama.init(jax.random.PRNGKey(0), cfg)


def test_kda_block_against_the_reference(model, reference):
    cfg, params = model
    lp = layer_of(params, 1)                 # a KDA layer of the moe run
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 96, cfg.d_model))
    want = jax.vmap(lambda s: reference.kda_mixer(file_of(cfg), lp, s))(x)
    mixer = llama._kda_sharded(None, cfg.kda_heads, cfg.norm_eps)
    assert rel(llama._kda_block(cfg, lp, x, mixer), want) < 1e-5


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_mla_block_against_the_reference(model, reference, attn):
    cfg, params = model
    assert llama.layer_runs(cfg)[2][:2] == ("mla", "moe")
    lp = layer_of(params, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model))
    want = jax.vmap(lambda s: reference.mla_mixer(file_of(cfg), lp, s))(x)
    impl = llama._mixer_impls(cfg, attn, None)["mla"]
    assert rel(llama._mla_block(cfg, lp, x, impl), want) < 1e-5


def test_sigmoid_router_with_a_bias_that_changes_the_choice(model, reference):
    """The bias moves the top-k choice and nothing else: weights are the
    chosen scores without it, renormalised and scaled; the whole layer (all
    experts held) is the reference's; the bias's gradient is exactly zero."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, experts_held=None)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    lp = layer_of(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    xt = x.reshape(-1, cfg.d_model)
    weight, expert, counts, _ = llama._route_tokens(cfg, lp, xt)
    unbiased = llama._route_tokens(
        cfg, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, xt)[1]
    assert int(jnp.sum(jnp.sort(expert) != jnp.sort(unbiased))) > 0
    assert int(jnp.sum(counts)) == cfg.expert_top_k * 64
    np.testing.assert_allclose(jnp.sum(weight, axis=-1), cfg.routed_scale,
                               rtol=1e-6)
    want = reference.experts_ffn(file_of(cfg), lp, xt)
    got, _ = llama._moe_ffn(cfg, lp, x)
    assert rel(got.reshape(want.shape), want) < 1e-5
    g = jax.grad(lambda b: jnp.sum(llama._moe_ffn(
        cfg, {**lp, "router_bias": b}, x)[0] ** 2))(lp["router_bias"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up(reference, held):
    """Over all ``n_experts / held`` shares of a layer, the held experts'
    parts, with the shared expert counted once, sum to the uncut reference's
    layer output; a share's weights are the uncut layer's experts."""
    whole = kimi_tiny(held=None)
    full = layer_of(llama.init(jax.random.PRNGKey(0), whole), 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, whole.d_model))
    xt = x.reshape(-1, whole.d_model)
    want = reference.experts_ffn(file_of(whole), full, xt)
    shared = reference.swiglu(xt, full["shared_gate"], full["shared_up"],
                              full["shared_down"])
    total = 0.0
    for first in range(0, whole.n_experts, held):
        cfg = kimi_tiny(held=(first, held))
        lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
        np.testing.assert_array_equal(lp["w_up"],
                                      full["w_up"][first:first + held])
        part, _ = llama._moe_ffn(cfg, lp, x)
        assert rel(part.reshape(xt.shape), reference.experts_ffn(
            file_of(cfg), lp, xt)) < 1e-5
        total = total + part.reshape(xt.shape) - shared
    assert rel(total + shared, want) < 1e-5


@pytest.mark.parametrize("bias, passes", [(0.0, 1), (10.0, 8)])
def test_every_held_unit_is_computed(reference, monkeypatch, bias, passes):
    """The held experts take their units a pass of static rows at a time, as
    many passes as arrived: a bias that sends every token to the two held
    experts of 32 makes 128 units where a pass takes 16, and the layer's
    output and every gradient are still the reference's, as they are where
    one pass is enough."""
    cfg = kimi_tiny(n_experts=32, held=(0, 2))
    assert llama.held_pass_rows(cfg, 64) == 32
    assert llama.held_pass_rows(kimi_tiny(), 64) == 128   # never over k * T
    monkeypatch.setattr(llama, "_HELD_PASS_OVER_SHARE", 2)
    assert llama.held_pass_rows(cfg, 64) == 16
    lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
    lp["router_bias"] = jnp.zeros(32).at[:2].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, cfg.d_model))
    arrived = llama._route_tokens(cfg, lp, x[0])[2][:2]
    assert -(-int(jnp.sum(arrived)) // 16) == passes
    ours = lambda lp, x: jnp.sum(jnp.sin(llama._moe_ffn(cfg, lp, x)[0]))
    theirs = lambda lp, x: jnp.sum(jnp.sin(reference.experts_ffn(
        file_of(cfg), lp, x[0])))
    got, want = (jax.value_and_grad(f, argnums=(0, 1))(lp, x)
                 for f in (ours, theirs))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert rel(got[1][1], want[1][1]) < 1e-5
    for name in lp:
        if name in want[1][0] and name != "router_bias":
            assert rel(got[1][0][name], want[1][0][name]) < 1e-5, name


# ------------------------------------------------------------- the stack

def test_the_published_pattern_builds_its_runs():
    kinds = PUBLISHED.layer_kinds
    assert len(kinds) == 27 and kinds[0] == ("kda", "dense")
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert all(f == "moe" for _, f in kinds[1:])
    runs = llama.layer_runs(PUBLISHED)
    assert len(runs) == 15 and sum(n for *_, n in runs) == 27
    assert runs[:4] == (("kda", "dense", 1), ("kda", "moe", 2),
                        ("mla", "moe", 1), ("kda", "moe", 3))
    assert max(n for *_, n in runs) <= llama._INLINE_MAX_LAYERS
    assert llama.layer_runs(llama.olmoe_1b_7b()) == (("attn", "moe", 16),)
    with pytest.raises(ValueError, match="neither list"):
        llama.layer_kinds(3, [1, 2], [], 1)


def test_the_published_27_layers_build_and_run():
    cfg = kimi_tiny(n_layers=27)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    assert len(params["layers"]) == 15
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs,
                                        is_leaf=lambda s: isinstance(
                                            s, jax.sharding.PartitionSpec)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab)
    logits = jax.jit(lambda p, t: llama.apply(cfg, p, t))(params, tokens)
    assert logits.shape == (1, 64, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    counts = llama.expert_unit_counts(cfg, params, tokens)
    assert counts.shape == (26, cfg.n_experts)
    assert [int(c) for c in counts.sum(axis=1)] == [2 * 64] * 26


@pytest.mark.parametrize("name,leaves,checksum,loss", [
    ("tiny", 12, 55238.10294479898, 6.17431116104126),
    ("moe_tiny", 13, 156614.26303055455, 6.086923122406006),
    ("olmoe", 15, 208743.01844608856, 6.17168664932251),
    ("looped", 16, 99763.4812040137, 5.88877534866333)])
def test_a_homogeneous_configuration_is_what_it_was(name, leaves, checksum,
                                                    loss):
    """One run, the parameter tree and the weights for a seed that the
    commit before runs existed gave (numbers taken from it), and its loss."""
    cfg = {
        "tiny": llama.tiny(), "moe_tiny": llama.moe_tiny(),
        "olmoe": dataclasses.replace(
            llama.moe_tiny(), n_kv_heads=4, capacity_factor=None,
            moe_renormalize=False, moe_z_coef=1e-3, qk_norm=True),
        "looped": dataclasses.replace(
            llama.tiny(), n_kv_heads=4, ut_steps=3, sandwich_norm=True,
            exit_gate=True, exit_entropy_coef=0.1)}[name]
    assert len(llama.layer_runs(cfg)) == 1
    params = llama.init(jax.random.PRNGKey(7), cfg)
    assert isinstance(params["layers"], dict)
    flat = jax.tree.leaves(params)
    assert len(flat) == leaves
    total = sum(np.sum(np.abs(np.asarray(a, np.float64))) * (i + 1)
                for i, a in enumerate(flat))
    assert total == pytest.approx(checksum, rel=1e-12)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    got = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="dots",
                                     loss_chunk=16))(params, (tokens, tokens))
    assert float(got) == pytest.approx(loss, rel=1e-6)


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


def _names(jaxpr):
    """The names ``checkpoint_name`` left in a jaxpr, sub-jaxprs included."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _names(sub)
    return found


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
