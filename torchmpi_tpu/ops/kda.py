"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel decay,
in its chunked form, forward and backward, in plain XLA.

The recurrence, for one head with keys and values ``d`` wide, state ``S``
(d x d, float32, zero at the start), decay ``a_t = exp(g_t)`` in (0, 1)^d
and write strength ``b_t`` in (0, 1)::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

("Kimi Linear: An Expressive, Efficient Attention Architecture", Moonshot AI,
2025-10; the delta rule of Yang et al., arXiv:2406.06484, with Mamba-style
decay a channel).  A token-by-token ``lax.scan`` of it is what
``benchmark/reference/kimi-linear-48b-a3b.py`` and the tests hold this file
to; nothing here runs one.

The chunked form (chunks of ``CHUNK`` = 64 tokens; ``G_r`` the sum of ``g``
over the chunk's rows up to r, ``S0`` the state that enters the chunk)::

    M_ij = sum_c k_ic k_jc exp(G_ic - G_jc)    (i > j)
    P_ti = sum_c q_tc k_ic exp(G_tc - G_ic)    (t >= i)
    T  = (I + Diag(b) tril(M, -1))^-1 Diag(b)
    W  = T (K * e^G)        U  = T V - W S0
    O  = (Q * e^G) S0 + tril(P) U
    S' = Diag(e^{G_C}) S0 + (K * e^{G_C - G})^T U

``M``, ``P``, ``T``, ``W`` and ``T V`` are formed for all chunks at once
(:func:`_intra`); ``U``, ``O`` and ``S'`` run chunk after chunk, a
``lax.scan`` over L / 64 steps each batched over batch and heads
(:func:`_inter`).  Every exponent is a difference ``G_i - G_j`` with i >= j,
so never positive: ``e^{-G}`` alone overflows float32 after a few tokens of
strong decay.  The products that need both sides of such a difference run in
sub-blocks of ``SUB`` = 16 rows: off the diagonal each side is taken against
the first row of the row block, which lies between them, and on the diagonal
the 16 x 16 x d exponents are formed one by one (sub-blocks of 8 would halve
those elementwise passes and double the copies of k the other products
read: 0.1 GB more of a plan that has 0.15 to spare at Kimi Linear's widths;
compiled, not run).  ``T``'s inverse is that of
a unit lower-triangular 64 x 64 matrix, by substitution in blocks
(:func:`_unit_lower_inverse`, which says why not by the powers of N).  The state and the decay
sums are float32; the other products take operands of the inputs' type and
accumulate in float32.

The gradient is a hand-written rule (``jax.custom_vjp``): the backward pass
holds the inputs and the chunk-entry states and, a group of heads at a time,
forms the chunk-local part again for all chunks at once, runs the recurrence
backward once, a reverse scan over the chunks, and differentiates the
chunk-local part.  The forward rule names what
a caller's ``jax.checkpoint`` must keep so that neither scan runs twice
(``KDA_RESIDUAL_NAMES``: the output and the chunk-entry states), as
``ops.flash_attention`` names its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64
SUB = 16

# What the forward rule names (``checkpoint_name``): the output, which the
# layer reads again, and the chunk-entry states, which the backward scan
# reads.  A policy that keeps neither runs the forward scan again.
KDA_RESIDUAL_NAMES = ("kda_o", "kda_state")

_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST
# Three bfloat16 passes a product: float32's exponent and 16 of its mantissa's
# bits, for what is rounded to the operands' type once it is formed.
_NEAR = lax.Precision.HIGH


def n_chunks(seq_len: int) -> int:
    """Chunks of the recurrence a sequence of ``seq_len`` tokens takes."""
    return -(-seq_len // CHUNK)


def _pair_decay(Gb, strict: bool):
    """``exp(G_ic - G_jc)`` for the pairs inside each sub-block, (..., nb,
    SUB, SUB, D), for i > j (``strict``) or i >= j and 0 elsewhere: each
    exponent formed as the difference it is.  Never written out: every use
    multiplies and sums it in the pass that forms it."""
    i = lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    j = lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    keep = (i > j if strict else i >= j)[..., None]
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]
    return jnp.exp(jnp.where(keep, diff, -1e30))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diag_blocks(a, k, Gb, strict: bool):
    """``sum_c a_ic k_jc exp(G_ic - G_jc)`` inside each sub-block, (..., nb,
    SUB, SUB) float32, for i > j (``strict``) or i >= j, zero elsewhere.
    These are the recurrence's elementwise passes, SUB * C * D exponentials
    a chunk, and the gradient is written out to make two of them, not the
    four autodiff makes: with ``A_ic = sum_j M'_ij k_jc E_ijc`` and ``K_jc =
    sum_i M'_ij a_ic E_ijc`` the gradients of a and k, that of G is ``a * A -
    k * K`` (E's derivative in G_i is E, in G_j is -E).  (M's and P's blocks
    in one call, the exponentials formed once for both, held 0.9 GB more at
    Kimi Linear's widths: compiled, not run.)"""
    return jnp.sum(a[..., :, None, :] * k[..., None, :, :]
                   * _pair_decay(Gb, strict), axis=-1)


def _diag_blocks_bwd(strict, saved, dM):
    a, k, Gb = saved
    weighted = dM[..., None] * _pair_decay(Gb, strict)
    da = jnp.sum(weighted * k[..., None, :, :], axis=-2)
    dk = jnp.sum(weighted * a[..., :, None, :], axis=-3)
    return da, dk, a * da - k * dk


_diag_blocks.defvjp(lambda a, k, Gb, strict: (_diag_blocks(a, k, Gb, strict),
                                              (a, k, Gb)), _diag_blocks_bwd)


def _block_diagonal(blocks):
    """(..., nb, SUB, SUB) -> (..., CHUNK, CHUNK) with the blocks on the
    diagonal."""
    nb = blocks.shape[-3]
    eye = jnp.eye(nb, dtype=blocks.dtype)[:, None, :, None]
    full = blocks[..., :, :, None, :] * eye
    return full.reshape(*blocks.shape[:-3], nb * SUB, nb * SUB)


def _substitute(N):
    """``(I - N)^-1`` for strictly lower-triangular blocks N (..., s, s) by
    forward substitution, a row after the other (``X_i = e_i + sum_{j<i}
    N_ij X_j``), elementwise in float32: s - 1 short passes over the
    blocks."""
    s = N.shape[-1]
    eye = jnp.eye(s, dtype=N.dtype)
    rows = [jnp.broadcast_to(eye[0], N.shape[:-2] + (s,))]
    for i in range(1, s):
        known = jnp.stack(rows, axis=-2)                         # (..., i, s)
        row = lax.index_in_dim(N, i, axis=-2, keepdims=False)[..., :i]
        rows.append(eye[i] + jnp.sum(row[..., None] * known, axis=-2))
    return jnp.stack(rows, axis=-2)


@jax.custom_vjp
def _unit_lower_inverse(N):
    """``(I - N)^-1`` for strictly lower-triangular N (..., C, C), float32:
    the ``SUB`` x ``SUB`` blocks on the diagonal by forward substitution
    (:func:`_substitute`), then pairs of blocks merged (``[[A, 0], [B N_21 A,
    B]]``) until one is left.  With keys of unit norm and ``beta <= 1`` no
    entry of the inverse passes 1, and every step here is a sum of products of
    such entries.  NOT the six products ``(I + N)(I + N^2) ... (I + N^32)``:
    the powers of N grow like binomial coefficients once a chunk's keys are
    correlated (``N^32`` to 1e17 for collinear keys) and the inverse is what
    is left of their cancellation; float32 has none of it left, and the
    recurrence's state grew a thousandfold a chunk after one optimizer step
    had moved seeded keys together (my chip runs, PR 32).  The gradient is
    written out (``X' = X N' X``), so a backward pass holds the inverse and
    no intermediate."""
    C = N.shape[-1]
    lead = N.shape[:-2]

    def blocks(size, row, col):
        """Blocks (2p + row, 2p + col) of N cut into ``size`` x ``size``."""
        m = C // size
        cut = N.reshape(*lead, m, size, m, size)
        # (one index at a time: two in one subscript are a gather, which the
        # chip's compiler runs as a loop)
        return jnp.stack([cut[..., 2 * p + row, :, :, :][..., 2 * p + col, :]
                          for p in range(m // 2)], axis=-3)

    size = SUB
    X = _substitute(jnp.concatenate([blocks(size, 0, 0), blocks(size, 1, 1)],
                                    axis=-3))
    m = C // size // 2
    X = jnp.stack([X[..., :m, :, :], X[..., m:, :, :]],
                  axis=-3).reshape(*lead, 2 * m, size, size)
    while size < C:
        pairs = X.reshape(*lead, -1, 2, size, size)
        A, B = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = jnp.matmul(jnp.matmul(B, blocks(size, 1, 0), precision=_EXACT),
                         A, precision=_EXACT)
        X = jnp.concatenate(
            [jnp.concatenate([A, jnp.zeros_like(A)], axis=-1),
             jnp.concatenate([low, B], axis=-1)], axis=-2)
        size *= 2
    return X[..., 0, :, :]


def _unit_lower_inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (jnp.matmul(jnp.matmul(t, g, precision=_NEAR), t,
                       precision=_NEAR),)


_unit_lower_inverse.defvjp(lambda N: (_unit_lower_inverse(N),) * 2,
                           _unit_lower_inverse_bwd)


def _intra(q, k, v, g, beta):
    """The chunk-local part, for all chunks at once.  q, k, v: (..., C, D);
    g: (..., C, D) float32; beta: (..., C) float32.  Returns ``(W, TV, Qe,
    P, Kdec, dC)`` as the module docstring has them: (..., C, D) in the
    inputs' type, ``P`` (..., C, C), ``dC`` (..., D) float32."""
    dt = q.dtype
    C, D = q.shape[-2:]
    nb = C // SUB
    lead = q.shape[:-2]
    # The decay sums, float32: a product with the lower-triangular ones (a
    # ``cumsum`` lowers to a windowed reduction five times as slow here).
    G = jnp.matmul(jnp.tril(jnp.ones((C, C), _F32)), g, precision=_EXACT)
    qf, kf = q.astype(_F32), k.astype(_F32)
    blocked = lambda a: a.reshape(*lead, nb, SUB, D)
    Gb, qb, kb = blocked(G), blocked(qf), blocked(kf)
    # Off the diagonal: row block b against every earlier row j, both sides
    # taken against the block's first row, so both exponents are <= 0.
    ref = Gb[..., :, :1, :]                                  # (.., nb, 1, D)
    near = jnp.exp(Gb - ref)                                 # (.., nb, SUB, D)
    far = jnp.exp(jnp.minimum(ref - G[..., None, :, :], 0.0))  # (.., nb, C, D)
    rows = jnp.stack([kb * near, qb * near], axis=-4).astype(dt)
    cols = (kf[..., None, :, :] * far).astype(dt)
    off = jnp.einsum("...xbic,...bjc->...xbij", rows, cols,
                     preferred_element_type=_F32)            # (.., 2, nb, SUB, C)
    earlier = (lax.broadcasted_iota(jnp.int32, (nb, 1, C), 2)
               < SUB * lax.broadcasted_iota(jnp.int32, (nb, 1, C), 0))
    off = jnp.where(earlier, off, 0.0).reshape(*lead, 2, C, C)
    M = off[..., 0, :, :] + _block_diagonal(_diag_blocks(kb, kb, Gb, True))
    P = off[..., 1, :, :] + _block_diagonal(_diag_blocks(qb, kb, Gb, False))
    # T = (I - N)^-1 Diag(beta), N = -Diag(beta) tril(M, -1) nilpotent.
    T = (_unit_lower_inverse(-beta[..., :, None] * M)
         * beta[..., None, :]).astype(dt)
    eG = jnp.exp(G)
    W = jnp.matmul(T, (kf * eG).astype(dt), preferred_element_type=_F32)
    TV = jnp.matmul(T, v, preferred_element_type=_F32)
    GC = G[..., -1:, :]
    return (W.astype(dt), TV.astype(dt), (qf * eG).astype(dt), P.astype(dt),
            (kf * jnp.exp(GC - G)).astype(dt), jnp.exp(GC[..., 0, :]))


def _inter(S, chunk):
    """One chunk of the recurrence, batched over its leading axes: the state
    ``S`` (..., D, D) float32 that enters it and the chunk's ``_intra``
    tensors -> (the state that leaves it, the chunk's output (..., C, D))."""
    W, TV, Qe, P, Kdec, dC = chunk
    dt = W.dtype
    dot = lambda a, b: jnp.matmul(a, b, preferred_element_type=_F32)
    Sd = S.astype(dt)
    U = (TV.astype(_F32) - dot(W, Sd)).astype(dt)
    O = dot(Qe, Sd) + dot(P, U)
    S = dC[..., :, None] * S + dot(jnp.swapaxes(Kdec, -1, -2), U)
    return S, O.astype(dt)


def _by_chunk(tree):
    """(B, H, N, ...) leaves -> (N, B, H, ...): the scan's axis first."""
    return jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), tree)


@jax.custom_vjp
def _kda_chunks(q, k, v, g, beta):
    """q, k, v, g: (B, H, N, C, D); beta: (B, H, N, C) -> o (B, H, N, C, D)."""
    return _kda_chunks_fwd(q, k, v, g, beta)[0]


def _kda_chunks_fwd(q, k, v, g, beta):
    B, H, _, _, D = q.shape

    def step(S, chunk):
        S2, O = _inter(S, chunk)
        return S2, (S, O)

    _, (states, o) = lax.scan(step, jnp.zeros((B, H, D, D), _F32),
                              _by_chunk(_intra(q, k, v, g, beta)))
    o = checkpoint_name(jnp.moveaxis(o, 0, 2), KDA_RESIDUAL_NAMES[0])
    states = checkpoint_name(states, KDA_RESIDUAL_NAMES[1])
    return o, (q, k, v, g, beta, states)


# Heads whose backward pass runs at once (:func:`_kda_chunks_bwd`).
_HEAD_GROUP = 8


def _kda_chunks_bwd(saved, do):
    """A group of heads at a time: the chunk-local part formed again and
    kept for its gradient, the recurrence run backward over the group's
    chunks, the chunk-local gradient.  The chunk-local part's float32
    temporaries, a dozen arrays of the log-decay's size, are most of what a
    backward pass holds (Kimi Linear's 32 heads at 16,384 tokens: 4 GB at
    once, 1 GB by groups of 8); a scan step over 8 heads is short of work
    either way, and the groups' four scans cost less than forming the
    chunk-local part of all heads a second time would."""
    *inputs, states = saved
    B, H = states.shape[1:3]
    groups = H // _HEAD_GROUP if H % _HEAD_GROUP == 0 else 1
    split = lambda a, axis: jnp.moveaxis(
        a.reshape(*a.shape[:axis], groups, H // groups, *a.shape[axis + 1:]),
        axis, 0)

    def group(xs):
        inputs, states, do = xs
        chunks, intra_vjp = jax.vjp(_intra, *inputs)

        def step(dS, xs):
            S, chunk, dO = xs
            _, vjp = jax.vjp(_inter, S, chunk)
            return vjp((dS, dO))

        _, dchunks = lax.scan(step, jnp.zeros_like(states[0]),
                              (states, _by_chunk(chunks), _by_chunk(do)),
                              reverse=True)
        return intra_vjp(jax.tree.map(lambda a: jnp.moveaxis(a, 0, 2),
                                      dchunks))

    grads = lax.map(group, (jax.tree.map(lambda a: split(a, 1), tuple(inputs)),
                            split(states, 2), split(do, 1)))
    return jax.tree.map(
        lambda a: jnp.moveaxis(a, 0, 1).reshape(B, H, *a.shape[3:]), grads)


_kda_chunks.defvjp(_kda_chunks_fwd, _kda_chunks_bwd)


@jax.named_scope("kda")
def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array) -> jax.Array:
    """The gated delta rule over a sequence from a zero state.  q, k, v: (B,
    L, H, D) in the compute type, q and k as the recurrence takes them
    (normalised and scaled by the caller); ``g`` (B, L, H, D) float32, the
    log of the decay a channel, <= 0; ``beta`` (B, L, H) float32.  Returns o
    (B, L, H, D).  L is padded to whole chunks here (a padded token writes
    nothing: k = 0, beta = 0, g = 0) and cropped again."""
    B, L, H, D = q.shape
    pad = -L % CHUNK
    N = (L + pad) // CHUNK

    def chunked(a):                     # (B, L, H, ...) -> (B, H, N, C, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(B, H, N, CHUNK, *a.shape[3:])

    o = _kda_chunks(chunked(q), chunked(k), chunked(v),
                    chunked(g.astype(_F32)), chunked(beta.astype(_F32)))
    return jnp.moveaxis(o.reshape(B, H, N * CHUNK, D), 1, 2)[:, :L]


def kda_recurrent(q, k, v, g, beta):
    """The same function token by token, float32: the recurrence as the
    module docstring writes it.  For tests; no program runs it."""
    B, L, H, D = q.shape
    f = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                  # (B, H, D) and (B, H)
        S = jnp.exp(g_t)[..., None] * S
        kS = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_EXACT)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - kS,
                           precision=_EXACT)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_EXACT)

    _, o = lax.scan(step, jnp.zeros((B, H, D, D), _F32),
                    (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1)
