"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel decay,
in its chunked form, forward and backward: two Pallas kernels where a head's
channels fill whole lanes, plain XLA elsewhere.

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

``M``, ``P``, ``T``, ``W`` and ``T V`` are chunk-local (:func:`_intra`);
``U``, ``O`` and ``S'`` run chunk after chunk (:func:`_inter`).  Every
exponent is a difference ``G_i - G_j`` with i >= j,
so never positive: ``e^{-G}`` alone overflows float32 after a few tokens of
strong decay.  The products that need both sides of such a difference run in
sub-blocks of ``SUB`` = 16 rows: off the diagonal each side is taken against
the first row of the row block, which lies between them, and on the diagonal
the 16 x 16 x d exponents are formed one by one.  ``T``'s inverse is that
of a unit lower-triangular 64 x 64 matrix, by substitution in blocks
(:func:`_unit_lower_inverse`, which says why not by the powers of N).  The
state and the decay sums are float32; the other products take operands of
the inputs' type and accumulate in float32.

The gradient is a hand-written rule (``jax.custom_vjp``): the backward pass
holds the inputs, the chunk-entry states and, in the kernels, every chunk's
inverse ``(I + Diag(b) tril(M, -1))^-1`` and ``P`` as the forward pass made
them; it forms the rest of the chunk-local part again (``M``, then ``T``,
``W``, ``T V`` from the inverse it was given), runs the recurrence backward
once over the chunks and differentiates the chunk-local part.  The forward
rule names what a caller's ``jax.checkpoint`` must keep so that the
recurrence never runs twice and no tile is inverted twice
(``KDA_RESIDUAL_NAMES``), as ``ops.flash_attention`` names its own; a layer
of B x L tokens and H heads keeps, beside its inputs
(:func:`residual_bytes`):

* ``kda_o``, the output, (B, L, H, D) in the inputs' type: a layer input's
  bytes (134 MB at Kimi Linear's 1 x 16,384 x 32 x 128 in bfloat16);
* ``kda_state``, the state that enters each chunk, (L / 64, B, H, D, D)
  float32: four times that (537 MB);
* ``kda_inverse``, from the kernels alone, each chunk's inverse, 64 x 64
  float32, 16 KB a tile: once that again (134 MB).  Inverting a tile is 40%
  of the forward kernel's time and was 7.7 of the backward kernel's 29.3 ms
  a layer; written out and read back it is 0.16 ms a layer each way at the
  HBM's peak, in kernels whose DMA engines idle nine tenths of the time.  It
  is kept exactly as computed: a bfloat16 inverse would be another result;
* ``kda_p``, from the kernels alone, each chunk's ``tril(P)`` in the inputs'
  type, as the forward pass rounded it for its own product: half that (67
  MB), for 4.2 ms a layer of the backward kernel (the 16 lane sums of
  ``P``'s diagonal blocks and the q rows of the products off them).

Which form runs is read from the head width alone (:func:`_takes_kernel`),
on every backend (off the TPU through the Pallas interpreter, as
``ops.flash_attention``):

* ``head_dim % 128 == 0`` (Kimi Linear's 128): two Mosaic kernels,
  ``kda_fwd`` and ``kda_bwd``, on a grid of (batch, groups of
  ``_HEADS_A_STEP`` heads, chunks) whose chunk axis runs in turn.  A step
  loads a chunk's tiles of q, k, v and g (64 x 128 a head, out of the (B,
  L, H * D) layout the layer keeps: no chunked copy) and beta into VMEM
  once, and every intermediate of the algebra above lives and dies there
  (:func:`_tile_forward`: the decay sums, ``near``/``far``, the
  off-diagonal products, the diagonal blocks a column of all four at a
  time, the inverse by substitution and merges, ``T``, ``W``, ``T V``,
  ``Q e^G``, ``K e^{G_C - G}``); the state, float32, stays in a VMEM scratch
  from one chunk to the next (:func:`_tile_inter`) and is written out, as it
  enters each chunk, as a residual; the tile's inverse and ``P`` are the
  others (two heads' tiles side by side, a block of 64 x 128, so that a
  store fills whole lanes).  ``kda_bwd`` walks the chunks from the last: it
  forms the tile again but for the inverse and ``P``, which it reads
  (:func:`_tile_local`, then :func:`_tile_chunk`), takes
  :func:`_tile_inter`'s gradient with the state's cotangent in scratch and
  then the tile's (:func:`_tile_backward`, the gradient written out on VMEM
  tiles: ``dG = rows * drows - cols * dcols``, ``N' = X^T X' X^T``), and
  writes dq, dk, dv, dg and dbeta.  Nothing else chunk-local is ever in HBM,
  and there is no scan.
* other widths: :func:`_intra` for all chunks at once, :func:`_inter` in a
  ``lax.scan``, and in the backward pass autodiff of both from the inputs
  and the states alone (:func:`_plain_fwd`, :func:`_plain_bwd`).  No
  configuration has such a width: this form is the algebra as XLA's own
  operations, kept short, and the kernels' oracle in ``tests/``, its
  gradient autodiff's where the kernels' is written out.

The two forms share no code, only the algebra: same sub-blocks, same
exponents (differences, never positive), same float32 decay sums, state and
inverse at ``Precision.HIGHEST``, same operands rounded to the inputs' type.

The layer's passes round the recurrence (what makes q, k, v and g of the
projections' outputs, and the output's norm and gate) are ``ops.kda_mixer``'s
and choose between their two forms by the same rule, ``head_dim % 128 == 0``:
fused kernels in this file's (B, L, H * D) layout, or ``jax.numpy``.

A Mosaic kernel is not partitioned by the compiler: on a mesh of several
devices the caller runs :func:`kda`, between those passes, inside a
``shard_map`` over the batch and the heads (``models.llama._kda_sharded``),
as it does the flash kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16

# What the forward rule names (``checkpoint_name``): the output, which the
# layer reads again, the chunk-entry states, which the backward pass over
# the chunks reads, and, from the kernels, every tile's inverse and ``P``,
# which ``kda_bwd`` reads in place of forming them again.  A policy that
# does not keep them all runs the forward recurrence again.
KDA_RESIDUAL_NAMES = ("kda_o", "kda_state", "kda_inverse", "kda_p")

_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST


def n_chunks(seq_len: int) -> int:
    """Chunks of the recurrence a sequence of ``seq_len`` tokens takes."""
    return -(-seq_len // CHUNK)


def residual_bytes(batch: int, seq_len: int, heads: int, head_dim: int,
                   dtype) -> dict[str, int]:
    """The bytes one KDA layer keeps from its forward to its backward pass
    beside its inputs, by the name each array carries
    (``KDA_RESIDUAL_NAMES``): the output in ``dtype``, the float32 state that
    enters each chunk and, where the kernels run, each chunk's tile of the
    float32 inverse and of ``P`` in ``dtype``.  From shapes alone."""
    tiles = n_chunks(seq_len) * batch * heads
    item = jnp.dtype(dtype).itemsize
    kept = (tiles * CHUNK * head_dim * item, tiles * head_dim * head_dim * 4)
    if _takes_kernel(head_dim):
        kept += (tiles * CHUNK * CHUNK * 4, tiles * CHUNK * CHUNK * item)
    return dict(zip(KDA_RESIDUAL_NAMES, kept))


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


def _diag_blocks(a, k, Gb, strict: bool):
    """``sum_c a_ic k_jc exp(G_ic - G_jc)`` inside each sub-block, (..., nb,
    SUB, SUB) float32, for i > j (``strict``) or i >= j, zero elsewhere: SUB
    * C * D exponentials a chunk."""
    return jnp.sum(a[..., :, None, :] * k[..., None, :, :]
                   * _pair_decay(Gb, strict), axis=-1)


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
    had moved seeded keys together (my chip runs, PR 32)."""
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


def _chunked(a):
    """(B, L, H, ...) -> (B, H, N, CHUNK, ...), L whole chunks."""
    a = jnp.moveaxis(a, 2, 1)
    return a.reshape(*a.shape[:2], -1, CHUNK, *a.shape[3:])


def _unchunked(a):
    """(B, H, N, CHUNK, ...) -> (B, L, H, ...)."""
    return jnp.moveaxis(a.reshape(*a.shape[:2], -1, *a.shape[4:]), 1, 2)


def _by_chunk(tree):
    """(B, H, N, ...) leaves -> (N, B, H, ...): the scan's axis first."""
    return jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), tree)


def _plain_fwd(q, k, v, g, beta):
    """The plain form forward: the chunk-local part of all chunks at once,
    then the chunks in a scan.  q, k, v, g (B, L, H, D), beta (B, L, H), L
    whole chunks -> o (B, L, H, D) and the chunk-entry states (N, B, H, D,
    D) float32."""
    B, _, H, D = q.shape

    def step(S, chunk):
        S2, O = _inter(S, chunk)
        return S2, (S, O)

    _, (states, o) = lax.scan(
        step, jnp.zeros((B, H, D, D), _F32),
        _by_chunk(_intra(*map(_chunked, (q, k, v, g, beta)))))
    return _unchunked(jnp.moveaxis(o, 0, 2)), states


def _plain_bwd(q, k, v, g, beta, states, do):
    """The plain form backward, by autodiff of its two parts: the
    chunk-local part formed again and kept for its gradient, the recurrence
    run backward over the chunks from the states kept, the chunk-local
    gradient."""
    chunks, intra_vjp = jax.vjp(_intra, *map(_chunked, (q, k, v, g, beta)))

    def step(dS, xs):
        S, chunk, dO = xs
        _, vjp = jax.vjp(_inter, S, chunk)
        return vjp((dS, dO))

    _, dchunks = lax.scan(
        step, jnp.zeros_like(states[0]),
        (states, _by_chunk(chunks), _by_chunk(_chunked(do))), reverse=True)
    return tuple(map(_unchunked, intra_vjp(
        jax.tree.map(lambda a: jnp.moveaxis(a, 0, 2), dchunks))))


# ------------------------------------------------------------ the kernels
#
# One chunk of a few heads a grid step, the chunks of a head in turn: a
# chunk's tiles of q, k, v and g (CHUNK x D a head) and beta are loaded into
# VMEM once, everything :func:`_intra` forms on the way to its six results
# lives and dies there, and :func:`_inter`'s step follows on the spot, the
# state in a VMEM scratch.  The tile functions are that algebra on
# two-dimensional values (rows on sublanes, channels or columns on lanes), in
# what Mosaic lowers: static slices, broadcasts along one axis, sums along
# one axis, ``dot_general``.  The kernels read and write the sequence as the
# layer lays it out, (B, L, H * D), a block of CHUNK rows and a head's D
# lanes: no chunked copy of anything stands in HBM.

def _dot(a, b, contract=(1, 0), precision=None):
    """``a @ b`` or, by ``contract``, ``a @ b^T`` (1, 1) and ``a^T @ b`` (0,
    0): float32 out of operands of the inputs' type."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=precision, preferred_element_type=_F32)


_exact = functools.partial(_dot, precision=_EXACT)


def _each_block(a, row_of):
    """(CHUNK, n) -> (CHUNK, n): every row of a sub-block takes ``row_of``
    its block, (SUB, n) -> (1, n)."""
    return jnp.concatenate(
        [jnp.broadcast_to(row_of(a[lo:lo + SUB]), (SUB, a.shape[1]))
         for lo in range(0, CHUNK, SUB)], axis=0)


def _block_row(a, j: int):
    """Every row of a sub-block takes the block's row ``j``."""
    return _each_block(a, lambda block: block[j:j + 1])


def _block_sum(a):
    """Every row of a sub-block takes the sum of the block's rows."""
    return _each_block(a, lambda block: jnp.sum(block, axis=0, keepdims=True))


def _block_columns(a, row):
    """(CHUNK, CHUNK) -> (CHUNK, SUB): of each row the columns of its own
    sub-block (the diagonal blocks, one under the other)."""
    out = jnp.zeros((CHUNK, SUB), a.dtype)
    for lo in range(0, CHUNK, SUB):
        mine = (row[:, :SUB] >= lo) & (row[:, :SUB] < lo + SUB)
        out = jnp.where(mine, a[:, lo:lo + SUB], out)
    return out


def _decay_sums(g, row, col):
    """``G_r``, the sum of g over the tile's rows up to r: float32, a product
    with the lower-triangular ones as in :func:`_intra`."""
    return _exact((col <= row).astype(_F32), g)


def _transposed(a, row, col):
    """A row (1, CHUNK) as a column (CHUNK, 1) and the other way round:
    through the diagonal of a tile."""
    return jnp.sum(jnp.where(row == col, a, 0.0),
                   axis=0 if a.shape[1] == 1 else 1, keepdims=True)


def _tile_off_block(G, qf, kf, lo: int):
    """Row block ``lo`` against every earlier row, both sides taken against
    the block's first row: ``near`` (SUB, D), ``far`` (CHUNK, D) and the
    product's two operands before they are rounded, ``rows`` (k's then q's,
    2 SUB x D) and ``cols`` (CHUNK, D)."""
    ref = G[lo:lo + 1]
    near = jnp.exp(G[lo:lo + SUB] - ref)
    far = jnp.exp(jnp.minimum(ref - G, 0.0))
    rows = jnp.concatenate([kf[lo:lo + SUB] * near, qf[lo:lo + SUB] * near],
                           axis=0)
    return near, far, rows, kf * far


def _earlier(rows: int, lo: int):
    """(rows, CHUNK): the columns before row ``lo``.  (An iota of its own:
    Mosaic refuses a slice of one along sublanes.)"""
    return lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 1) < lo


def _tile_pair_decay(G, kf, j: int):
    """``exp(G_i - G_j)`` of every row i against row ``j`` of its own
    sub-block (1 where i < j: every use masks those), and ``k_j`` beside
    it."""
    return (jnp.exp(jnp.minimum(G - _block_row(G, j), 0.0)),
            _block_row(kf, j))


def _tile_inverse(N, row, col):
    """:func:`_unit_lower_inverse` on one tile: the diagonal blocks by
    forward substitution, the four at once (a block's row j is final after
    step j - 1 and is then added into the rows below it), then the two merges
    as products of whole tiles, N's blocks under the diagonal masked in."""
    Nc = _block_columns(N, row)
    X = (row == col).astype(_F32)
    for j in range(SUB - 1):
        X = X + Nc[:, j:j + 1] * _block_row(X, j)
    size = SUB
    while size < CHUNK:
        below = ((row // size) % 2 == 1) & (col // size == row // size - 1)
        X = X + _exact(_exact(X, jnp.where(below, N, 0.0)), X)
        size *= 2
    return X


def _tile_local(q, k, g, brow, P=None):
    """:func:`_intra` for one chunk of one head up to the inverse: q, k
    (CHUNK, D), g (CHUNK, D) float32, beta as a row (1, CHUNK) -> the decay
    sums ``G``, q and k in float32, ``M`` and ``P`` (masked, float32), beta as
    a column and the tile's two iotas.  Given ``P`` (``kda_bwd``, as
    ``kda_fwd`` rounded and kept it) only ``M`` is formed: half the lane
    sums of the diagonal blocks and half the rows of the products off it."""
    dt = q.dtype
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    bcol = _transposed(brow, row, col)
    G = _decay_sums(g, row, col)
    qf, kf = q.astype(_F32), k.astype(_F32)
    off = [jnp.zeros((2 * SUB, CHUNK), _F32)]
    for lo in range(SUB, CHUNK, SUB):
        _, _, rows, cols = _tile_off_block(G, qf, kf, lo)
        if P is not None:
            rows = rows[:SUB]                   # k's alone
        off.append(jnp.where(_earlier(rows.shape[0], lo),
                             _dot(rows.astype(dt), cols.astype(dt), (1, 1)),
                             0.0))
    # The diagonal blocks, column j of all four at a time.
    inside = col - row // SUB * SUB
    Md = jnp.zeros((CHUNK, CHUNK), _F32)
    Pd = jnp.zeros((CHUNK, CHUNK), _F32)
    for j in range(SUB):
        E, kj = _tile_pair_decay(G, kf, j)
        kE = kj * E
        Md = jnp.where(inside == j, jnp.sum(kf * kE, -1, keepdims=True), Md)
        if P is None:
            Pd = jnp.where(inside == j, jnp.sum(qf * kE, -1, keepdims=True),
                           Pd)
    M = (jnp.concatenate([o[:SUB] for o in off], axis=0)
         + jnp.where(col < row, Md, 0.0))
    if P is None:
        P = (jnp.concatenate([o[SUB:] for o in off], axis=0)
             + jnp.where(col <= row, Pd, 0.0))
    return G, qf, kf, M, P, bcol, row, col


def _tile_chunk(v, brow, X, local):
    """The rest of :func:`_intra` for the tile, from :func:`_tile_local`'s
    results and the inverse ``X = (I - N)^-1`` (CHUNK, CHUNK) float32, formed
    on the spot (``kda_fwd``) or read back (``kda_bwd``): the six results
    (``dC`` (1, D); ``P`` rounded to the inputs' type, or as given in it) and
    what the backward tile reads again."""
    dt = v.dtype
    G, qf, kf, M, P, bcol, row, col = local
    T = (X * brow).astype(dt)
    eG = jnp.exp(G)
    Ke = (kf * eG).astype(dt)
    GC = G[CHUNK - 1:]
    out = (_dot(T, Ke).astype(dt), _dot(T, v).astype(dt),
           (qf * eG).astype(dt), P.astype(dt),
           (kf * jnp.exp(GC - G)).astype(dt), jnp.exp(GC))
    return out, (G, qf, kf, M, X, T, eG, Ke, bcol, row, col)


def _tile_forward(q, k, v, g, brow):
    """:func:`_intra` for one chunk of one head: q, k, v (CHUNK, D), g
    (CHUNK, D) float32, beta as a row (1, CHUNK).  Returns the six results
    (``P`` the fourth) and the inverse, float32: the backward kernel reads
    both back."""
    local = _tile_local(q, k, g, brow)
    _, _, _, M, _, bcol, row, col = local
    # T = (I - N)^-1 Diag(beta), N = -Diag(beta) tril(M, -1) nilpotent.
    X = _tile_inverse(-bcol * M, row, col)
    return _tile_chunk(v, brow, X, local)[0], X


def _tile_backward(q, k, v, g, brow, saved, dW, dTV, dQe, dP, dKdec, ddC):
    """The gradient of :func:`_tile_forward`'s six results in q, k, v, g and
    beta (a row), the forward intermediates formed again.  Two rules carry
    it: a product of two decayed operands gives ``dG = rows * drows - cols *
    dcols``, whatever row both sides were taken against (``exp(G_i - G_j)``'s
    derivative in ``G_i`` is itself, in ``G_j`` its negative); the inverse
    ``X = (I - N)^-1`` gives ``N' = X^T X' X^T``, so the tile holds the
    inverse and no intermediate of it."""
    dt = q.dtype
    G, qf, kf, M, X, T, eG, Ke, bcol, row, col = saved
    f = lambda a: a.astype(_F32)
    dW, dTV = dW.astype(dt), dTV.astype(dt)
    # W = T Ke, TV = T v, T = X Diag(beta).
    dT = _dot(dW, Ke, (1, 1)) + _dot(dTV, v, (1, 1))
    dKe = _dot(T, dW, (0, 0))
    dv = _dot(T, dTV, (0, 0))
    dN = _exact(_exact(X, dT * brow, (0, 0)), X, (1, 1))
    # N = -Diag(beta) tril(M, -1): M is masked already.
    dbeta = (jnp.sum(dT * X, axis=0, keepdims=True) - _transposed(
        jnp.sum(dN * M, -1, keepdims=True), row, col))           # (1, CHUNK)
    dM = jnp.where(col < row, -bcol * dN, 0.0)
    dP = jnp.where(col <= row, f(dP), 0.0)
    # Ke = k e^G, Qe = q e^G, Kdec = k e^{G_C - G}, dC = e^{G_C}.
    decay = jnp.exp(G[CHUNK - 1:] - G)
    dkd = f(dKdec) * decay
    dq = f(dQe) * eG
    dk = dKe * eG + dkd
    dG = kf * (dKe * eG - dkd) + qf * dq
    last = (jnp.sum(kf * dkd, axis=0, keepdims=True)
            + ddC * jnp.exp(G[CHUNK - 1:]))
    dG = dG + jnp.where(row[:, :1] == CHUNK - 1, last, 0.0)
    # The off-diagonal blocks of M and P.
    zero = jnp.zeros((SUB, q.shape[1]), _F32)
    dq_rows, dk_rows, dG_rows = [zero], [zero], [zero]
    for lo in range(SUB, CHUNK, SUB):
        near, far, rows, cols = _tile_off_block(G, qf, kf, lo)
        dA = jnp.where(_earlier(2 * SUB, lo), jnp.concatenate(
            [dM[lo:lo + SUB], dP[lo:lo + SUB]], axis=0), 0.0).astype(dt)
        drows = _dot(dA, cols.astype(dt))                        # (2 SUB, D)
        dcols = _dot(dA, rows.astype(dt), (0, 0))                # (CHUNK, D)
        dk_rows.append(near * drows[:SUB])
        dq_rows.append(near * drows[SUB:])
        both = rows * drows
        dG_rows.append(both[:SUB] + both[SUB:])
        dk = dk + far * dcols
        dG = dG - cols * dcols
    dq = dq + jnp.concatenate(dq_rows, axis=0)
    dk = dk + jnp.concatenate(dk_rows, axis=0)
    dG = dG + jnp.concatenate(dG_rows, axis=0)
    # The diagonal blocks: ``da`` sums over j, ``dk_j`` over a block's rows.
    dMc, dPc = _block_columns(dM, row), _block_columns(dP, row)
    da_k, da_q, dk_j = (jnp.zeros_like(dG),) * 3
    for j in range(SUB):
        E, kj = _tile_pair_decay(G, kf, j)
        wM, wP = dMc[:, j:j + 1] * E, dPc[:, j:j + 1] * E
        da_k = da_k + wM * kj
        da_q = da_q + wP * kj
        dk_j = jnp.where(row[:, :1] % SUB == j,
                         _block_sum(kf * wM + qf * wP), dk_j)
    dq = dq + da_q
    dk = dk + da_k + dk_j
    dG = dG + kf * (da_k - dk_j) + qf * da_q
    dg = _exact((col >= row).astype(_F32), dG)
    return dq.astype(dt), dk.astype(dt), dv.astype(dt), dg, dbeta


def _tile_update(St, chunk):
    """``U`` of a chunk, and the state that enters it as the products read
    it.  The state is kept transposed (``St`` (D, D) float32, values on the
    rows), so that the decay a key channel runs along lanes."""
    W, TV = chunk[:2]
    Sd = St.astype(W.dtype)
    return (TV.astype(_F32) - _dot(W, Sd, (1, 1))).astype(W.dtype), Sd


def _tile_inter(St, chunk):
    """:func:`_inter` on one tile: the state that leaves the chunk and the
    chunk's output."""
    _, _, Qe, P, Kdec, dC = chunk
    U, Sd = _tile_update(St, chunk)
    O = _dot(Qe, Sd, (1, 1)) + _dot(P, U)
    return St * dC + _dot(U, Kdec, (0, 0)), O.astype(P.dtype)


def _tile_inter_bwd(St, dSt, chunk, dO):
    """The gradient of :func:`_tile_inter`: the cotangent of the state that
    leaves the chunk (``dSt``, transposed like it) and of the chunk's output
    -> that of the state that enters it and of the six chunk-local
    tensors."""
    W, _, Qe, P, Kdec, dC = chunk
    dt = W.dtype
    U, Sd = _tile_update(St, chunk)
    dSd = dSt.astype(dt)
    dU = _dot(P, dO, (0, 0)) + _dot(Kdec, dSd, (1, 1))
    dUd = dU.astype(dt)
    dchunk = (-_dot(dUd, Sd), dU, _dot(dO, Sd), _dot(dO, U, (1, 1)),
              _dot(U, dSd), jnp.sum(dSt * St, axis=0, keepdims=True))
    return (dSt * dC + _dot(dO, Qe, (0, 0)) - _dot(dUd, W, (0, 0))), dchunk


# Heads a grid step takes, one after the other in one body.  On the chip, at
# Kimi Linear's shapes, a layer forward and backward: 1 head 46.2 ms, 2 44.1,
# 4 43.9, 8 43.0; the body is traced and lowered once for each head, 0.6 s a
# program a head.  (Those were read while ``kda_bwd`` formed the whole tile
# again.  Since it reads the inverse and ``P``, at 2 heads: ``kda_fwd`` 16.0
# ms a layer as before, ``kda_bwd`` 27.2 -> 15.4 in the cell's step; alone,
# with beta's layout, 18.6 and 29.3 -> 17.5.  Two heads' tiles also fill the
# 128 lanes of a block of kept tiles.)
_HEADS_A_STEP = 2


def _heads_a_step(H: int) -> int:
    return _HEADS_A_STEP if H % _HEADS_A_STEP == 0 else 1


def _head_tiles(i: int, D: int, beta, *refs):
    """Head ``i`` of a grid step: its lanes of the sequence blocks, its
    lanes of the blocks of kept tiles, and its tiles and row of beta."""
    lanes = slice(i * D, (i + 1) * D)
    return (lanes, slice(i * CHUNK, (i + 1) * CHUNK),
            (*(ref[:, lanes] for ref in refs), beta[i:i + 1, :]))


def _kda_fwd_kernel(q, k, v, g, beta, o, states, inverse, pairs, St):
    @pl.when(pl.program_id(2) == 0)
    def _():
        St[...] = jnp.zeros_like(St)

    for i in range(St.shape[0]):
        lanes, mine, tile = _head_tiles(i, St.shape[1], beta, q, k, v, g)
        chunk, inverse[:, mine] = _tile_forward(*tile)
        pairs[:, mine] = chunk[3]
        states[i] = St[i]
        St[i], o[:, lanes] = _tile_inter(St[i], chunk)


def _kda_bwd_kernel(q, k, v, g, beta, states, inverse, pairs, do, dq, dk, dv,
                    dg, dbeta, dSt):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dSt[...] = jnp.zeros_like(dSt)

    for i in range(dSt.shape[0]):
        lanes, mine, tile = _head_tiles(i, dSt.shape[1], beta, q, k, v, g)
        q_i, k_i, v_i, g_i, brow = tile
        chunk, saved = _tile_chunk(
            v_i, brow, inverse[:, mine],
            _tile_local(q_i, k_i, g_i, brow, pairs[:, mine]))
        dSt[i], dchunk = _tile_inter_bwd(states[i], dSt[i], chunk,
                                         do[:, lanes])
        *grads, dbeta[i:i + 1, :] = _tile_backward(*tile, saved, *dchunk)
        for ref, value in zip((dq, dk, dv, dg), grads):
            ref[:, lanes] = value


def _takes_kernel(head_dim: int) -> bool:
    """The kernels run where a head's channels fill whole lanes."""
    return head_dim % 128 == 0


def _off_tpu() -> bool:
    """Off the TPU the kernels run through the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def _kernel_call(kernel, name: str, H: int, outs, *ins, reverse: bool,
                 interpret: bool):
    """One of the two kernels on the grid (B, H / heads a step, N), the
    chunks innermost and in turn, last to first if ``reverse``.  ``ins`` and
    ``outs`` name what each operand is: ``"sequence"`` (B, L, H * D), a block
    of CHUNK rows and a step's heads' lanes out of the layout the layer
    keeps; ``"beta"`` (B, L, H), laid out as (N, B, H / heads, heads, CHUNK),
    a block of a step's heads' rows; ``"states"`` (N, B, H, D, D), a step's
    heads' states; ``"tiles"`` (N, B, H / heads, CHUNK, heads * CHUNK), a
    step's heads' CHUNK x CHUNK tiles (of the inverse, of ``P``) side by
    side, so that a store fills whole lanes."""
    B, L, lanes = ins[0][1].shape
    D, N, heads = lanes // H, L // CHUNK, _heads_a_step(H)
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)
    a_step = lambda b, h, n: (at(n), b, h, 0, 0)
    specs = {
        "sequence": pl.BlockSpec((None, CHUNK, heads * D),
                                 lambda b, h, n: (b, at(n), h)),
        "beta": pl.BlockSpec((None, None, None, heads, CHUNK), a_step),
        "states": pl.BlockSpec((None, None, heads, D, D), a_step),
        "tiles": pl.BlockSpec((None, None, None, CHUNK, heads * CHUNK),
                              a_step)}
    shapes = {"sequence": (B, L, H * D),
              "beta": (N, B, H // heads, heads, CHUNK),
              "states": (N, B, H, D, D),
              "tiles": (N, B, H // heads, CHUNK, heads * CHUNK)}
    by_chunk = lambda beta: beta.reshape(
        B, N, CHUNK, H // heads, heads).transpose(1, 0, 3, 4, 2)
    return pl.pallas_call(
        kernel,
        grid=(B, H // heads, N),
        in_specs=[specs[kind] for kind, _ in ins],
        out_specs=[specs[kind] for kind, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shapes[kind], t) for kind, t in outs],
        scratch_shapes=[pltpu.VMEM((heads, D, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,              # the kernel's name in the compiled program
    )(*(by_chunk(a) if kind == "beta" else a for kind, a in ins))


# Jitted, so that a program's layers share one trace and one lowering of the
# body, and on (B, L, H * D) arguments: reshaped inside the call, the
# sequence arrays were laid out again on the way in (25 ms a step, on the
# chip), where the caller's reshape is its producers' own layout.
@functools.partial(jax.jit, static_argnames=("H", "interpret"))
def _kda_kernel(q, k, v, g, beta, *, H: int, interpret: bool):
    """``kda_fwd``: the whole recurrence forward, q, k, v, g (B, L, H * D)
    and beta (B, L, H), L whole chunks -> o (B, L, H * D), the chunk-entry
    states (N, B, H, D, D) float32, each transposed, and every tile's
    inverse ``(I - N)^-1``, float32, and ``P``, in the inputs' type, each (N,
    B, H / heads, CHUNK, heads * CHUNK), the tiles of a grid step's heads side
    by side."""
    return _kernel_call(
        _kda_fwd_kernel, "kda_fwd", H,
        (("sequence", q.dtype), ("states", _F32), ("tiles", _F32),
         ("tiles", q.dtype)),
        ("sequence", q), ("sequence", k), ("sequence", v), ("sequence", g),
        ("beta", beta), reverse=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("H", "interpret"))
def _kda_kernel_bwd(q, k, v, g, beta, states, inverse, pairs, do, *, H: int,
                    interpret: bool):
    """``kda_bwd``: the cotangent of o -> those of q, k, v, g and beta, in
    their shapes, from the inputs and what ``kda_fwd`` kept."""
    *grads, dbeta = _kernel_call(
        _kda_bwd_kernel, "kda_bwd", H,
        (("sequence", q.dtype),) * 3 + (("sequence", _F32), ("beta", _F32)),
        ("sequence", q), ("sequence", k), ("sequence", v), ("sequence", g),
        ("beta", beta), ("states", states), ("tiles", inverse),
        ("tiles", pairs), ("sequence", do), reverse=True,
        interpret=interpret)
    return (*grads, dbeta.transpose(1, 0, 4, 2, 3).reshape(beta.shape))


def _flat(a):
    """(B, L, H, D) -> (B, L, H * D): the layout the layer keeps."""
    return a.reshape(*a.shape[:2], -1)


# ------------------------------------------------------- the recurrence

@jax.custom_vjp
def _kda_chunks(q, k, v, g, beta):
    """q, k, v, g: (B, L, H, D), L whole chunks; beta: (B, L, H) -> o (B, L,
    H, D)."""
    return _kda_chunks_fwd(q, k, v, g, beta)[0]


def _kda_chunks_fwd(q, k, v, g, beta):
    """-> o and the residuals: the five inputs, then what the forward pass
    made and the backward pass reads, each under its name (the chunk-entry
    states; from the kernels also the tiles' inverses and ``P``)."""
    if _takes_kernel(q.shape[-1]):
        o, *kept = _kda_kernel(*map(_flat, (q, k, v, g)), beta,
                               H=q.shape[2], interpret=_off_tpu())
        o = o.reshape(q.shape)
    else:
        o, *kept = _plain_fwd(q, k, v, g, beta)
    o = checkpoint_name(o, KDA_RESIDUAL_NAMES[0])
    kept = map(checkpoint_name, kept, KDA_RESIDUAL_NAMES[1:])
    return o, (q, k, v, g, beta, *kept)


def _kda_chunks_bwd(saved, do):
    """One pass over the chunks, last to first, from the inputs and what the
    forward pass kept: no forward recurrence runs again, and in the kernels
    no tile is inverted again and no ``P`` formed again."""
    q, k, v, g, beta, *kept = saved
    if not _takes_kernel(do.shape[-1]):
        return _plain_bwd(q, k, v, g, beta, *kept, do)
    *grads, dbeta = _kda_kernel_bwd(
        *map(_flat, (q, k, v, g)), beta, *kept, _flat(do), H=do.shape[2],
        interpret=_off_tpu())
    return (*(a.reshape(do.shape) for a in grads), dbeta)


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
    L = q.shape[1]
    padded = lambda a: jnp.pad(
        a, ((0, 0), (0, -L % CHUNK)) + ((0, 0),) * (a.ndim - 2))
    return _kda_chunks(padded(q), padded(k), padded(v),
                       padded(g.astype(_F32)), padded(beta.astype(_F32)))[:, :L]


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
