"""Flash attention as a Pallas TPU kernel.

Online-softmax blocked attention (the same accumulation algebra as
parallel/sequence.py's ring steps, here tiled *within* a chip).  Canonical
streamed layout: the grid is (batch*head, q-blocks, k-blocks); Pallas
delivers one (block_q, D) Q tile and one (block_k, D) K/V tile per program
to VMEM, and the running (max, denom, accum) state lives in VMEM scratch
that persists across the sequentially-iterated k dimension — the (L, L)
score matrix never exists in HBM and the K/V working set is one tile, so
sequence length is bounded by HBM, not VMEM (pallas_guide.md: memory
hierarchy, MXU notes, scratch shapes).

Causal mode predicates whole K blocks above the diagonal off with
``pl.when``, skipping ~half the MXU work, and the index maps name no new
block for such a pair, so nothing is fetched for it either.  A pair that
runs runs one of two bodies (:func:`_when_unmasked`): where no mask edge
crosses it (every row sees every key: 120 of the 136 pairs a head at L =
16,384 on 1024-row tiles, 6 of 10 at L = 4,096; :func:`blocks_met` counts
them) the body without the in-block mask, and the masked one where the
diagonal or a band's left edge crosses it.  With a ``window`` (a row sees
its own key and the ``window - 1`` before it) the blocks wholly left of the
band go too, and the grid's inner dimension is only as long as the band is
wide in blocks (:func:`_band_k_map`): a windowed layer's cost grows with
``L * window``, not ``L * L``.

What a pair costs beside its products (PERF.md section 6, PR 42: the
compiler's own bundles, and a kernel A/B on the chip): a (1024, 1024)
float32 score block is 1,024 registers of 64, so every pass over it is the
compiler's spills and fills, and the forward kernel was bound by its one
vector-store slot, not by arithmetic.  Hence: q, k, v and do go to the MXU in
the dtype they were loaded in (:func:`_mxu`); the softmax scale is taken on
the (bq, D) Q tile, not on the block (:func:`_scaled`); and the forward keeps
its running max and denominator on all 128 lanes of a register
(:func:`_over`), which at a window layer's 512-row tile took the cross-lane
unit out of the critical path (``flash_fwd`` 10.3 -> 7.2 ms a Laguna sliding
layer).  At 1024-row tiles both kernels now stand within a tenth of what
their matmul issues alone take; the rest is per-program overhead.

A training step forms each score block once in the forward pass and once in
the backward pass: the backward is one kernel (see its section), and the
forward rule names its residuals (``RESIDUAL_NAMES``) so that a caller's
``jax.checkpoint`` can keep them and not replay the forward kernel.

Grouped queries: K and V stay at their own head count, (B * KV, L, D)
beside q's (B * H, L, D), and are never repeated to the query heads in HBM:
program ``b`` of a grid's first dimension (batch * query head) names K/V
head ``b // (H // KV)`` in its index maps, and ``flash_bwd`` sums dk and dv
over a group where they are formed, in a float32 block of the K/V head's
whole (L, D) that stays in VMEM while the group's heads pass
(:func:`operand_plan` counts what a call still moves for layout alone).

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter, keeping CPU tests exact.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The names the forward rule gives its two residuals that are not inputs
# (``jax.ad_checkpoint.checkpoint_name``).  A caller that wraps attention in
# ``jax.checkpoint`` saves these names in its policy, or the backward pass
# replays the whole forward kernel to get them back: a Pallas call is no dot,
# so ``dots_with_no_batch_dims_saveable`` alone keeps neither
# (``models/llama.py:_wrap_remat``).
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _pair_kind(causal: bool, q_start, bq: int, k_start, bk: int,
               window: Optional[int] = None, seq_len: int = 0):
    """``(runs, whole)`` of the pair of a ``bq``-row Q block at ``q_start``
    and a ``bk``-key K block at ``k_start``, on program ids or on numpy's
    integers alike.  It runs unless causal masking blanks all of it (the K
    block lies strictly above the diagonal of the Q block) or, with a
    ``window``, the keys all lie left of the band of its first row, or the Q
    block, counted from a band's first, lies past the ``seq_len`` rows there
    are.  It is ``whole`` where no mask edge crosses it: every row sees every
    key, the last key no later than the first row and, with a ``window``, the
    first key inside the band of the last row."""
    if not causal:
        return True, True
    runs = q_start + bq - 1 >= k_start
    whole = k_start + bk - 1 <= q_start
    if window is not None:
        inside = q_start < seq_len
        runs = runs & (k_start + bk - 1 > q_start - window) & inside
        whole = whole & (k_start > q_start + bq - 1 - window) & inside
    return runs, whole


def _when_unmasked(causal: bool, q_start, bq: int, k_start, compute,
                   window: Optional[int] = None, bk: int = 0,
                   seq_len: int = 0):
    """Run ``compute(masked)`` for a pair that runs (:func:`_pair_kind`):
    ``compute(False)``, the body without the in-block mask, where the pair is
    whole, ``compute(True)`` where the diagonal or the band's left edge
    crosses it, and neither where masking blanks the pair.  Two bodies under
    complementary scalar predicates: a whole pair pays for no mask."""
    if not causal:
        compute(False)
        return
    runs, whole = _pair_kind(causal, q_start, bq, k_start, bk, window,
                             seq_len)
    pl.when(whole)(lambda: compute(False))
    pl.when(runs & jnp.logical_not(whole))(lambda: compute(True))


def _unmasked_k(causal: bool, block_q: int, block_k: int, nk: int):
    """``(qi, ki) -> ki`` for a K-side index map: under causal masking, the
    K blocks past the last one that Q block ``qi`` meets name that last one
    again, so the pipeline fetches nothing for a pair that does not run."""
    if not causal:
        return lambda qi, ki: ki
    return lambda qi, ki: jnp.minimum(
        ki, jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1))


def _unmasked_q(causal: bool, block_q: int, block_k: int, nq: int):
    """``(ki, qi) -> qi`` for a Q-side index map: the Q blocks before the
    first one that K block ``ki`` meets name that first one."""
    if not causal:
        return lambda ki, qi: qi
    return lambda ki, qi: jnp.maximum(
        qi, jnp.minimum((ki * block_k) // block_q, nq - 1))


# A windowed call (causal, row i sees keys i - window < j <= i, q and k of one
# length) walks the band alone: its grid's inner dimension counts blocks from
# the band's first, which the two functions below name, and is as long as the
# widest band of any outer block (``_band_k_map``, ``_band_q_map``).

def _first_k(window: int, block_q: int, block_k: int, qi, lo=jnp.maximum):
    """The first K block with a key some row of Q block ``qi`` sees: the
    block of its first row's oldest key.  ``lo``: the maximum to use, jnp's
    on a program id, Python's on a block number."""
    return lo(qi * block_q - window + 1, 0) // block_k


def _first_q(block_q: int, block_k: int, ki):
    """The first Q block with a row that sees some key of K block ``ki``:
    the block of its first key's own row."""
    return (ki * block_k) // block_q


def _band_k_map(window: int, block_q: int, block_k: int, nq: int):
    """``((qi, ki) -> K block, width)`` of a windowed grid whose K side is
    innermost: program ``ki`` of Q block ``qi`` is the band's ``ki``-th K
    block, and past the diagonal's block that one again, so nothing is
    fetched for a pair that does not run; ``width`` is the most K blocks any
    Q block's band holds."""
    last = lambda qi: (qi * block_q + block_q - 1) // block_k
    width = max(last(i) - _first_k(window, block_q, block_k, i, max) + 1
                for i in range(nq))
    return (lambda qi, ki: jnp.minimum(
        _first_k(window, block_q, block_k, qi) + ki, last(qi))), width


def _band_q_map(window: int, block_q: int, block_k: int, nq: int, nk: int):
    """``((ki, qi) -> Q block, width)`` of a windowed grid whose Q side is
    innermost, as :func:`_band_k_map`: the last Q block K block ``ki``
    meets holds the last row its last key is the oldest of."""
    last = lambda ki, hi: hi(
        (ki * block_k + block_k + window - 2) // block_q, nq - 1)
    width = max(last(i, min) - _first_q(block_q, block_k, i) + 1
                for i in range(nk))
    return (lambda ki, qi: jnp.minimum(
        _first_q(block_q, block_k, ki) + qi, last(ki, jnp.minimum))), width


def _band_mask(s, q_start, k_start, window: Optional[int]):
    """Scores ``s`` (bq, bk) of the pair at ``(q_start, k_start)`` with
    what causal masking hides set to ``NEG_INF``: keys after the row's own
    and, with a ``window``, keys ``window`` or more before it (one unsigned
    comparison of row less column sees both edges: a key after the row's own
    wraps past every window)."""
    if window is None:
        rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(rows >= cols, s, NEG_INF)
    back = (lax.broadcasted_iota(jnp.int32, s.shape, 0)
            - lax.broadcasted_iota(jnp.int32, s.shape, 1)
            + (q_start - k_start))                  # keys back from its own
    return jnp.where(back.astype(jnp.uint32) < window, s, NEG_INF)


def _mxu(a, b, contract):
    """``a`` and ``b`` contracted over the axes ``contract`` names, one of
    each, into float32: an MXU product of the operands as they are.  A tile
    loaded in bfloat16 goes in with no conversion up on the VPU (the v5e has
    no bfloat16 VALU) and, where both operands are loaded ones, in half the
    matmul issues; a block computed in float32 (``p``, ``ds``) stays float32
    beside it, and the MXU's single pass rounds it to bfloat16 as it always
    did (the interpreter keeps it whole, as it always did)."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _scaled(q_ref, scale: float):
    """The Q tile times ``scale``, multiplied in float32 and rounded back to
    its own dtype: the softmax scale taken once on (bq, D) and not on every
    (bq, bk) score block the tile meets."""
    return (q_ref[:, :].astype(jnp.float32) * scale).astype(q_ref.dtype)


_STAT_LANES = 128     # a row statistic is kept on all lanes of one register


def _over(x, n: int):
    """A row statistic ``x`` (bq, _STAT_LANES), one value a row on every
    lane, as (bq, n): whole registers side by side, no lane broadcast."""
    if n % _STAT_LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == _STAT_LANES else pltpu.repeat(x, n // _STAT_LANES, axis=1)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                 causal: bool, scale: float, window: Optional[int] = None,
                 seq_len: int = 0):
    """One (batch*head, q-block, k-block) program.  Scratch (acc, m, l)
    persists across the k dimension (innermost, sequential on TPU).  With a
    ``window`` that dimension counts from the band's first K block
    (:func:`_first_k`)."""
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * bq
    k_start = ki * bk
    if window is not None:
        k_start = (_first_k(window, bq, bk, qi) + ki) * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)
        m_ref[:, :] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:, :] = jnp.zeros_like(l_ref)

    def _compute(masked: bool):
        s = _mxu(_scaled(q_ref, scale), k_ref[:, :], ((1,), (1,)))
        if masked:
            s = _band_mask(s, q_start, k_start, window)
        m_prev = m_ref[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if masked and window is not None:
            # A row may see no key of the band's first block: its max is
            # still NEG_INF there, and exp(NEG_INF - NEG_INF) would be 1.
            # (A row of a whole pair has seen a key.)
            p = jnp.exp(s - _over(jnp.where(m_new > NEG_INF, m_new, 0.0), bk))
        else:
            p = jnp.exp(s - _over(m_new, bk))
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :] = l_ref[:, :] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :] = m_new
        acc_ref[:, :] = (acc_ref[:, :] * _over(corr, acc_ref.shape[1])
                         + _mxu(p, v_ref[:, :], ((1,), (0,))))

    _when_unmasked(causal, q_start, bq, k_start, _compute, window, bk,
                   seq_len)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :], 1e-20)
        o_ref[:, :] = (acc_ref[:, :]
                       / _over(l, acc_ref.shape[1])).astype(o_ref.dtype)
        # log-sum-exp per query row — the single residual the backward
        # kernel needs to re-form p = exp(s - lse) block-by-block.
        lse_ref[:, :] = (m_ref[:, :] + jnp.log(l))[:, :1]


def _kv_head(rep: int):
    """``b -> K/V head`` of program ``b`` (batch * query head) where ``rep``
    query heads share one: ``b // rep``, the heads of a group adjacent; ``b``
    itself where each has its own."""
    return (lambda b: b) if rep == 1 else (lambda b: b // rep)


def _flash_bh(qbh, kbh, vbh, *, causal: bool, block_q: int, block_k: int,
              interpret: bool, scale: Optional[float] = None,
              out_dtype=None, window: Optional[int] = None):
    """(BH, L, D) flash attention forward; returns (o, lse).

    ``kbh``/``vbh`` may hold fewer heads than ``qbh``, (BH / rep, Lk, D):
    query head ``b`` then reads K/V head ``b // rep`` (grouped queries).
    They may have a different sequence length than ``qbh`` (the
    ring caller attends local Q against a circulating K/V chunk), and
    ``vbh`` a width of its own: q and k are ``D`` wide, v and o ``Dv``
    (latent attention's 192 and 128; no operand is padded to the other's).
    ``out_dtype`` overrides the output dtype (the ring carries its partial
    outputs in f32 across steps so per-step rounding doesn't accumulate).
    ``window`` (causal, ``Lk == L``): the band alone, see the module's text.
    """
    BH, L, D = qbh.shape
    Lk, Dv = vbh.shape[1:]
    kv_of = _kv_head(BH // kbh.shape[0])
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    out_dtype = qbh.dtype if out_dtype is None else out_dtype
    grid = (BH, L // block_q, Lk // block_k)
    kernel = functools.partial(_attn_kernel, causal=causal, scale=scale)
    k_of = _unmasked_k(causal, block_q, block_k, grid[2])
    if window is not None:
        k_of, width = _band_k_map(window, block_q, block_k, grid[1])
        grid = (BH, grid[1], width)
        kernel = functools.partial(kernel, window=window, seq_len=L)
    kv_block = lambda d: pl.BlockSpec(
        (None, block_k, d), lambda b, qi, ki: (kv_of(b), k_of(qi, ki), 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((BH, L, Dv), out_dtype),
                   jax.ShapeDtypeStruct((BH, L, 1), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            kv_block(D), kv_block(Dv),
        ],
        out_specs=(pl.BlockSpec((None, block_q, Dv), lambda b, qi, ki: (b, qi, 0)),
                   pl.BlockSpec((None, block_q, 1),
                                lambda b, qi, ki: (b, qi, 0))),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),  # output accumulator
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),    # running max
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),    # denominator
        ],
        interpret=interpret,
        name="flash_fwd",       # the kernel's name in the compiled program
    )(qbh, kbh, vbh)


# ------------------------------------------------------------------ backward
#
# FlashAttention-2 backward.  The forward's saved log-sum-exp re-forms the
# probability block p = exp(s - lse), and delta_i = rowsum(do_i * o_i) gives
# the softmax Jacobian: ds = p * (dp - delta), dp = do @ v^T.  Three
# gradients come out of one (q-block, k-block) pair: dv += p^T @ do,
# dk += ds^T @ q, dq += ds @ k.  dk/dv accumulate along q and dq along k, so
# no grid order keeps all three accumulators in one tile of VMEM.
#
#   * ``flash_bwd`` forms s, p, dp, ds ONCE per pair (5 matrix products):
#     grid (BH, k-blocks, q-blocks), q innermost; dk/dv accumulate in VMEM
#     scratch across q; dq is a float32 output block over the whole (Lq, D)
#     of one batch*head, so it stays in VMEM across that b's sweep, is
#     zeroed at the sweep's first program, added into a block of rows at a
#     time and written back once.  It needs 2 * Lq * D * 4 bytes of VMEM
#     (both pipeline buffers) on top of the tiles.  Where ``group`` query
#     heads share a K/V head (K and V hold BH / group heads), dk and dv are
#     float32 output blocks over that K/V head's whole (Lk, D) and (Lk, Dv),
#     which stay in VMEM while the group's heads pass in turn (their block
#     index is ``b // group``): zeroed at the group's first program, each
#     K block's sums added in when its sweep ends, written back once.  No
#     gradient is ever written a query head and summed in HBM.  Another
#     2 * Lk * (D + Dv) * 4 bytes.
#   * Where that does not fit ``_VMEM_BUDGET`` (a local chunk past about
#     80k rows at D=128), two streaming kernels whose working set is one
#     tile a side do the same work with the score block formed twice (7
#     products): ``flash_bwd_dq`` on grid (BH, q-blocks, k-blocks),
#     ``flash_bwd_dkv`` on (BH, k-blocks, q-blocks).
#
# Neither form ever holds the (L, L) score matrix.

# VMEM of one v5e TensorCore is 128 MiB, of which a kernel gets 16 MiB
# unless it states its need (``vmem_limit_bytes``).  The backward kernels
# state theirs, and ``flash_bwd`` is the form taken while its need stays
# under this budget.
_VMEM_BUDGET = 100 * 1024 * 1024


def _lanes(d: int) -> int:
    """The lanes a tile ``d`` wide is counted as in VMEM: past one register
    of 128 lanes, whole registers (a latent head's 192 takes 256, and the
    compiler refused ``flash_bwd`` the 47 MB that 192 gave it for the 52.7 it
    needed); up to 128, the width itself, as the budget was set against."""
    return d if d <= 128 else -(-d // 128) * 128


def _bwd_vmem_bytes(block_q: int, block_k: int, D: int, in_dtype,
                    out_dtype, Dv: Optional[int] = None) -> int:
    """VMEM a streaming backward kernel asks for, from its shapes (q, k and
    their gradients ``D`` wide, v, do and dv ``Dv``, which is ``D`` unless
    given): every streamed tile in both pipeline buffers (a (block_q, 1)
    column of lse or delta pads to 128 lanes), the float32 accumulators, and
    four float32 (block_q, block_k) blocks for s/p, dp/ds and the operands
    the compiler transposes; a width counts as the lanes it takes
    (:func:`_lanes`).  The masked and the unmasked body stand under
    complementary predicates and share those blocks: the count is one
    body's.  At 1024-wide blocks and D=Dv=128 in bfloat16 this gives 22 MiB,
    where the compiler's own count for ``flash_bwd`` is 15.3 beside its
    resident blocks (a Laguna full layer, 48 heads over 8; 15.8 with one
    body and v converted to float32: ``tests/test_aot_compile.py`` holds the
    count under that)."""
    isz, osz = jnp.dtype(in_dtype).itemsize, jnp.dtype(out_dtype).itemsize
    D, Dv = _lanes(D), _lanes(D if Dv is None else Dv)
    wide = max(block_q, block_k)
    tiles = ((block_q + block_k) * (D + Dv) * isz            # q, do; k, v
             + 2 * block_q * 128 * 4                         # lse, delta
             + wide * (D + Dv) * osz)                        # dq, or dk and dv
    return 2 * tiles + wide * (D + Dv) * 4 + 4 * block_q * block_k * 4


def _bwd_form(L: int, Lk: int, D: int, Dv: int, block_q: int, block_k: int,
              in_dtype, out_dtype, rep: int, budget: int):
    """``(form, vmem)``: which backward runs at those shapes, read from them
    and the ``budget``, and the VMEM it states.  ``"one"``: ``flash_bwd``
    with its float32 dq block of the whole (L, D) in both buffers;
    ``"group"`` (``rep`` > 1 query heads a K/V head): that with the K/V
    head's float32 dk and dv of the whole (Lk, D) and (Lk, Dv) beside it;
    ``"streamed"``: the two streaming kernels."""
    stream = _bwd_vmem_bytes(block_q, block_k, D, in_dtype, out_dtype, Dv)
    one = stream + 2 * L * _lanes(D) * 4
    group = one + 2 * Lk * (_lanes(D) + _lanes(Dv)) * 4
    if rep > 1 and group <= budget:
        return "group", group
    return ("one", one) if one <= budget else ("streamed", stream)


def _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
              k_start, *, masked: bool, scale: float,
              window: Optional[int] = None):
    """One (q-block, k-block) pair of the backward: the operands as loaded, q
    times ``scale`` (:func:`_scaled`), and the float32 blocks ``p`` and
    ``ds`` (bq, bk) every gradient is a product of.  The scale is in ``s``
    through q and in dk through ``ds^T q``; ``ds`` itself is without it, so
    dq takes it on its (bq, D) product.  ``masked``: a mask edge crosses the
    pair (:func:`_when_unmasked`)."""
    q, k, do = _scaled(q_ref, scale), k_ref[:, :], do_ref[:, :]
    s = _mxu(q, k, ((1,), (1,)))
    if masked:
        s = _band_mask(s, q_start, k_start, window)
    p = jnp.exp(s - lse_ref[:, 0][:, None])
    dp = _mxu(do, v_ref[:, :], ((1,), (1,)))
    ds = p * (dp - delta_ref[:, 0][:, None])
    return q, k, do, p, ds


def _attn_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                     causal: bool, scale: float,
                     window: Optional[int] = None, seq_len: int = 0,
                     group: int = 1):
    """One (batch*head, k-block, q-block) program of ``flash_bwd``.
    ``dq_ref`` is the whole (Lq, D) of this batch*head; with ``dq_ref``
    None the program is ``flash_bwd_dkv``'s.  With a ``window`` the q
    dimension counts from the band's first Q block (:func:`_first_q`).
    ``group`` > 1: ``dk_ref`` and ``dv_ref`` are the float32 whole (Lk, D)
    and (Lk, Dv) of the K/V head that ``group`` consecutive batch*heads
    share, and take the sum over them."""
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    q_start = qi * bq
    k_start = ki * bk
    if window is not None:
        q_start = (_first_q(bq, bk, ki) + qi) * bq

    if dq_ref is not None:
        @pl.when((ki == 0) & (qi == 0))
        def _init_dq():
            dq_ref[:, :] = jnp.zeros_like(dq_ref)

    if group > 1:
        @pl.when((pl.program_id(0) % group == 0) & (ki == 0) & (qi == 0))
        def _init_dkv():
            dk_ref[:, :] = jnp.zeros_like(dk_ref)
            dv_ref[:, :] = jnp.zeros_like(dv_ref)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    def _compute(masked: bool):
        q, k, do, p, ds = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, q_start, k_start,
                                    masked=masked, scale=scale, window=window)
        dv_acc[:, :] += _mxu(p, do, ((0,), (0,)))                  # p^T @ do
        dk_acc[:, :] += _mxu(ds, q, ((0,), (0,)))                  # ds^T @ q
        if dq_ref is not None:
            rows = pl.ds(pl.multiple_of(q_start, bq), bq)
            dq_ref[rows, :] += _mxu(ds, k, ((1,), (0,))) * scale   # ds @ k

    # No Q block wholly above the diagonal for this K block; no mask below it.
    _when_unmasked(causal, q_start, bq, k_start, _compute, window, bk,
                   seq_len)

    @pl.when(qi == nq - 1)
    def _finalize():
        if group > 1:
            rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)
            dk_ref[rows, :] += dk_acc[:, :]
            dv_ref[rows, :] += dv_acc[:, :]
        else:
            dk_ref[:, :] = dk_acc[:, :].astype(dk_ref.dtype)
            dv_ref[:, :] = dv_acc[:, :].astype(dv_ref.dtype)


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, acc_ref, *, causal: bool, scale: float,
                        window: Optional[int] = None, seq_len: int = 0):
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * bq
    k_start = ki * bk
    if window is not None:
        k_start = (_first_k(window, bq, bk, qi) + ki) * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    def _compute(masked: bool):
        _, k, _, _, ds = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, q_start, k_start,
                                   masked=masked, scale=scale, window=window)
        acc_ref[:, :] += _mxu(ds, k, ((1,), (0,)))

    _when_unmasked(causal, q_start, bq, k_start, _compute, window, bk,
                   seq_len)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[:, :] = (acc_ref[:, :] * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, **static):
    _attn_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
                     dk_ref, dv_ref, dk_acc, dv_acc, **static)


def _flash_bh_bwd(qbh, kbh, vbh, dobh, lse, delta, *, causal: bool,
                  block_q: int, block_k: int, interpret: bool,
                  scale: Optional[float] = None, out_dtype=None,
                  vmem_budget: int = _VMEM_BUDGET,
                  window: Optional[int] = None):
    """Backward against an externally-supplied (lse, delta).

    For single-chip flash, lse/delta come from this call's own forward; the
    ring caller instead passes the *globally combined* lse and the delta of
    the final output — then ``p = exp(s - lse)`` is the globally-normalized
    probability block and each per-chunk call yields that chunk's exact
    gradient contribution (the FlashAttention-2 identity carried across
    ring steps).

    One ``flash_bwd`` kernel where its resident dq block fits
    ``vmem_budget`` (read from the shapes, see the section comment), else
    the two streaming kernels.  With a ``window`` every grid's inner
    dimension is the band's (the module's text).

    ``kbh``/``vbh`` (BH / rep, Lk, D): grouped queries, dk and dv come at
    that head count too, the sum over a group's ``rep`` query heads taken
    in float32: in ``flash_bwd``'s VMEM where the K/V head's two float32
    blocks fit the budget beside dq's, else of the gradients a query head
    that the kernels write (:func:`_group_sum`)."""
    BH, L, D = qbh.shape
    Lk, Dv = vbh.shape[1:]
    rep = BH // kbh.shape[0]
    own, kv_of = _kv_head(1), _kv_head(rep)     # a program's head, its K/V's
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    dq_dtype = qbh.dtype if out_dtype is None else out_dtype
    dkv_dtype = kbh.dtype if out_dtype is None else out_dtype
    dkv_shape = (jax.ShapeDtypeStruct((BH, Lk, D), dkv_dtype),
                 jax.ShapeDtypeStruct((BH, Lk, Dv), dkv_dtype))
    dkv_scratch = [pltpu.VMEM((block_k, D), jnp.float32),
                   pltpu.VMEM((block_k, Dv), jnp.float32)]

    # Grid (BH, k-blocks, q-blocks): flash_bwd and flash_bwd_dkv.
    nq, nk = L // block_q, Lk // block_k
    static = dict(causal=causal, scale=scale)
    q_of, k_of = (_unmasked_q(causal, block_q, block_k, nq),
                  _unmasked_k(causal, block_q, block_k, nk))
    grid_q, grid_k = (BH, nk, nq), (BH, nq, nk)     # the innermost side's
    if window is not None:
        static.update(window=window, seq_len=L)
        q_of, q_width = _band_q_map(window, block_q, block_k, nq, nk)
        k_of, k_width = _band_k_map(window, block_q, block_k, nq)
        grid_q, grid_k = (BH, nk, q_width), (BH, nq, k_width)
    q_block2 = lambda d: pl.BlockSpec(
        (None, block_q, d), lambda b, ki, qi: (b, q_of(ki, qi), 0))
    k_block2 = lambda d, head=kv_of: pl.BlockSpec(
        (None, block_k, d), lambda b, ki, qi: (head(b), ki, 0))
    qrow2 = q_block2(1)
    in_specs2 = [q_block2(D), k_block2(D), k_block2(Dv), q_block2(Dv),
                 qrow2, qrow2]
    dkv_specs2 = (k_block2(D, own), k_block2(Dv, own))  # a query head's
    args = (qbh, kbh, vbh, dobh, lse, delta)

    form, vmem = _bwd_form(L, Lk, D, Dv, block_q, block_k, qbh.dtype,
                           dkv_dtype, rep, vmem_budget)
    if form != "streamed":
        whole = lambda rows, d, head: pl.BlockSpec(
            (None, rows, d), lambda b, ki, qi: (head(b), 0, 0))
        dkv_out, dkv_specs = dkv_shape, dkv_specs2
        if form == "group":     # the K/V head's own, float32, kept in VMEM
            dkv_out = (jax.ShapeDtypeStruct(kbh.shape, jnp.float32),
                       jax.ShapeDtypeStruct(vbh.shape, jnp.float32))
            dkv_specs = (whole(Lk, D, kv_of), whole(Lk, Dv, kv_of))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_attn_bwd_kernel, **static,
                              group=rep if form == "group" else 1),
            out_shape=(jax.ShapeDtypeStruct((BH, L, D), jnp.float32),
                       *dkv_out),
            grid=grid_q,
            in_specs=in_specs2,
            out_specs=(whole(L, D, own), *dkv_specs),
            scratch_shapes=dkv_scratch,
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=interpret,
            name="flash_bwd",
        )(*args)
        if form == "group":
            return (dq.astype(dq_dtype), dk.astype(dkv_dtype),
                    dv.astype(dkv_dtype))
        return dq.astype(dq_dtype), *_group_sum(rep, dk, dv)

    streaming = pltpu.CompilerParams(vmem_limit_bytes=vmem)
    q_block = lambda d: pl.BlockSpec((None, block_q, d),
                                     lambda b, qi, ki: (b, qi, 0))
    k_block = lambda d: pl.BlockSpec(
        (None, block_k, d), lambda b, qi, ki: (kv_of(b), k_of(qi, ki), 0))
    qrow = q_block(1)
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, **static),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), dq_dtype),
        grid=grid_k,
        in_specs=[q_block(D), k_block(D), k_block(Dv), q_block(Dv), qrow,
                  qrow],
        out_specs=q_block(D),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=streaming,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, **static),
        out_shape=dkv_shape,
        grid=grid_q,
        in_specs=in_specs2,
        out_specs=dkv_specs2,
        scratch_shapes=dkv_scratch,
        compiler_params=streaming,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)
    return dq, *_group_sum(rep, dk, dv)


def _group_sum(rep: int, *grads):
    """Gradients a query head, (BH, Lk, d) each, to their K/V heads', (BH /
    rep, Lk, d): the sum over a group's ``rep`` adjacent heads in float32,
    rounded once.  The arrays themselves where every head has its own."""
    if rep == 1:
        return grads
    return tuple(
        jnp.sum(g.reshape(-1, rep, *g.shape[1:]), axis=1,
                dtype=jnp.float32).astype(g.dtype) for g in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _flash_core(causal, block_q, block_k, interpret, scale, window,
                qbh, kbh, vbh):
    o, _ = _flash_bh(qbh, kbh, vbh, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret, scale=scale,
                     window=window)
    return o


def _flash_core_fwd(causal, block_q, block_k, interpret, scale, window,
                    qbh, kbh, vbh):
    o, lse = _flash_bh(qbh, kbh, vbh, causal=causal, block_q=block_q,
                       block_k=block_k, interpret=interpret, scale=scale,
                       window=window)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (qbh, kbh, vbh, o, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, scale, window, res,
                    dobh):
    qbh, kbh, vbh, obh, lse = res
    # delta_i = rowsum(do_i * o_i): tiny (BH, L) f32, computed outside Pallas.
    delta = jnp.sum(dobh.astype(jnp.float32) * obh.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # (BH, L, 1)
    return _flash_bh_bwd(qbh, kbh, vbh, dobh, lse, delta, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, scale=scale, window=window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _auto_block(L: int, cap: int = 1024) -> int:
    """Default tile size: the whole sequence when L <= cap (a single block
    is always tile-legal), else the largest power-of-two divisor of L up to
    ``cap``.  Big tiles keep the MXU fed and amortize the per-program
    overhead (some 800 cycles of 6,000 a pair at 1024): measured on v5e at
    L=8192 (fwd+bwd, H=32, D=128) when the backward was two kernels,
    128-blocks reached 12 TFLOP/s, 512 62, 1024 85; the one ``flash_bwd``
    at L=16,384 executes 155 of the chip's 197 on 1024-row tiles and 105 on
    the window layers' 512 (PERF.md section 6, PR 40); past 1024 the VMEM
    working set no longer fits.  Low-2-adic long sequences (no >=128 tile
    divides them) raise rather than silently degrading to sliver tiles."""
    if L <= cap:
        return L
    b = cap
    while b > 1 and L % b:
        b //= 2
    if b < 128:
        raise ValueError(
            f"seq len {L} has no power-of-two tile in [128, {cap}]; pad the "
            f"sequence or pass block_q/block_k explicitly")
    return b


def _window_block(L: int, window: Optional[int]) -> int:
    """Tile size of a causal call, from the length and the window alone;
    without a window, :func:`_auto_block`'s.  A
    pair of ``b``-row blocks is executed whole, so a Q block's band of
    ``window + b - 1`` keys costs ``ceil((window - 1) / b) + 1`` blocks:
    at a 512-key window 4 times the band's scores at 1024, 2 at 512, 1.5 at
    256, on tiles that run the slower the smaller they are (``_auto_block``).
    The largest power of two up to the window, within [256, 1024] and
    dividing ``L``.  Measured on v5e at ``L`` = 16,384, ``window`` 512, 72
    heads of 128, forward and backward: 39.0 ms at 256, 29.9 at 512, 38.6 at
    1024, where the causal kernels take 128.2 (PERF.md section 6, PR 40)."""
    if window is None:
        return _auto_block(L)
    if L <= 256:
        return L
    b = 256
    while b * 2 <= min(window, 1024) and L % (b * 2) == 0:
        b *= 2
    return b if L % b == 0 else _auto_block(L)


def blocks_met(L: int, window: Optional[int] = None) -> dict:
    """What a causal call of :func:`flash_attention` at length ``L`` runs,
    from its shapes: the ``tile`` it chooses, its ``q_blocks``, the forward
    grid's inner dimension (``grid_inner``) and the K blocks a Q block meets,
    the most any does and the mean (``k_blocks_max``, ``k_blocks_mean``),
    counted as the distinct blocks the kernel's own index map names over
    that dimension: a pair it names no new block for is neither fetched nor
    run.  ``edge_blocks_mean``: of those, the mean number that run the
    masked body, the diagonal or the band's left edge crossing them, by the
    kernel's own predicates (:func:`_pair_kind`); the others run the body
    without a mask.  A counter for outside the step."""
    if window is not None and window >= L:
        window = None
    b = _window_block(L, window)
    n = L // b
    qi, ki = np.arange(n)[:, None], np.arange(n)[None]
    if window is None:
        k_of, inner = _unmasked_k(True, b, b, n), n
    else:
        k_of, inner = _band_k_map(window, b, b, n)
        ki = _first_k(window, b, b, qi, np.maximum) + np.arange(inner)[None]
    named = np.asarray(k_of(qi, np.arange(inner)[None]))
    met = [len(set(row)) for row in named.tolist()]
    runs, whole = _pair_kind(True, qi * b, b, ki * b, b, window, L)
    assert runs.sum(axis=1).tolist() == met     # it runs what it names
    return {"tile": b, "q_blocks": n, "grid_inner": inner,
            "k_blocks_max": max(met), "k_blocks_mean": float(np.mean(met)),
            "edge_blocks_mean": float(np.mean((runs & ~whole).sum(axis=1)))}


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Blocked attention of q (B, L, H, D) with k (B, L, KV, D) and v (B, L,
    KV, Dv) at their own head count, ``H % KV == 0`` (grouped queries: query
    head h reads K/V head ``h // (H // KV)``, which is never repeated in
    HBM); v may have a width of its own, and o, (B, L, H, Dv), then has it
    too.
    ``window`` (with ``causal``): row i sees keys ``i - window < j <= i``,
    its own and the ``window - 1`` before it; the kernels run the blocks
    that hold such a pair and fetch no other, and the tile comes from
    ``(L, window)`` (:func:`_window_block`).  None: every key up to its own.

    Differentiable: a ``custom_vjp`` pairs the forward with a
    FlashAttention-2 style backward Pallas kernel (``flash_bwd``: all three
    gradients from one pass over the score blocks, dk and dv summed over a
    group of query heads inside it), so training never materializes the (L,
    L) score matrix either.  Under ``jax.checkpoint``,
    save ``RESIDUAL_NAMES`` in the policy or the forward kernel runs again
    in the backward pass.  Sequence length must be divisible by the (clamped)
    block sizes; callers pad or pick L accordingly.  Off-TPU the interpreter
    path keeps the semantics identical for tests.
    """
    B, L, H, D = q.shape
    KV, Dv = v.shape[2:]
    if k.shape != (B, L, KV, D) or v.shape[:2] != (B, L) or H % KV:
        raise ValueError(
            f"q (B, L, H, D) takes k (B, L, KV, D) and v (B, L, KV, Dv) with "
            f"H a multiple of KV (got q {q.shape}, k {k.shape}, v {v.shape})")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is the causal band's width in keys, the "
                         f"row's own among them: causal=True and window >= 1 "
                         f"(got causal={causal}, window={window})")
    if window is not None and window >= L:
        window = None               # every row sees all it may: plain causal
    block_q = (_window_block(L, window) if block_q is None
               else min(block_q, L))
    block_k = (_window_block(L, window) if block_k is None
               else min(block_k, L))
    if L % block_q or L % block_k:
        raise ValueError(f"seq len {L} not divisible by blocks "
                         f"({block_q}, {block_k})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # (B, L, heads, d) -> (B*heads, L, d), each array at its own head count
    bh = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, L, x.shape[3])
    obh = _flash_core(causal, block_q, block_k, interpret,
                      None if scale is None else float(scale), window,
                      bh(q), bh(k), bh(v))
    return obh.reshape(B, H, L, Dv).transpose(0, 2, 1, 3)


def operand_plan(B: int, L: int, H: int, KV: int, D: int, Dv: int,
                 dtype=jnp.bfloat16, window: Optional[int] = None) -> dict:
    """What a causal call of :func:`flash_attention` at those shapes, forward
    and backward, writes to HBM for its operands' layout alone, from the
    shapes: ``kv_repeat``, the query heads a K/V head serves;
    ``repeated_bytes``, what K and V, dk and dv take at the query heads'
    count beyond their own: 0 for K and V always, and for dk and dv while the
    group's float32 blocks fit ``flash_bwd``'s VMEM beside dq
    (``dkv_in_kernel``; else they are written a query head and summed);
    ``copied_bytes``, q, k, v and o forward and do, dq, dk and dv backward,
    each once between (L, heads, d) and the kernels' (heads, L, d): an upper
    bound, the compiler folds such a copy into the pass that makes or reads
    the array where it can.  A counter for outside the step; under a remat
    policy that replays a layer's forward the forward's half runs twice."""
    tile = _window_block(L, None if window is None or window >= L else window)
    form, _ = _bwd_form(L, L, D, Dv, tile, tile, dtype, dtype, H // KV,
                        _VMEM_BUDGET)
    head = jnp.dtype(dtype).itemsize * B * L * (D + Dv)   # q and o, k and v
    return {"kv_repeat": H // KV, "dkv_in_kernel": form == "group",
            "repeated_bytes": (H - KV) * head if form != "group" else 0,
            "copied_bytes": 2 * (H + KV) * head}


# ------------------------------------------------------- ring building blocks
#
# Per-block entry points for ring attention (parallel/sequence.py): each ring
# step runs local Q against the circulating K/V chunk through these kernels,
# and the online-softmax carry continues *across* steps via the returned lse
# (forward: log-sum-exp combine of per-chunk partials; backward: the global
# lse re-normalizes every chunk's probability block).  The distributed ring
# thereby inherits the kernel's memory law — no (L, L) score matrix at any
# scale, which is the property the ring schedule exists to preserve
# (reference: lib/resources.cpp:588-678 circulates chunks for exactly this
# streaming reason).


def _resolve_blocks(Lq: int, Lk: int, block_q: Optional[int],
                    block_k: Optional[int]):
    """Clamp + validate tile sizes against the actual sequence lengths —
    a non-dividing block would silently truncate the Pallas grid and leave
    uncovered output rows unwritten."""
    block_q = _auto_block(Lq) if block_q is None else min(block_q, Lq)
    block_k = _auto_block(Lk) if block_k is None else min(block_k, Lk)
    if Lq % block_q or Lk % block_k:
        raise ValueError(f"seq lens ({Lq}, {Lk}) not divisible by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def flash_fwd_block(qbh, kbh, vbh, *, causal: bool,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None,
                    out_dtype=None):
    """One attention block: (BH, Lq, D) Q against a (BH, Lk, D) K/V chunk.
    Returns ``(o, lse)`` with o normalized by this block's own denominator
    and lse = m + log(l) per query row — everything a caller needs to
    log-sum-exp-combine partials from several chunks exactly."""
    block_q, block_k = _resolve_blocks(qbh.shape[1], kbh.shape[1],
                                       block_q, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_bh(qbh, kbh, vbh, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret, scale=scale,
                     out_dtype=out_dtype)


def flash_bwd_block(qbh, kbh, vbh, dobh, lse, delta, *, causal: bool,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None,
                    out_dtype=None):
    """Gradient contribution of one K/V chunk given the *global* lse and
    delta = rowsum(do * o_final).  Returns (dq, dk, dv) for this chunk."""
    block_q, block_k = _resolve_blocks(qbh.shape[1], kbh.shape[1],
                                       block_q, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_bh_bwd(qbh, kbh, vbh, dobh, lse, delta, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, scale=scale,
                         out_dtype=out_dtype)
