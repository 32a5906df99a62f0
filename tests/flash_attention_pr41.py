"""The flash kernels as they stood at PR 41, frozen: one body a kernel, the
in-block mask on every pair of a causal call, q, k, v and do converted to
float32 before the products.  ``tests/test_ops.py`` holds the kernels of
``torchmpi_tpu/ops/flash_attention.py`` to these, to the bit where nothing
but the mask's select was taken from a pair.  A copy of that file's kernels,
``_flash_bh`` and ``_flash_bh_bwd`` at commit 9a3bcdd, nothing edited; not a
test module, and nothing of the program imports it."""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

def _when_unmasked(causal: bool, q_start, bq: int, k_start, compute,
                   window: Optional[int] = None, bk: int = 0,
                   seq_len: int = 0):
    """Run ``compute`` unless causal masking blanks the whole pair (the K
    block lies strictly above the diagonal of the Q block) or, with a
    ``window``, the ``bk`` keys all lie left of the band of its first row, or
    the Q block, counted from a band's first, lies past the ``seq_len``
    rows there are."""
    if causal and window is not None:
        pl.when((q_start + bq - 1 >= k_start)
                & (k_start + bk - 1 > q_start - window)
                & (q_start < seq_len))(compute)
    elif causal:
        pl.when(q_start + bq - 1 >= k_start)(compute)
    else:
        compute()


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

    def _compute():
        q = q_ref[:, :].astype(jnp.float32)
        k = k_ref[:, :].astype(jnp.float32)
        v = v_ref[:, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _band_mask(s, q_start, k_start, window)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        if window is None:
            p = jnp.exp(s - m_new[:, None])
        else:
            # A row may see no key of the band's first block: its max is
            # still NEG_INF there, and exp(NEG_INF - NEG_INF) would be 1.
            p = jnp.exp(s - jnp.where(m_new > NEG_INF, m_new, 0.0)[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new
        acc_ref[:, :] = (acc_ref[:, :] * corr[:, None]
                         + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                               preferred_element_type=jnp.float32))

    _when_unmasked(causal, q_start, bq, k_start, _compute, window, bk,
                   seq_len)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[:, :] = (acc_ref[:, :] / l[:, None]).astype(o_ref.dtype)
        # log-sum-exp per query row — the single residual the backward
        # kernel needs to re-form p = exp(s - lse) block-by-block.
        lse_ref[:, 0] = m_ref[:, 0] + jnp.log(l)


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
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
        ],
        interpret=interpret,
        name="flash_fwd",       # the kernel's name in the compiled program
    )(qbh, kbh, vbh)


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
    given): every streamed tile in
    both pipeline buffers (a (block_q, 1) column of lse or delta pads to 128
    lanes), the float32 accumulators, and four float32 (block_q, block_k)
    blocks for s/p, dp/ds and the operands the compiler transposes; a width
    counts as the lanes it takes (:func:`_lanes`).  At 1024-wide blocks and D=Dv=128 in bfloat16 this gives 22 MiB, where the
    compiler's own count for ``flash_bwd`` is 16.6 beside its dq block."""
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
              k_start, *, causal: bool, scale: float,
              window: Optional[int] = None):
    """One (q-block, k-block) pair of the backward: the float32 operands and
    the blocks ``p`` and ``ds`` (bq, bk) every gradient is a product of."""
    q = q_ref[:, :].astype(jnp.float32)
    k = k_ref[:, :].astype(jnp.float32)
    v = v_ref[:, :].astype(jnp.float32)
    do = do_ref[:, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = _band_mask(s, q_start, k_start, window)
    p = jnp.exp(s - lse_ref[:, 0][:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[:, 0][:, None]) * scale
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

    def _compute():
        q, k, do, p, ds = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, q_start, k_start,
                                    causal=causal, scale=scale, window=window)
        dv_acc[:, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # p^T @ do
        dk_acc[:, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # ds^T @ q
        if dq_ref is not None:
            rows = pl.ds(pl.multiple_of(q_start, bq), bq)
            dq_ref[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)                # ds @ k

    # Skip Q blocks wholly above the diagonal for this K block.
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

    def _compute():
        _, k, _, _, ds = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, q_start, k_start,
                                   causal=causal, scale=scale, window=window)
        acc_ref[:, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_unmasked(causal, q_start, bq, k_start, _compute, window, bk,
                   seq_len)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[:, :] = acc_ref[:, :].astype(dq_ref.dtype)


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
