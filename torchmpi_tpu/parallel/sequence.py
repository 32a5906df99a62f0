"""Sequence / context parallelism: ring attention and Ulysses.

Absent from the reference (SURVEY.md §5.7) but first-class here — the
reference's closest machinery is the chunked-ring schedule + communication
plan generator (lib/resources.cpp:588-678, lib/detail/README.md:1-48), and
**ring attention is exactly that schedule** applied to attention: each device
owns a sequence chunk of K/V and per step (a) computes block attention of its
local Q against the K/V chunk it currently holds while (b) passing the chunk
to its ring neighbour with ``ppermute`` — compute hides the ICI hop, the
same overlap discipline as the reference's reduce-scatter rings.

Three strategies over an ``sp`` mesh axis:

* :func:`ring_flash_attention` — the production path: K/V circulate the
  ring and every per-chunk block runs through the Pallas flash kernels
  (ops/flash_attention.py), with the f32 online-softmax state carried
  across ring steps by log-sum-exp combination.  Neither plane of the
  composition ever materializes a score matrix: per device the memory is
  O(L_local * block), not O(L_local^2) — the regime SP exists for.
* :func:`ring_attention` — the same ring schedule with a plain XLA einsum
  per block: numerically exact (f32 end to end), the correctness oracle
  the flash ring is tested against, and fine at short L_local.
* :func:`ulysses_attention` — two ``all_to_all``s swap sequence sharding for
  head sharding, run ordinary attention on full-length sequences for a head
  subset, swap back (the all-to-all alternative; needs heads % p == 0).

Both are written for ``shard_map`` bodies (arrays are per-device shards) and
are reverse-mode differentiable (ppermute/all_to_all transpose to the
opposite permutation, giving the backward ring).

Layout convention: (seq, heads, head_dim) per device; batch handled by vmap
or a leading dim via the wrappers in :func:`make_ring_attention`.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import AXIS_SP
from ..ops.flash_attention import (
    _auto_block as _flash_auto_block,
    flash_bwd_block,
    flash_fwd_block,
)

NEG_INF = -1e30


def _block_update(q, k, v, o, m, l, mask, scale):
    """One flash-style block accumulation step.

    q: (Lq, H, D); k, v: (Lk, KV, D) with KV | H — grouped-query attention
    is native: K/V arrive at their true head count (so the ring circulates
    1/``H//KV`` of the bytes) and are repeated to H *here*, block-locally,
    where the copy is transient.  The accumulators o/m/l and all softmax
    arithmetic are float32 regardless of the input dtype — matching
    full_attention's f32 softmax so ring and full paths agree in bf16.
    ``mask``: (Lq, Lk) boolean, True = attend.
    """
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    # scores: (H, Lq, Lk) via per-head contraction (MXU-friendly batched
    # GEMM), ACCUMULATED in f32 — an .astype after a bf16 einsum would
    # round the scores first (~6e-2 on unit-scale inputs) and break the
    # f32-end-to-end oracle contract.
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, :, :], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)                       # (H, Lq)
    m_new = jnp.maximum(m, m_blk.T)                   # (Lq, H)
    # exp with the new running max; fully-masked rows stay zero.
    p = jnp.exp(s - m_new.T[:, :, None])              # (H, Lq, Lk)
    p = jnp.where(mask[None, :, :], p, 0.0)
    corr = jnp.exp(m - m_new)                         # (Lq, H)
    l_new = l * corr + jnp.sum(p, axis=-1).T
    o_new = (o * corr[:, :, None]
             + jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)))
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over the full (distributed) sequence, shard_map body.

    Per-device shapes: q = (L_local, H, D); k, v = (L_local, KV, D) with
    KV | H (GQA: K/V circulate the ring at their true head count — 1/(H/KV)
    of the repeated-KV traffic and memory — and are expanded per block inside
    :func:`_block_update`).  Output (L_local, H, D).  The global sequence is
    the concatenation of shards in rank order.
    """
    p = lax.psum(1, axis)
    me = lax.axis_index(axis)
    Lq, H, D = q.shape
    Lk = k.shape[0]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    ring = [(i, (i + 1) % p) for i in range(p)]

    q_pos = me * Lq + jnp.arange(Lq)                  # global query positions

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        # The chunk we hold at step i originated at rank (me - i) mod p.
        src = (me - i) % p
        k_pos = src * Lk + jnp.arange(Lk)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((Lq, Lk), bool)
        o, m, l = _block_update(q, k_cur, v_cur, o, m, l, mask, scale)
        # Hand the chunk to the next rank while the next block computes —
        # the ring schedule of the reference's plans (detail/README.md:1-48).
        k_nxt = lax.ppermute(k_cur, axis, ring)
        v_nxt = lax.ppermute(v_cur, axis, ring)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((Lq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Lq, H), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(p))
    return (o / jnp.maximum(l, 1e-20)[:, :, None]).astype(q.dtype)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False, scale: Optional[float] = None) -> jax.Array:
    """Plain single-device attention, (L, H, D) layout — the correctness
    reference and the inner kernel for Ulysses.  GQA-native: K/V may arrive
    at KV | H heads and are expanded locally."""
    L, H, D = q.shape
    rep = H // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    # Scores and softmax in f32 regardless of input dtype — this is the
    # exactness contract the ring/flash paths are compared against (bf16
    # softmax drifts ~1e-2 at L=512, enough to mask or falsely flag ring
    # bugs in bf16 oracle comparisons).  The MXU takes bf16 inputs with
    # f32 accumulation either way, so this costs layout only.
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((L, k.shape[0]), bool))
        s = jnp.where(mask[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    causal: bool = False,
    scale: Optional[float] = None,
    local_impl: str = "einsum",
) -> jax.Array:
    """All-to-all sequence parallelism (Ulysses), shard_map body.

    Per-device in/out: q (L/p, H, D), k/v (L/p, KV, D) with KV | H
    (GQA-native: the K/V all-to-alls move KV/p head-groups — 1/(H/KV) of
    the repeated-KV traffic — and the local kernel expands locally).
    First all-to-all converts to full sequence / head subset; local
    attention runs on the full length; the second restores sequence
    sharding.  Needs ``H % p == 0`` and ``KV % p == 0`` (repeat K/V up to
    a multiple of p first otherwise).

    ``local_impl``: ``"einsum"`` (exact oracle; materializes the local
    (H/p, L, L) scores) or ``"flash"`` — the Pallas flash kernels on the
    gathered full-length sequence, extending the flash memory law to the
    a2a path: Ulysses' local L is the GLOBAL length, so at long context
    the einsum's score matrix is the full quadratic and flash is the only
    viable local kernel.
    """
    p = lax.psum(1, axis)   # static at trace time (axis sizes are known)
    if q.shape[1] % p or k.shape[1] % p:
        raise ValueError(
            f"ulysses_attention needs H % p == 0 and KV % p == 0 to split "
            f"heads over the a2a (got H={q.shape[1]}, KV={k.shape[1]}, "
            f"p={p}); repeat K/V up to a multiple of p first")
    # (L/p, H, D) -> (L, H/p, D): split heads, concat sequence.
    qh = lax.all_to_all(q, axis, split_axis=1, concat_axis=0, tiled=True)
    kh = lax.all_to_all(k, axis, split_axis=1, concat_axis=0, tiled=True)
    vh = lax.all_to_all(v, axis, split_axis=1, concat_axis=0, tiled=True)
    if local_impl == "flash":
        from ..ops.flash_attention import flash_attention as _flash

        oh = _flash(qh[None], kh[None], vh[None], causal=causal,
                    scale=scale)[0]
    elif local_impl == "einsum":
        oh = full_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        raise ValueError("local_impl must be 'einsum' or 'flash'")
    # (L, H/p, D) -> (L/p, H, D).
    return lax.all_to_all(oh, axis, split_axis=0, concat_axis=1, tiled=True)


# ------------------------------------------------- ring x flash composition
#
# The ring schedule above with the Pallas flash kernels as the per-chunk
# block primitive.  Forward: each step computes (o_chunk, lse_chunk) for the
# circulating K/V chunk and folds it into the running (o, lse) by exact
# log-sum-exp combination — the same online-softmax algebra _block_update
# does elementwise, but with the (Lq, Lk) scores living only in VMEM tiles
# inside the kernel.  Backward: a second ring pass; the *global* lse and
# delta = rowsum(do * o) re-normalize every chunk's probability block
# (FlashAttention-2 identity), so each step's dk/dv contribution is exact
# and accumulates in f32 carriers that circulate with their chunk, arriving
# home after the full lap.
#
# Causal structure: the chunk held at step i originated at rank (me - i) mod
# p, so i == 0 is the local diagonal block (causal mask), i >= 1 is either
# entirely past (me >= i: attend all, no mask) or entirely future (me < i:
# skip — lax.cond elides the kernels, mirroring the reference ring's
# skip-empty-chunk steps).  The loop is unrolled over the (static) ring size
# so each step picks the right kernel variant at trace time.


def _lse_combine(o, lse, o_b, lse_b):
    """Exact combination of two normalized attention partials (f32)."""
    lse_new = jnp.logaddexp(lse, lse_b)
    w, w_b = jnp.exp(lse - lse_new), jnp.exp(lse_b - lse_new)
    return o * w + o_b * w_b, lse_new


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _ring_flash_core(axis, causal, rep, block_q, block_k, interpret, scale,
                     qbh, kbh, vbh):
    """(BH, L, D) ring flash attention, shard_map body.  kbh/vbh are at the
    native KV head count (BKV = BH / rep rows) and circulate at that count;
    blocks expand them transiently."""
    o, _ = _ring_flash_fwd_loop(axis, causal, rep, block_q, block_k,
                                interpret, scale, qbh, kbh, vbh)
    return o.astype(qbh.dtype)


def _ring_flash_fwd_loop(axis, causal, rep, block_q, block_k, interpret,
                         scale, qbh, kbh, vbh):
    p = lax.psum(1, axis)
    me = lax.axis_index(axis)
    ring = [(r, (r + 1) % p) for r in range(p)]
    expand = ((lambda x: jnp.repeat(x, rep, axis=0)) if rep > 1
              else (lambda x: x))

    def block(k_c, v_c, is_diag):
        return flash_fwd_block(
            qbh, expand(k_c), expand(v_c), causal=causal and is_diag,
            block_q=block_q, block_k=block_k, interpret=interpret,
            scale=scale, out_dtype=jnp.float32)

    k_cur, v_cur = kbh, vbh
    o = lse = None
    for i in range(p):
        if i:
            k_cur = lax.ppermute(k_cur, axis, ring)
            v_cur = lax.ppermute(v_cur, axis, ring)
        if i == 0:
            o, lse = block(k_cur, v_cur, True)
        elif causal:
            def _attend(o=o, lse=lse, k_cur=k_cur, v_cur=v_cur):
                return _lse_combine(o, lse, *block(k_cur, v_cur, False))

            def _skip(o=o, lse=lse):
                return o, lse

            o, lse = lax.cond(me >= i, _attend, _skip)
        else:
            o, lse = _lse_combine(o, lse, *block(k_cur, v_cur, False))
    return o, lse


def _ring_flash_fwd(axis, causal, rep, block_q, block_k, interpret, scale,
                    qbh, kbh, vbh):
    o, lse = _ring_flash_fwd_loop(axis, causal, rep, block_q, block_k,
                                  interpret, scale, qbh, kbh, vbh)
    o = o.astype(qbh.dtype)
    return o, (qbh, kbh, vbh, o, lse)


def _ring_flash_bwd(axis, causal, rep, block_q, block_k, interpret, scale,
                    res, do):
    qbh, kbh, vbh, o, lse = res
    p = lax.psum(1, axis)
    me = lax.axis_index(axis)
    ring = [(r, (r + 1) % p) for r in range(p)]
    expand = ((lambda x: jnp.repeat(x, rep, axis=0)) if rep > 1
              else (lambda x: x))
    gsum = ((lambda g: g.reshape(-1, rep, *g.shape[1:]).sum(axis=1))
            if rep > 1 else (lambda g: g))

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # (BH, L, 1)

    def block(k_c, v_c, is_diag):
        dq_b, dk_b, dv_b = flash_bwd_block(
            qbh, expand(k_c), expand(v_c), do, lse, delta,
            causal=causal and is_diag, block_q=block_q, block_k=block_k,
            interpret=interpret, scale=scale, out_dtype=jnp.float32)
        return dq_b, gsum(dk_b), gsum(dv_b)

    dq = jnp.zeros(qbh.shape, jnp.float32)
    dk = jnp.zeros(kbh.shape, jnp.float32)
    dv = jnp.zeros(vbh.shape, jnp.float32)
    k_cur, v_cur = kbh, vbh
    for i in range(p):
        if i:
            k_cur = lax.ppermute(k_cur, axis, ring)
            v_cur = lax.ppermute(v_cur, axis, ring)
        if i == 0:
            dq_b, dk_b, dv_b = block(k_cur, v_cur, True)
            dq, dk, dv = dq + dq_b, dk + dk_b, dv + dv_b
        elif causal:
            def _attend(dq=dq, dk=dk, dv=dv, k_cur=k_cur, v_cur=v_cur):
                dq_b, dk_b, dv_b = block(k_cur, v_cur, False)
                return dq + dq_b, dk + dk_b, dv + dv_b

            def _skip(dq=dq, dk=dk, dv=dv):
                return dq, dk, dv

            dq, dk, dv = lax.cond(me >= i, _attend, _skip)
        else:
            dq_b, dk_b, dv_b = block(k_cur, v_cur, False)
            dq, dk, dv = dq + dq_b, dk + dk_b, dv + dv_b
        # dk/dv ride one hop behind their chunk's k/v (accumulate, then
        # move) — after the p-th hop each chunk's gradient is back home.
        dk = lax.ppermute(dk, axis, ring)
        dv = lax.ppermute(dv, axis, ring)
    return (dq.astype(qbh.dtype), dk.astype(kbh.dtype),
            dv.astype(vbh.dtype))


_ring_flash_core.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Ring attention with Pallas flash block kernels, shard_map body.

    Same contract as :func:`ring_attention` — per-device q (L_local, H, D),
    k/v (L_local, KV, D) with KV | H, output (L_local, H, D) — but per-chunk
    compute streams through the flash kernels, so device memory is
    O(L_local * block * heads), independent of the (L_local)^2 score size.
    """

    L, H, D = q.shape
    KV = k.shape[1]
    rep = H // KV
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    interpret = jax.default_backend() != "tpu"
    bq = _flash_auto_block(L) if block_q is None else block_q
    bk = _flash_auto_block(k.shape[0]) if block_k is None else block_k
    qbh = q.transpose(1, 0, 2)                       # (H, L, D)
    kbh = k.transpose(1, 0, 2)
    vbh = v.transpose(1, 0, 2)
    obh = _ring_flash_core(axis, causal, rep, bq, bk, interpret, scale,
                           qbh, kbh, vbh)
    return obh.transpose(1, 0, 2)


def ring_flash_attention_batched(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Batched form: q (B, L_local, H, D), k/v (B, L_local, KV, D).  Folds
    batch into the kernel grid's BH dimension (cheaper than vmap: one
    pallas_call, one ppermute per step for the whole batch)."""

    B, L, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    interpret = jax.default_backend() != "tpu"
    bq = _flash_auto_block(L) if block_q is None else block_q
    bk = _flash_auto_block(k.shape[1]) if block_k is None else block_k
    qbh = q.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    kbh = k.transpose(0, 2, 1, 3).reshape(B * KV, L, D)
    vbh = v.transpose(0, 2, 1, 3).reshape(B * KV, L, D)
    obh = _ring_flash_core(axis, causal, rep, bq, bk, interpret, scale,
                           qbh, kbh, vbh)
    return obh.reshape(B, H, L, D).transpose(0, 2, 1, 3)


# -------------------------------------------- zigzag (balanced causal) ring
#
# The contiguous-chunk causal ring is load-imbalanced: device d computes
# d+1 chunk-blocks, so device p-1 does p x device 0's work and the step
# time is the worst device's.  The zigzag layout splits the sequence into
# 2p chunks and gives device d the PAIR (d, 2p-1-d) — one early, one late —
# so every device computes exactly the same block area at every ring step:
#   * step 0 (own pair):   qa x ka diag + qb x ka full + qb x kb diag
#   * src < me ("past"):   [qa;qb] x ka   — one full (2Lc x Lc) block
#   * src > me ("future"): qb x [ka;kb]   — one full (Lc x 2Lc) block
# (qa = early chunk, ka/kb = the circulating pair's halves; the two
# non-diagonal cases are the SAME FLOP count, so the cond branches are
# balanced by construction).  All blocks run through the flash kernels
# with the same global-lse carry/backward as ring_flash above.


def zigzag_indices(L: int, p: int) -> np.ndarray:
    """Row order mapping a contiguous (L, ...) sequence into the zigzag
    layout: device d's shard is chunks (d, 2p-1-d) of the 2p-chunk split.
    ``x[zigzag_indices(L, p)]`` lays rows device-contiguously; invert with
    ``np.argsort``."""
    if L % (2 * p):
        raise ValueError(f"L={L} not divisible by 2p={2 * p}")
    Lc = L // (2 * p)
    order = []
    for d in range(p):
        order.extend(range(d * Lc, (d + 1) * Lc))
        order.extend(range((2 * p - 1 - d) * Lc, (2 * p - d) * Lc))
    return np.asarray(order)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _zigzag_core(axis, rep, block_q, block_k, scale, qbh, kbh, vbh):
    """(BH, 2*Lc, D) zigzag ring flash attention (causal), shard_map body.
    Rows are the device's (early, late) chunk pair; kbh/vbh at native KV
    head count."""
    o, _ = _zigzag_fwd_loop(axis, rep, block_q, block_k, scale,
                            qbh, kbh, vbh)
    return o.astype(qbh.dtype)


def _zz_block(q, k, v, rep, causal, block_q, block_k, scale):
    expand = (lambda x: jnp.repeat(x, rep, axis=0)) if rep > 1 else (lambda x: x)
    interpret = jax.default_backend() != "tpu"
    return flash_fwd_block(q, expand(k), expand(v), causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, scale=scale,
                           out_dtype=jnp.float32)


def _zigzag_fwd_loop(axis, rep, block_q, block_k, scale, qbh, kbh, vbh):
    p = lax.psum(1, axis)
    me = lax.axis_index(axis)
    ring = [(r, (r + 1) % p) for r in range(p)]
    Lc = qbh.shape[1] // 2
    qa, qb = qbh[:, :Lc], qbh[:, Lc:]
    blk = partial(_zz_block, rep=rep, block_q=block_q, block_k=block_k,
                  scale=scale)

    k_cur, v_cur = kbh, vbh
    o = lse = None
    for i in range(p):
        if i:
            k_cur = lax.ppermute(k_cur, axis, ring)
            v_cur = lax.ppermute(v_cur, axis, ring)
        ka, va = k_cur[:, :Lc], v_cur[:, :Lc]
        if i == 0:
            o_a, lse_a = blk(qa, ka, va, causal=True)
            o_b1, lse_b1 = blk(qb, ka, va, causal=False)
            o_b2, lse_b2 = blk(qb, k_cur[:, Lc:], v_cur[:, Lc:], causal=True)
            o_b, lse_b = _lse_combine(o_b1, lse_b1, o_b2, lse_b2)
            o = jnp.concatenate([o_a, o_b], axis=1)
            lse = jnp.concatenate([lse_a, lse_b], axis=1)
        else:
            def _past(o=o, lse=lse, ka=ka, va=va):
                # src < me: the whole local pair attends the early half.
                o_blk, lse_blk = blk(qbh, ka, va, causal=False)
                return _lse_combine(o, lse, o_blk, lse_blk)

            def _future(o=o, lse=lse, k_cur=k_cur, v_cur=v_cur):
                # src > me: only the late chunk attends — the full pair.
                o_blk, lse_blk = blk(qb, k_cur, v_cur, causal=False)
                o_pad = jnp.concatenate(
                    [jnp.zeros((o_blk.shape[0], Lc, o_blk.shape[2]),
                               o_blk.dtype), o_blk], axis=1)
                lse_pad = jnp.concatenate(
                    [jnp.full((lse_blk.shape[0], Lc, 1), NEG_INF,
                              lse_blk.dtype), lse_blk], axis=1)
                return _lse_combine(o, lse, o_pad, lse_pad)

            o, lse = lax.cond(me >= i, _past, _future)
    return o, lse


def _zigzag_fwd(axis, rep, block_q, block_k, scale, qbh, kbh, vbh):
    o, lse = _zigzag_fwd_loop(axis, rep, block_q, block_k, scale,
                              qbh, kbh, vbh)
    o = o.astype(qbh.dtype)
    return o, (qbh, kbh, vbh, o, lse)


def _zigzag_bwd(axis, rep, block_q, block_k, scale, res, do):
    qbh, kbh, vbh, o, lse = res
    p = lax.psum(1, axis)
    me = lax.axis_index(axis)
    ring = [(r, (r + 1) % p) for r in range(p)]
    Lc = qbh.shape[1] // 2
    qa, qb = qbh[:, :Lc], qbh[:, Lc:]
    expand = ((lambda x: jnp.repeat(x, rep, axis=0)) if rep > 1
              else (lambda x: x))
    gsum = ((lambda g: g.reshape(-1, rep, *g.shape[1:]).sum(axis=1))
            if rep > 1 else (lambda g: g))
    interpret = jax.default_backend() != "tpu"

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # (BH, 2Lc, 1)
    do_a, do_b = do[:, :Lc], do[:, Lc:]
    lse_a, lse_b = lse[:, :Lc], lse[:, Lc:]
    dl_a, dl_b = delta[:, :Lc], delta[:, Lc:]

    def bblk(q, k, v, dob, lseb, deltab, causal):
        dq_b, dk_b, dv_b = flash_bwd_block(
            q, expand(k), expand(v), dob, lseb, deltab, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            scale=scale, out_dtype=jnp.float32)
        return dq_b, gsum(dk_b), gsum(dv_b)

    dq = jnp.zeros(qbh.shape, jnp.float32)
    dk = jnp.zeros(kbh.shape, jnp.float32)
    dv = jnp.zeros(vbh.shape, jnp.float32)
    k_cur, v_cur = kbh, vbh

    def pad_front(x):
        return jnp.concatenate(
            [jnp.zeros((x.shape[0], Lc, x.shape[2]), x.dtype), x], axis=1)

    def pad_back(x):
        return jnp.concatenate(
            [x, jnp.zeros((x.shape[0], Lc, x.shape[2]), x.dtype)], axis=1)

    for i in range(p):
        if i:
            k_cur = lax.ppermute(k_cur, axis, ring)
            v_cur = lax.ppermute(v_cur, axis, ring)
        ka, va = k_cur[:, :Lc], v_cur[:, :Lc]
        if i == 0:
            dq_a, dk_a, dv_a = bblk(qa, ka, va, do_a, lse_a, dl_a, True)
            dq_b1, dk_b1, dv_b1 = bblk(qb, ka, va, do_b, lse_b, dl_b, False)
            dq_b2, dk_b2, dv_b2 = bblk(qb, k_cur[:, Lc:], v_cur[:, Lc:],
                                       do_b, lse_b, dl_b, True)
            dq = dq + jnp.concatenate([dq_a, dq_b1 + dq_b2], axis=1)
            dk = dk + jnp.concatenate([dk_a + dk_b1, dk_b2], axis=1)
            dv = dv + jnp.concatenate([dv_a + dv_b1, dv_b2], axis=1)
        else:
            def _past(dq=dq, dk=dk, dv=dv, ka=ka, va=va):
                dq_p, dk_p, dv_p = bblk(qbh, ka, va, do, lse, delta, False)
                return (dq + dq_p, dk + pad_back(dk_p), dv + pad_back(dv_p))

            def _future(dq=dq, dk=dk, dv=dv, k_cur=k_cur, v_cur=v_cur):
                dq_f, dk_f, dv_f = bblk(qb, k_cur, v_cur, do_b, lse_b,
                                        dl_b, False)
                return (dq + pad_front(dq_f), dk + dk_f, dv + dv_f)

            dq, dk, dv = lax.cond(me >= i, _past, _future)
        # Gradients ride one hop behind their chunk pair — home after p hops.
        dk = lax.ppermute(dk, axis, ring)
        dv = lax.ppermute(dv, axis, ring)
    return (dq.astype(qbh.dtype), dk.astype(kbh.dtype),
            dv.astype(vbh.dtype))


_zigzag_core.defvjp(_zigzag_fwd, _zigzag_bwd)


def zigzag_ring_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Balanced causal ring attention, shard_map body — per-device arrays
    in ZIGZAG layout: q (2*Lc, H, D) holding global chunks (d, 2p-1-d),
    k/v (2*Lc, KV, D) likewise.  Output in the same layout.  Causal only
    (the layout exists to balance the causal triangle; for non-causal the
    plain ring is already balanced)."""
    L2, H, D = q.shape
    rep = H // k.shape[1]
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    qbh = q.transpose(1, 0, 2)
    kbh = k.transpose(1, 0, 2)
    vbh = v.transpose(1, 0, 2)
    obh = _zigzag_core(axis, rep, block_q, block_k, scale, qbh, kbh, vbh)
    return obh.transpose(1, 0, 2)


def zigzag_ring_flash_attention_batched(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = AXIS_SP,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Batched zigzag body: q (B, 2*Lc, H, D), k/v (B, 2*Lc, KV, D) in the
    zigzag layout; batch folds into the kernel grid dim (same trick as
    :func:`ring_flash_attention_batched`)."""
    B, L2, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    qbh = q.transpose(0, 2, 1, 3).reshape(B * H, L2, D)
    kbh = k.transpose(0, 2, 1, 3).reshape(B * KV, L2, D)
    vbh = v.transpose(0, 2, 1, 3).reshape(B * KV, L2, D)
    obh = _zigzag_core(axis, rep, block_q, block_k, scale, qbh, kbh, vbh)
    return obh.reshape(B, H, L2, D).transpose(0, 2, 1, 3)


def make_zigzag_ring_attention(mesh: Mesh, axis: str = AXIS_SP):
    """Compiled balanced causal ring over ``mesh``: ``fn(q, k, v) -> o`` on
    global CONTIGUOUS (L, H, D) arrays — rows are permuted into the zigzag
    layout on the way in and back on the way out.  Each call pays a cross-
    device ACTIVATION reshard (measured 25-34 MB at the sp_volume
    geometry); training loops should use :func:`make_zigzag_layout`
    instead, which permutes 4-byte token ids at the data boundary and
    keeps activations zigzag-resident."""
    p = mesh.shape[axis]

    def fn(q, k, v):
        L = q.shape[0]
        idx = zigzag_indices(L, p)
        inv = np.argsort(idx)
        body = partial(zigzag_ring_flash_attention, axis=axis)
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
        return mapped(q[idx], k[idx], v[idx])[inv]

    return jax.jit(fn)


def make_zigzag_layout(mesh: Mesh, axis: str = AXIS_SP):
    """Zigzag-RESIDENT training layout — the llama integration's 4-byte-
    per-token discipline (models/llama.py make_loss_fn's 'ring-zigzag'
    path) as a public API: permute TOKEN IDS and positions into the zigzag
    row order once at the data boundary, run the whole network on zigzag-
    resident activations, and call the ring attention directly.  The
    per-call activation reshard :func:`make_zigzag_ring_attention` pays
    (three (L, H, D) gathers in + one out, 25-34 MB at the sp_volume
    geometry) never happens — the only permuted array is the int32 token
    stream (4 B/token) plus its positions.

    Returns ``(to_zigzag, from_zigzag, attention)``:

    * ``to_zigzag(x, row_axis=0)`` — permute a per-token array (token ids,
      targets, positions) into zigzag order along ``row_axis``.  Apply to
      MODEL INPUTS; feed ``to_zigzag(jnp.arange(L))`` as the positions so
      RoPE/position encodings see original coordinates.
    * ``from_zigzag(y, row_axis=0)`` — the inverse; apply to logits /
      final hidden states when original order matters (loss against
      zigzag-permuted targets needs no unpermute — means commute).
    * ``attention(q, k, v)`` — jitted balanced causal ring flash on
      zigzag-resident q (L, H, D), k/v (L, KV, D) sharded on ``axis``.
    """
    p = mesh.shape[axis]

    def to_zigzag(x, row_axis: int = 0):
        idx = zigzag_indices(x.shape[row_axis], p)
        return jnp.take(jnp.asarray(x), jnp.asarray(idx), axis=row_axis)

    def from_zigzag(y, row_axis: int = 0):
        inv = np.argsort(zigzag_indices(y.shape[row_axis], p))
        return jnp.take(jnp.asarray(y), jnp.asarray(inv), axis=row_axis)

    attention = jax.jit(shard_map(
        partial(zigzag_ring_flash_attention, axis=axis), mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)), out_specs=P(axis),
        check_vma=False))
    return to_zigzag, from_zigzag, attention


# ------------------------------------------------------------ jit wrappers

def make_ring_attention(mesh: Mesh, axis: str = AXIS_SP, causal: bool = False,
                        impl: str = "ring"):
    """Compiled sequence-parallel attention over ``mesh``.

    Returns ``fn(q, k, v) -> o`` on *global* (L, H, D) arrays sharded on the
    sequence axis; ``impl`` chooses 'ring_flash' (production), 'ring' (XLA
    einsum blocks — the exact oracle), or 'ulysses'.
    """
    if impl == "ring":
        body = partial(ring_attention, axis=axis, causal=causal)
    elif impl == "ring_flash":
        body = partial(ring_flash_attention, axis=axis, causal=causal)
    elif impl == "ulysses":
        body = partial(ulysses_attention, axis=axis, causal=causal)
    elif impl == "ulysses_flash":
        body = partial(ulysses_attention, axis=axis, causal=causal,
                       local_impl="flash")
    else:
        raise ValueError("impl must be 'ring', 'ring_flash', 'ulysses', "
                         "or 'ulysses_flash'")

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)
