"""The grouped matmul of a weight gradient that ADDS into sums it is given.

``tgmm_add(lhs, rhs, group_sizes, sums)``: rows of ``lhs`` (M, K) and ``rhs``
(M, N) lie in contiguous segments of ``group_sizes[g]`` rows for group g;
returns ``sums`` (G, K, N) float32 with ``lhs[seg g]^T @ rhs[seg g]`` added to
``sums[g]``, in float32 and in place (the operand is aliased to the result).

It is megablox's ``tgmm`` (``jax.experimental.pallas.ops.tpu.megablox``, whose
group metadata it calls) but for one thing: a group that has no rows is not
visited.  Megablox's kernel visits every group, because the output it writes
has to be zeroed for the empty ones; called with ``existing_out=`` it
therefore reads and writes every group's sum, 16 x 2304 x 896 float32 twice a
call (264 MB) where a block of the ``ep`` path's backward pass
(``models/llama.py:_held_swiglu_bwd``) has rows for 4 to 6 of 16 experts.
With the sums aliased, a group that is not visited simply keeps its sum, and
a call with no rows at all moves nothing and returns the sums to the bit.
Each visited (group, K tile, N tile) starts its VMEM accumulator from the
sum's tile and writes it back once.

Rows past the groups' total (a block that is valid in part) belong to no
group and are not read.  In interpret mode off the TPU, as the kernels it
stands beside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox


def _group_rows(meta, at, tm, width):
    """The rows of row tile ``m_tile_ids[at]`` that belong to group
    ``group_ids[at]``, as a (tm, width) mask."""
    offsets, group_ids, m_tile_ids = meta
    group = group_ids[at]
    row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + m_tile_ids[at] * tm
    return (row >= offsets[group]) & (row < offsets[group + 1])


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def tgmm_add(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
             sums: jax.Array, tiling=(512, 1024, 1024),
             interpret: bool = False) -> jax.Array:
    """``sums[g] + lhs[seg g]^T @ rhs[seg g]`` for every group g with rows,
    ``sums[g]`` itself for the others.  ``lhs`` (M, K) and ``rhs`` (M, N) of
    one dtype, M whole row tiles of ``tiling[0]``; ``group_sizes`` (G,) int32;
    ``sums`` (G, K, N) float32.  ``tiling`` is (rows, K, N) of one grid step;
    ragged K and N tiles are the block machinery's."""
    (M, K), N = lhs.shape, rhs.shape[1]
    tm, tk, tn = tiling[0], min(tiling[1], K), min(tiling[2], N)
    if rhs.shape[0] != M or M % tm:
        raise ValueError(f"{lhs.shape} and {rhs.shape}: rows must agree and "
                         f"be whole tiles of {tm}")
    if sums.shape != (group_sizes.shape[0], K, N) or sums.dtype != jnp.float32:
        raise ValueError(f"sums {sums.shape} {sums.dtype}: expected float32 "
                         f"{(group_sizes.shape[0], K, N)}")
    meta, visits = _megablox.make_group_metadata(
        group_sizes=group_sizes, m=M, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False)

    # The sum's tile stands in VMEM five times (in and out, each twice for the
    # pipeline, and the accumulator) and the row tiles twice as loaded and
    # once in float32: more than the 16 MiB a kernel gets unasked at the tile
    # that fills the MXU, so the kernel states its need.
    vmem = int(1.25 * (5 * 4 * tk * tn
                       + (2 * lhs.dtype.itemsize + 4) * tm * (tk + tn))) + (
                           4 << 20)

    def kernel(meta, lhs, rhs, sums, out, acc):
        at, last = pl.program_id(2), pl.num_programs(2) - 1
        group_ids = meta[1]
        group = group_ids[at]

        @pl.when((at == 0) | (group_ids[jnp.maximum(at - 1, 0)] != group))
        def _start():
            acc[...] = sums[...]

        # as megablox masks them: the rows of another group that shares the
        # row tile (and rows past the last group) add nothing
        left = lax.select(_group_rows(meta, at, tm, tk),
                          lhs[...].astype(jnp.float32),
                          jnp.zeros((tm, tk), jnp.float32))
        right = lax.select(_group_rows(meta, at, tm, tn),
                           rhs[...].astype(jnp.float32),
                           jnp.zeros((tm, tn), jnp.float32))
        acc[...] += lax.dot_general(
            left.astype(lhs.dtype), right.astype(rhs.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when((at == last)
                 | (group_ids[jnp.minimum(at + 1, last)] != group))
        def _store():
            out[...] = acc[...]

    of_group = pl.BlockSpec((None, tk, tn),
                            lambda n, k, at, meta: (meta[1][at], k, n))
    row_tile = lambda width, col: pl.BlockSpec(
        (tm, width), lambda n, k, at, meta: (meta[2][at], col(n, k)))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(sums.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[row_tile(tk, lambda n, k: k),
                      row_tile(tn, lambda n, k: n), of_group],
            out_specs=of_group,
            grid=(pl.cdiv(N, tn), pl.cdiv(K, tk), visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        input_output_aliases={5: 0},     # flat: the metadata is three
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=lhs.size * lhs.itemsize * pl.cdiv(N, tn)
            + rhs.size * rhs.itemsize * pl.cdiv(K, tk) + 2 * sums.size * 4),
        interpret=interpret, name="tgmm_add")(meta, lhs, rhs, sums)
