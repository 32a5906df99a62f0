"""Rows added to the sums of the indices they name, by DMA through VMEM.

``scatter_add_rows(sums, index, rows, group_sizes)``: ``sums`` (N, 1, D)
float32, ``index`` (R,) int32, ``rows`` (R, D) of any float dtype, whose
first ``sum(group_sizes)`` rows lie in contiguous segments of
``group_sizes[g]`` rows for group g.  Returns ``sums.at[index].add(rows in
float32, mode="drop")`` over the rows the groups cover, in place (the operand
is aliased to the result): row r of a group is added to ``sums[index[r]]``, a
row whose index is not in [0, N) is not moved, rows past the groups' total
belong to no group and are not read.

**Within one group the indices in [0, N) are distinct.**  That is the
contract the kernel rests on, and what the expert layer's passes give it
(``models/llama.py:_held_pass``: a group is one expert's segment of the
sorted units, and a token meets an expert once).  The same index in two
groups is the case it exists for: a token's two or three units in one pass.

``sums`` stays in HBM, one row a slab: (N, 1, D) lies row after row there
(``T(1,128)``), so a row is one contiguous DMA, where a row of (N, D) is D /
128 pieces of an (8, 128) tiling that Mosaic will not slice ("Slice shape
along dimension 0 must be aligned to tiling (8), but is 1").  The grid visits
(row tile, group) pairs in order, as megablox's grouped matmuls and
``ops/tgmm.py`` do (:func:`_visits`: a group without rows and a row tile past
the groups not visited at all; a tile that two groups share visited once for
each, the other's rows left out of the DMAs).  A visit starts one DMA a row
``sums[index[r]] -> held[r]`` into a (tile, 1, D) float32 VMEM buffer, waits
for them, adds the tile of ``rows`` that the block machinery pipelined in
(converted to float32 there, never in HBM; the buffer read as whole (8, 128)
registers, which the same bytes are) into a second buffer and starts the DMAs
back from that.  Rows of one visit never collide, so nothing orders them; two
visits of ONE group do not either, so the next visit's reads are started
beside this visit's add where the next visit is of the same group, into the
other pair of buffers.  At a change of group every write is waited for before
the next group's first read starts: that is what makes a token's units in two
groups a sum and not a race.

On the v5e, at the Mellum2 cell's pass (8,192 rows of 2,304 bfloat16 into
65,536 sums, 16 calls in a loop): 47 ns a row where XLA's own scatter-add
takes 279 to 285, to the bit the same sums (PERF.md section 6, PR 52; what
binds it there, and what the choices below were measured against).  In
interpret mode off the TPU, as the kernels it stands beside.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Two visits' buffers are in VMEM at once (the next one's reads fly beside
# this one's add), each as gathered and as added.
_SLOTS = 2
# Rows a step of a whole tile's DMA loop, compiled: eight rows' address
# arithmetic packs into the bundles one row's leaves empty (8 bundles a DMA
# for 17; 47 ns a row for 60 at the Mellum2 pass).
_UNROLL = 8


def row_tile(width: int) -> int:
    """Rows of one visit for sums ``width`` numbers wide, from the width
    alone: 256 (64 to 256 measured alike, 512 no better), fewer where four
    float32 buffers of that many rows would pass 16 MiB; a power of two."""
    fit = max(8, min(256, (16 << 20) // (2 * _SLOTS * 4 * width)))
    return 1 << (fit.bit_length() - 1)


def _lanes(n: int, width: int):
    """``n`` rows of ``width`` numbers as whole (8, 128) registers where the
    width is whole lanes: the shape a buffer is added in.  Row after row in
    memory either way."""
    if width % 128 or n * width % 1024:
        return (n, 1, width)
    return (n * width // 1024, 8, 128)


def _visits(group_sizes: jax.Array, tiles: int, tm: int):
    """The (row tile, group) pairs a grid visits for ``tiles`` row tiles of
    ``tm`` rows, in order: ``((offsets, group_ids, tile_ids), visits)`` as
    megablox's ``make_group_metadata(visit_empty_groups=False)`` gives them
    (a group's first row at ``offsets[g]``; visit v is of group
    ``group_ids[v]`` in row tile ``tile_ids[v]``; ``visits`` of them), in a
    handful of elementwise ops over (visits, groups) where megablox's
    ``repeat`` and ``histogram`` are two dozen small programs with loops in
    them, run anew every call (1.6 ns a row of the Mellum2 pass on the chip;
    on the CPU 0.4 s of compile a program that holds it for 1.35)."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // tm
    count = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), G - 1)
    of_group = lambda a: jnp.sum(jnp.where(
        group[:, None] == jnp.arange(G)[None, :], a[None, :], 0), axis=1)
    tile = jnp.clip(of_group(first) + v - of_group(upto - count), 0,
                    tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group, tile.astype(jnp.int32)), upto[-1]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def scatter_add_rows(sums: jax.Array, index: jax.Array, rows: jax.Array,
                     group_sizes: jax.Array, tile: int | None = None,
                     interpret: bool = False) -> jax.Array:
    """``sums`` with row r of ``rows`` added to ``sums[index[r]]`` in float32
    for every r a group covers whose index is in [0, N).  ``sums`` (N, 1, D)
    float32, aliased to the result (compiled, D whole lanes of 128);
    ``index`` (R,) int32, distinct in [0, N) within a group; ``rows`` (R, D),
    R whole row tiles; ``group_sizes`` (G,) int32, their sum at most R.
    ``tile`` is the rows of one visit: :func:`row_tile` of D, or the largest
    power of two below it that divides R, unless given."""
    (N, _, D), R = sums.shape, rows.shape[0]
    tm = tile or math.gcd(R, row_tile(D))
    unroll = 1 if interpret else _UNROLL        # less for the CPU to compile
    if sums.shape != (N, 1, D) or sums.dtype != jnp.float32:
        raise ValueError(f"sums {sums.shape} {sums.dtype}: expected float32 "
                         f"(N, 1, D)")
    if D % 128 and not interpret:
        raise ValueError(f"sums {sums.shape}: on the chip a row is whole "
                         f"lanes of 128 (Mosaic slices no other)")
    if rows.shape != (R, D) or index.shape != (R,) or R % tm or tm % 8:
        raise ValueError(f"rows {rows.shape} and index {index.shape} for "
                         f"sums {sums.shape}: rows must agree and be whole "
                         f"tiles of {tm}, a tile whole sublanes of 8")
    meta, visits = _visits(group_sizes.astype(jnp.int32), R // tm, tm)
    block = _lanes(tm, D)
    by_row, in_lanes = (_SLOTS, tm, 1, D), (_SLOTS, *block)

    def kernel(meta, index, rows, _, out, held, added, moving, got, put):
        offsets, group_ids, tile_ids = meta
        at, last = pl.program_id(0), pl.num_programs(0) - 1
        group = group_ids[at]
        fresh = (at == 0) | (group_ids[jnp.maximum(at - 1, 0)] != group)
        more = (at < last) & (group_ids[jnp.minimum(at + 1, last)] == group)
        slot = at % _SLOTS

        def copy(slot, back, row=0, i=0, n=1):
            """The DMA of ``n`` sums from ``i`` on to ``held[slot]`` from
            ``row`` on, or ``back`` from those rows of ``added[slot]``."""
            there = out.at[pl.ds(i, n)]
            if back:
                return pltpu.make_async_copy(
                    added.reshape(by_row).at[slot, pl.ds(row, n)], there,
                    put.at[slot])
            return pltpu.make_async_copy(
                there, held.at[slot, pl.ds(row, n)], got.at[slot])

        def start(visit, slot, back):
            """Start the DMAs of ``visit``'s rows (those of its group in its
            row tile whose index names a sum) and note how many."""
            g, base = group_ids[visit], tile_ids[visit] * tm
            lo = jnp.maximum(offsets[g], base)
            hi = jnp.minimum(offsets[g + 1], base + tm)

            def one(r, n):
                i = index[r]
                named = i.astype(jnp.uint32) < N

                @pl.when(named)
                def _():
                    copy(slot, back, r - base, i).start()

                return n + named.astype(jnp.int32)

            def some(step, n):
                for r in range(unroll):
                    n = one(base + step * unroll + r, n)
                return n

            # a whole tile (the rule) is a loop of a known length, unrolled
            moving[int(back), slot] = lax.fori_loop(
                lo, hi, one, 0) if unroll == 1 else lax.cond(
                    hi - lo == tm,
                    lambda: lax.fori_loop(0, tm // unroll, some, 0),
                    lambda: lax.fori_loop(lo, hi, one, 0))

        def wait(slot, back):
            """Wait for every DMA started on ``slot`` and not yet waited
            for.  A wait takes its descriptor's bytes off the semaphore: a
            whole tile's worth is ONE wait of the buffer's size (a wait a
            row cost 5 to 8 ns a row), any other count a wait a row."""
            n = moving[int(back), slot]
            each = lambda: lax.fori_loop(
                0, n, lambda _, c: copy(slot, back).wait() or c, 0)
            if N >= tm:
                lax.cond(n == tm,
                         lambda: copy(slot, back, n=tm).wait() or 0, each)
            else:
                each()
            moving[int(back), slot] = 0

        @pl.when(at == 0)
        def _():
            for back in (0, 1):
                for s in range(_SLOTS):
                    moving[back, s] = 0

        @pl.when(fresh)
        def _():
            start(at, slot, False)

        @pl.when(more)
        def _():
            start(at + 1, (at + 1) % _SLOTS, False)

        wait(slot, False)
        wait(slot, True)        # of the visit before last: long landed
        added[slot] = held.reshape(in_lanes)[slot] + rows[...].astype(
            jnp.float32)
        start(at, slot, True)

        @pl.when(jnp.logical_not(more))
        def _():
            for s in range(_SLOTS):
                wait(s, True)

    itemsize = rows.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(sums.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec(block, lambda at, meta, index: (
                meta[2][at], 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            grid=(visits,),
            scratch_shapes=[pltpu.VMEM(by_row, jnp.float32),
                            pltpu.VMEM(in_lanes, jnp.float32),
                            pltpu.SMEM((2, _SLOTS), jnp.int32),
                            pltpu.SemaphoreType.DMA((_SLOTS,)),
                            pltpu.SemaphoreType.DMA((_SLOTS,))]),
        input_output_aliases={5: 0},     # flat: the metadata is three
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Mosaic's own checks of every DMA's two addresses are 10 of the
            # 18 bundles a DMA (81 ns a row for 55); `named` above keeps an
            # index inside the sums and `start`'s bounds a row inside its tile
            disable_bounds_checks=True,
            vmem_limit_bytes=int(1.25 * tm * D * (
                8 * _SLOTS + 2 * itemsize + 4)) + (4 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=R * D, transcendentals=0,
            bytes_accessed=R * D * (8 + itemsize)),
        interpret=interpret, name="scatter_add_rows")(
            meta, index, rows.reshape(_lanes(R, D)), sums)
