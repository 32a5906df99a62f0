"""``ops.scatter_add_rows``: a pass's rows added to the sums of the indices
they name, one group's segment at a time, against
``sums.at[index].add(rows in float32, mode="drop")`` over the rows the groups
cover.  In interpret mode on the CPU, as ``tgmm_add``'s tests; the chip's
compiler is asked in ``tests/test_aot_compile.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.ops import scatter_add_rows as module
from torchmpi_tpu.ops.scatter_add_rows import scatter_add_rows

N, D, R, TILE = 40, 128, 64, 16

# name -> (rows' dtype, group sizes, the index of each group's rows); a group's
# indices are distinct below N, as the contract has it
CASES = {
    "bfloat16-ragged": (jnp.bfloat16, [10, 22, 5, 27], None),
    "float32-ragged": (jnp.float32, [10, 22, 5, 27], None),
    # token 7 in groups 0 and 1, rows 3 and 9 of row tile 0: the collision the
    # visits exist for
    "twice-in-one-tile": (jnp.bfloat16, [6, 8, 20],
                          [[1, 2, 3, 7, 8, 9], [0, 4, 5, 7, 11, 12, 13, 14],
                           list(range(20, 40))]),
    # token 7 in group 0 (tile 0) and in group 2 (tile 2)
    "twice-across-tiles": (jnp.bfloat16, [16, 16, 16],
                           [list(range(16)), list(range(16, 32)),
                            [7] + list(range(24, 39))]),
    # N and above name no sum, inside a group as past the groups
    "dropped": (jnp.bfloat16, [12, 12],
                [[0, N, 2, N + 3, 4, 5, 6, 7, 8, 2 ** 30, 10, 11],
                 list(range(12, 23)) + [N]]),
    "no-rows": (jnp.bfloat16, [0, 0, 0], None),
    "all-dropped": (jnp.bfloat16, [20, 4], [[N] * 20, [N + 1] * 4]),
    # groups 1, 2 and 3 share row tile 1
    "three-groups-a-tile": (jnp.bfloat16, [18, 4, 5, 30], None),
    "empty-groups": (jnp.bfloat16, [0, 16, 0, 0, 9, 0], None),
    "whole-tiles": (jnp.float32, [16, 16, 16, 16], None),
    "one-group": (jnp.bfloat16, [0, 37, 0], None),
}


def _index(sizes, given, rng):
    groups = given or [rng.permutation(N)[:n] for n in sizes]
    index = np.concatenate([np.asarray(g, np.int64) for g in groups]
                           + [np.zeros((0,), np.int64)])
    # rows past the groups belong to none: whatever they name must not move
    return np.concatenate([index, rng.integers(0, N, R - len(index))])


@pytest.mark.parametrize("case", list(CASES))
def test_against_xlas_scatter_add(case):
    dtype, sizes, given = CASES[case]
    rng = np.random.default_rng(3)
    index = _index(sizes, given, rng)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    sums = jax.random.normal(keys[0], (N, 1, D), jnp.float32)
    rows = jax.random.normal(keys[1], (R, D), dtype)
    got = scatter_add_rows(sums, jnp.asarray(index, jnp.int32), rows,
                           jnp.asarray(sizes, jnp.int32), tile=TILE,
                           interpret=True)
    n = sum(sizes)
    want = np.asarray(sums[:, 0], np.float64)
    keep = index[:n] < N
    np.add.at(want, index[:n][keep], np.asarray(rows[:n], np.float64)[keep])
    assert got.shape == sums.shape and got.dtype == jnp.float32
    assert np.abs(np.asarray(got[:, 0]) - want).max() < 1e-5
    touched = np.zeros(N, bool)
    touched[index[:n][keep]] = True
    # a sum no row names is the one that came in, to the bit
    assert np.array_equal(np.asarray(got)[~touched], np.asarray(sums)[~touched])
    xla = sums[:, 0].at[jnp.asarray(index[:n])].add(
        rows[:n].astype(jnp.float32), mode="drop")
    assert np.abs(np.asarray(got[:, 0]) - np.asarray(xla)).max() < 1e-5


@pytest.mark.parametrize("what", ["rows-not-whole-tiles", "sums-two-d",
                                  "sums-bfloat16", "index-length",
                                  "compiled-and-not-whole-lanes"])
def test_shapes_it_refuses(what):
    sums = jnp.zeros((N, 1, D), jnp.float32)
    index, rows = jnp.zeros((R,), jnp.int32), jnp.zeros((R, D), jnp.bfloat16)
    sizes = jnp.asarray([R], jnp.int32)
    if what == "rows-not-whole-tiles":
        index, rows = index[:R - 4], rows[:R - 4]
    elif what == "sums-two-d":
        sums = sums[:, 0]
    elif what == "sums-bfloat16":
        sums = sums.astype(jnp.bfloat16)
    elif what == "index-length":
        index = index[:R - 16]
    else:
        sums, rows = sums[..., :48], rows[:, :48]
    with pytest.raises(ValueError):
        scatter_add_rows(sums, index, rows, sizes, tile=TILE,
                         interpret=what != "compiled-and-not-whole-lanes")


def test_the_tile_follows_from_the_width_alone():
    """256 rows (what the chip A/B of PR 52 found no worse than any other)
    wherever two visits' float32 buffers, as gathered and as added, stay
    inside 16 MiB; a power of two, so that it divides a pass's rows."""
    assert module.row_tile(2304) == module.row_tile(2048) == 256
    assert module.row_tile(128) == 256 and module.row_tile(8192) == 128
    assert module.row_tile(1 << 20) == 8


@pytest.mark.parametrize("tiles,tm,sizes", [
    (4, 16, [10, 22, 5, 27]), (4, 16, [0, 0, 0]), (4, 16, [16, 16, 16, 16]),
    (2, 16, [1, 0, 1, 0, 30]), (8, 8, [3, 3, 3, 3, 3, 3, 3, 3]),
    (3, 16, [0, 48, 0])])
def test_the_visits_are_megabloxs(tiles, tm, sizes):
    """The kernel's own account of the (row tile, group) pairs is what
    megablox's ``make_group_metadata`` gives ``tgmm_add`` for the same
    groups, visit for visit."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    sizes = jnp.asarray(sizes, jnp.int32)
    (offsets, groups, tile_ids), visits = module._visits(sizes, tiles, tm)
    (want_offsets, want_groups, want_tiles), want = (
        backend.make_group_metadata(
            group_sizes=sizes, m=tiles * tm, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=sizes.shape[0], visit_empty_groups=False))
    n = int(want)
    assert int(visits) == n
    assert np.array_equal(offsets, want_offsets)
    assert np.array_equal(groups[:n], want_groups[:n])
    assert np.array_equal(tile_ids[:n], want_tiles[:n])
