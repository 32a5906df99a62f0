"""Pallas ring collective tests — interpreter path on the 8-device CPU mesh
checked against the XLA eager collectives (reference correctness model:
fill = rank makes results algebraic, test/collectives_all.lua:52-54,298-311;
the rings under test mirror lib/detail/collectives_cuda.cpp:202-388)."""

import numpy as np
import pytest

import jax.numpy as jnp

from torchmpi_tpu.collectives import eager, pallas_ring
from torchmpi_tpu.runtime import config


@pytest.fixture(autouse=True)
def _fresh_ring_cache():
    pallas_ring.clear_cache()
    yield
    pallas_ring.clear_cache()


def _expect_sum(comm, n, dtype=np.float32):
    """allreduce of fill-by-rank = p(p-1)/2 everywhere."""
    p = comm.size
    return np.full((p, n), p * (p - 1) / 2, dtype)


class TestRingAllreduce:
    def test_matches_eager_fill_by_rank(self, world):
        n = 3000  # not lane-aligned: exercises padding
        x = eager.fill_by_rank(world, (n,))
        out = pallas_ring.ring_allreduce(world, x)
        ref = eager.allreduce(world, x)
        np.testing.assert_allclose(eager.to_numpy(out), eager.to_numpy(ref))
        np.testing.assert_allclose(eager.to_numpy(out), _expect_sum(world, n))

    def test_random_values_match_numpy(self, world):
        rng = np.random.RandomState(0)
        vals = rng.randn(world.size, 5000).astype(np.float32)
        x = eager.shard(world, vals)
        out = eager.to_numpy(pallas_ring.ring_allreduce(world, x))
        expect = np.broadcast_to(vals.sum(0), vals.shape)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-4)

    def test_small_array_fewer_elements_than_lanes(self, world):
        x = eager.fill_by_rank(world, (5,))
        out = pallas_ring.ring_allreduce(world, x)
        np.testing.assert_allclose(eager.to_numpy(out), _expect_sum(world, 5))

    def test_int32(self, world):
        vals = np.arange(world.size * 300, dtype=np.int32).reshape(
            world.size, 300)
        x = eager.shard(world, vals)
        out = eager.to_numpy(pallas_ring.ring_allreduce(world, x))
        np.testing.assert_array_equal(out, np.broadcast_to(vals.sum(0),
                                                           vals.shape))

    def test_rejects_non_sum(self, world):
        x = eager.fill_by_rank(world, (128,))
        with pytest.raises(ValueError, match="sum"):
            pallas_ring.ring_allreduce(world, x, op="max")

    def test_mean(self, world):
        """op='mean' folds the replica mean into the ring epilogue (what
        the engine's DP sync needs)."""
        n = 600
        x = eager.fill_by_rank(world, (n,))
        out = eager.to_numpy(pallas_ring.ring_allreduce(world, x, op="mean"))
        np.testing.assert_allclose(out, (world.size - 1) / 2.0, rtol=1e-6)

    def test_bfloat16(self, world):
        """bf16 rides the ring in its wire dtype (in-dtype reduction like
        the vendor path); values chosen exactly representable."""
        import jax.numpy as jnp

        vals = np.tile(np.arange(world.size, dtype=np.float32)[:, None],
                       (1, 400))
        x = eager.shard(world, vals).astype(jnp.bfloat16)
        out = pallas_ring.ring_allreduce(world, x)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            eager.to_numpy(out.astype(jnp.float32)),
            world.size * (world.size - 1) / 2.0)

    def test_inner_form_inside_shard_map(self, world):
        """inner_ring_allreduce is callable inside a user shard_map body —
        the compiled-engine integration surface."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from torchmpi_tpu.runtime.communicator import RANK_AXIS

        n = 384
        x = eager.fill_by_rank(world, (n,))

        def body(xb):
            return pallas_ring.inner_ring_allreduce(
                xb[0], world.size, mean=True)[None]

        fn = jax.jit(shard_map(body, mesh=world.mesh(), in_specs=P(RANK_AXIS),
                               out_specs=P(RANK_AXIS), check_vma=False))
        out = eager.to_numpy(fn(x))
        np.testing.assert_allclose(out, (world.size - 1) / 2.0, rtol=1e-6)

    def test_rejects_bad_shape(self, world):
        x = eager.fill_by_rank(world, (2, 3))  # (p, 2, 3): not flat
        with pytest.raises(ValueError, match="rank-major"):
            pallas_ring.ring_allreduce(world, x)

    def test_single_buffer_slot(self, world, fresh_config):
        """nslots=1 forces a credit wait on every step after the first."""
        config.set("num_buffers_per_collective", 1)
        x = eager.fill_by_rank(world, (2048,))
        out = pallas_ring.ring_allreduce(world, x)
        np.testing.assert_allclose(eager.to_numpy(out),
                                   _expect_sum(world, 2048))

    def test_small_max_buffer_forces_subchunks(self, world, fresh_config):
        """max_buffer_size below the chunk size splits each step's transfer
        into pipelined sub-chunk RDMAs (the reference's buffer-bounded
        chunk loop, detail/collectives.cpp:128-326)."""
        config.set("min_buffer_size", 512)
        config.set("max_buffer_size", 1024)  # 2 lanes of f32
        n = world.size * 1024  # chunk = 1024 elems = 4KiB -> q = 4
        rows, q, subrows = pallas_ring._geometry(n, world.size, 4)
        assert q > 1
        x = eager.fill_by_rank(world, (n,))
        out = pallas_ring.ring_allreduce(world, x)
        np.testing.assert_allclose(eager.to_numpy(out), _expect_sum(world, n))


class TestRingReduceScatter:
    def test_matches_eager(self, world):
        n = world.size * 100
        rng = np.random.RandomState(1)
        vals = rng.randn(world.size, n).astype(np.float32)
        x = eager.shard(world, vals)
        out = eager.to_numpy(pallas_ring.ring_reduce_scatter(world, x))
        ref = eager.to_numpy(eager.reduce_scatter(world, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

    def test_owned_chunk_is_mine(self, world):
        p = world.size
        n = p * 64
        x = eager.fill_by_rank(world, (n,))
        out = eager.to_numpy(pallas_ring.ring_reduce_scatter(world, x))
        total = p * (p - 1) / 2
        assert out.shape == (p, 64)
        np.testing.assert_allclose(out, np.full((p, 64), total, np.float32))

    def test_rejects_indivisible(self, world):
        x = eager.fill_by_rank(world, (world.size * 10 + 1,))
        with pytest.raises(ValueError, match="divisible"):
            pallas_ring.ring_reduce_scatter(world, x)


class TestRingAllgather:
    def test_gathers_in_rank_order(self, world):
        p = world.size
        n = 40
        vals = np.stack([np.full((n,), r, np.float32) for r in range(p)])
        x = eager.shard(world, vals)
        out = eager.to_numpy(pallas_ring.ring_allgather(world, x))
        assert out.shape == (p, p * n)
        expect = np.concatenate([np.full((n,), r, np.float32)
                                 for r in range(p)])
        for r in range(p):
            np.testing.assert_allclose(out[r], expect)


class TestGeometry:
    def test_respects_max_buffer(self, fresh_config):
        config.set("min_buffer_size", 1 << 10)
        config.set("max_buffer_size", 1 << 12)
        rows, q, subrows = pallas_ring._geometry(1 << 20, 8, 4)
        # chunk = 131072 elems * 4B = 512KiB; target 4KiB -> q = 128
        assert q == 128
        assert subrows * q == rows
        assert subrows * 128 * 4 <= (1 << 12)

    def test_single_subchunk_when_small(self, fresh_config):
        rows, q, subrows = pallas_ring._geometry(4096, 8, 4)
        assert q == 1
