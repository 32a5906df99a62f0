"""Runtime tests: lifecycle, communicator hierarchy, handles, config.

Models the reference suite: start/stop smoke (test/startstop.lua:18-28) and
the communicator-hierarchy unit test with rank%3 keys and cartesian
predicate checks (test/hierarchical_communicators.lua:30-81).
"""

import numpy as np
import pytest

import jax

import torchmpi_tpu as mpi
from torchmpi_tpu.runtime import config
from torchmpi_tpu.runtime.communicator import Communicator, CommunicatorType
from torchmpi_tpu.runtime.handles import SynchronizationHandle, wait


class TestLifecycle:
    def test_start_stop(self, devices):
        """Smoke: init, names print, barrier, clean stop
        (reference: test/startstop.lua:18-28)."""
        if mpi.started():
            mpi.stop()
        mpi.start(with_tpu=False, devices=devices)
        assert mpi.started()
        assert mpi.size() == 8
        assert mpi.rank() == 0
        assert "Communicator" in mpi.communicator_names()
        mpi.barrier()
        mpi.stop()
        assert not mpi.started()

    def test_double_start_raises(self, world):
        with pytest.raises(RuntimeError):
            mpi.start(with_tpu=False)

    def test_stop_idempotent(self, devices):
        if mpi.started():
            mpi.stop()
        mpi.stop()  # no-op
        mpi.start(with_tpu=False, devices=devices)
        mpi.stop()
        mpi.stop()


class TestCompileCache:
    """``lifecycle.use_compile_cache``: the persistent cache's directory is
    part of its key, so it must stay put."""

    @pytest.fixture()
    def cache_dir_config(self):
        prior = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prior)

    def test_env_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                 cache_dir_config):
        from torchmpi_tpu.runtime import lifecycle

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert lifecycle.use_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None

    def test_fixed_path_beside_the_package(self, monkeypatch,
                                           cache_dir_config):
        import os

        from torchmpi_tpu.runtime import lifecycle

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = lifecycle.use_compile_cache()
        assert lifecycle.use_compile_cache() == first
        assert first == os.path.join(
            os.path.dirname(os.path.dirname(mpi.__file__)), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first


class TestCommunicatorHierarchy:
    """Reference: test/hierarchical_communicators.lua:30-81 — push rank%3,
    check intra group shapes and the cartesian predicate."""

    def test_rank_mod_3_split(self, world):
        # 8 ranks keyed rank%3 -> groups {0,3,6}, {1,4,7}, {2,5} — uneven,
        # hence tree (non-cartesian), like n=8, div=3 in the reference
        # (cartesian iff n % div == 0).
        level = mpi.push_communicator(lambda r: r % 3)
        comm = mpi.stack.at(level)
        assert comm.num_groups == 3
        assert sorted(len(g) for g in comm.groups) == [2, 3, 3]
        assert not comm.cartesian
        # tree: inter links roots only (resources.cpp:288-347)
        assert len(comm.inter_group_ranks) == 1
        assert len(comm.inter_group_ranks[0]) == 3

    def test_rank_mod_2_cartesian(self, world):
        # 8 % 2 == 0 -> equal groups -> cartesian; inter links same-intra-rank
        # peers (one inter group per intra position).
        level = mpi.push_communicator(lambda r: r % 2)
        comm = mpi.stack.at(level)
        assert comm.num_groups == 2
        assert comm.cartesian
        assert len(comm.inter_group_ranks) == 4
        for ig in comm.inter_group_ranks:
            assert len(ig) == 2
        # 2-D mesh view exists and has the right shape
        mesh = comm.mesh2d()
        assert mesh.devices.shape == (2, 4)

    def test_nested_push_refines_parent(self, world):
        """A child split refines the parent partition (the reference splits
        the parent's intraComm, resources.cpp:199-287)."""
        l1 = mpi.push_communicator(lambda r: r // 4)  # {0..3}, {4..7}
        l2 = mpi.push_communicator(lambda r: r % 2)   # refines within each
        c2 = mpi.stack.at(l2)
        assert c2.num_groups == 4
        parent = mpi.stack.at(l1)
        # every child group must be inside one parent group
        for g in c2.group_ranks:
            parents = {parent.group_of_rank(r) for r in g}
            assert len(parents) == 1

    def test_forced_tree_mode(self, devices):
        if mpi.started():
            mpi.stop()
        config.reset()
        mpi.start(with_tpu=False, devices=devices, tree_communicators=True)
        level = mpi.push_communicator(lambda r: r % 2)
        comm = mpi.stack.at(level)
        assert not comm.cartesian  # equal groups, but tree mode forced
        mpi.stop()
        config.reset()

    def test_cursor_and_span(self, world):
        l1 = mpi.push_communicator(lambda r: r // 4)
        assert mpi.stack.level == l1
        mpi.set_communicator(0)
        assert mpi.stack.level == 0
        mpi.set_collective_span(0, 2)
        assert mpi.stack.span == (0, 2)
        with pytest.raises(IndexError):
            mpi.set_collective_span(0, 5)
        with pytest.raises(IndexError):
            mpi.set_communicator(7)

    def test_communicator_guard(self, world):
        l1 = mpi.push_communicator(lambda r: r // 4)
        mpi.set_communicator(0)
        with mpi.CommunicatorGuard(mpi.stack, l1, CommunicatorType.INTER):
            assert mpi.stack.level == l1
            assert mpi.stack.type == CommunicatorType.INTER
        assert mpi.stack.level == 0
        assert mpi.stack.type == CommunicatorType.INTRA

    def test_key_too_long_rejected(self, world):
        with pytest.raises(ValueError):
            Communicator(mpi.stack.world().devices, ["x" * 2000] * 8)

    def test_num_nodes(self, world):
        # single-host fixture: all devices on process 0
        assert mpi.num_nodes_in_communicator() == 1


class TestHandles:
    def test_ready_handle(self):
        h = SynchronizationHandle.ready(payload=42)
        assert wait(h) == 42
        assert wait(None) is None

    def test_future_handle(self):
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(1) as pool:
            f = pool.submit(lambda: 7)
            h = SynchronizationHandle.from_future(f)
            assert h.wait() == 7
            assert h.done
            assert h.wait() == 7  # idempotent

    def test_array_handle(self, world):
        import jax.numpy as jnp

        x = jnp.ones((4, 4))
        h = SynchronizationHandle.from_arrays(x * 2)
        out = wait(h)
        np.testing.assert_allclose(np.asarray(out), 2.0)

    def test_callbacks(self):
        calls = []
        h = SynchronizationHandle.ready(payload=1)
        h.add_done_callback(lambda: calls.append(1))
        assert calls == [1]


class TestConfig:
    def test_get_set(self, fresh_config):
        assert config.get("use_hierarchical_collectives") is True
        config.set("min_buffer_size", 123)
        assert config.get("min_buffer_size") == 123
        assert config.constants.min_buffer_size == 123

    def test_unknown_key(self, fresh_config):
        with pytest.raises(KeyError):
            config.get("no_such_knob")
        with pytest.raises(KeyError):
            config.set("no_such_knob", 1)

    def test_constants_attr_protocol(self, fresh_config):
        """Unknown names raise AttributeError (not KeyError) so
        hasattr/copy/pickle probing of the facade stays benign."""
        assert not hasattr(config.constants, "no_such_knob")
        assert not hasattr(config.constants, "__deepcopy__")
        with pytest.raises(AttributeError):
            config.constants.no_such_knob

    def test_freeze(self, fresh_config):
        config.freeze()
        with pytest.raises(RuntimeError):
            config.set("min_buffer_size", 5)

    def test_snapshot_defaults(self, fresh_config):
        snap = config.snapshot()
        # reference defaults preserved (constants.cpp:129-155)
        assert snap["small_allreduce_size_cpu"] == 1 << 16
        assert snap["small_allreduce_size_gpu"] == 1 << 16
        assert snap["min_buffer_size"] == 1 << 17
        assert snap["max_buffer_size"] == 1 << 20
        assert snap["num_buffers_per_collective"] == 3
