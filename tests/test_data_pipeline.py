"""Streaming input data plane (torchmpi_tpu/data): determinism, sharding
correctness, prefetch-depth memory bounds, lifecycle hardening
(shutdown, exception propagation, leak-free abandonment), overlap
accounting, and the engine's knob-gated auto-wrap — including the
pipeline-off identity and the pipeline-on-vs-off loss-trajectory
equivalence the acceptance criteria pin.

The background-stager-vs-step interleaving is the new race class; this
file rides the sanitizer drill (scripts/sanitize_drill.py) alongside the
other thread-heavy suites.
"""

import threading
import time

import numpy as np
import pytest

import jax

from torchmpi_tpu.data import (DataPipeline, DeviceStage, HostStage,
                               Staged, engine_wrap)
from torchmpi_tpu.data.staging import HostScratchPool
from torchmpi_tpu.runtime import config
from torchmpi_tpu.utils.data import Dataset, ShardedIterator, synthetic_mnist

pytestmark = pytest.mark.data


def _ds(n=128, d=4):
    return Dataset(x=np.arange(n * d, dtype=np.float32).reshape(n, d),
                   y=np.arange(n, dtype=np.int32))


def _batches(n_batches=6, p=8, b=2, d=4, delay_s=0.0):
    """Rank-major host batches; optional per-batch producer stall (the
    chaos.straggler_delay shape on the input plane)."""
    rng = np.random.RandomState(0)
    out = [(rng.randn(p, b, d).astype(np.float32),
            rng.randint(0, 4, (p, b)).astype(np.int32))
           for _ in range(n_batches)]
    if delay_s == 0.0:
        return out

    def gen():
        for xb, yb in out:
            time.sleep(delay_s)
            yield xb, yb
    return gen()


def _thread_count():
    return threading.active_count()


def _settle(predicate, tries=50, dt=0.1) -> bool:
    for _ in range(tries):
        if predicate():
            return True
        time.sleep(dt)
    return predicate()


# ---------------------------------------------------------------- host stage


class TestHostStage:
    def test_order_deterministic_single_producer(self):
        src = ShardedIterator(_ds(), global_batch=16, num_shards=8,
                              shuffle=True, seed=7)
        plain = [(x.copy(), y.copy()) for x, y in src]
        src2 = ShardedIterator(_ds(), global_batch=16, num_shards=8,
                               shuffle=True, seed=7)
        staged = list(HostStage(src2, depth=3))
        assert len(staged) == len(plain)
        for (xa, ya), (xb, yb) in zip(plain, staged):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_order_deterministic_with_worker_pool(self):
        """Multi-worker transform keeps batch order bit-identical to the
        serial form — the reordering contract the acceptance criteria
        need for pipeline-on == pipeline-off trajectories."""
        items = list(range(40))

        def slowish(i):
            # Uneven per-item latency: without seq reordering this
            # WOULD scramble (later items finish first).
            time.sleep(0.001 * ((i * 7) % 5))
            return i * 10

        got = list(HostStage(items, depth=2, workers=4, transform=slowish))
        assert got == [i * 10 for i in items]

    def test_worker_exception_surfaces_at_its_slot(self):
        def boom(i):
            if i == 5:
                raise RuntimeError("transform failed on 5")
            return i

        it = iter(HostStage(list(range(10)), depth=2, workers=3,
                            transform=boom))
        got = []
        with pytest.raises(RuntimeError, match="failed on 5"):
            for v in it:
                got.append(v)
        # Everything BEFORE the failing slot arrived, in order.
        assert got == [0, 1, 2, 3, 4]

    def test_source_exception_propagates(self):
        def src():
            yield (1, 2)
            raise ValueError("loader died")

        with pytest.raises(ValueError, match="loader died"):
            list(HostStage(src(), depth=2))

    def test_abandonment_releases_threads_without_close(self):
        """Dropping a half-consumed iterator (no close(), no generator
        GC luck) must release the producer promptly — the seed
        ThreadedIterator leak this subsystem fixes."""
        before = _thread_count()
        it = iter(HostStage(_batches(100), depth=2))
        next(it)
        del it                       # no close(): __del__ must stop it
        assert _settle(lambda: _thread_count() <= before), \
            "producer thread leaked after abandonment"

    def test_slow_consumer_memory_bounded(self):
        """The producer may run at most depth (+ workers) items ahead of
        the consumer no matter how slow the consumer is."""
        produced = []

        def src():
            for i in range(100):
                produced.append(i)
                yield i

        it = iter(HostStage(src(), depth=3))
        assert next(it) == 0
        time.sleep(0.5)              # consumer stalls; producer must too
        # depth queued + 1 in producer hand + 1 consumed.
        assert len(produced) <= 3 + 2
        it.close()

    def test_worker_pool_memory_bounded(self):
        produced = []

        def src():
            for i in range(100):
                produced.append(i)
                yield i

        it = iter(HostStage(src(), depth=3, workers=2,
                            transform=lambda v: v))
        assert next(it) == 0
        time.sleep(0.5)
        # permits = depth + workers, + 1 reader hand + 1 consumed.
        assert len(produced) <= 3 + 2 + 2
        it.close()


# -------------------------------------------------------------- device stage


class TestDeviceStage:
    def test_yields_staged_pairs_with_wait(self, world):
        got = list(DeviceStage(_batches(4), world.mesh(), depth=2))
        assert len(got) == 4
        for xb, yb in got:
            assert isinstance(xb, Staged) and isinstance(yb, Staged)
            assert xb.wait_s >= 0.0 and yb.wait_s == 0.0
            assert xb.array.shape == (16, 4)

    def test_sharding_correct_across_ranks(self, world):
        """Each device owns exactly its rank's rows of the global batch —
        the per-host sharded-loading contract."""
        from jax.sharding import NamedSharding, PartitionSpec
        from torchmpi_tpu.runtime.communicator import RANK_AXIS

        batches = _batches(2, p=8, b=2, d=4)
        (sx, _sy), = list(DeviceStage(batches[:1], world.mesh(), depth=1))
        expect_sh = NamedSharding(world.mesh(), PartitionSpec(RANK_AXIS))
        assert sx.array.sharding.is_equivalent_to(expect_sh, sx.array.ndim)
        flat = batches[0][0].reshape(16, 4)
        np.testing.assert_array_equal(np.asarray(sx.array), flat)
        for shard in sx.array.addressable_shards:
            rank = shard.index[0].start // 2
            np.testing.assert_array_equal(
                np.asarray(shard.data), flat[rank * 2:(rank + 1) * 2])

    def test_prefetch_depth_bounds_inflight(self, world):
        """A stalled consumer holds at most depth queued + 1 in the
        producer's hand staged batches — the device-memory bound."""
        staged = []

        def src():
            for i, b in enumerate(_batches(50)):
                staged.append(i)
                yield b

        it = iter(DeviceStage(src(), world.mesh(), depth=2))
        next(it)
        time.sleep(0.5)
        assert len(staged) <= 2 + 2
        it.close()

    def test_producer_exception_propagates(self, world):
        def src():
            yield _batches(1)[0]
            raise RuntimeError("host loader exploded")

        it = DeviceStage(src(), world.mesh(), depth=2)
        with pytest.raises(RuntimeError, match="exploded"):
            list(it)

    def test_abandonment_releases_thread(self, world):
        before = _thread_count()
        it = iter(DeviceStage(_batches(50), world.mesh(), depth=2))
        next(it)
        del it
        assert _settle(lambda: _thread_count() <= before), \
            "device-stage producer leaked after abandonment"

    def test_stats_and_bytes(self, world):
        stage = DeviceStage(_batches(4, p=8, b=2, d=4), world.mesh(),
                            depth=2)
        list(stage)
        s = stage.stats.snapshot()
        assert s["batches"] == 4
        # x: 16*4 f32 + y: 16 i32 per batch.
        assert s["staged_bytes_per_batch"] == 16 * 4 * 4 + 16 * 4
        assert 0.0 <= s["overlap_fraction"] <= 1.0

    def test_overlap_gauge_plausible(self, world):
        """Fast producer + slow consumer -> overlap near 1; a straggling
        producer (chaos.straggler_delay shape) + eager consumer -> the
        gauge must drop well below it."""
        fast = DeviceStage(_batches(6), world.mesh(), depth=2)
        for _ in fast:
            time.sleep(0.05)         # consumer is the bottleneck
        hidden = fast.stats.overlap_fraction()

        slow = DeviceStage(_batches(6, delay_s=0.05), world.mesh(),
                           depth=2)
        list(slow)                   # producer is the bottleneck
        starved = slow.stats.overlap_fraction()
        assert hidden > 0.8
        assert starved < hidden - 0.3

    def test_publishes_input_metrics(self, world):
        from torchmpi_tpu.obs.metrics import Registry
        from torchmpi_tpu.obs import serve

        reg = Registry()
        stage = DeviceStage(_batches(3), world.mesh(), depth=2,
                            publish=False)
        # Route the feed through a private registry by publishing from
        # the stats the stage accumulated (the live path publishes the
        # same numbers per batch; here the registry contract is pinned).
        list(stage)
        st = stage.stats
        serve.publish_input(staged_bytes=st.staged_bytes,
                            stage_s=st.stage_s,
                            overlap_fraction=st.overlap_fraction(),
                            registry=reg)
        assert (reg.counter("tmpi_data_staged_bytes_total").value()
                == st.staged_bytes)
        g = reg.gauge("tmpi_data_input_overlap_fraction").value()
        assert 0.0 <= g <= 1.0
        text = reg.to_prometheus()
        assert "tmpi_data_stage_seconds_bucket" in text


# ------------------------------------------------------------- scratch pool


class TestHostScratchPool:
    def test_reuses_ready_buffer(self):
        class FakeReady:
            def is_ready(self):
                return True

        pool = HostScratchPool(2)
        a = np.arange(8, dtype=np.float32)
        b1 = pool.cast(a, np.float16)
        pool.track(b1, FakeReady())
        b2 = pool.cast(a + 1, np.float16)
        assert b2 is b1                       # recycled
        np.testing.assert_array_equal(b2, (a + 1).astype(np.float16))
        assert pool.hits == 1 and pool.misses == 1

    def test_inflight_buffer_never_reused(self):
        class NeverReady:
            def is_ready(self):
                return False

        pool = HostScratchPool(2)
        a = np.arange(8, dtype=np.float32)
        b1 = pool.cast(a, np.float16)
        pool.track(b1, NeverReady())
        b2 = pool.cast(a, np.float16)
        assert b2 is not b1                   # transfer still in flight
        assert pool.misses == 2

    def test_pool_disabled_on_cpu_backend(self, world):
        # device_put may alias host memory on CPU: the pipeline must
        # force the pool off there regardless of the knob.
        config.set("data_reuse_host_buffers", True)
        pipe = DataPipeline(_batches(1), world.mesh(), cast=np.float16)
        assert pipe.device.reuse_host_buffers is False


# ----------------------------------------------------------------- pipeline


class TestDataPipeline:
    def test_bit_identical_to_direct_iteration(self, world):
        """Pipeline on/off yields bit-identical batch order and content —
        per rank, per epoch (the determinism acceptance row)."""
        ds = _ds(256)
        direct = ShardedIterator(ds, global_batch=32, num_shards=8, seed=3)
        piped = DataPipeline(
            ShardedIterator(ds, global_batch=32, num_shards=8, seed=3),
            world.mesh())
        for epoch in range(2):
            for (xa, ya), (sx, sy) in zip(direct, piped):
                np.testing.assert_array_equal(
                    np.asarray(sx.array), xa.reshape(-1, xa.shape[-1]))
                np.testing.assert_array_equal(
                    np.asarray(sy.array), ya.reshape(-1))

    def test_len_and_reiteration(self, world):
        base = ShardedIterator(_ds(128), global_batch=32, num_shards=8)
        pipe = DataPipeline(base, world.mesh())
        assert len(pipe) == len(base) == 4
        assert len(list(pipe)) == 4
        assert len(list(pipe)) == 4          # epochs restart cleanly

    def test_transform_runs_on_workers_deterministically(self, world):
        def double(batch):
            xb, yb = batch
            return xb * 2.0, yb

        base = _batches(8)
        pipe = DataPipeline(list(base), world.mesh(), transform=double,
                            workers=3)
        got = list(pipe)
        assert len(got) == 8
        for (xb, _), (sx, _) in zip(base, got):
            np.testing.assert_array_equal(np.asarray(sx.array),
                                          (xb * 2.0).reshape(-1, 4))


# ---------------------------------------------------------- engine wrapping


class TestEngineWrap:
    def test_off_is_identity(self, world):
        config.set("data_pipeline", "off")
        it = [1, 2, 3]
        assert engine_wrap(it, world.mesh()) is it

    def test_auto_passes_prestaged_lists_through(self, world):
        from torchmpi_tpu.utils.data import DevicePrefetchIterator

        config.set("data_pipeline", "auto")
        resident = list(DevicePrefetchIterator(_batches(2), world.mesh()))
        assert engine_wrap(resident, world.mesh()) is resident
        # "on" forces the pipeline even over pre-staged pairs.
        config.set("data_pipeline", "on")
        wrapped = engine_wrap(resident, world.mesh())
        assert isinstance(wrapped, DataPipeline)
        got = list(wrapped)
        assert len(got) == 2 and isinstance(got[0][0], Staged)

    def test_auto_wraps_bare_iterators_once(self, world):
        config.set("data_pipeline", "auto")
        base = ShardedIterator(_ds(64), global_batch=16, num_shards=8)
        wrapped = engine_wrap(base, world.mesh())
        assert isinstance(wrapped, DataPipeline)
        assert engine_wrap(wrapped, world.mesh()) is wrapped   # no rewrap

    def test_bad_mode_raises(self, world):
        config.set("data_pipeline", "sideways")
        with pytest.raises(ValueError, match="data_pipeline"):
            engine_wrap([1], world.mesh())

    def test_workers_knob_without_transform_is_inert(self, world):
        """A tuned data_host_workers with no transform must be inert
        (there is no host work to parallelize) — never a crash of every
        engine_wrap'd train() call; EXPLICIT workers without a transform
        still raises like HostStage."""
        config.set("data_pipeline", "auto")
        config.set("data_host_workers", 2)
        pipe = engine_wrap(_batches(2), world.mesh())
        assert isinstance(pipe, DataPipeline) and pipe.host is None
        assert len(list(pipe)) == 2
        with pytest.raises(ValueError, match="transform"):
            DataPipeline(_batches(2), world.mesh(), workers=2)


class TestEngineTrainsThroughPipeline:
    def _train(self, world, mode, epochs=2):
        from torchmpi_tpu.engine import AllReduceSGDEngine
        from torchmpi_tpu.models import mlp

        config.set("data_pipeline", mode)
        ds = synthetic_mnist(n=512, image_shape=(16,), n_classes=4)
        it = ShardedIterator(ds, global_batch=64, num_shards=world.size,
                             seed=11)
        params = mlp.init(jax.random.PRNGKey(0), in_dim=16, hidden=(32,),
                          n_classes=4)
        losses = []
        engine = AllReduceSGDEngine(
            mlp.loss_fn, lr=0.2, comm=world, mode="compiled",
            hooks={"on_update": lambda s: losses.append(s["loss"])})
        state = engine.train(params, it, epochs=epochs)
        acc = engine.test(
            state["params"],
            ShardedIterator(ds, global_batch=64, num_shards=world.size,
                            shuffle=False),
            mlp.accuracy)
        return [float(l) for l in losses], float(acc)

    def test_pipeline_on_off_identical_loss_trajectory(self, world):
        """The acceptance identity: training through the pipeline is
        bit-for-bit the same trajectory as the seed staging path."""
        losses_off, acc_off = self._train(world, "off")
        losses_on, acc_on = self._train(world, "on")
        assert losses_on == losses_off      # exact float equality
        assert acc_on == acc_off
        assert losses_on[-1] < 1.3          # and it actually learned

    def test_auto_wrap_trains_from_bare_batches(self, world):
        """train() over a plain list of numpy rank-major batches rides
        the pipeline under auto (no manual staging anywhere)."""
        from torchmpi_tpu.engine import AllReduceSGDEngine
        from torchmpi_tpu.models import mlp

        config.set("data_pipeline", "auto")
        rng = np.random.RandomState(0)
        batches = [(rng.randn(8, 8, 16).astype(np.float32),
                    rng.randint(0, 4, (8, 8)).astype(np.int32))
                   for _ in range(6)]
        params = mlp.init(jax.random.PRNGKey(0), in_dim=16, hidden=(32,),
                          n_classes=4)
        engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, comm=world,
                                    mode="compiled")
        state = engine.train(params, batches, epochs=2)
        assert np.isfinite(float(state["loss"]))

    def test_prestaged_wait_feeds_overlap_gauge(self, world):
        """The overlap gauge reads the pipeline's real wait: a straggling
        producer must pull the published overlap fraction DOWN even
        though the engine.stage span is a handoff (the satellite fix for
        sgdengine's blocked-time accounting)."""
        from torchmpi_tpu.engine import AllReduceSGDEngine
        from torchmpi_tpu.models import mlp
        from torchmpi_tpu.obs.metrics import registry as reg

        config.set("data_pipeline", "off")   # wrap by hand below
        config.set("obs_trace", True)        # turns the metrics feed on
        engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, comm=world,
                                    mode="compiled")

        def run(delay_s):
            # Fresh params per run: the compiled step donates them.
            params = mlp.init(jax.random.PRNGKey(0), in_dim=16,
                              hidden=(32,), n_classes=4)
            pipe = DataPipeline(_batches(8, p=8, b=8, d=16,
                                         delay_s=delay_s),
                                world.mesh())
            engine.train(params, pipe, epochs=1)
            return reg.gauge("tmpi_engine_overlap_fraction").value()

        overlap_fast = run(0.0)
        overlap_starved = run(0.25)
        assert overlap_starved < overlap_fast
        assert overlap_starved < 0.6
