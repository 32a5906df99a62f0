"""Utility-layer tests: profiler windows / bench-timer discipline
(reference: sgdengine.lua:38-63 NVPROF windowing, tester.lua:61-126 timing,
collectives_all.lua:192-199 dispatch-latency assertion) and rank-prefixed
logging (wrap.sh:69-77)."""

import os
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.utils.profiler import (StepWindowProfiler, Timer,
                                         assert_dispatch_latency,
                                         profiler_hooks)


class TestStepWindowProfiler:
    def test_window_produces_trace(self, tmp_path):
        """Steps [start, end) are bracketed by one jax.profiler trace whose
        files land in the logdir (the NVPROF steady-state window)."""
        logdir = str(tmp_path / "tr")
        prof = StepWindowProfiler(logdir=logdir, start_step=2, end_step=4,
                                  enabled=True)
        f = jax.jit(lambda x: x * 2 + 1)
        x = jnp.arange(8.0)
        for t in range(6):
            x = f(x)
            prof.step(t)
        prof.stop()   # idempotent after the window
        # trace_path names the run directory THIS capture dumped
        # (<logdir>/plugins/profile/<run>/), not the logdir root — the
        # root accumulates every capture ever taken there.
        assert prof.trace_path is not None
        assert prof.trace_path.startswith(logdir)
        assert os.path.isdir(prof.trace_path)
        assert prof.trace_path != logdir
        files = [os.path.join(dp, f2)
                 for dp, _, fs in os.walk(prof.trace_path) for f2 in fs]
        assert files, "no trace files written in the run dir"

    def test_disabled_by_default_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TPU_PROFILE", raising=False)
        prof = StepWindowProfiler(logdir=str(tmp_path))
        for t in range(10):
            prof.step(t)
        assert prof.trace_path is None

    def test_engine_hooks_drive_window(self, world, tmp_path):
        """profiler_hooks wires the window into the engine's hook protocol
        (reference: the engine's NVPROF hook)."""
        from torchmpi_tpu.engine import AllReduceSGDEngine
        from torchmpi_tpu.models import mlp
        from torchmpi_tpu.utils.data import ShardedIterator, synthetic_mnist

        prof = StepWindowProfiler(logdir=str(tmp_path / "tr"), start_step=1,
                                  end_step=3, enabled=True)
        ds = synthetic_mnist(n=256, image_shape=(8, 8), n_classes=4)
        it = ShardedIterator(ds, global_batch=64, num_shards=world.size)
        engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, comm=world,
                                    hooks=profiler_hooks(prof))
        engine.train(mlp.init(jax.random.PRNGKey(0), in_dim=64, hidden=(16,),
                              n_classes=4), it, epochs=1)
        assert prof.trace_path is not None
        # The engine installed the window, so the text of its compiled step
        # stands beside the capture and the run record knows the window.
        with open(os.path.join(prof.trace_path, "step.hlo.txt")) as fh:
            assert 'op_name="jit(step)/optimizer/' in fh.read()
        assert engine.last_run.profiler is prof
        # A CPU capture records host threads alone: said, not read as zeros.
        with pytest.raises(ValueError, match="no whole step"):
            engine.last_run.device


class TestTimer:
    def test_warmup_skipped(self):
        """Timer averages only the timed runs (reference warmup-skip
        protocol, tester.lua:61-126)."""
        calls = []

        def fn():
            calls.append(time.perf_counter())
            time.sleep(0.01)

        mean = Timer(warmup=3, runs=4).measure(fn)
        assert len(calls) == 7
        assert 0.005 < mean < 0.1


class TestDispatchLatency:
    def test_fast_dispatch_passes(self):
        best = assert_dispatch_latency(lambda: None, budget_s=1.0, tries=3)
        assert best < 1.0

    def test_slow_dispatch_warns(self):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert_dispatch_latency(lambda: time.sleep(0.002),
                                    budget_s=1e-6, tries=2)
        assert any("latency" in str(x.message) for x in w)


class TestLogging:
    def test_log_to_file_per_rank(self, tmp_path, monkeypatch):
        """LOG_TO_FILE=1 writes <dir>/rank_<r>.log with the [rank/size]
        prefix (wrap.sh:69-77)."""
        import importlib

        from torchmpi_tpu.utils import logging as tlog

        monkeypatch.setenv("LOG_TO_FILE", "1")
        monkeypatch.setenv("TORCHMPI_TPU_LOG_DIR", str(tmp_path))
        importlib.reload(tlog)
        logger = tlog.get_logger("tmpi-test-logger")
        logger.info("hello from the test")
        for h in logger.handlers:
            h.flush()
        path = tmp_path / "rank_0.log"
        assert path.exists()
        content = path.read_text()
        assert "hello from the test" in content and "[0/1]" in content
