"""Failure detection and elastic recovery (runtime/failure.py) — new beyond
the reference (SURVEY.md §5.3: absent there; errors were fatal).  Heartbeat
liveness over localhost UDP, fault classification, and the checkpoint-fenced
elastic loop with device-count shrink on the virtual mesh."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchmpi_tpu.runtime import failure
from torchmpi_tpu.runtime.failure import free_udp_ports
from torchmpi_tpu.utils import checkpoint


def _wait_until(pred, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class TestHeartbeat:
    def test_all_alive(self):
        ports = free_udp_ports(3)
        eps = [("127.0.0.1", p) for p in ports]
        mons = [failure.HeartbeatMonitor(r, eps, interval=0.05)
                for r in range(3)]
        try:
            # Everyone should keep seeing everyone well past the timeout.
            time.sleep(0.6)
            for r, m in enumerate(mons):
                assert m.dead_peers() == [], (r, m.dead_peers())
                assert m.alive_peers() == [x for x in range(3) if x != r]
        finally:
            for m in mons:
                m.stop()

    def test_detects_dead_peer_once(self):
        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        deaths = []
        m0 = failure.HeartbeatMonitor(0, eps, interval=0.05,
                                      on_failure=deaths.append)
        m1 = failure.HeartbeatMonitor(1, eps, interval=0.05)
        try:
            time.sleep(0.3)
            assert m0.dead_peers() == []
            m1.stop()   # rank 1 dies
            assert _wait_until(lambda: m0.dead_peers() == [1]), m0.dead_peers()
            time.sleep(0.4)   # no duplicate callback on later sweeps
            assert deaths == [1], deaths
        finally:
            m0.stop()

    def test_job_token_rejects_foreign_traffic(self):
        """A monitor with a different job token (a stale process of a
        previous run, or a stray sender) must not refresh liveness — its
        datagrams fail the token check and its peer is never 'heard'."""
        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        m0 = failure.HeartbeatMonitor(0, eps, interval=0.05, token=1)
        m1 = failure.HeartbeatMonitor(1, eps, interval=0.05, token=2)
        try:
            time.sleep(0.5)
            assert m0.heard_peers() == [], m0.heard_peers()
            assert m1.heard_peers() == [], m1.heard_peers()
        finally:
            m0.stop()
            m1.stop()
        # Same endpoint list -> same default token: traffic accepted.
        m0 = failure.HeartbeatMonitor(0, eps, interval=0.05)
        m1 = failure.HeartbeatMonitor(1, eps, interval=0.05)
        try:
            assert _wait_until(lambda: m0.heard_peers() == [1])
        finally:
            m0.stop()
            m1.stop()

    def test_validation(self):
        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        with pytest.raises(ValueError):
            failure.HeartbeatMonitor(5, eps)
        with pytest.raises(ValueError):
            failure.HeartbeatMonitor(0, eps, interval=1.0, timeout=0.5)

    def test_lossy_udp_no_false_peer_death(self):
        """Pins the claim in failure.py:HeartbeatMonitor ('one lost ping
        does not kill a peer; timeout should span several intervals'): with
        a seeded 30% per-datagram drop rate — well inside the slack of
        timeout = 8 intervals — no peer is ever declared dead across many
        probe intervals, in either direction."""
        import random

        class LossySock:
            """Wraps the monitor's UDP socket, dropping sends with a
            deterministic seeded coin — the chaos-proxy idea applied to
            the datagram plane (a TCP proxy can't carry UDP)."""

            def __init__(self, sock, rate, seed):
                self._sock = sock
                self._rate = rate
                self._rng = random.Random(seed)

            def sendto(self, data, addr):
                if self._rng.random() < self._rate:
                    return len(data)   # swallowed by the 'network'
                return self._sock.sendto(data, addr)

            def __getattr__(self, name):
                return getattr(self._sock, name)

        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        interval, timeout = 0.05, 0.4   # 8 intervals of slack
        mons = [failure.HeartbeatMonitor(r, eps, interval=interval,
                                         timeout=timeout)
                for r in range(2)]
        try:
            for r, m in enumerate(mons):
                m._sock = LossySock(m._sock, rate=0.3, seed=100 + r)
            time.sleep(2.5)   # ~50 probe intervals under 30% loss
            for r, m in enumerate(mons):
                assert m.dead_peers() == [], (r, m.dead_peers())
                assert m.heard_peers() == [1 - r], (r, m.heard_peers())
        finally:
            for m in mons:
                m.stop()

    def test_startup_grace_spans_slow_peers(self):
        """A peer that has never spoken gets startup_grace (not timeout)
        before it can be declared dead — peers launch at different times."""
        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        m = failure.HeartbeatMonitor(0, eps, interval=0.05, timeout=0.15,
                                     startup_grace=10.0)
        try:
            time.sleep(0.5)   # well past timeout; rank 1 never started
            assert m.dead_peers() == []
        finally:
            m.stop()
        m = failure.HeartbeatMonitor(0, eps, interval=0.05, timeout=0.15,
                                     startup_grace=0.2)
        try:
            assert _wait_until(lambda: m.dead_peers() == [1])
        finally:
            m.stop()


class TestClassification:
    def test_injector_fires_once_per_step(self):
        inj = failure.FaultInjector([2, 5])
        inj.maybe_fail(0)
        with pytest.raises(failure.InjectedFault):
            inj.maybe_fail(2)
        inj.maybe_fail(2)   # consumed
        with pytest.raises(failure.InjectedFault):
            inj.maybe_fail(5)
        assert inj.fired == [2, 5]

    def test_injector_duplicate_steps_fire_each(self):
        """A step listed twice faults its first two occurrences — the
        elastic loop replays steps after restore, so this drills repeated
        failure of the same step."""
        inj = failure.FaultInjector([3, 3])
        for _ in range(2):
            with pytest.raises(failure.InjectedFault):
                inj.maybe_fail(3)
        inj.maybe_fail(3)   # budget consumed
        assert inj.fired == [3, 3]

    def test_is_device_failure(self):
        assert failure.is_device_failure(failure.InjectedFault("x"))
        assert failure.is_device_failure(RuntimeError("device lost: UNAVAILABLE"))
        assert not failure.is_device_failure(TypeError("bad arg"))
        assert not failure.is_device_failure(ValueError("shape mismatch"))
        assert not failure.is_device_failure(RuntimeError("plain logic error"))
        # The word "device" alone must NOT classify: disk-full and
        # wrong-device programming errors are not recoverable chip faults.
        assert not failure.is_device_failure(OSError(28, "No space left on device"))
        assert not failure.is_device_failure(RuntimeError("tensor on wrong device"))
        # XlaRuntimeError classifies by status code: chip loss yes,
        # deterministic OOM no (replay would just OOM again).
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert failure.is_device_failure(
            XlaRuntimeError("UNAVAILABLE: device coredump"))
        assert not failure.is_device_failure(
            XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))


def _quadratic_builder(ckpt_template, target, lr=0.35):
    """build(devices, restored) for run_elastic: SGD on ||w - target||^2 with
    w replicated over a dp mesh of exactly the given devices."""

    def build(devices, restored):
        mesh = Mesh(np.array(devices), ("dp",))
        repl = NamedSharding(mesh, P())
        if restored is None:
            w = jnp.zeros_like(jnp.asarray(target))
            start = {"params": {"w": w}, "loss": jnp.inf}
        else:
            start = restored
        state = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), repl),
                             start)

        @jax.jit
        def step_fn(state, step):
            w = state["params"]["w"]
            g = 2 * (w - jnp.asarray(target))
            w = w - lr * g
            return {"params": {"w": w},
                    "loss": jnp.sum((w - jnp.asarray(target)) ** 2)}

        return state, lambda s, i: step_fn(s, i)

    return build


class TestElastic:
    def test_runs_to_completion_without_faults(self, devices, tmp_path):
        target = np.arange(4.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        out = failure.run_elastic(_quadratic_builder(None, target), mgr,
                                  n_steps=10, devices=devices)
        assert out["restarts"] == 0 and out["steps_run"] == 10
        np.testing.assert_allclose(np.asarray(out["state"]["params"]["w"]),
                                   target, atol=1e-2)

    def test_recovers_from_injected_fault(self, devices, tmp_path):
        target = np.arange(4.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        inj = failure.FaultInjector([5])
        restarts = []
        out = failure.run_elastic(
            _quadratic_builder(None, target), mgr, n_steps=10,
            devices=devices, injector=inj,
            on_restart=lambda n, exc: restarts.append((n, type(exc).__name__)))
        assert out["restarts"] == 1
        assert restarts == [(1, "InjectedFault")]
        # Replay from the checkpointed step: total successful steps > 10 - 1
        # is not required, but the final state must have converged.
        np.testing.assert_allclose(np.asarray(out["state"]["params"]["w"]),
                                   target, atol=1e-2)

    def test_elastic_shrink_to_fewer_devices(self, devices, tmp_path):
        """After the fault only 4 of 8 devices are healthy: the loop must
        rebuild on the survivors and keep training from the checkpoint."""
        target = np.arange(8.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        inj = failure.FaultInjector([6])
        pool = {"devices": list(devices)}
        seen_meshes = []

        base = _quadratic_builder(None, target)

        def build(devs, restored):
            seen_meshes.append(len(devs))
            return base(devs, restored)

        def healthy():
            pool["devices"] = pool["devices"][:4]
            return pool["devices"]

        out = failure.run_elastic(build, mgr, n_steps=12, devices=devices,
                                  injector=inj, healthy_devices=healthy)
        assert out["restarts"] == 1
        assert seen_meshes == [8, 4]
        state = out["state"]
        assert len(state["params"]["w"].sharding.device_set) == 4
        np.testing.assert_allclose(np.asarray(state["params"]["w"]),
                                   target, atol=1e-2)

    def test_fault_during_recovery_consumes_budget(self, devices, tmp_path):
        """A second fault raised inside the rebuild itself (e.g. the device
        list still names the dead chip) must consume a restart, not escape."""
        target = np.arange(4.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        inj = failure.FaultInjector([4])
        base = _quadratic_builder(None, target)
        calls = {"n": 0}

        def build(devs, restored):
            calls["n"] += 1
            if calls["n"] == 2:    # first rebuild after the step fault
                raise failure.InjectedFault("chip still dead during rebuild")
            return base(devs, restored)

        out = failure.run_elastic(build, mgr, n_steps=10, devices=devices,
                                  injector=inj, max_restarts=3)
        assert out["restarts"] == 2 and calls["n"] == 3
        np.testing.assert_allclose(np.asarray(out["state"]["params"]["w"]),
                                   target, atol=1e-2)

    def test_stop_from_on_failure_callback(self):
        """docs/failure.md wires teardown into on_failure; stop() from that
        callback (the prober thread) must not deadlock or raise."""
        ports = free_udp_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        stopped = []
        holder = {}

        def teardown(rank):
            holder["m"].stop()
            stopped.append(rank)

        holder["m"] = failure.HeartbeatMonitor(
            0, eps, interval=0.05, timeout=0.15, startup_grace=0.2,
            on_failure=teardown)
        assert _wait_until(lambda: stopped == [1]), stopped
        # Socket really closed and threads wound down.
        assert holder["m"]._stop.is_set()
        assert _wait_until(lambda: not holder["m"]._rx.is_alive())

    def test_non_device_errors_reraise(self, devices, tmp_path):
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)

        def build(devs, restored):
            def step_fn(s, i):
                raise TypeError("programming error")
            return {"params": {"w": jnp.zeros(2)}}, step_fn

        with pytest.raises(TypeError):
            failure.run_elastic(build, mgr, n_steps=3, devices=devices)

    def test_restart_budget_exhausted(self, devices, tmp_path):
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=1)
        inj = failure.FaultInjector([1, 2, 3])
        target = np.arange(2.0, dtype=np.float32)
        with pytest.raises(failure.InjectedFault):
            failure.run_elastic(_quadratic_builder(None, target), mgr,
                                n_steps=6, devices=devices, injector=inj,
                                max_restarts=2)


class TestWatchdogAndAbort:
    def test_watchdog_fires_on_stall(self):
        """No kick for > timeout -> expiry action fires (the test seam
        stands in for the production os._exit)."""
        import threading

        fired = threading.Event()
        wd = failure.Watchdog(timeout=0.4, _on_expire=fired.set)
        try:
            assert fired.wait(2.0), "watchdog did not fire on stall"
        finally:
            wd.stop()

    def test_watchdog_kicks_keep_it_quiet(self):
        import threading

        fired = threading.Event()
        wd = failure.Watchdog(timeout=0.5, _on_expire=fired.set)
        try:
            for _ in range(8):
                time.sleep(0.1)
                wd.kick()
            assert not fired.is_set()
        finally:
            wd.stop()

    def test_watchdog_validation(self):
        with pytest.raises(ValueError):
            failure.Watchdog(timeout=0.0)

    def test_run_elastic_kicks_watchdog_and_stops_it(self, devices,
                                                     tmp_path):
        """The run_elastic wiring: fast steps keep the watchdog quiet,
        and the loop stops it on return (no expiry after completion)."""
        import threading

        target = np.arange(4.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        fired = threading.Event()
        wd = failure.Watchdog(timeout=30.0, _on_expire=fired.set)
        out = failure.run_elastic(_quadratic_builder(None, target), mgr,
                                  n_steps=6, devices=devices, watchdog=wd)
        assert out["steps_run"] == 6
        assert not fired.is_set()
        assert not wd._thread.is_alive()     # stopped on return

    def test_run_elastic_watchdog_converts_wedged_step(self, devices,
                                                       tmp_path):
        """A step_fn that stops making progress (the in-collective wedge
        heartbeats cannot see) expires the watchdog while the step is
        still stuck — the production action is os._exit(EXIT_STALLED);
        the seam records the firing instead."""
        import threading

        target = np.arange(4.0, dtype=np.float32)
        mgr = checkpoint.CheckpointManager(str(tmp_path), save_interval=2)
        fired = threading.Event()
        wd = failure.Watchdog(timeout=0.4, _on_expire=fired.set)
        base = _quadratic_builder(None, target)

        def build(devs, restored):
            state, step_fn = base(devs, restored)

            def wedging(s, i):
                if i == 2:
                    # "Wedged in a collective": wait long enough that the
                    # only way `fired` gets set is the watchdog expiring
                    # DURING the stuck step.
                    assert fired.wait(10.0), \
                        "watchdog never fired during the wedged step"
                return step_fn(s, i)

            return state, wedging

        out = failure.run_elastic(build, mgr, n_steps=4, devices=devices,
                                  watchdog=wd)
        assert out["steps_run"] == 4     # the seam lets the run finish
        assert fired.is_set()
        assert not wd._thread.is_alive()

    def test_abort_on_peer_failure_exits_process(self):
        """The heartbeat->exit bridge: a subprocess whose peer vanishes
        force-exits with EXIT_PEER_FAILURE even though its main thread is
        wedged in an endless sleep (the launcher then re-forms the job)."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import sys, time\n"
            f"sys.path.insert(0, {repo!r})\n"
            "from torchmpi_tpu.runtime import failure\n"
            "eps = [('127.0.0.1', p) for p in failure.free_udp_ports(2)]\n"
            "mon = failure.HeartbeatMonitor(\n"
            "    0, eps, interval=0.05, timeout=0.3, startup_grace=0.5,\n"
            "    on_failure=failure.abort_on_peer_failure(0))\n"
            "time.sleep(60)  # 'wedged' main thread; peer 1 never comes up\n"
        )
        # Pin the child to CPU: the watchdog under test is pure-socket and
        # needs no backend, and a child that reached for an accelerator
        # would fight its parent for it.
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == failure.EXIT_PEER_FAILURE, (
            r.returncode, r.stderr[-500:])
