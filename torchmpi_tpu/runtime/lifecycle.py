"""Process/mesh lifecycle: the TPU-native ``mpi.start`` / ``mpi.stop``.

The reference's ``MPI.start`` captures the hostname, loads the FFI, calls
``MPI_Init_thread(MPI_THREAD_MULTIPLE)``, pushes the global communicator,
binds the process to one CUDA device from ``OMPI_COMM_WORLD_LOCAL_RANK``,
runs an optional custom communicator hook, then builds the per-node 2-level
communicator and configures the collective selector
(reference: torchmpi/init.lua:31-99, :417-461; lib/torch_mpi.cpp:233-306).

TPU-native mapping: process-group creation is ``jax.distributed.initialize``
(PJRT/coordination service stands in for mpirun+MPI_Init); device binding is
implicit — PJRT enumerates the chips and a "rank" is a device, not a process;
the per-node communicator split keys on each device's host
(``process_index``), putting the fast intra-host ICI axis below the DCN axis.
"""

from __future__ import annotations

import atexit
import os
import socket
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax

from .. import _startup
from . import config
from . import handles as _handles
from .communicator import (
    Communicator,
    CommunicatorType,
    stack,
)

_state_lock = threading.RLock()
_started = False
_hostname: Optional[str] = None
_need_inter_node: bool = False
_distributed_initialized: bool = False
_process_index: int = 0


def _derive_span(name: str, t0_ns: int, t1_ns: int) -> None:
    """Register two of the start-up account's stamps as an observability
    span (no-op with obs_trace off), on the tracer's clock: the aligned
    cluster timeline when obs/clocksync.apply ran (raw monotonic otherwise,
    the offset defaults to 0)."""
    from ..obs import tracer as _obs_tracer

    if _obs_tracer.enabled():
        offset = _obs_tracer.clock_offset()
        _obs_tracer.record(name, t0_ns - offset, t1_ns - offset,
                           _obs_tracer.current_correlation())


def _backend_is_up() -> Optional[bool]:
    """Whether a JAX backend is initialised already (``None``: this JAX
    does not say)."""
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return None


def started() -> bool:
    return _started


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that stays
    put, and return it.  The directory is part of the cache key, so one
    that moves (a temporary name, a pid, the time) never hits.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing is
    set here; otherwise it is ``.jax_cache`` beside the package, i.e. at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _multi_host_env() -> bool:
    """Whether the environment announces a multi-host deployment that needs
    ``jax.distributed.initialize`` (TPU pod workers / explicit coordinator).
    Mirrors the reference reading launcher-provided env vars for its world
    shape (OMPI_COMM_WORLD_LOCAL_RANK etc., init.lua:70-80)."""
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return True
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    return False


def hostname() -> str:
    """Cached hostname, captured once at start (reference: init.lua:40-46 —
    captured *before* MPI init because forking after is unsafe; here it is
    merely cached for log prefixes)."""
    global _hostname
    if _hostname is None:
        _hostname = socket.gethostname()
    return _hostname


def start(
    with_tpu: bool = True,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    tree_communicators: bool = False,
    cartesian_communicators: Optional[bool] = None,
    custom_communicator_init: Optional[Callable[[], None]] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialise the runtime (reference: MPI.start, init.lua:31-99).

    Order mirrors the reference:
      1. hostname capture (init.lua:40-46),
      2. process-group creation — ``jax.distributed.initialize`` when
         multi-host coordinates are given or present in the environment
         (the ``MPI_Init_thread`` moment, torch_mpi.cpp:233-245),
      3. communicator-mode flags (init.lua:61-65),
      4. world communicator push (torch_mpi.cpp:247-249),
      5. optional custom communicator hook (init.lua:84-91),
      6. per-node two-level communicator split (init.lua:417-461),
      7. collective selector configuration (init.lua:463-555).

    ``devices`` overrides the world device list (tests use a subset or a CPU
    mesh); default is ``jax.devices()`` — every chip PJRT can see.
    """
    global _started, _need_inter_node
    # The start-up account's stamps (_startup.py), always taken; handed over
    # when the call returns, so one that raises leaves none.
    stamps = {"t_enter": time.monotonic_ns()}
    with _state_lock:
        if _started:
            raise RuntimeError("start() called twice without stop()")

        hostname()

        # (2) process group.  jax.distributed.initialize is only needed (and
        # only legal) in true multi-process deployments; single-controller
        # tests and single-host runs skip it.  Besides the explicit
        # coordinator_address, auto-initialize when the environment announces
        # a multi-host deployment — otherwise each host would silently form
        # its own world and data-parallel training would run split-brain.
        global _distributed_initialized
        if coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            _distributed_initialized = True
        elif _multi_host_env() and not _distributed_initialized:
            # jax itself reads only JAX_COORDINATOR_ADDRESS from the env;
            # the world shape the launcher plumbs (scripts/launch.sh
            # JAX_NUM_PROCESSES/JAX_PROCESS_ID) must be passed explicitly —
            # a bare initialize() off a TPU pod raises "Number of processes
            # must be defined".  All-None args keep pod auto-detection.
            def _ienv(*names):
                for n in names:
                    v = os.environ.get(n)
                    if v:
                        return int(v)
                return None

            jax.distributed.initialize(
                coordinator_address=os.environ.get("JAX_COORDINATOR_ADDRESS"),
                num_processes=_ienv("JAX_NUM_PROCESSES", "NUM_PROCESSES"),
                process_id=_ienv("JAX_PROCESS_ID", "PROCESS_ID"),
            )
            _distributed_initialized = True
        stamps["t_group"] = time.monotonic_ns()

        # (3) communicator-mode flags (reference: init.lua:61-65 forwarding
        # into torchmpi_set_tree|cartesian_communicator).  Written every
        # start so a previous session's mode cannot leak into this one.
        # Default: cartesian unless tree was requested.  An explicit
        # cartesian_communicators=False with tree_communicators=False selects
        # *flat* inter-links (single roots group) — a third mode the
        # reference reaches via kUseCartesian=false, kUseTree=false.
        if cartesian_communicators is None:
            cartesian_communicators = not tree_communicators
        if tree_communicators and cartesian_communicators:
            raise ValueError("tree and cartesian communicator modes are exclusive")
        config.set("use_tree_communicators", bool(tree_communicators))
        config.set("use_cartesian_communicators", bool(cartesian_communicators))

        # (4) world communicator.  The compile cache is settled before the
        # first device query, so every program of the run goes through it.
        use_compile_cache()
        stamps["backend_was_up"] = _backend_is_up()
        if devices is None:
            devices = jax.devices() if with_tpu else jax.devices("cpu")
        stamps["t_backend"] = time.monotonic_ns()
        world = Communicator(devices, name="global")
        stack.reset(world)

        # (5) custom hook, before the default per-node split
        # (reference: init.lua:84-91: presence of the hook suppresses the
        # default per-node communicator creation).
        if custom_communicator_init is not None:
            custom_communicator_init()
        else:
            _init_per_node_communicators(world)
        stamps["t_communicators"] = time.monotonic_ns()

        # (7) selector — imported lazily to avoid a cycle.
        from ..collectives import selector as _selector

        _selector.configure()

        # Captured while the runtime is definitely up: the shutdown
        # obsdump below runs after jax.distributed teardown, when
        # process_index may no longer answer.
        global _process_index
        try:
            _process_index = int(jax.process_index())
        except Exception:
            _process_index = 0

        _started = True
    stamps["t_selector"] = time.monotonic_ns()
    # Lifecycle boundaries register as spans (torchmpi_tpu/obs), derived
    # from the account's stamps: a restarted world's wiring cost shows up on
    # the merged timeline next to the transport frames it triggers.
    _derive_span("runtime.start", stamps["t_enter"], stamps["t_selector"])
    # Live telemetry endpoint (obs/serve.py, knob-gated off by default):
    # a fresh world is not draining, whatever a prior stop() left behind.
    from ..obs import serve as _obs_serve

    _obs_serve.health.set_draining(False)
    _obs_serve.maybe_start(rank=_process_index)
    # Job history plane (both knob-gated off by default): stamp the
    # journal's rank and start the metrics-history sampler beside the
    # endpoint — the trend feed /history serves and `tmpi-trace why`
    # reads post-hoc.
    from ..obs import history as _obs_history
    from ..obs import journal as _obs_journal

    _obs_journal.set_rank(_process_index)
    _obs_history.maybe_start(rank=_process_index)
    stamps["t_return"] = time.monotonic_ns()
    _startup.ACCOUNT.starts.append(stamps)


def _init_per_node_communicators(world: Communicator) -> None:
    """Split the world by host into a 2-level hierarchy
    (reference: initPerNodeCommunicators, init.lua:417-461).

    The reference scans cudaIPC peer access to build the intra-node group
    key; the TPU analogue of "devices with a fast private interconnect" is
    the set of chips owned by one host process (ICI domain), keyed by
    ``process_index``.  The collective span is then widened to cover both
    levels so hierarchical collectives traverse intra-ICI then DCN
    (reference: init.lua:445-446).
    """
    global _need_inter_node
    n_hosts = world.num_nodes()
    if n_hosts <= 1:
        _need_inter_node = False
        return
    level = stack.push(
        [str(d.process_index) for d in world.devices],
        name=f"host({hostname()})",
    )
    stack.set_collective_span(0, level + 1)
    _need_inter_node = stack.at(level).num_groups > 1


def need_inter_node_collectives() -> bool:
    """Whether any communicator level crosses hosts
    (reference: MPI.needInterNodeCollectives, init.lua:449)."""
    return _need_inter_node


def stop() -> None:
    """Tear down (reference: torchmpi_stop, torch_mpi.cpp:282-306): drain
    async work, stop the parameter-server thread, free retained resources,
    then drop the communicator stack.  Safe to call once after start()."""
    global _started, _need_inter_node, _distributed_initialized
    stamps = {"t_enter": time.monotonic_ns()}
    with _state_lock:
        if not _started:
            return
        # Flag the teardown on /healthz BEFORE the drains below: a
        # supervisor polling this rank must read "leaving on purpose",
        # not "wedged", for the duration of the stop.
        try:
            from ..obs import serve as _obs_serve

            _obs_serve.health.set_draining(True)
        except Exception:
            pass
        _handles.sync_all()
        try:
            from .. import parameterserver as _ps

            _ps.shutdown()
        except Exception:
            pass
        # Drop compiled collective executables so dead meshes aren't pinned
        # (the reference frees retained storages here, torch_mpi.cpp:292-300).
        from ..collectives import eager as _eager
        from ..collectives import pallas_ring as _pallas_ring
        from ..nn import _replica_stats_fn
        from ..data.staging import _local_mesh_rows

        _eager.clear_cache()
        _pallas_ring.clear_cache()
        _replica_stats_fn.cache_clear()
        _local_mesh_rows.cache_clear()
        stack.clear()
        _need_inter_node = False
        if _distributed_initialized:
            try:
                jax.distributed.shutdown()
            finally:
                _distributed_initialized = False
        _started = False
    stamps["t_down"] = time.monotonic_ns()
    _derive_span("runtime.stop", stamps["t_enter"], stamps["t_down"])
    # History sampler stops (final persist included) before the obsdump
    # so the on-disk history covers the teardown drain above.
    try:
        from ..obs import history as _obs_history

        _obs_history.stop()
    except Exception:
        pass
    _maybe_shutdown_obsdump()
    # The endpoint outlives the obsdump (a poller can watch the teardown
    # drain) and closes last; best-effort at interpreter exit.
    try:
        from ..obs import serve as _obs_serve

        _obs_serve.stop()
    except Exception:
        pass
    stamps["t_return"] = time.monotonic_ns()
    _startup.ACCOUNT.stops.append(stamps)


def _maybe_shutdown_obsdump() -> None:
    """With ``obs_dump_dir`` set, every rank leaves its self-describing
    ``obsdump-<rank>.json`` bundle behind at shutdown (after the stop
    span, so the teardown itself is on the timeline) — the input
    ``tmpi-trace merge-ranks`` / ``tmpi-trace report`` join into the
    cluster view.  Best-effort: a failed dump must not turn a clean stop
    into a crash."""
    from ..obs import aggregate as _obs_aggregate
    from ..obs import native as _obs_native

    dump_dir = _obs_native.cluster_config()["dump_dir"]
    if not dump_dir:
        return
    try:
        _obs_aggregate.write_obsdump(dump_dir, rank=_process_index)
    except Exception:
        from ..utils.logging import get_logger

        get_logger("torchmpi_tpu.lifecycle").exception(
            "shutdown obsdump to %s failed (suppressed)", dump_dir)


atexit.register(stop)


# ----------------------------------------------------------------- identity

def rank() -> int:
    """Process rank — alias of :func:`process_rank` (reference: mpi.rank()).

    Contract: the reference's one-process-one-GPU model splits into two
    clean pairs here, because one controller process drives many devices:

    * process plane — ``0 <= process_rank() < process_count()``;
    * device plane — ``0 <= r < size()`` for the device ranks ``r`` of a
      communicator (``Communicator.rank_of`` / :func:`local_device_ranks`).

    ``rank()``/``size()`` intentionally pair *across* the planes for
    reference-API familiarity; use the explicit pairs above when the
    distinction matters (``rank()`` never reaches ``size()-1`` on a pod).
    """
    return jax.process_index()


def process_rank() -> int:
    """This controller process's index: ``0 <= process_rank() <
    process_count()`` (the multi-host pair of :func:`rank`)."""
    return jax.process_index()


def process_count() -> int:
    """Number of controller processes (hosts) in the world."""
    return jax.process_count()


def size() -> int:
    """World size in *devices* (one rank per chip, the reference's
    one-process-one-GPU model mapped to one-device-per-rank).  Pairs with
    device ranks (``Communicator.rank_of``), not with :func:`rank`."""
    if stack.depth:
        return stack.world().size
    return len(jax.devices())


def local_device_ranks(comm: Optional[Communicator] = None) -> List[int]:
    """Device ranks (positions in ``comm``, default the world) owned by this
    process — the bridge between the process and device planes."""
    c = comm if comm is not None else (stack.world() if stack.depth else None)
    devices = c.devices if c is not None else jax.devices()
    me = jax.process_index()
    return [i for i, d in enumerate(devices) if d.process_index == me]


def local_devices() -> List[jax.Device]:
    return list(jax.local_devices())


def communicator_names() -> str:
    """Stack description (reference: mpi.communicatorNames, torch_mpi.cpp:105-127)."""
    return stack.names()


def barrier() -> None:
    """World barrier (reference: mpi.barrier).

    A zero-payload psum over the current communicator's devices, blocked on
    — every device must participate before any result materialises.
    """
    from ..collectives import eager as _eager

    _eager.barrier(stack.current())
