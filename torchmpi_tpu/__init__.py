"""torchmpi_tpu — a TPU-native distributed training framework with the
capabilities of TorchMPI (reference: facebookresearch/TorchMPI, mounted at
/root/reference), redesigned for JAX/XLA/Pallas over PJRT.

Typical usage mirrors the reference's 4-step recipe (reference: README.md:20-41):

    import torchmpi_tpu as mpi
    mpi.start()
    ...shard data by rank, broadcast initial params,
       pmean(grads) each step, SGD...
    mpi.stop()

Top-level namespace = the reference's ``mpi`` table (torchmpi/init.lua):
lifecycle (:func:`start`/:func:`stop`/:func:`rank`/:func:`size`/
:func:`barrier`), communicator stack management, sync/async collectives and
handle waits.  Subpackages: ``collectives``, ``nn``, ``engine``,
``parameterserver``, ``parallel``, ``models``, ``utils``.
"""

from ._startup import ACCOUNT as _ACCOUNT  # first: it stamps the import's start

from .version import __version__  # noqa: F401

from .runtime import (  # noqa: F401
    Communicator,
    CommunicatorGuard,
    CommunicatorType,
    SynchronizationHandle,
    barrier,
    communicator_names,
    config,
    hostname,
    local_device_ranks,
    local_devices,
    need_inter_node_collectives,
    process_count,
    process_rank,
    rank,
    size,
    stack,
    start,
    started,
    stop,
    sync_all,
)
from .runtime.handles import wait as sync_handle  # noqa: F401  (mpi.syncHandle)
from .runtime.handles import wait_all as sync_handles  # noqa: F401

from . import collectives  # noqa: F401
from .collectives import (  # noqa: F401
    allgather,
    allgatherv,
    allreduce,
    allreduce_scalar,
    alltoall,
    async_,
    broadcast,
    broadcast_scalar,
    reduce,
    reduce_scalar,
    reduce_scatter,
    sendreceive,
    sendreceive_scalar,
)
from .collectives.selector import availability as collective_availability  # noqa: F401


def push_communicator(keys, name=None):
    """Split the current communicator by per-rank key
    (reference: torchmpi_push_communicator, torch_mpi.cpp:251-259)."""
    return stack.push(keys, name=name)


def set_communicator(level, type=CommunicatorType.INTRA):
    """Move the (level, intra/inter) cursor (reference: torch_mpi.cpp:261-264)."""
    stack.set_communicator(level, type)


def set_collective_span(begin, end):
    """Bound hierarchical collectives to levels [begin, end)
    (reference: torch_mpi.cpp:84-95)."""
    stack.set_collective_span(begin, end)


def num_nodes_in_communicator():
    """Distinct hosts in the current communicator
    (reference: torchmpi_num_nodes_in_communicator, torch_mpi.cpp:321-350)."""
    return stack.current().num_nodes()


def startup():
    """This process's start-up account (``_startup.py``): the package's
    import, ``start()`` and ``stop()`` by their parts, and every program the
    process traced, lowered, compiled or loaded, on the clock a profiler
    capture shares.  ``mpi.startup().summary()`` is the table."""
    return _ACCOUNT


_ACCOUNT.imported()     # last: the import's end; jax is here, so listen
