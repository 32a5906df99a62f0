"""Eager collectives over rank-major arrays — the TPU-native L2.

The reference's collectives engine operates on dense tensors, one resident
per rank/process (reference: lib/collectives.cpp:126-455 CPU,
lib/collectives_cuda.cpp:36-366 GPU, custom rings lib/detail/*).  The
TPU-native data model replacing "one tensor per rank" is the **rank-major
array**: a single ``jax.Array`` of shape ``(p, *s)`` sharded over axis 0
across the communicator's devices, so shard ``r`` *is* rank ``r``'s tensor.
Collectives are ``shard_map``-ped XLA collectives over the communicator's
mesh — XLA lowers them onto ICI/DCN rings, replacing the reference's
hand-built chunked ring transports (lib/detail/collectives_cuda.cpp:202-899)
and their communication plans (lib/resources.cpp:588-678).

Grouped variants (``groups=...``) run the collective independently inside
rank subgroups via XLA ``replica_groups`` — the mechanism behind
intra/inter/tree hierarchical composition (see hierarchical.py).  Ranks not
in any group are placed in singleton groups, i.e. they keep their value, the
SPMD analogue of "not a member of this MPI communicator".

Sync variants block until the result is resident (the reference's sync
collectives); async variants return a :class:`SynchronizationHandle`
immediately — JAX dispatch is already asynchronous, so the handle's wait is
``block_until_ready``, replacing the offload-pool futures
(reference: lib/resources.cpp:399-481).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..runtime import config
from ..runtime.communicator import Communicator, RANK_AXIS
from ..runtime.handles import SynchronizationHandle, in_flight

Groups = Optional[Tuple[Tuple[int, ...], ...]]

_REDUCE_OPS = ("sum", "max", "min", "mean")


# --------------------------------------------------------------------------
# data movement: host <-> rank-major
# --------------------------------------------------------------------------

def _rank_sharding(comm: Communicator) -> NamedSharding:
    return NamedSharding(comm.mesh(), P(RANK_AXIS))


def shard(comm: Communicator, per_rank: Any) -> jax.Array:
    """Build a rank-major array from per-rank values.

    ``per_rank`` is a sequence of ``p`` equal-shaped arrays (rank r's tensor)
    or an already-stacked ``(p, *s)`` array.  This replaces the reference's
    implicit placement "the tensor lives on my GPU" (one process per device).

    Multi-controller (``jax.process_count() > 1``): each process contributes
    only the rows its devices own via
    ``jax.make_array_from_process_local_data`` — no host ever materializes a
    device buffer for rows it cannot address (the reference analogue: each
    node only pins its own GPUs' tensors).  All processes still pass the
    same full ``(p, *s)`` host array (cheap: host RAM, not HBM).
    """
    if isinstance(per_rank, (list, tuple)):
        stacked = np.stack([np.asarray(v) for v in per_rank])
    else:
        stacked = np.asarray(per_rank) if not isinstance(per_rank, jax.Array) else per_rank
    if stacked.shape[0] != comm.size:
        raise ValueError(
            f"rank-major leading dim {stacked.shape[0]} != communicator size {comm.size}"
        )
    sh = _rank_sharding(comm)
    if isinstance(stacked, jax.Array) or jax.process_count() == 1:
        return jax.device_put(stacked, sh)
    from ..runtime.lifecycle import local_device_ranks

    local = np.ascontiguousarray(stacked[np.asarray(local_device_ranks(comm))])
    return jax.make_array_from_process_local_data(sh, local, stacked.shape)


def fill_by_rank(comm: Communicator, shape: Sequence[int], dtype=jnp.float32,
                 fn: Callable[[int], Any] = lambda r: r) -> jax.Array:
    """Rank-dependent fill, the test workhorse (reference:
    test/collectives_all.lua:52-54 — fill = rank makes results algebraic)."""
    per = [np.full(tuple(shape), fn(r), dtype=dtype) for r in range(comm.size)]
    return shard(comm, per)


def to_numpy(x: jax.Array) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def rank_slice(x: jax.Array, r: int) -> np.ndarray:
    """Rank r's tensor out of a rank-major array."""
    return to_numpy(x)[r]


# --------------------------------------------------------------------------
# group plumbing
# --------------------------------------------------------------------------

def _complete_groups(comm: Communicator, groups: Groups) -> Groups:
    """Extend ``groups`` with singletons so they partition all ranks.

    XLA replica_groups must cover every participant; ranks outside the
    requested groups become singletons (collective = identity), modelling
    non-membership of an MPI sub-communicator.
    """
    if groups is None:
        return None
    covered = set()
    for g in groups:
        covered.update(g)
    missing = [r for r in range(comm.size) if r not in covered]
    full = tuple(tuple(g) for g in groups) + tuple((r,) for r in missing)
    return full


def _group_tables(comm: Communicator, groups: Groups) -> Tuple[np.ndarray, np.ndarray]:
    """Per-rank (position-in-group, group-size) lookup tables, embedded as
    constants in the compiled body and indexed by ``axis_index``."""
    p = comm.size
    pos = np.zeros((p,), dtype=np.int32)
    gsize = np.full((p,), p, dtype=np.int32)
    if groups is None:
        pos[:] = np.arange(p)
    else:
        for g in groups:
            for i, r in enumerate(g):
                pos[r] = i
                gsize[r] = len(g)
    return pos, gsize


def _member_table(comm: Communicator, user_groups: Groups) -> np.ndarray:
    """True for ranks covered by the *user's* groups (before singleton
    completion) — non-members must keep their value in rooted collectives."""
    p = comm.size
    member = np.ones((p,), dtype=bool)
    if user_groups is not None:
        member[:] = False
        for g in user_groups:
            for r in g:
                member[r] = True
    return member


def _validate_rooted_groups(comm: Communicator, user_groups: Groups, root: int) -> None:
    """Every group must actually contain position ``root`` — MPI errors on a
    root outside the communicator; we mirror that host-side.  With no groups,
    the whole communicator is the group."""
    if root < 0:
        raise ValueError(f"root must be non-negative, got {root}")
    sizes = [len(g) for g in user_groups] if user_groups is not None else [comm.size]
    for s in sizes:
        if root >= s:
            raise ValueError(
                f"root position {root} out of range for group of size {s}"
            )


def _validate_full_equal_groups(comm: Communicator, user_groups: Groups,
                                what: str) -> None:
    """Shape-changing grouped collectives (allgather, reduce_scatter) need
    every rank covered and all groups equal-sized — otherwise per-rank output
    shapes would differ, which SPMD cannot express."""
    if user_groups is None:
        return
    covered = sorted(r for g in user_groups for r in g)
    if covered != list(range(comm.size)):
        raise ValueError(
            f"grouped {what} requires groups covering every rank "
            f"(uncovered ranks would need a different output shape); "
            f"got coverage {covered} of {comm.size} ranks"
        )
    sizes = {len(g) for g in user_groups}
    if len(sizes) != 1:
        raise ValueError(
            f"grouped {what} requires equal-sized groups, got sizes "
            f"{sorted(len(g) for g in user_groups)}"
        )


# --------------------------------------------------------------------------
# compiled collective bodies (cached per communicator/op/groups)
# --------------------------------------------------------------------------

_jit_cache: Dict[Any, Callable] = {}


def _cached(comm: Communicator, key: Tuple, builder: Callable[[], Callable]) -> Callable:
    # Keyed on the Mesh itself (hashable by device grid + axis names), not
    # id(): a freed mesh's address can be reused by a NEW mesh, which would
    # silently serve an executable bound to the old device layout.  Keying
    # the object also pins it alive exactly as long as its executable is
    # cached; stop() clears both together.
    full_key = (comm.mesh(), key)
    fn = _jit_cache.get(full_key)
    if fn is None:
        fn = builder()
        _jit_cache[full_key] = fn
    return fn


def clear_cache() -> None:
    """Drop all compiled collective executables.  Called by ``stop()`` so
    dead meshes/devices are not pinned across start/stop cycles — the analogue
    of the reference freeing retained storages at teardown
    (torch_mpi.cpp:282-306)."""
    _jit_cache.clear()


def _psum_like(op: str, x, axis, groups):
    if op == "sum" or op == "mean":
        out = lax.psum(x, axis, axis_index_groups=groups)
        return out
    if op == "max":
        return lax.pmax(x, axis, axis_index_groups=groups)
    if op == "min":
        return lax.pmin(x, axis, axis_index_groups=groups)
    raise ValueError(f"unsupported reduction {op!r} (have {_REDUCE_OPS})")


def _mean_div(op: str, out, gsize_of_me):
    if op == "mean":
        return out / gsize_of_me.astype(out.dtype)
    return out


def _make_allreduce(comm: Communicator, op: str, groups: Groups) -> Callable:
    mesh = comm.mesh()
    pos, gsize = _group_tables(comm, groups)
    gsize_c = jnp.asarray(gsize)

    def body(x):
        out = _psum_like(op, x, RANK_AXIS, groups)
        me = lax.axis_index(RANK_AXIS)
        return _mean_div(op, out, gsize_c[me])

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_broadcast(comm: Communicator, root: int, groups: Groups,
                    member: np.ndarray) -> Callable:
    """Broadcast as a masked psum: only the root contributes, everyone in the
    group receives the sum — one XLA collective, the latency-optimal shape
    for small messages (the reference's small-bcast path,
    collectives.cpp:142-147 cutoffs; large messages: XLA pipelines it).

    ``root`` is an *intra-group position* when groups are given, a rank
    otherwise (reference broadcast semantics: root rank of current comm).
    Non-member ranks (singleton completion groups) contribute their own value
    so they keep it — non-membership of an MPI communicator.
    """
    mesh = comm.mesh()
    pos, _ = _group_tables(comm, groups)
    pos_c = jnp.asarray(pos)
    member_c = jnp.asarray(member)

    def body(x):
        me = lax.axis_index(RANK_AXIS)
        is_contributor = jnp.where(member_c[me], pos_c[me] == root, True)
        contrib = jnp.where(is_contributor, x, jnp.zeros_like(x))
        return lax.psum(contrib, RANK_AXIS, axis_index_groups=groups)

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_reduce(comm: Communicator, root: int, op: str, groups: Groups) -> Callable:
    """Reduce-to-root: root gets the reduction, others keep their input
    (reference: lib/collectives.cpp reduce — non-root outputs untouched)."""
    mesh = comm.mesh()
    pos, gsize = _group_tables(comm, groups)
    pos_c = jnp.asarray(pos)
    gsize_c = jnp.asarray(gsize)

    def body(x):
        s = _psum_like(op, x, RANK_AXIS, groups)
        me = lax.axis_index(RANK_AXIS)
        s = _mean_div(op, s, gsize_c[me])
        return jnp.where(pos_c[me] == root, s, x)

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_allgather(comm: Communicator, groups: Groups) -> Callable:
    """Allgather along axis 0 of each rank's tensor; with groups, gathers
    within each (equal-sized) group.  Mirrors the reference's gatherv with
    auto-resized output (collectives.cpp:245-290): output leading dim is
    group_size x n."""
    mesh = comm.mesh()
    if groups is not None:
        sizes = {len(g) for g in groups}
        if len(sizes) != 1:
            raise ValueError("grouped allgather requires equal-sized groups "
                             "(uneven tree groups: gather per group instead)")

    def body(x):
        # x: (1, *s) block -> (group, *s)
        g = lax.all_gather(x[0], RANK_AXIS, axis=0, tiled=False,
                           axis_index_groups=groups)
        return g[None]

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_allgatherv(comm: Communicator, groups: Groups) -> Callable:
    """Uneven-group allgather: every rank's output is padded to the largest
    group (the SPMD-expressible form of the reference's auto-resizing
    gatherv, collectives.cpp:245-290 — per-rank output *shapes* must agree
    under one compiled program, so smaller groups zero-pad).

    Implementation gathers the full axis then selects each rank's group
    members with a static index table — O(p) traffic instead of O(group),
    the price of shape uniformity; use :func:`allgather` when groups are
    equal-sized."""
    mesh = comm.mesh()
    p = comm.size
    gmax = max(len(g) for g in groups)
    idx = np.zeros((p, gmax), np.int32)
    valid = np.zeros((p, gmax), bool)
    for g in groups:
        for r in g:
            idx[r, :len(g)] = g
            valid[r, :len(g)] = True
    idx_c, valid_c = jnp.asarray(idx), jnp.asarray(valid)

    def body(x):
        # x: (1, *s) block -> (gmax, *s), zero rows past the group size.
        full = lax.all_gather(x[0], RANK_AXIS, axis=0, tiled=False)  # (p, *s)
        me = lax.axis_index(RANK_AXIS)
        rows = jnp.take(full, idx_c[me], axis=0)
        mask = valid_c[me].reshape((gmax,) + (1,) * (full.ndim - 1))
        return jnp.where(mask, rows, 0)[None]

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_reduce_scatter(comm: Communicator, op: str, groups: Groups) -> Callable:
    """Ring reduce-scatter: rank r of each group ends with the r-th chunk of
    the group reduction — the first half of the reference's ring allreduce
    plan (lib/detail/README.md:1-48, resources.cpp:588-678), as a native XLA
    collective."""
    mesh = comm.mesh()
    if op not in ("sum", "mean"):
        raise ValueError("reduce_scatter supports sum/mean")
    _, gsize = _group_tables(comm, groups)
    gsize_c = jnp.asarray(gsize)

    def body(x):
        # x: (1, n) block; scatter along the last data axis.
        out = lax.psum_scatter(x, RANK_AXIS, scatter_dimension=1, tiled=True,
                               axis_index_groups=groups)
        me = lax.axis_index(RANK_AXIS)
        return _mean_div(op, out, gsize_c[me])

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_sendreceive(comm: Communicator, src: int, dst: int) -> Callable:
    """sendrecv_replace: dst's tensor becomes src's, everyone else unchanged
    (reference: lib/collectives.cpp sendreceive / Sendrecv_replace)."""
    mesh = comm.mesh()

    def body(x):
        moved = lax.ppermute(x, RANK_AXIS, perm=[(src, dst)])
        me = lax.axis_index(RANK_AXIS)
        return jnp.where(me == dst, moved, x)

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_alltoall(comm: Communicator) -> Callable:
    """All-to-all: rank r sends chunk i of its tensor to rank i (chunked on
    the leading data axis).  Not in the reference's collective set — added
    because it is the primitive behind Ulysses sequence parallelism (§5.7)."""
    mesh = comm.mesh()

    def body(x):
        # x: (1, p*c, *s) -> exchange: (1, p*c, *s) with chunks swapped
        out = lax.all_to_all(x, RANK_AXIS, split_axis=1, concat_axis=1, tiled=True)
        return out

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


def _make_barrier(comm: Communicator) -> Callable:
    mesh = comm.mesh()

    def body(x):
        return lax.psum(x, RANK_AXIS)

    fn = shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS), out_specs=P(RANK_AXIS),
                   check_vma=False)
    return jax.jit(fn)


# --------------------------------------------------------------------------
# public sync API
# --------------------------------------------------------------------------

def _check(comm: Communicator, x: jax.Array) -> None:
    if x.ndim < 1 or x.shape[0] != comm.size:
        raise ValueError(
            f"expected rank-major array with leading dim {comm.size}, got {x.shape}"
        )


def allreduce(comm: Communicator, x: jax.Array, op: str = "sum",
              groups: Groups = None) -> jax.Array:
    """Sync allreduce (reference: torchmpi_allreduce_*, collectives.cpp:327-430)."""
    _check(comm, x)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("allreduce", op, groups), lambda: _make_allreduce(comm, op, groups))
    out = fn(x)
    out.block_until_ready()
    return out


def broadcast(comm: Communicator, x: jax.Array, root: int = 0,
              groups: Groups = None) -> jax.Array:
    _check(comm, x)
    _validate_rooted_groups(comm, groups, root)
    member = _member_table(comm, groups)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("broadcast", root, groups),
                 lambda: _make_broadcast(comm, root, groups, member))
    out = fn(x)
    out.block_until_ready()
    return out


def reduce(comm: Communicator, x: jax.Array, root: int = 0, op: str = "sum",
           groups: Groups = None) -> jax.Array:
    _check(comm, x)
    _validate_rooted_groups(comm, groups, root)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("reduce", root, op, groups), lambda: _make_reduce(comm, root, op, groups))
    out = fn(x)
    out.block_until_ready()
    return out


def allgather(comm: Communicator, x: jax.Array, groups: Groups = None) -> jax.Array:
    """Returns rank-major (p, g, *s): slice r is the full gather seen by rank
    r (g = group size).  Reference auto-resizes the output tensor the same
    way (collectives.cpp:245-290)."""
    _check(comm, x)
    _validate_full_equal_groups(comm, groups, "allgather")
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("allgather", groups), lambda: _make_allgather(comm, groups))
    out = fn(x)
    out.block_until_ready()
    return out


def allgatherv(comm: Communicator, x: jax.Array,
               groups: Groups = None) -> Tuple[jax.Array, np.ndarray]:
    """Shape-changing allgather for *uneven* groups (the tree-mode levels
    :func:`allgather` rejects).  Returns ``(out, counts)``: ``out`` is
    rank-major ``(p, gmax, *s)`` zero-padded past each rank's group size,
    ``counts[r]`` is how many leading rows of slice r are valid — the
    auto-resize information of the reference's gatherv
    (collectives.cpp:245-290) carried out-of-band, since SPMD programs need
    one static output shape."""
    _check(comm, x)
    if groups is None:
        groups = (tuple(range(comm.size)),)
    else:
        flat = [r for g in groups for r in g]
        if len(flat) != len(set(flat)):
            raise ValueError(
                f"allgatherv groups must be disjoint (each rank in at most "
                f"one group); got {groups}")
        groups = _complete_groups(comm, groups)
    counts = np.zeros((comm.size,), np.int64)
    for g in groups:
        for r in g:
            counts[r] = len(g)
    fn = _cached(comm, ("allgatherv", groups),
                 lambda: _make_allgatherv(comm, groups))
    out = fn(x)
    out.block_until_ready()
    return out, counts


def reduce_scatter(comm: Communicator, x: jax.Array, op: str = "sum",
                   groups: Groups = None) -> jax.Array:
    _check(comm, x)
    if x.ndim != 2:
        raise ValueError("reduce_scatter expects rank-major (p, n) flat vectors")
    _validate_full_equal_groups(comm, groups, "reduce_scatter")
    shards = len(groups[0]) if groups is not None else comm.size
    if x.shape[1] % shards != 0:
        raise ValueError(
            f"reduce_scatter data axis {x.shape[1]} not divisible by group size {shards}"
        )
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("reduce_scatter", op, groups),
                 lambda: _make_reduce_scatter(comm, op, groups))
    out = fn(x)
    out.block_until_ready()
    return out


def sendreceive(comm: Communicator, x: jax.Array, src: int, dst: int) -> jax.Array:
    _check(comm, x)
    fn = _cached(comm, ("sendreceive", src, dst), lambda: _make_sendreceive(comm, src, dst))
    out = fn(x)
    out.block_until_ready()
    return out


def alltoall(comm: Communicator, x: jax.Array) -> jax.Array:
    _check(comm, x)
    if x.ndim < 2:
        raise ValueError("alltoall expects rank-major (p, n, ...) arrays")
    if x.shape[1] % comm.size != 0:
        raise ValueError("alltoall needs data axis divisible by communicator size")
    fn = _cached(comm, ("alltoall",), lambda: _make_alltoall(comm))
    out = fn(x)
    out.block_until_ready()
    return out


def barrier(comm: Communicator) -> None:
    """Zero-payload rendezvous (reference: mpi.barrier -> MPI_Barrier)."""
    fn = _cached(comm, ("barrier",), lambda: _make_barrier(comm))
    token = shard(comm, np.zeros((comm.size, 1), dtype=np.float32))
    fn(token).block_until_ready()


# --------------------------------------------------------------------------
# async API: dispatch now, wait via handle
# --------------------------------------------------------------------------

def _async(sync_like: Callable, comm: Communicator, *args, **kwargs) -> SynchronizationHandle:
    """Dispatch without blocking; the handle's wait is block_until_ready —
    the stream arm of the reference's handle union (resources.cpp:1173-1223).
    JAX's async dispatch replaces the offload thread pools: the Python call
    returns as soon as the computation is enqueued (the reference asserts
    <50us dispatch; test_collectives mirrors that assertion)."""
    out = sync_like(*args, **kwargs)
    h = SynchronizationHandle.from_arrays(out)
    in_flight.register(h, config.get("num_async_collectives_in_flight"))
    return h


def allreduce_async(comm: Communicator, x: jax.Array, op: str = "sum",
                    groups: Groups = None) -> SynchronizationHandle:
    _check(comm, x)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("allreduce", op, groups), lambda: _make_allreduce(comm, op, groups))
    return _async(fn, comm, x)


def broadcast_async(comm: Communicator, x: jax.Array, root: int = 0,
                    groups: Groups = None) -> SynchronizationHandle:
    _check(comm, x)
    _validate_rooted_groups(comm, groups, root)
    member = _member_table(comm, groups)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("broadcast", root, groups),
                 lambda: _make_broadcast(comm, root, groups, member))
    return _async(fn, comm, x)


def reduce_async(comm: Communicator, x: jax.Array, root: int = 0, op: str = "sum",
                 groups: Groups = None) -> SynchronizationHandle:
    _check(comm, x)
    _validate_rooted_groups(comm, groups, root)
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("reduce", root, op, groups), lambda: _make_reduce(comm, root, op, groups))
    return _async(fn, comm, x)


def allgather_async(comm: Communicator, x: jax.Array,
                    groups: Groups = None) -> SynchronizationHandle:
    _check(comm, x)
    _validate_full_equal_groups(comm, groups, "allgather")
    groups = _complete_groups(comm, groups)
    fn = _cached(comm, ("allgather", groups), lambda: _make_allgather(comm, groups))
    return _async(fn, comm, x)


def sendreceive_async(comm: Communicator, x: jax.Array, src: int, dst: int) -> SynchronizationHandle:
    _check(comm, x)
    fn = _cached(comm, ("sendreceive", src, dst), lambda: _make_sendreceive(comm, src, dst))
    return _async(fn, comm, x)


# --------------------------------------------------------------------------
# scalar collectives (reference: lib/collectives.cpp:38-59 + C wrappers)
# --------------------------------------------------------------------------

def allreduce_scalar(comm: Communicator, values, op: str = "sum", dtype=np.float64,
                     groups: Groups = None):
    """Latency-bound one-element collective.  ``values`` is a per-rank
    sequence (or a single value replicated to all ranks)."""
    if np.isscalar(values):
        values = [values] * comm.size
    x = shard(comm, np.asarray(values, dtype=dtype).reshape(comm.size, 1))
    out = allreduce(comm, x, op=op, groups=groups)
    return to_numpy(out)[:, 0]


def broadcast_scalar(comm: Communicator, values, root: int = 0, dtype=np.float64,
                     groups: Groups = None):
    if np.isscalar(values):
        values = [values] * comm.size
    x = shard(comm, np.asarray(values, dtype=dtype).reshape(comm.size, 1))
    out = broadcast(comm, x, root=root, groups=groups)
    return to_numpy(out)[:, 0]


def reduce_scalar(comm: Communicator, values, root: int = 0, op: str = "sum",
                  dtype=np.float64, groups: Groups = None):
    """Scalar reduce-to-root (reference: reduceScalar,
    collectives.cpp:44-48): slot ``root`` holds the reduction, other slots
    keep their local value — the in-place MPI_Reduce contract."""
    if np.isscalar(values):
        values = [values] * comm.size
    x = shard(comm, np.asarray(values, dtype=dtype).reshape(comm.size, 1))
    out = reduce(comm, x, root=root, op=op, groups=groups)
    return to_numpy(out)[:, 0]


def sendreceive_scalar(comm: Communicator, values, src: int, dst: int,
                       dtype=np.float64):
    """Scalar sendrecv_replace (reference: sendreceiveScalar,
    collectives.cpp:56-59): slot ``dst`` becomes slot ``src``'s value, in
    place; every other slot is untouched."""
    if np.isscalar(values):
        values = [values] * comm.size
    x = shard(comm, np.asarray(values, dtype=dtype).reshape(comm.size, 1))
    out = sendreceive(comm, x, src=src, dst=dst)
    return to_numpy(out)[:, 0]
