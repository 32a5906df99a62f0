"""Custom device-plane ring collectives as Pallas TPU kernels.

The reference's defining native asset is its hand-built ring collectives
with controlled chunking that could beat the vendor library inside an IPC
group (reference: lib/detail/collectives_cuda.cpp:202-388 IPC ring
allreduce, claim README.md:106; plan algebra lib/resources.cpp:588-678 and
lib/detail/README.md:1-48).  This module is the TPU equivalent: ring
reduce-scatter / allgather / allreduce over a communicator's mesh axis,
written against the inter-chip RDMA primitives
(``pltpu.make_async_remote_copy``) instead of cudaIPC ``cudaMemcpyAsync``
pulls, with the transfer geometry driven by the same buffer knobs the
reference's rings consume (``min/max_buffer_size``,
``num_buffers_per_collective`` — reference: lib/constants.cpp:150-152,
consumed at lib/detail/collectives.cpp:128-326).

Schedule (the reference's ring plan, resources.cpp:588-678):

* reduce-scatter: p-1 steps; at step s rank ``me`` sends chunk
  ``(me - s - 1) mod p`` (its running partial) to its right neighbour and
  accumulates the chunk arriving from the left into
  ``(me - s - 2) mod p``; after p-1 steps rank ``me`` owns the fully
  reduced chunk ``me``.
* allgather: p-1 steps circulating the owned chunks; at step s rank ``me``
  forwards chunk ``(me - s) mod p`` and stores the arriving
  ``(me - s - 1) mod p``.
* allreduce = reduce-scatter then allgather (detail/README.md:1-48),
  fused into ONE kernel so only one collective kernel is ever in flight
  (see ``_ar_kernel``).

Transport details mirroring the reference's staging design:

* Chunks are staged through VMEM send/recv slot buffers (the analogue of
  the per-(ptr, chunk) staging buffers, detail/collectives.cpp:128-154);
  ``num_buffers_per_collective`` sets the slot count.
* Each step's transfer is split into sub-chunks of at most
  ``max_buffer_size`` bytes, all started back-to-back so they pipeline on
  the wire (the reference's buffer-size-bounded chunk loop).
* Slot reuse is credit-flow-controlled: a rank signals a capacity
  semaphore to its *left* neighbour when it has consumed a staging slot,
  and waits for credit from its *right* neighbour before overwriting a
  slot — ranks on a ring can skew by up to p-2 steps, so without credits a
  fast sender would overwrite a slot the receiver has not read (the
  reference gets this for free from its event-ordered per-chunk streams,
  detail/collectives_cuda.cpp:202-388).

Sum is the only reduction, like the reference's rings (MPI_SUM only,
detail/collectives.cpp:163-165).

On a CPU mesh the kernels run under Pallas TPU *interpret* mode
(``pltpu.InterpretParams``), which emulates the RDMA/semaphore semantics —
the correctness fixture for the 8-device virtual mesh; on a real TPU mesh
they compile to Mosaic with true inter-chip DMA.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..runtime import config
from ..runtime.communicator import Communicator, RANK_AXIS

_LANE = 128

# Distinct collective ids for the barrier semaphores of the two kernels.
# Two ring kernels sharing ONE collective id must never be concurrently in
# flight (ring-skewed devices would wait on each other's barrier semaphore —
# the deadlock documented at _ar_kernel); callers that issue several rings
# inside one program (the engine's per-dtype gradient buckets) pass a
# distinct ``collective_id`` per ring from the caller-block base below.
_RS_COLLECTIVE_ID = 0x52
_AG_COLLECTIVE_ID = 0x53
# Base for caller-assigned ids (engine buckets use BASE, BASE+1, ...).
CALLER_COLLECTIVE_ID_BASE = 0x60


def _geometry(n: int, p: int, itemsize: int) -> Tuple[int, int, int]:
    """(rows, q, subrows): per-chunk row count (lanes of 128), sub-chunk
    count per step, and rows per sub-chunk — from the config buffer knobs.

    rows is padded so every chunk is whole lanes; q splits a step's
    transfer into <= max_buffer_size byte pieces (>= min_buffer_size when
    the chunk allows it), the reference's buffer geometry
    (constants.cpp:150-152).
    """
    per_chunk = math.ceil(n / p) if n else 1
    rows = max(1, math.ceil(per_chunk / _LANE))
    chunk_bytes = rows * _LANE * itemsize
    max_buf = max(int(config.get("max_buffer_size")), _LANE * itemsize)
    min_buf = max(int(config.get("min_buffer_size")), _LANE * itemsize)
    # Target piece size: within [min_buf, max_buf], never above the chunk.
    target = min(max(min_buf, min(chunk_bytes, max_buf)), max_buf)
    q = max(1, math.ceil(chunk_bytes / target))
    subrows = math.ceil(rows / q)
    rows = subrows * q  # pad so sub-chunks tile the chunk exactly
    return rows, q, subrows


def _neighbours(axis: str, p: int):
    me = lax.axis_index(axis)
    left = lax.rem(me + p - 1, p)
    right = lax.rem(me + 1, p)
    return me, left, right


def _ring_barrier(left, right) -> None:
    """Rendezvous with both ring neighbours before touching staging slots
    (the reference's comm barrier before IPC ring entry,
    detail/collectives_cuda.cpp:226-233)."""
    sem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(sem, inc=1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(sem, inc=1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(sem, 2)


def _step_exchange(send_stage, recv_stage, send_sem, recv_sem, cap_sem,
                   slot: int, q: int, subrows: int, right, left,
                   need_credit: bool) -> None:
    """One ring step: RDMA my send slot to right's recv slot (q pipelined
    sub-chunks), wait for my incoming data from left, leaving credit
    bookkeeping to the caller."""
    if need_credit:
        # Right neighbour must have freed this slot (signalled us) before
        # we overwrite its staging memory.
        pltpu.semaphore_wait(cap_sem, 1)
    copies = []
    for j in range(q):
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_stage.at[slot, pl.ds(j * subrows, subrows)],
            dst_ref=recv_stage.at[slot, pl.ds(j * subrows, subrows)],
            send_sem=send_sem.at[slot, j],
            recv_sem=recv_sem.at[slot, j],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        copies.append(rdma)
    for rdma in copies:
        rdma.wait()


def _rs_kernel(x_ref, out_ref, acc, send_stage, recv_stage,
               send_sem, recv_sem, cap_sem, *,
               p: int, q: int, subrows: int, nslots: int):
    """Ring reduce-scatter: x_ref (p, rows, 128) local partials ->
    out_ref (rows, 128) = fully reduced chunk ``me``."""
    me, left, right = _neighbours(RANK_AXIS, p)
    _ring_barrier(left, right)
    acc[:] = x_ref[:]
    for s in range(p - 1):
        slot = s % nslots
        send_idx = lax.rem(me - (s + 1) + 2 * p, p)
        recv_idx = lax.rem(me - (s + 2) + 2 * p, p)
        send_stage[slot] = acc[pl.ds(send_idx, 1)][0]
        _step_exchange(send_stage, recv_stage, send_sem, recv_sem, cap_sem,
                       slot, q, subrows, right, left,
                       need_credit=s >= nslots)
        acc[pl.ds(recv_idx, 1)] = (acc[pl.ds(recv_idx, 1)]
                                   + recv_stage[slot][None])
        # Slot consumed: extend credit to the writer (our left neighbour).
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    # Drain credits signalled by our right neighbour for slots we never
    # reused, so the regular semaphore ends the kernel at zero.
    tail = min(p - 1, nslots)
    if tail > 0:
        pltpu.semaphore_wait(cap_sem, tail)
    out_ref[:] = acc[pl.ds(me, 1)][0]


def _ar_kernel(x_ref, out_ref, acc, send_stage, recv_stage,
               send_sem, recv_sem, cap_sem, *,
               p: int, q: int, subrows: int, nslots: int):
    """Fused ring allreduce: reduce-scatter then allgather in ONE kernel.

    A single kernel (one barrier, slots/credits carried across both phases)
    rather than two composed pallas_calls: devices skew along the ring by
    up to p-2 steps, so with separate kernels a fast device would be inside
    the allgather kernel while a neighbour is still in reduce-scatter —
    two collective kernels concurrently in flight, which the barrier
    semantics do not support (and which deadlocks the interpreter).
    """
    me, left, right = _neighbours(RANK_AXIS, p)
    _ring_barrier(left, right)
    acc[:] = x_ref[:]
    t = 0
    for s in range(p - 1):  # phase 1: reduce-scatter
        slot = t % nslots
        send_idx = lax.rem(me - (s + 1) + 2 * p, p)
        recv_idx = lax.rem(me - (s + 2) + 2 * p, p)
        send_stage[slot] = acc[pl.ds(send_idx, 1)][0]
        _step_exchange(send_stage, recv_stage, send_sem, recv_sem, cap_sem,
                       slot, q, subrows, right, left,
                       need_credit=t >= nslots)
        acc[pl.ds(recv_idx, 1)] = (acc[pl.ds(recv_idx, 1)]
                                   + recv_stage[slot][None])
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        t += 1
    out_ref[pl.ds(me, 1)] = acc[pl.ds(me, 1)]
    for s in range(p - 1):  # phase 2: allgather of the owned chunks
        slot = t % nslots
        send_idx = lax.rem(me - s + 2 * p, p)
        recv_idx = lax.rem(me - (s + 1) + 2 * p, p)
        send_stage[slot] = out_ref[pl.ds(send_idx, 1)][0]
        _step_exchange(send_stage, recv_stage, send_sem, recv_sem, cap_sem,
                       slot, q, subrows, right, left,
                       need_credit=t >= nslots)
        out_ref[pl.ds(recv_idx, 1)] = recv_stage[slot][None]
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        t += 1
    tail = min(2 * (p - 1), nslots)
    if tail > 0:
        pltpu.semaphore_wait(cap_sem, tail)


def _ag_kernel(x_ref, out_ref, send_stage, recv_stage,
               send_sem, recv_sem, cap_sem, *,
               p: int, q: int, subrows: int, nslots: int):
    """Ring allgather: x_ref (rows, 128) owned chunk ->
    out_ref (p, rows, 128) with every rank's chunk."""
    me, left, right = _neighbours(RANK_AXIS, p)
    _ring_barrier(left, right)
    out_ref[pl.ds(me, 1)] = x_ref[:][None]
    for s in range(p - 1):
        slot = s % nslots
        send_idx = lax.rem(me - s + 2 * p, p)
        recv_idx = lax.rem(me - (s + 1) + 2 * p, p)
        send_stage[slot] = out_ref[pl.ds(send_idx, 1)][0]
        _step_exchange(send_stage, recv_stage, send_sem, recv_sem, cap_sem,
                       slot, q, subrows, right, left,
                       need_credit=s >= nslots)
        out_ref[pl.ds(recv_idx, 1)] = recv_stage[slot][None]
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    tail = min(p - 1, nslots)
    if tail > 0:
        pltpu.semaphore_wait(cap_sem, tail)


def _interpret_mode():
    """Real Mosaic on TPU, interpreter elsewhere (the CPU-mesh fixture)."""
    if jax.default_backend() == "tpu":
        return False
    return pltpu.InterpretParams()


def _scratch(dtype, rows: int, nslots: int, q: int, with_acc: Optional[int]):
    shapes = []
    if with_acc is not None:
        shapes.append(pltpu.VMEM((with_acc, rows, _LANE), dtype))
    shapes += [
        pltpu.VMEM((nslots, rows, _LANE), dtype),   # send staging slots
        pltpu.VMEM((nslots, rows, _LANE), dtype),   # recv staging slots
        pltpu.SemaphoreType.DMA((nslots, q)),
        pltpu.SemaphoreType.DMA((nslots, q)),
        pltpu.SemaphoreType.REGULAR,                # capacity credits
    ]
    return shapes


def _nslots(p: int) -> int:
    cap = int(config.get("max_num_buffers_per_collective_tpu"))
    return max(1, min(int(config.get("num_buffers_per_collective")), cap,
                      2 * (p - 1)))


def _ar_call(p: int, rows: int, q: int, subrows: int, nslots: int, dtype,
             collective_id: Optional[int] = None,
             interpret=None):
    kernel = functools.partial(_ar_kernel, p=p, q=q, subrows=subrows,
                               nslots=nslots)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p, rows, _LANE), dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(dtype, rows, nslots, q, with_acc=p),
        compiler_params=pltpu.CompilerParams(
            collective_id=(_RS_COLLECTIVE_ID if collective_id is None
                           else collective_id)),
        interpret=_interpret_mode() if interpret is None else interpret,
    )


def _rs_call(p: int, rows: int, q: int, subrows: int, nslots: int, dtype):
    kernel = functools.partial(_rs_kernel, p=p, q=q, subrows=subrows,
                               nslots=nslots)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(dtype, rows, nslots, q, with_acc=p),
        compiler_params=pltpu.CompilerParams(
            collective_id=_RS_COLLECTIVE_ID),
        interpret=_interpret_mode(),
    )


def _ag_call(p: int, rows: int, q: int, subrows: int, nslots: int, dtype):
    kernel = functools.partial(_ag_kernel, p=p, q=q, subrows=subrows,
                               nslots=nslots)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p, rows, _LANE), dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(dtype, rows, nslots, q, with_acc=None),
        compiler_params=pltpu.CompilerParams(
            collective_id=_AG_COLLECTIVE_ID),
        interpret=_interpret_mode(),
    )


_fn_cache = {}


def _cached_fn(comm: Communicator, key, builder):
    # Mesh object as key, not id() — see eager._cached: a recycled address
    # must not alias a new mesh onto an old layout's executable.
    full_key = (comm.mesh(), key)
    fn = _fn_cache.get(full_key)
    if fn is None:
        fn = _fn_cache[full_key] = builder()
    return fn


def clear_cache() -> None:
    _fn_cache.clear()


def _check(comm: Communicator, x: jax.Array) -> None:
    if x.ndim != 2 or x.shape[0] != comm.size:
        raise ValueError(
            f"pallas ring collectives expect rank-major (p, n) arrays with "
            f"p == {comm.size}, got {x.shape}")


# --------------------------------------------------------------------------
# inner-jit form: callable INSIDE a shard_map body (the compiled engine
# step's DP sync — the analogue of innerjit.py's lax wrappers, but executing
# the custom ring instead of XLA's lowering)
# --------------------------------------------------------------------------

def inner_ring_allreduce(x: jax.Array, p: int, mean: bool = False,
                         collective_id: Optional[int] = None,
                         force_kernel: bool = False) -> jax.Array:
    """Ring-allreduce the device-local flat vector ``x`` ``(n,)`` across the
    ``p`` ranks of the enclosing shard_map axis.

    This is the form a *compiled* training step uses: called inside the
    step's shard_map region it traces the fused reduce-scatter+allgather
    ring kernel straight into the step's XLA program, so flipping
    ``use_pallas_collectives`` changes what the engine's gradient sync
    executes (the reference's selector swapping NCCL for its p2p rings,
    nn.lua:18-27).  ``mean`` folds the replica-mean into the result.
    Supports every dtype the kernels stage (f32/bf16 — reduction happens
    in the wire dtype, like the vendor path's in-dtype rings).

    A caller tracing SEVERAL rings into one program must pass a distinct
    ``collective_id`` per ring (see CALLER_COLLECTIVE_ID_BASE): ids name
    barrier semaphores, and two in-flight rings on one semaphore deadlock
    on ring-skewed devices.

    ``force_kernel=True`` traces the Pallas kernel for REAL Mosaic
    lowering (interpret off) whatever backend this process runs on: the
    AOT topology compiles lower for a TPU while running on a CPU host, and
    the verdict wanted there is the TPU compiler's, not the interpreter's
    — ``_interpret_mode()`` keys on the RUNNING backend and would
    otherwise bake interpret mode into a TPU-targeted lowering.
    """
    if x.ndim != 1:
        raise ValueError(f"inner ring allreduce expects a flat (n,) local "
                         f"vector, got {x.shape}")
    if p == 1:
        return x
    n = x.shape[0]
    rows, q, subrows = _geometry(n, p, x.dtype.itemsize)
    nslots = _nslots(p)
    ar = _ar_call(p, rows, q, subrows, nslots, x.dtype,
                  collective_id=collective_id,
                  interpret=False if force_kernel else None)
    padded = p * rows * _LANE
    flat = jnp.zeros((padded,), x.dtype).at[:n].set(x)
    out = ar(flat.reshape(p, rows, _LANE)).reshape(padded)[:n]
    if mean:
        out = out / jnp.asarray(p, x.dtype)
    return out


# --------------------------------------------------------------------------
# public API (rank-major, mirroring eager.py semantics)
# --------------------------------------------------------------------------

def ring_allreduce(comm: Communicator, x: jax.Array, op: str = "sum",
                   ) -> jax.Array:
    """Ring allreduce of a rank-major (p, n) array: reduce-scatter then
    allgather, 2(p-1) neighbour exchanges moving 2n(p-1)/p elements per
    rank (the ring-optimal volume the reference's bench model assumes,
    test/collectives_all.lua:313-318).  ``op``: 'sum' or 'mean' (the rings
    reduce with sum like the reference's MPI_SUM-only rings; mean is a
    folded epilogue scale)."""
    _check(comm, x)
    if op not in ("sum", "mean"):
        raise ValueError("pallas ring collectives support op='sum'/'mean' "
                         "only (reference rings are MPI_SUM only)")
    p = comm.size
    if p == 1:
        return x
    n = x.shape[1]
    rows, q, subrows = _geometry(n, p, x.dtype.itemsize)
    nslots = _nslots(p)

    def build():
        def body(xb):
            return inner_ring_allreduce(xb[0], p, mean=(op == "mean"))[None]

        return jax.jit(shard_map(body, mesh=comm.mesh(), in_specs=P(RANK_AXIS),
                                 out_specs=P(RANK_AXIS), check_vma=False))

    key = ("allreduce", op, n, str(x.dtype), rows, q, subrows, nslots)
    return _cached_fn(comm, key, build)(x)


def ring_reduce_scatter(comm: Communicator, x: jax.Array, op: str = "sum",
                        ) -> jax.Array:
    """Ring reduce-scatter of a rank-major (p, n) array: rank r's slice of
    the output (p, n/p) is the r-th chunk of the sum — the first phase of
    the reference's ring plan (detail/README.md:1-48)."""
    _check(comm, x)
    if op != "sum":
        raise ValueError("pallas ring collectives support op='sum' only")
    p = comm.size
    n = x.shape[1]
    if n % p != 0:
        raise ValueError(f"reduce_scatter data axis {n} not divisible by {p}")
    if p == 1:
        return x
    per = n // p
    rows, q, subrows = _geometry(n, p, x.dtype.itemsize)
    nslots = _nslots(p)

    def build():
        rs = _rs_call(p, rows, q, subrows, nslots, x.dtype)

        def body(xb):
            # Chunk c holds elements [c*per, (c+1)*per) lane-padded.
            chunks = jnp.zeros((p, rows * _LANE), xb.dtype)
            chunks = chunks.at[:, :per].set(xb[0].reshape(p, per))
            owned = rs(chunks.reshape(p, rows, _LANE))
            return owned.reshape(rows * _LANE)[None, :per]

        return jax.jit(shard_map(body, mesh=comm.mesh(), in_specs=P(RANK_AXIS),
                                 out_specs=P(RANK_AXIS), check_vma=False))

    key = ("reduce_scatter", n, str(x.dtype), rows, q, subrows, nslots)
    return _cached_fn(comm, key, build)(x)


def ring_allgather(comm: Communicator, x: jax.Array) -> jax.Array:
    """Ring allgather of a rank-major (p, n) array -> (p, p*n): every
    rank's slice holds all ranks' data in rank order (the second phase of
    the ring plan)."""
    _check(comm, x)
    p = comm.size
    n = x.shape[1]
    if p == 1:
        return x
    # Each rank's whole block is one circulating chunk.
    rows, q, subrows = _geometry(n, 1, x.dtype.itemsize)
    nslots = _nslots(p)

    def build():
        ag = _ag_call(p, rows, q, subrows, nslots, x.dtype)

        def body(xb):
            chunk = jnp.zeros((rows * _LANE,), xb.dtype).at[:n].set(xb[0])
            full = ag(chunk.reshape(rows, _LANE))
            return full.reshape(p, rows * _LANE)[:, :n].reshape(1, p * n)

        return jax.jit(shard_map(body, mesh=comm.mesh(), in_specs=P(RANK_AXIS),
                                 out_specs=P(RANK_AXIS), check_vma=False))

    key = ("allgather", n, str(x.dtype), rows, q, subrows, nslots)
    return _cached_fn(comm, key, build)(x)
