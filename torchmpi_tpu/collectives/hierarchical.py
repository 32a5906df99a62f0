"""Hierarchical composition: cursor/span -> replica groups, plus the tree
3-step allreduce algebra.

The reference composes collectives across communicator levels two ways
(reference: lib/collectives_cuda.cpp:501-581, docs/communicators.md:24-32):

* **cartesian** (all intra groups equal): 2-step — intra ring then inter
  ring; on TPU this is a single grouped XLA collective (or a psum over both
  axes of the 2-D mesh): XLA decomposes onto ICI/DCN itself.
* **tree** (uneven groups): 3-step — intra reduce to root, allreduce among
  roots, intra broadcast — which we express as three grouped psums inside
  one compiled program.

The *collective span* selects which stack levels participate
(reference: torch_mpi.cpp:84-95): span [b, e) means "allreduce over each of
level b's groups, decomposed through levels b+1..e-1".  Because XLA owns the
decomposition, the semantics reduce to: replica groups = level b's partition.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..runtime import config
from ..runtime.communicator import (
    Communicator,
    CommunicatorStack,
    CommunicatorType,
    RANK_AXIS,
)
from . import eager

Groups = Optional[Tuple[Tuple[int, ...], ...]]


def groups_for_cursor(stack: CommunicatorStack) -> Tuple[Communicator, Groups]:
    """Resolve the (level, intra/inter, span) cursor to replica groups over
    the world mesh.

    All stack levels partition the same world device list (push refines the
    parent partition), so every collective compiles against the world mesh
    with groups selecting the participants — the SPMD realisation of the
    reference's "current communicator" dispatch (torch_mpi.cpp:96-135).
    """
    b, e = stack.span
    world = stack.world()
    if e - b > 1:
        # Multi-level span: full collective within each of level b's groups.
        comm = stack.at(b)
        groups = comm.group_ranks if comm.num_groups > 1 else None
        return world, groups
    comm = stack.at(b)
    if stack.type == CommunicatorType.INTER:
        return world, comm.inter_group_ranks
    groups = comm.group_ranks if comm.num_groups > 1 else None
    return world, groups


def allreduce_tree(comm: Communicator, x: jax.Array, op: str = "sum") -> jax.Array:
    """Explicit 3-step tree allreduce over uneven groups
    (reference: docs/communicators.md:24-32; collectives_cuda.cpp:501-581
    non-cartesian branch: intra reduce -> roots allreduce -> intra bcast).

    Semantically identical to a flat grouped psum; kept as a first-class
    algorithm because (a) it is the span-restricted form when only the inter
    level participates for part of the traversal, and (b) it preserves the
    reference's algorithm switch (kUseHierarchicalCollectives).
    """
    if op not in ("sum", "mean", "max", "min"):
        raise ValueError(f"unsupported reduction {op!r}")
    eager._check(comm, x)
    mesh = comm.mesh()
    p = comm.size
    intra_groups, roots_partition, is_root_c = _tree_tables(comm)
    base_op = "sum" if op == "mean" else op

    def body(v):
        # step 1: intra allreduce (covers "reduce to root")
        s = eager._psum_like(base_op, v, RANK_AXIS, intra_groups)
        # step 2: allreduce among roots only
        t = eager._psum_like(base_op, s, RANK_AXIS, roots_partition)
        # step 3: intra broadcast from root (masked psum)
        me = lax.axis_index(RANK_AXIS)
        contrib = jnp.where(is_root_c[me], t, jnp.zeros_like(t))
        out = lax.psum(contrib, RANK_AXIS, axis_index_groups=intra_groups)
        if op == "mean":
            out = out / jnp.asarray(p, out.dtype)
        return out

    fn = eager._cached(
        comm,
        ("tree_allreduce", op, intra_groups, roots_partition),
        lambda: jax.jit(shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS),
                                  out_specs=P(RANK_AXIS), check_vma=False)),
    )
    out = fn(x)
    out.block_until_ready()
    return out


def _tree_tables(comm: Communicator, root: Optional[int] = None):
    """Shared setup for the tree collectives: the intra partition, the
    inter partition over the group roots (∪ {root} when an explicit root
    participates), and the group-root membership mask — one construction
    site so the three tree algorithms cannot diverge."""
    import numpy as np

    intra_groups = eager._complete_groups(comm, comm.group_ranks)
    inter = set(comm.root_ranks)
    if root is not None:
        inter.add(int(root))
    inter_partition = eager._complete_groups(comm, (tuple(sorted(inter)),))
    is_groot = np.zeros((comm.size,), dtype=bool)
    for r in comm.root_ranks:
        is_groot[r] = True
    return intra_groups, inter_partition, jnp.asarray(is_groot)


def broadcast_tree(comm: Communicator, x: jax.Array, root: int = 0) -> jax.Array:
    """Explicit 2-step tree broadcast over uneven groups: root -> every
    group root over the inter plane, then each group root -> its group
    (reference 2-step algebra: docs/communicators.md:24-32 — and the
    reference's own CUDA hierarchical broadcast gives up with an MPI
    fallback, collectives_cuda.cpp:429-439 "NYI", so this closes that NYI
    rather than mirroring it).

    ``root`` is a world rank; it need not be a group root — the inter step
    runs over roots ∪ {root}, so the value reaches every group's root
    regardless of which group the root sits in.
    """
    eager._check(comm, x)
    mesh = comm.mesh()
    intra_groups, inter_partition, is_groot_c = _tree_tables(comm, root)

    def body(v):
        me = lax.axis_index(RANK_AXIS)
        # step 1: root -> the group roots (masked psum over the inter set;
        # ranks outside it sit in singleton completion groups, untouched).
        c1 = jnp.where(me == root, v, jnp.zeros_like(v))
        t = lax.psum(c1, RANK_AXIS, axis_index_groups=inter_partition)
        # step 2: each group root -> its whole group.
        c2 = jnp.where(is_groot_c[me], t, jnp.zeros_like(t))
        return lax.psum(c2, RANK_AXIS, axis_index_groups=intra_groups)

    fn = eager._cached(
        comm,
        ("tree_broadcast", int(root), intra_groups, inter_partition),
        lambda: jax.jit(shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS),
                                  out_specs=P(RANK_AXIS), check_vma=False)),
    )
    out = fn(x)
    out.block_until_ready()
    return out


def reduce_tree(comm: Communicator, x: jax.Array, root: int = 0,
                op: str = "sum") -> jax.Array:
    """Explicit 2-step tree reduce (the broadcast dual): intra reduce to
    each group root, then reduce among roots to ``root``.  Non-root ranks
    keep their input (eager.reduce's contract).  ``op``: sum/mean — the
    masked inter step routes with additive identities, which max/min do
    not have; the hierarchical dispatcher falls back to the flat form for
    those."""
    if op not in ("sum", "mean"):
        raise ValueError("reduce_tree supports op='sum'/'mean'")
    eager._check(comm, x)
    mesh = comm.mesh()
    p = comm.size
    intra_groups, inter_partition, is_groot_c = _tree_tables(comm, root)

    def body(v):
        me = lax.axis_index(RANK_AXIS)
        # step 1: intra reduce — every member of a group holds its group sum.
        s = lax.psum(v, RANK_AXIS, axis_index_groups=intra_groups)
        # step 2: group roots contribute their group sums; the masked psum
        # over the inter set lands the total on every inter member, root
        # included.
        c2 = jnp.where(is_groot_c[me], s, jnp.zeros_like(s))
        t = lax.psum(c2, RANK_AXIS, axis_index_groups=inter_partition)
        if op == "mean":
            t = t / jnp.asarray(p, t.dtype)
        return jnp.where(me == root, t, v)

    fn = eager._cached(
        comm,
        ("tree_reduce", int(root), op, intra_groups, inter_partition),
        lambda: jax.jit(shard_map(body, mesh=mesh, in_specs=P(RANK_AXIS),
                                  out_specs=P(RANK_AXIS), check_vma=False)),
    )
    out = fn(x)
    out.block_until_ready()
    return out


def broadcast_hierarchical(comm: Communicator, x: jax.Array,
                           root: int = 0) -> jax.Array:
    """Level-wide broadcast choosing the 2-step tree when hierarchy is on
    and the level actually has groups; flat masked-psum broadcast
    otherwise."""
    if not config.get("use_hierarchical_collectives") or comm.num_groups <= 1:
        return eager.broadcast(comm, x, root=root)
    return broadcast_tree(comm, x, root=root)


def reduce_hierarchical(comm: Communicator, x: jax.Array, root: int = 0,
                        op: str = "sum") -> jax.Array:
    """Level-wide reduce-to-root: 2-step tree for sum/mean under the
    hierarchy knob, flat grouped form otherwise (max/min always flat —
    see reduce_tree)."""
    if (not config.get("use_hierarchical_collectives")
            or comm.num_groups <= 1 or op not in ("sum", "mean")):
        return eager.reduce(comm, x, root=root, op=op)
    return reduce_tree(comm, x, root=root, op=op)


def allreduce_hierarchical(comm: Communicator, x: jax.Array, op: str = "sum") -> jax.Array:
    """Level-wide allreduce choosing cartesian 2-step vs tree 3-step
    (reference: collectives_cuda.cpp:650-661 flat-vs-hierarchical switch +
    :501-581).  With ``use_hierarchical_collectives`` off, a flat psum over
    all ranks (the reference's flat RDMA ring)."""
    if not config.get("use_hierarchical_collectives") or comm.num_groups <= 1:
        return eager.allreduce(comm, x, op=op)
    if comm.cartesian:
        # Equal groups: one grouped XLA collective over everything; XLA's
        # own hierarchy (ICI ring per axis) is the 2-step composition.
        return eager.allreduce(comm, x, op=op)
    return allreduce_tree(comm, x, op=op)
