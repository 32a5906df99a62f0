"""Shared model-zoo helpers: init primitives, parameter counting, and
spec-driven placement (used by llama.py and vit.py)."""

from __future__ import annotations

from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    """fan-in-scaled dense weight (1/sqrt(d_in))."""
    w = jax.random.normal(key, (d_in, d_out), jnp.float32)
    return (w * np.sqrt(1.0 / d_in)).astype(dtype)


def stack_dense(key, n: int, d_in: int, d_out: int, dtype) -> jax.Array:
    """(n, d_in, d_out) stack of independently initialized dense weights
    (the stacked-layer form both transformer families scan over)."""
    ks = jax.random.split(key, n)
    return jnp.stack([dense_init(k, d_in, d_out, dtype) for k in ks])


def num_params(params: Any) -> int:
    """Total element count; works on arrays and eval_shape structs alike
    (only ``.shape`` is read)."""
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))


def mesh_spec(spec: P, mesh: Mesh, shape=None) -> P:
    """THE axis-dropping rule, shared by every placement site: drop spec
    axes the mesh lacks; with ``shape`` also drop axes whose dimension the
    mesh axis size does not divide (e.g. a 10-class head over tp=4 stays
    replicated instead of erroring).  An entry that names several axes (a
    batch's rows over ``("dp", "ep")``) keeps those the mesh has, the one
    that is left as a plain name.  Keeping one copy prevents the placement
    helpers and the jit in/out shardings from disagreeing about the same
    leaf."""
    sizes = dict(mesh.shape)

    def keep(i, ax):
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if a in sizes)
            ax = kept[0] if len(kept) == 1 else kept or None
            size = int(np.prod([sizes[a] for a in kept]))
        elif ax in sizes:
            size = sizes[ax]
        else:
            return None
        if shape is not None and shape[i] % size != 0:
            return None
        return ax

    return P(*[keep(i, ax) for i, ax in enumerate(spec)])


def shard_by_specs(params: Any, mesh: Mesh, specs: Any) -> Any:
    """``device_put`` each leaf per its PartitionSpec under the shared
    :func:`mesh_spec` rule (shape-aware)."""
    return jax.tree.map(
        lambda a, s: jax.device_put(
            a, NamedSharding(mesh, mesh_spec(s, mesh, a.shape))),
        params, specs)
