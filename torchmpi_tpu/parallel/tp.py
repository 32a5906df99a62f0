"""Tensor (model) parallelism: sharded linear layers with explicit
collectives.

The reference ships TP as an example: ``MPLinear`` shards a Linear's *input*
dimension across ranks, each rank computes a partial product, and the
activations are allreduced forward (and gradInput backward)
(reference: examples/mnist/mnist_modelparallel.lua:28-55).  Promoted here to
a library feature (SURVEY.md §2.3 TP row) in the two Megatron-style forms:

* :func:`column_linear` — weight sharded on the **output** dim; no forward
  collective (activations come out feature-sharded).
* :func:`row_linear` — weight sharded on the **input** dim; partial products
  ``psum`` over the tp axis — exactly MPLinear's forward.  Reverse-mode AD
  of ``psum`` gives the gradInput allreduce the reference codes by hand.

A column->row pair makes an MLP block with ONE forward collective — the
layout that keeps TP traffic on ICI.  All functions are written for use
inside ``shard_map`` bodies over a mesh with a ``tp`` axis; array arguments
are the *local shards*.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..runtime import config
from .mesh import AXIS_TP

Params = dict


def resolve_wire_dtype(override=None):
    """The wire dtype for collectives inside manual shard_map regions, from
    the ``manual_wire_dtype`` knob (runtime/config.py).

    ``"auto"`` resolves per backend: bf16 on TPU (halves the bytes of every
    manual-stage gradient/activation collective; the TPU pipeline compiles
    bf16 psums in manual regions — proven by AOT compilation against named
    TPU topologies, TOPOLOGY_r06.json), f32 elsewhere (XLA-CPU's
    AllReducePromotion pass crashes on bf16 all-reduce inside partial-manual
    regions, and f32 wires keep full partial-sum accuracy).  An explicit
    ``override`` dtype wins over the knob.

    Under ``autotune_mode=cache|online``, ``"auto"`` first consults the
    compiled-mode autotune verdict for the running fabric
    (``autotune.compiled_wire_dtype`` — per-program AOT knob variants
    scored by HLO collective operand bytes); the backend heuristic is the
    fallback when no compiled winner exists.  ``off`` (the default) never
    consults it, and an explicit knob value always outranks the
    measurement.
    """
    if override is not None:
        return override
    knob = str(config.get("manual_wire_dtype"))
    if knob == "auto":
        from ..collectives import autotune as _autotune

        measured = _autotune.compiled_wire_dtype()
        if measured is not None:
            return (jnp.bfloat16 if measured == "bfloat16"
                    else jnp.float32)
        return (jnp.bfloat16 if jax.default_backend() == "tpu"
                else jnp.float32)
    dt = jnp.dtype(knob)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        raise ValueError(
            f"manual_wire_dtype must be 'auto', 'bfloat16' or 'float32', "
            f"got {knob!r}")
    return dt.type


def column_linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                  ) -> jax.Array:
    """y_local = x @ w_local (+ b_local); w sharded (d_in, d_out/p).

    Output is feature-sharded; no collective.  ``x`` must be replicated
    across the tp axis.
    """
    y = x @ w
    if b is not None:
        y = y + b
    return y


def row_linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
               axis: str = AXIS_TP) -> jax.Array:
    """y = psum_tp(x_local @ w_local) (+ b); w sharded (d_in/p, d_out).

    ``x`` is feature-sharded (e.g. a column_linear output).  The psum is the
    activation allreduce of MPLinear's forward; its transpose under AD is
    the backward gradInput allreduce (mnist_modelparallel.lua:42-55).
    ``b`` must be replicated — added once, after the reduction.
    """
    partial = x @ w
    y = lax.psum(partial, axis)
    if b is not None:
        y = y + b
    return y


def mlp_block(x: jax.Array, w_up: jax.Array, b_up: Optional[jax.Array],
              w_down: jax.Array, b_down: Optional[jax.Array],
              activation: Callable = jax.nn.relu, axis: str = AXIS_TP,
              ) -> jax.Array:
    """Megatron MLP: column(up) -> activation -> row(down); one psum total."""
    h = activation(column_linear(x, w_up, b_up))
    return row_linear(h, w_down, b_down, axis=axis)


# ----------------------------------------------------- Megatron f/g markers
# Megatron's conjugate identity/all-reduce pair, as ``custom_vjp`` s.  They
# make a hand-sharded tp block's vjp correct when taken PER DEVICE (inside a
# manual shard_map region, where no partitioner rewrites transposes): the
# block input's marker turns the per-shard backward partials into the true
# input cotangent, and the block output's marker pins the forward psum's
# transpose to identity (the cotangent arriving there is already complete).
# Without them, ``jax.vjp`` of the raw per-device program returns partial
# input cotangents — measured wrong; with them, exact (round-5 probe).
# Reference: the gradInput allreduce MPLinear's backward performs,
# examples/mnist/mnist_modelparallel.lua:42-55 — the same wire, placed by
# AD instead of by hand.


def block_input(x: jax.Array, axis: str = AXIS_TP,
                wire_dtype=None) -> jax.Array:
    """Megatron ``f``: identity forward, psum(axis) backward.  Wrap the
    (tp-replicated) input of each hand-sharded parallel block.  The
    backward psum is a GRADIENT wire: it rides ``wire_dtype``
    (default: :func:`resolve_wire_dtype` — bf16 on TPU, halving the
    bytes; f32 elsewhere)."""
    wire = resolve_wire_dtype(wire_dtype)

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g.astype(wire), axis).astype(g.dtype),)

    f.defvjp(fwd, bwd)
    return f(x)


def block_output(part: jax.Array, axis: str = AXIS_TP,
                 wire_dtype=None) -> jax.Array:
    """Megatron ``g``: psum(axis) forward, identity backward.  Reduce the
    per-shard partials of each hand-sharded parallel block.  The wire is
    ``wire_dtype`` (default: :func:`resolve_wire_dtype` — f32 on
    backends whose AllReducePromotion pass crashes on bf16 all-reduce
    inside partial-manual regions, bf16 on TPU where the compiler takes
    it and the bytes halve)."""
    wire = resolve_wire_dtype(wire_dtype)

    @jax.custom_vjp
    def f(p):
        return lax.psum(p.astype(wire), axis).astype(p.dtype)

    def fwd(p):
        return f(p), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f(part)


# ------------------------------------------------------------------ MPLinear
# The reference example as a standalone layer: input-dim sharding only.

def mp_linear_init(rng: jax.Array, d_in: int, d_out: int,
                   dtype=jnp.float32) -> Params:
    """Full (unsharded) parameters; shard with :func:`shard_mp_linear`."""
    w = jax.random.normal(rng, (d_in, d_out), jnp.float32) * np.sqrt(2.0 / d_in)
    return {"w": w.astype(dtype), "b": jnp.zeros((d_out,), dtype)}


def shard_mp_linear(params: Params, mesh: Mesh, axis: str = AXIS_TP) -> Params:
    """Place w input-dim-sharded and b replicated on the mesh."""
    return {
        "w": jax.device_put(params["w"], NamedSharding(mesh, P(axis, None))),
        "b": jax.device_put(params["b"], NamedSharding(mesh, P())),
    }


def make_mp_linear(mesh: Mesh, axis: str = AXIS_TP,
                   activation: Optional[Callable] = None):
    """Compiled MPLinear forward over the mesh: x feature-sharded in, output
    replicated out (reference MPLinear.updateOutput's allreduce completion).

    Returns ``fn(params, x)`` where ``x`` is the full (d_in,)-feature batch;
    sharding constraints let GSPMD split the contraction and insert the
    psum, which is how the hand-written allreduce becomes compiler-inserted.
    """

    def fwd(params, x):
        w_local, b = params["w"], params["b"]
        y = lax.psum(x @ w_local, axis)
        y = y + b
        return activation(y) if activation is not None else y

    fn = shard_map(
        fwd,
        mesh=mesh,
        in_specs=({"w": P(axis, None), "b": P()}, P(None, axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


# ------------------------------------------------- pjit sharding-rule helpers

def tp_specs_linear(shard_output: bool) -> Tuple[P, P]:
    """(w_spec, b_spec) for a linear under tp: column (output-sharded) or
    row (input-sharded) layout — the annotation form used by pjit'd models
    (GSPMD inserts the collectives the shard_map forms write explicitly)."""
    if shard_output:
        return P(None, AXIS_TP), P(AXIS_TP)
    return P(AXIS_TP, None), P()
