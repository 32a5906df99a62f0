"""Expert parallelism: a mixture-of-experts layer dispatched over an ``ep``
mesh axis.

Absent from the reference (SURVEY.md §2.3: "EP — absent; new in TPU build")
— added so the parallelism inventory is complete.  TPU-native shape:

* experts are sharded over ``ep`` (each device owns ``E / ep_size`` expert
  MLPs, stacked on a leading axis);
* tokens are routed top-k by a learned gate (k=1 switch-style with the raw
  gate prob as weight; k>1 GShard-style with renormalized weights and
  primary routes served before secondary ones), then moved to their
  experts' devices with ``lax.all_to_all`` — the same primitive as
  Ulysses — using **capacity buckets**: each (device, expert) pair gets a
  fixed-size slot buffer so shapes stay static for XLA (a token whose every
  choice is dropped passes through unchanged);
* expert compute is one batched GEMM over the local buckets (MXU-friendly),
  then the inverse all-to-all returns outputs to the tokens' home devices.

``shard_map`` body + a jit wrapper, same structure as parallel/sequence.py.

Two expert layers move tokens over ``ep``, and both through :func:`exchange`,
the one all-to-all written here.  This module's capacity-bucket layer is the
building block for a program that wants static buckets and accepts drops; no
model of ``models/`` uses it.  ``models.llama._moe_ffn_sorted`` is the
dropless one a model trains with.  On an axis wider than the choices a token
its units are sorted by destination rank and expert and sent a fixed pass of
rows a peer at a time (the uniform share first, then the overflow in smaller
passes), as many passes as arrived, so its shapes are as static as the
buckets' and nothing is dropped (:func:`pass_plan` counts what a rank sends
and receives).  On an axis no wider than that it gathers every rank's tokens
instead, runs the held experts on all of them and sends each rank the partial
sums of its tokens, by :func:`exchange` again (``models.llama._ep_form``,
``ep_exchange_plan``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import AXIS_EP

Params = dict


def init_experts(rng: jax.Array, n_experts: int, d_model: int, d_ff: int,
                 dtype=jnp.float32) -> Params:
    """Gate + stacked expert MLPs (leading axis = expert, sharded on ep)."""
    kg, k1, k2 = jax.random.split(rng, 3)
    s1 = np.sqrt(2.0 / d_model)
    s2 = np.sqrt(1.0 / d_ff)
    return {
        "gate": (jax.random.normal(kg, (d_model, n_experts), jnp.float32)
                 * 0.02).astype(dtype),
        "w_in": (jax.random.normal(k1, (n_experts, d_model, d_ff), jnp.float32)
                 * s1).astype(dtype),
        "w_out": (jax.random.normal(k2, (n_experts, d_ff, d_model), jnp.float32)
                  * s2).astype(dtype),
    }


def moe_specs() -> Params:
    return {"gate": P(), "w_in": P(AXIS_EP, None, None),
            "w_out": P(AXIS_EP, None, None)}


def shard_experts(params: Params, mesh: Mesh) -> Params:
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params, moe_specs())


def route_topk(probs: jax.Array, k: int, renormalize: bool):
    """The shared GShard routing step both MoE forms build on (this
    module's shard_map a2a dispatch and ``models.llama._moe_ffn``'s pjit
    einsum dispatch — one definition so dispatch priority and the
    renormalization guard cannot drift apart): top-k selection, optional
    weight renormalization over the chosen k (1e-9 guard), CHOICE-MAJOR
    flatten — all primary routes before any secondary route, so they win
    the capacity queue — and each routed unit's exclusive-cumsum position
    in its expert's queue.

    ``probs``: (T, E) gate probabilities.  Returns ``(expert_f, weight_f,
    onehot, pos_excl)``, each leading with k*T in choice-major order;
    ``pos_excl[u, e]`` counts earlier units routed to expert e (meaningful
    where ``onehot[u, e] == 1``)."""
    T, E = probs.shape
    weight, expert = lax.top_k(probs, k)                           # (T, k)
    if renormalize:
        weight = weight / jnp.maximum(jnp.sum(weight, axis=-1, keepdims=True),
                                      1e-9)
    expert_f = expert.T.reshape(k * T)
    weight_f = weight.T.reshape(k * T)
    onehot = jax.nn.one_hot(expert_f, E, dtype=jnp.int32)          # (kT, E)
    pos_excl = jnp.cumsum(onehot, axis=0) - onehot                 # (kT, E)
    return expert_f, weight_f, onehot, pos_excl


def exchange(blocks: jax.Array, axis: str) -> jax.Array:
    """``blocks`` (p, ...), one block for each rank of ``axis`` -> (p, ...):
    block j goes to rank j, and block i of the result is what rank i sent
    here.  Applied twice it is the identity, and it is its own transpose: a
    result goes home, and a gradient goes back, by the same call.  Scope
    ``moe.exchange`` in the device program."""
    with jax.named_scope("moe.exchange"):
        return lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0,
                              tiled=True)


def pass_plan(units: jax.Array, rows, axis: str):
    """What a rank of ``axis`` sends and receives when its routed units,
    sorted by expert (so by the rank that holds the expert, ranks holding
    equal contiguous shares), go out in passes of ``rows`` a peer: ``rows[0]``
    in the first pass and ``rows[1]`` in each pass after it.  ``units`` (E,)
    int32 counts this rank's units by expert.  Returns ``(sent, arrived,
    first, passes, overflow)``: ``sent`` (p, E / p) the units for each rank's
    experts, ``arrived`` (p, E / p) those each rank sends for the experts held
    here (one small :func:`exchange`), ``first`` (p,) where each rank's units
    begin in the sorted order, ``passes``, the same on every rank: as many as
    the fullest pair of ranks needs, so no unit is left behind, and
    ``overflow``, those of them after the first."""
    p = lax.psum(1, axis)
    sent = units.reshape(p, -1)
    arrived = exchange(sent, axis)
    total = jnp.sum(sent, axis=1)
    most = lax.pmax(jnp.max(total), axis)
    overflow = -(-jnp.maximum(most - rows[0], 0) // rows[1])
    return (sent, arrived, jnp.cumsum(total) - total,
            jnp.minimum(most, 1) + overflow, overflow)


def _moe_body(x, gate_w, w_in, w_out, *, n_experts: int, capacity: int,
              axis: str, k: int, renormalize: bool):
    """Per-device body.  x: (T_local, D); w_in/w_out: (E_local, D, F)/(E_local, F, D).

    Top-``k`` routing: each token dispatches to its k highest-gate experts
    (k=1 = switch-style with the raw gate prob as weight; k>1 = GShard-style
    with weights renormalized over the chosen k).  Every (token, choice)
    pair is an independent routed unit sharing the per-expert capacity
    budget; a token whose every choice is dropped passes through unchanged.
    """
    T, D = x.shape
    E_local = w_in.shape[0]
    p = lax.psum(1, axis)

    # --- route: the shared top-k / choice-major / capacity-queue step ---
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert, weight, onehot, pos_excl = route_topk(probs, k, renormalize)
    xu = jnp.tile(x, (k, 1))                                       # (k*T, D)

    # --- bucket units per expert with fixed capacity ---
    pos = jnp.take_along_axis(pos_excl, expert[:, None], axis=1)[:, 0]
    keep = pos < capacity
    # slot buffers: (E, C, D); dropped units simply never get scattered.
    slot_idx = expert * capacity + jnp.where(keep, pos, 0)
    buckets = jnp.zeros((n_experts * capacity, D), x.dtype)
    buckets = buckets.at[slot_idx].add(jnp.where(keep[:, None], xu, 0))
    buckets = buckets.reshape(n_experts, capacity, D)

    # --- all_to_all: device j gets, from every source device i, the buckets
    # destined for j's local experts.  Leading axis E = p * E_local in
    # global-expert order; tiled exchange splits it and stacks received
    # pieces in source order: recv[i] = device i's buckets for my experts.
    recv = exchange(buckets.reshape(p, E_local * capacity, D), axis)
    recv = recv.reshape(p, E_local, capacity, D)
    recv = jnp.moveaxis(recv, 0, 1).reshape(E_local, p * capacity, D)

    # --- expert compute: batched GEMM over local experts ---
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", recv, w_in))
    out = jnp.einsum("ecf,efd->ecd", h, w_out)                     # (E_local, pC, D)

    # --- inverse all_to_all: return outputs to token-home devices ---
    out = out.reshape(E_local, p, capacity, D)
    out = jnp.moveaxis(out, 1, 0).reshape(p, E_local * capacity, D)
    back = exchange(out, axis).reshape(n_experts * capacity, D)

    # --- un-bucket: gather each unit's slot, combine weighted choices ---
    yu = back[slot_idx]                                            # (k*T, D)
    yu = jnp.where(keep[:, None], yu * weight[:, None].astype(yu.dtype), 0)
    y = jnp.sum(yu.reshape(k, T, D), axis=0)
    any_kept = jnp.any(keep.reshape(k, T), axis=0)
    return jnp.where(any_kept[:, None], y, x)


def make_moe_layer(mesh: Mesh, n_experts: int, capacity: int,
                   axis: str = AXIS_EP, k: int = 1,
                   renormalize: Optional[bool] = None):
    """Compiled MoE layer over ``mesh``: ``fn(params, x)`` with x (T, D)
    sharded on ``axis`` (token-parallel in, token-parallel out).

    ``n_experts`` must be divisible by the ep axis size; ``capacity`` is the
    per-(device, expert) routed-unit budget (static shapes for XLA); ``k``
    experts per token (top-1 switch by default, top-2 GShard with
    ``renormalize`` defaulting to True for k > 1, raw-prob weighting for
    k = 1).
    """
    ep = mesh.shape[axis]
    if n_experts % ep != 0:
        raise ValueError(f"n_experts {n_experts} not divisible by ep={ep}")
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    if not 1 <= k <= n_experts:
        raise ValueError(f"k must be in [1, {n_experts}], got {k}")
    if renormalize is None:
        renormalize = k > 1
    body = partial(_moe_body, n_experts=n_experts, capacity=capacity,
                   axis=axis, k=k, renormalize=renormalize)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(lambda params, x: fn(x, params["gate"], params["w_in"],
                                        params["w_out"]))
