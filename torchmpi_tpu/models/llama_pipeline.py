"""Pipeline-parallel training for ``models/llama``: the stacked decoder
layers as stages over the mesh's ``pp`` axis, on the GPipe schedule
(:func:`make_pp_train_step`) and on 1F1B (:func:`make_1f1b_train_step`), with
the stage under GSPMD or hand-sharded over ``tp``
(:func:`_decoder_layer_tp_manual`, and the vocabulary-sharded loss that goes
with it), and the placement of the parameters for both
(:func:`param_specs_pp`, :func:`shard_params_pp`).

What a configuration must be for the stages to run it is ``llama._LACKS``'s
to say: each builder opens with its one :func:`llama._refuse`.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import pipeline as _pp
from ..parallel.mesh import AXIS_DP, AXIS_PP, AXIS_TP
from ._common import mesh_spec as _mesh_spec, shard_by_specs
from .llama import (Config, Params, _decoder_layer, _make_attn_impl,
                    _nll_from_hidden, _refuse, _wrap_remat,
                    _zero1_opt_shardings, param_specs, rms_norm, rope)


def _make_tp_ce_sum(axis: str):
    """Summed next-token CE with a VOCAB-COLUMN-SHARDED head, for use
    INSIDE a manual shard_map region: ``ce(head_local, h, targets)`` where
    ``head_local`` is this device's (D, V/tp) shard and ``h`` is
    tp-replicated.  Forward uses pmax/psum over ``axis`` for the global
    logsumexp and the cross-shard target-logit pick; backward is the
    ANALYTIC softmax-minus-onehot rule with an explicit psum on ``dh`` —
    a ``custom_vjp``, because inside a manual region no partitioner
    rewrites transposes and a plain ``lax.psum``'s transpose is identity
    (measured wrong, round-5 probe).  Collectives are legal under the
    1F1B schedule's ``lax.cond`` s: every predicate is uniform across the
    tp group (it depends only on (tick, stage)).

    Returns the SUM of per-token NLL over the block (callers divide by
    the global token count), so chunked accumulation composes by
    addition.  Reference: the tp-sharded classifier + criterion the
    reference runs per model-parallel shard, mnist_modelparallel.lua.
    """

    @jax.custom_vjp
    def ce(head_local, h, targets):
        return _fwd_core(head_local, h, targets)[0]

    def _fwd_core(head_local, h, targets):
        Vl = head_local.shape[-1]
        off = lax.axis_index(axis) * Vl
        logits = (h @ head_local).astype(jnp.float32)       # (B, C, Vl)
        m = lax.pmax(jnp.max(logits, axis=-1), axis)        # (B, C)
        e = jnp.exp(logits - m[..., None])
        s = lax.psum(jnp.sum(e, axis=-1), axis)             # (B, C)
        lse = jnp.log(s) + m
        tloc = targets - off
        in_shard = (tloc >= 0) & (tloc < Vl)
        tclip = jnp.clip(tloc, 0, Vl - 1)
        tlogit = jnp.take_along_axis(logits, tclip[..., None], axis=-1)[..., 0]
        tlogit = lax.psum(jnp.where(in_shard, tlogit, 0.0), axis)
        return jnp.sum(lse - tlogit), (e, s, m, in_shard, tclip)

    def fwd(head_local, h, targets):
        loss, (e, s, m, in_shard, tclip) = _fwd_core(head_local, h, targets)
        # Residuals are the SMALL terms only (m, s, masks: (B, C) each);
        # the (B, C, V/tp) exp array is recomputed in bwd from h @ head —
        # otherwise the chunked scan would stack full-logits-sized
        # residuals per chunk and loss_chunk's memory cap would be a lie.
        return loss, (head_local, h, s, m, in_shard, tclip)

    def bwd(saved, g):
        from ..parallel import tp as _tp

        head_local, h, s, m, in_shard, tclip = saved
        Vl = head_local.shape[-1]
        logits = (h @ head_local).astype(jnp.float32)
        p = jnp.exp(logits - m[..., None]) / s[..., None]   # local softmax cols
        sub = jnp.where(in_shard, g, 0.0)
        dl = p * g - jax.nn.one_hot(tclip, Vl, dtype=p.dtype) * sub[..., None]
        # dh sums over the local vocab shard only — psum completes it (the
        # seed hand-off downstream needs the true cotangent).  This is a
        # gradient wire: it rides the backend-gated manual wire dtype
        # (bf16 on TPU — half the bytes per seed hand-off; f32 elsewhere).
        wire = _tp.resolve_wire_dtype()
        dh = lax.psum((dl @ head_local.T.astype(jnp.float32)).astype(wire),
                      axis).astype(jnp.float32)
        dw = jnp.einsum("bcd,bcv->dv", h.astype(jnp.float32), dl)
        return (dw.astype(head_local.dtype), dh.astype(h.dtype),
                np.zeros(tclip.shape, jax.dtypes.float0))

    ce.defvjp(fwd, bwd)
    return ce


def _nll_from_hidden_tp_manual(head_local: jax.Array, h: jax.Array,
                               targets: jax.Array, loss_chunk: int,
                               axis: str = AXIS_TP) -> jax.Array:
    """Mean next-token NLL from post-norm hidden states with the head
    vocab-sharded over the manual ``axis`` — the manual-region counterpart
    of :func:`_nll_from_hidden`, same chunking contract (``loss_chunk``
    caps the live (B, C, V/tp) f32 logits)."""
    B, L, _ = h.shape
    N = B * L
    ce = _make_tp_ce_sum(axis)
    if not loss_chunk:
        return ce(head_local, h, targets) / N
    C = int(loss_chunk)
    if L % C:
        raise ValueError(f"seq len {L} not divisible by loss_chunk {C}")

    def step(acc, idx):
        h_c = lax.dynamic_slice_in_dim(h, idx * C, C, axis=1)
        t_c = lax.dynamic_slice_in_dim(targets, idx * C, C, axis=1)
        return acc + ce(head_local, h_c, t_c), None

    total, _ = lax.scan(step, jnp.zeros((), jnp.float32), jnp.arange(L // C))
    return total / N


def _decoder_layer_tp_manual(cfg: Config, lp, h, positions,
                             markers: bool = False):
    """Decoder block under MANUAL tensor parallelism: ``lp`` leaves are this
    device's tp shards (wq/wk/wv/gate/up column shards, wo/down row shards;
    norms replicated) and the block writes its own Megatron collectives —
    exactly two ``psum`` s over ``tp``.  Attention runs the Pallas flash
    kernels on the LOCAL head shard: this is the composition GSPMD cannot
    produce (it would replicate the unpartitionable custom call and gather
    its operands — measured, BASELINE.md round 4).

    ``markers=True`` wraps each parallel block in the Megatron f/g
    ``custom_vjp`` pair (``parallel.tp.block_input``/``block_output``) so
    the layer's vjp is correct when taken PER DEVICE — required by the
    cond-free 1F1B body, which calls ``jax.vjp`` inside the manual region
    where no partitioner rewrites transposes.  The GPipe path (AD from
    outside the shard_map) differentiates the unmarked form."""
    from ..ops import flash_attention as _flash
    from ..parallel import tp as _tp

    if cfg.qk_norm:
        raise NotImplementedError(
            "QK-norm runs over the whole q and k projections, which the "
            "tp-manual stage holds as column shards: it would need a psum of "
            "the squares over tp that this stage does not write; use the "
            "GSPMD pipeline (tp_manual=False) or make_train_step")
    B, L, _ = h.shape
    hd = cfg.head_dim
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    if markers:
        # After the (replicated) norm, before the sharded projections: the
        # backward psum the marker adds must deliver the COMPLETE branch
        # cotangent to the norm so its weight grads arrive whole.
        x = _tp.block_input(x, AXIS_TP)
    Hl = lp["wq"].shape[-1] // hd          # local head count (H / tp)
    KVl = lp["wk"].shape[-1] // hd
    q = rope((x @ lp["wq"]).reshape(B, L, Hl, hd), positions, cfg.rope_theta)
    k = rope((x @ lp["wk"]).reshape(B, L, KVl, hd), positions, cfg.rope_theta)
    v = (x @ lp["wv"]).reshape(B, L, KVl, hd)
    o = _flash(q, k, v, causal=True,
               scale=float(1.0 / np.sqrt(hd)))

    def tp_sum(part):
        # The wire dtype is backend-gated (parallel.tp.resolve_wire_dtype):
        # f32 off-TPU — partial-sum accuracy, and XLA-CPU's
        # AllReducePromotion pass asserts on bf16 all-reduce inside
        # partial-manual regions (crashes the compiler at 8B width) — and
        # bf16 on TPU, where the pipeline compiles it clean (proven by AOT
        # topology compilation, TOPOLOGY_r06.json) at half the bytes.
        if markers:
            return _tp.block_output(part, AXIS_TP)
        wire = _tp.resolve_wire_dtype()
        return lax.psum(part.astype(wire), AXIS_TP).astype(h.dtype)

    h = h + tp_sum(o.reshape(B, L, Hl * hd) @ lp["wo"])   # row-sharded
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    if markers:
        x = _tp.block_input(x, AXIS_TP)
    g = jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])  # local d_ff shard
    h = h + tp_sum(g @ lp["w_down"])                      # row-sharded
    return h


def _gspmd_compose(mesh: Mesh) -> bool:
    """Does this mesh carry dp/tp axes the pipeline should hand to GSPMD
    (auto axes) alongside manual pp?  One definition for both schedules."""
    sizes = dict(mesh.shape)
    return sizes.get(AXIS_TP, 1) > 1 or sizes.get(AXIS_DP, 1) > 1


def _make_pp_stage_fn_tp_manual(cfg: Config, remat: str,
                                markers: bool = False):
    """Stage program for the tp-MANUAL pipeline: scans ``V`` hand-sharded
    decoder layers (see :func:`_decoder_layer_tp_manual`; ``markers`` for
    the cond-free 1F1B body's in-region vjp)."""

    def stage_fn(lp_stage, h):
        positions = jnp.arange(h.shape[1])

        def layer(h, lp):
            return _decoder_layer_tp_manual(cfg, lp, h, positions,
                                            markers=markers), None

        h, _ = lax.scan(_wrap_remat(layer, remat), h, lp_stage)
        return h

    return stage_fn


def _make_pp_stage_fn(cfg: Config, attn_impl: Callable, remat: str):
    """One pipeline stage: scan ``V`` decoder layers over a (mb, L, D)
    carrier — shared by the GPipe and 1F1B steps so the two schedules run
    the identical stage program."""

    def stage_fn(lp_stage, h):
        # lp_stage: layer pytree with leading dim V; h: (mb, L, D).
        positions = jnp.arange(h.shape[1])

        def layer(h, lp):
            h, _ = _decoder_layer(cfg, lp, h, positions, attn_impl)
            return h, None

        # Per-layer checkpointing bounds the stage's activation memory the
        # way GPipe needs at depth (shared taxonomy: _wrap_remat).
        h, _ = lax.scan(_wrap_remat(layer, remat), h, lp_stage)
        return h

    return stage_fn


def make_pp_train_step(cfg: Config, mesh: Mesh, n_microbatches: int,
                       lr: float = 3e-4, attn: str = "full",
                       remat: str = "none", loss_chunk: int = 0,
                       optimizer=None, opt_state_example=None,
                       zero1: bool = False, stage_tp: str = "auto"):
    """Pipeline-parallel training step: the stacked decoder layers become
    pipeline stages over the mesh's ``pp`` axis (BASELINE config 4's
    pipelined model parallelism applied to the flagship transformer).

    Layers are cut into ``S`` contiguous stages of ``n_layers/S`` each;
    embed and the output head run outside the pipeline (replicated over pp —
    the GPipe carrier must be one (mb, L, D) shape).  The GPipe schedule is
    the differentiable sharded-I/O one (parallel/pipeline.py), so
    ``jax.grad`` produces the backward pipeline.

    **3-D composition**: when the mesh also carries ``tp`` and/or ``dp``
    axes, only ``pp`` is manual in the pipeline's shard_map
    (``auto_other_axes``) and the rest is GSPMD's: stage parameters arrive
    tp-sharded per :func:`param_specs` (place with
    ``shard_params_pp(params, mesh, cfg)``), micro-batches are dp-sharded
    on their batch dim, and the compiler inserts the tp activation psums
    and dp gradient reductions inside every stage tick — the
    multi-communicator-level run of the reference (EASGD over DP with two
    communicators, examples/mnist/mnist_parameterserver_easgd_dataparallel
    .lua:28-36) expressed as one jit over one mesh.  ``zero1=True``
    additionally shards optimizer moments over dp (needs ``optimizer`` +
    ``opt_state_example``).

    ``attn`` supports 'full' and 'flash' (ring/sp does not compose with the
    stage carrier).

    ``stage_tp``: 'auto' (GSPMD partitions the stage over tp — right for
    attn='full', which it tp-shards natively) or 'manual' — the stage body
    is HAND-sharded: tp joins pp as a manual shard_map axis, each device's
    stage_fn gets raw weight shards, writes the two Megatron psums itself,
    and runs the Pallas flash kernels on its own head shard.  'manual' is
    the long-context 3-D form.  GSPMD cannot partition a Pallas custom
    call, so under 'auto' + attn='flash' the kernel nests its own
    shard_map over dp x tp (:func:`_flash_attention_sharded`) while the
    projections around it stay GSPMD's.  'manual' requires attn='flash'.

    Returns ``(step, V)`` with ``V = n_layers/S`` layers per stage.
    Without ``optimizer``: ``step(params, tokens, targets) -> (params,
    loss)`` (plain SGD at ``lr``).  With ``optimizer`` (an optax
    gradient transform): ``step(params, opt_state, tokens, targets) ->
    (params, opt_state, loss)``.  ``params`` as from :func:`init` placed by
    :func:`shard_params_pp`; global batch must be divisible by
    ``n_microbatches``.
    """
    _refuse(cfg, "make_pp_train_step")
    S = mesh.shape[AXIS_PP]
    sizes = dict(mesh.shape)
    compose = _gspmd_compose(mesh)
    if cfg.n_layers % S:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={S}")
    V = cfg.n_layers // S
    if attn not in ("full", "flash"):
        raise ValueError("pp step supports attn='full'|'flash'")
    if zero1 and (optimizer is None or opt_state_example is None):
        raise ValueError("zero1 needs optimizer and opt_state_example")
    if stage_tp == "manual":
        tp = sizes.get(AXIS_TP, 1)
        if AXIS_TP not in mesh.axis_names:
            raise ValueError("stage_tp='manual' needs a tp mesh axis")
        if attn != "flash":
            raise ValueError("stage_tp='manual' runs the flash kernels on "
                             "the local head shard; pass attn='flash'")
        if (cfg.n_heads % tp or cfg.n_kv_heads % tp or cfg.d_ff % tp
                or cfg.d_model % tp):
            raise ValueError(
                f"tp={tp} must divide n_heads/n_kv_heads/d_ff/d_model")
        stage_fn = _make_pp_stage_fn_tp_manual(cfg, remat)
        # Stacked stage-param specs: (S, V, per-layer dims) — pp on the
        # stage dim, tp on the Megatron weight dims.
        stage_specs = {k: P(AXIS_PP, None, *tuple(sp)[1:])
                       for k, sp in param_specs(cfg)["layers"].items()}
        manual = [AXIS_TP]
        io_batch = None
        if sizes.get(AXIS_DP, 1) > 1:
            # dp manual too: an auto batch axis would still gather the
            # Pallas call's operands to replicate it over dp.
            manual.append(AXIS_DP)
            io_batch = AXIS_DP
        pipe = _pp.make_pipeline_fn(mesh, stage_fn, n_microbatches,
                                    axis=AXIS_PP, manual_axes=tuple(manual),
                                    param_in_specs=stage_specs,
                                    io_batch_axis=io_batch)
    elif stage_tp == "auto":
        scale = 1.0 / np.sqrt(cfg.head_dim)
        attn_impl = _make_attn_impl(cfg, attn, mesh if compose else None,
                                    scale)
        stage_fn = _make_pp_stage_fn(cfg, attn_impl, remat)
        pipe = _pp.make_pipeline_fn(mesh, stage_fn, n_microbatches,
                                    axis=AXIS_PP, auto_other_axes=compose)
    else:
        raise ValueError("stage_tp must be 'auto' or 'manual'")

    def constrain(x, spec):
        if not compose:
            return x
        kept = _mesh_spec(spec, mesh, x.shape)
        return lax.with_sharding_constraint(x, NamedSharding(mesh, kept))

    def loss_fn(params, tokens, targets):
        h = params["embed"][tokens]                     # (B, L, D)
        h = constrain(h, P(AXIS_DP, None, None))
        M = n_microbatches
        B = h.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} micro-batches")
        # Micro-batch axis to pp (the pipe's manual axis), per-micro-batch
        # batch dim to dp: each stage tick computes on 1/dp of a micro-batch.
        hm = h.reshape(M, B // M, *h.shape[1:])
        hm = constrain(hm, P(AXIS_PP, AXIS_DP, None, None))
        # (n_layers, ...) -> (S, V, ...): one stage row per pipeline device,
        # V layers inside each stage's scan.
        staged = jax.tree.map(
            lambda a: a.reshape(S, V, *a.shape[1:]), params["layers"])
        hm = pipe(staged, hm)
        h = hm.reshape(B, *h.shape[1:])
        h = constrain(h, P(AXIS_DP, None, None))
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        return _nll_from_hidden(params["head"], h, targets, loss_chunk)

    if optimizer is None:
        def step(params, tokens, targets):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
            params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params, grads)
            return params, loss

        return jax.jit(step, donate_argnums=(0,)), V

    opt_sh = (_zero1_opt_shardings(cfg, mesh, opt_state_example,
                                   specs=param_specs_pp(cfg))
              if zero1 else None)

    def step_opt(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        if opt_sh is not None:
            opt_state = jax.lax.with_sharding_constraint(opt_state, opt_sh)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return jax.jit(step_opt, donate_argnums=(0, 1)), V


def make_1f1b_train_step(cfg: Config, mesh: Mesh, n_microbatches: int,
                         lr: float = 3e-4, attn: str = "full",
                         remat: str = "none", loss_chunk: int = 0,
                         stage_tp: str = "auto",
                         manual_schedule: str = "combined"):
    """Pipeline-parallel llama training on the **1F1B / PipeDream-flush**
    schedule: same stage split and stage program as
    :func:`make_pp_train_step` (shared ``_make_pp_stage_fn``), but the
    explicit interleaved schedule caps the per-stage activation stash at
    ~S micro-batches instead of GPipe's M (parallel/pipeline.py:
    ``make_1f1b_step`` + ``pipeline_stats``) — the schedule that matters
    when M is large enough to amortize the bubble.

    The full model trains: stage grads come from the scheduled vjps, the
    final-norm and output-head grads accumulate at the last stage
    (``loss_params``), and the embedding grad is scatter-added from the
    pipeline-input gradients (``return_dx``).  Returns ``(step, V)``;
    ``step(params, tokens, targets) -> (params, loss)`` (SGD at ``lr``),
    params placed by :func:`shard_params_pp`.

    ``stage_tp='manual'`` (requires ``attn='flash'`` and a tp mesh axis,
    like :func:`make_pp_train_step`'s): the stage body is HAND-sharded —
    tp (and dp when present) join pp as manual shard_map axes, the layers
    carry Megatron f/g markers so the schedule's in-region vjps are exact,
    and the flash kernels run on the local head shard.  This is the
    long-context 3-D form on the S-bounded schedule: GPipe's manual stage
    stashes M micro-batch activations; this one bounds the stash per
    ``manual_schedule`` — ``"combined"`` (default): the packed cond-free
    body, T ~= M+2S-1 ticks at stash <= 2S-1, best wall-clock;
    ``"alternating"``: classic cond-gated one-op ticks, stash <= S+1, the
    memory-optimal form (see ``pipeline.make_1f1b_step``).  The head
    enters vocab-sharded over tp (analytic tp-CE); loss is cond-gated to
    the last stage either way.
    """
    _refuse(cfg, "make_1f1b_train_step")
    S = mesh.shape[AXIS_PP]
    sizes = dict(mesh.shape)
    if cfg.n_layers % S:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={S}")
    V = cfg.n_layers // S
    if attn not in ("full", "flash"):
        raise ValueError("pp step supports attn='full'|'flash'")
    M = n_microbatches

    def loss_fn(lp, h, tgt):
        h = rms_norm(h, lp["norm"], cfg.norm_eps)
        return _nll_from_hidden(lp["head"], h, tgt, loss_chunk)

    lp_example = jax.eval_shape(
        lambda: {"norm": jnp.zeros((cfg.d_model,), jnp.float32),
                 "head": jnp.zeros((cfg.d_model, cfg.vocab), jnp.float32)})
    compose = _gspmd_compose(mesh)
    if stage_tp == "manual":
        tp = sizes.get(AXIS_TP, 1)
        if AXIS_TP not in mesh.axis_names:
            raise ValueError("stage_tp='manual' needs a tp mesh axis")
        if attn != "flash":
            raise ValueError("stage_tp='manual' runs the flash kernels on "
                             "the local head shard; pass attn='flash'")
        if (cfg.n_heads % tp or cfg.n_kv_heads % tp or cfg.d_ff % tp
                or cfg.d_model % tp or cfg.vocab % tp):
            raise ValueError(
                f"tp={tp} must divide n_heads/n_kv_heads/d_ff/d_model/vocab")
        stage_fn = _make_pp_stage_fn_tp_manual(cfg, remat, markers=True)
        stage_specs = {k: P(AXIS_PP, None, *tuple(sp)[1:])
                       for k, sp in param_specs(cfg)["layers"].items()}
        manual = [AXIS_TP]
        io_batch = None
        if sizes.get(AXIS_DP, 1) > 1:
            manual.append(AXIS_DP)
            io_batch = AXIS_DP

        # The head enters VOCAB-SHARDED over tp (its resting layout —
        # no per-step gather of the (D, vocab) matrix) and the loss is
        # the analytic tp-sharded CE; norm stays replicated.
        def loss_fn_manual(lp, h, tgt):
            h = rms_norm(h, lp["norm"], cfg.norm_eps)
            return _nll_from_hidden_tp_manual(lp["head"], h, tgt, loss_chunk)

        pipe = _pp.make_1f1b_step(mesh, stage_fn, loss_fn_manual, M,
                                  axis=AXIS_PP,
                                  loss_params_example=lp_example,
                                  return_dx=True,
                                  manual_axes=tuple(manual),
                                  param_in_specs=stage_specs,
                                  io_batch_axis=io_batch,
                                  loss_param_specs={
                                      "norm": P(),
                                      "head": P(None, AXIS_TP)},
                                  manual_schedule=manual_schedule)
    elif stage_tp == "auto":
        if manual_schedule != "combined":
            # The auto path always runs the cond-gated alternating body;
            # silently accepting the knob would let a caller believe they
            # selected a schedule they did not get.
            raise ValueError("manual_schedule applies to stage_tp='manual' "
                             "only (the auto path is always cond-gated)")
        scale = 1.0 / np.sqrt(cfg.head_dim)
        # No mesh for the kernel here, unlike make_pp_train_step: a
        # shard_map nested in this schedule's lax.cond ticks aborts XLA's
        # SPMD partitioner (spmd_partitioner_util.cc check failure, on the
        # CPU mesh too).  So attn='flash' with composed dp/tp does not
        # lower for a TPU on this path; stage_tp='manual' is the flash form.
        attn_impl = _make_attn_impl(cfg, attn, None, scale)
        stage_fn = _make_pp_stage_fn(cfg, attn_impl, remat)
        # dp/tp compose via GSPMD (auto axes): the scheduled lax.cond
        # predicates depend only on (tick, stage), so they are uniform
        # along dp/tp and the partitioner's placements execute
        # consistently inside the branches.
        pipe = _pp.make_1f1b_step(mesh, stage_fn, loss_fn, M, axis=AXIS_PP,
                                  loss_params_example=lp_example,
                                  return_dx=True,
                                  auto_other_axes=compose)
    else:
        raise ValueError("stage_tp must be 'auto' or 'manual'")

    def constrain(x, spec):
        if not compose:
            return x
        kept = _mesh_spec(spec, mesh, x.shape)
        return lax.with_sharding_constraint(x, NamedSharding(mesh, kept))

    def step(params, tokens, targets):
        B, L = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} micro-batches")
        h = params["embed"][tokens]                     # (B, L, D)
        # Batch to dp BEFORE the micro-batch reshape (GPipe's compose path
        # pins the same thing) — the hint propagates through the reshape;
        # constraining the (M, mb, ...) form directly trips an XLA-CPU
        # compiler abort at the partial-manual shard_map boundary.
        h = constrain(h, P(AXIS_DP, None, None))
        hm = h.reshape(M, B // M, L, -1)
        tm = targets.reshape(M, B // M, L)
        staged = jax.tree.map(
            lambda a: a.reshape(S, V, *a.shape[1:]), params["layers"])
        lp = {"norm": params["norm"], "head": params["head"]}
        loss, g_staged, g_lp, dx = pipe(staged, lp, hm, tm)
        g_layers = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), g_staged)
        # Embedding grad: scatter-add the pipeline-input gradients back to
        # the used rows (d embed[t] = sum of dx over positions with token t).
        d_embed = jnp.zeros(params["embed"].shape, jnp.float32)
        d_embed = d_embed.at[tokens.reshape(-1)].add(
            dx.reshape(B * L, -1).astype(jnp.float32))
        grads = {"embed": d_embed, "layers": g_layers,
                 "norm": g_lp["norm"], "head": g_lp["head"]}
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                              params, grads)
        return params, loss

    return jax.jit(step, donate_argnums=(0,)), V


def param_specs_pp(cfg: Config) -> Params:
    """PartitionSpec pytree for the pipeline step: stacked layer leaves'
    leading (n_layers) axis shards over ``pp`` — contiguous rows land on
    contiguous stages, matching the (S, V) reshape inside the step — while
    the within-layer dims keep :func:`param_specs`' Megatron tp layout.
    Embed/norm stay replicated; the head keeps its tp column sharding."""
    base = param_specs(cfg)
    layers = {k: P(AXIS_PP, *tuple(s)[1:]) for k, s in base["layers"].items()}
    return {"embed": base["embed"], "layers": layers,
            "norm": base["norm"], "head": base["head"]}


def shard_params_pp(params: Params, mesh: Mesh,
                    cfg: Optional[Config] = None) -> Params:
    """Place an :func:`init` pytree for the pipeline step: stacked layer
    leaves (n_layers, ...) sharded over ``pp`` (and, with ``cfg`` given,
    tp within each stage per :func:`param_specs_pp` — the 3-D layout);
    embed/norm replicated."""
    if cfg is not None:
        return shard_by_specs(params, mesh, param_specs_pp(cfg))

    def place(path_is_layer, a):
        spec = P(AXIS_PP) if path_is_layer else P()
        return jax.device_put(a, NamedSharding(mesh, spec))

    return {
        "embed": place(False, params["embed"]),
        "layers": jax.tree.map(lambda a: place(True, a), params["layers"]),
        "norm": place(False, params["norm"]),
        "head": place(False, params["head"]),
    }

