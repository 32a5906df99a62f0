"""Micro-batch pipeline parallelism across TPU chips.

The reference stops at BlockSequential's stepwise backward (one block's
compute while another block's collective is in flight,
BlockSequential.lua:114-151) — no true multi-stage pipeline exists there
(SURVEY.md §2.3 PP row).  This module adds the real thing for BASELINE
config 4 ("BlockSequential model-parallel CNN pipelined across TPU chips"):

GPipe schedule over a ``pp`` mesh axis, TPU-native form:
* stage parameters are **stacked** on a leading axis sharded over ``pp`` —
  each chip holds exactly its stage's weights;
* the schedule is a ``lax.scan`` over M + S - 1 ticks; each tick every
  stage runs its block on its in-flight micro-batch and hands the
  activation to the next stage with a neighbour ``ppermute`` — the
  chip-to-chip ICI hop, one neighbour exchange per tick, the same
  communication shape as the reference's chunked rings
  (lib/detail/README.md:1-48);
* reverse-mode AD through the scan + ppermute yields the backward pipeline
  (ppermute transposes to the opposite shift), so ``jax.grad`` of a
  pipelined loss "just works".

Constraints (standard GPipe): every stage maps (mb, d) -> (mb, d) with one
shared carrier shape; embed/head live outside the pipeline or inside stage
parameters.

Two schedules:
* GPipe via AD (``make_pipeline_fn``): differentiable, sharded I/O by
  default (inputs hop to stage 0 per group, outputs ship from the last
  stage — no psum broadcast); stashes M micro-batch activations per stage.
* 1F1B / PipeDream-flush (``make_1f1b_step``): explicit interleaved
  forward/backward driven by a statically simulated schedule
  (``schedule_1f1b``), capping the stash at S instead of M — the schedule
  the reference's overlap discipline (BlockSequential.lua:114-151) points
  toward at multi-stage scale.  ``pipeline_stats`` reports tick counts,
  bubble fraction, and stash bounds for both.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import AXIS_PP

StageFn = Callable[[Any, jax.Array], jax.Array]   # (stage_params, h) -> h


def stack_stage_params(per_stage: list) -> Any:
    """Stack S same-structure stage pytrees on a new leading axis (the axis
    sharded over pp)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def stage_sharding(mesh: Mesh, params_stacked: Any, axis: str = AXIS_PP) -> Any:
    """device_put stacked params with the leading (stage) axis on ``axis``."""
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))), params_stacked)


def _check_one_stage_per_device(params_local, S):
    # params_local leaves: (1, ...) — this chip's stage.  A leading dim != 1
    # means the stacked stage count doesn't match the pp axis: squeezing
    # would silently drop stages.
    for leaf in jax.tree.leaves(params_local):
        if leaf.shape[0] != 1:
            raise ValueError(
                f"stacked stage count {leaf.shape[0] * S} != pp axis size "
                f"{S}; one stage per pipeline device required")
    return jax.tree.map(lambda a: a[0], params_local)


def make_pipeline_fn(
    mesh: Mesh,
    stage_fn: StageFn,
    n_microbatches: int,
    axis: str = AXIS_PP,
    sharded_io: Optional[bool] = None,
    auto_other_axes: bool = False,
    manual_axes: Optional[Sequence[str]] = None,
    param_in_specs: Any = None,
    io_batch_axis: Optional[str] = None,
):
    """Build ``fn(params_stacked, x) -> y`` running the GPipe schedule.

    ``x``: (M, mb, d) micro-batched input (M = n_microbatches);
    ``y``: (M, mb, d) final-stage outputs.  params_stacked leading axis
    sharded over ``axis``.

    ``sharded_io`` (default: on whenever ``M % S == 0`` and S > 1) shards
    the micro-batch axis of x and y over the pipeline stages instead of
    replicating them: per chip the I/O footprint drops from ``M`` to
    ``M/S`` micro-batches.  Stage g's input shard is handed to stage 0 by a
    single neighbour-payload ``ppermute`` right before its group of ticks
    runs, and each output group is shipped from the last stage to its owner
    the same way — there is no all-stage ``psum`` broadcast on the output
    path.

    ``auto_other_axes=True`` makes only ``axis`` manual in the shard_map
    and leaves every other mesh axis to GSPMD — the 3-D composition hook:
    stage params arrive tp-sharded and micro-batches dp-sharded, and the
    compiler partitions the stage compute over those axes while this
    schedule drives the pp hand-offs (the multi-communicator-level
    composition of the reference, ref
    examples/mnist/mnist_parameterserver_easgd_dataparallel.lua:28-36,
    played out inside one jit).

    ``manual_axes`` + ``param_in_specs`` instead make EXTRA mesh axes
    manual alongside ``axis`` (remaining axes stay auto): the stage_fn
    then receives raw per-device weight shards and writes its own
    collectives over those axes.  This exists because GSPMD cannot
    partition a Pallas custom call — an auto-sharded stage replicates
    flash attention over dp x tp, gathering its operands every tick
    (measured, BASELINE.md round 4); a tp-manual stage body runs flash on
    its own head shard.  ``param_in_specs`` is the stacked-params spec
    pytree (leading dim = ``axis``; tp on the weight dims).
    """
    S = mesh.shape[axis]
    M = n_microbatches
    if sharded_io is None:
        sharded_io = S > 1 and M % S == 0
    if sharded_io and M % S:
        raise ValueError(f"sharded_io needs M % S == 0, got M={M}, S={S}")
    if manual_axes is not None:
        if param_in_specs is None:
            raise ValueError("manual_axes needs param_in_specs (per-leaf "
                             "stacked-param specs)")
        sm_kwargs = dict(axis_names={axis, *manual_axes})
    else:
        sm_kwargs = dict(axis_names={axis}) if auto_other_axes else {}
    param_specs_in = P(axis) if param_in_specs is None else param_in_specs
    # ``io_batch_axis`` manual-shards each micro-batch's BATCH dim too
    # (x: (M, mb, ...) -> M over ``axis``, mb over the batch axis), for
    # fully-manual bodies where even the batch axis must not be GSPMD's
    # (the Pallas-in-stage case: an auto batch axis would still gather the
    # custom call's operands).
    io_spec = (P(axis) if io_batch_axis is None
               else P(axis, io_batch_axis))
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick_fn(p_stage, stage, t, feed, h_in, out_buf):
        """One pipeline tick: run the stage, bank the last stage's result,
        hand the activation to the neighbour (the ICI hop)."""
        h = jnp.where(stage == 0, feed, h_in)
        h_out = stage_fn(p_stage, h)
        mb_idx = t - stage
        valid = (mb_idx >= 0) & (mb_idx < M)
        h_out = jnp.where(valid, h_out, jnp.zeros_like(h_out))
        write = valid & (stage == S - 1)
        idx = jnp.clip(mb_idx, 0, M - 1)
        slot = lax.dynamic_slice_in_dim(out_buf, idx, 1, axis=0)
        new_slot = jnp.where(write, h_out[None], slot)
        out_buf = lax.dynamic_update_slice_in_dim(out_buf, new_slot, idx, axis=0)
        h_next = lax.ppermute(h_out, axis, fwd_perm)
        return h_next, out_buf

    def body_replicated(params_local, x):
        p_stage = _check_one_stage_per_device(params_local, S)
        stage = lax.axis_index(axis)
        mb_shape = x.shape[1:]

        def tick(carry, t):
            h_in, out_buf = carry
            feed = x[jnp.minimum(t, M - 1)]
            return tick_fn(p_stage, stage, t, feed, h_in, out_buf), None

        h0 = jnp.zeros(mb_shape, x.dtype)
        out0 = jnp.zeros((M,) + mb_shape, x.dtype)
        (_, out), _ = lax.scan(tick, (h0, out0), jnp.arange(M + S - 1))
        # Everyone but the last stage holds zeros; one psum replicates the
        # result to all stages.
        return lax.psum(out, axis)

    def body_sharded(params_local, x_shard):
        p_stage = _check_one_stage_per_device(params_local, S)
        stage = lax.axis_index(axis)
        G = M // S                    # micro-batches per group (= per shard)
        mb_shape = x_shard.shape[1:]

        h = jnp.zeros(mb_shape, x_shard.dtype)
        out_buf = jnp.zeros((M,) + mb_shape, x_shard.dtype)
        t0 = 0
        # Feed phase: group g's input shard hops from its owner directly to
        # stage 0 right before its G ticks run (one neighbour-sized payload
        # per group instead of a full replicated copy of x per stage).
        for g in range(S):
            feed_buf = (x_shard if g == 0
                        else lax.ppermute(x_shard, axis, [(g, 0)]))

            def tick(carry, i, feed_buf=feed_buf, t0=t0):
                h_in, ob = carry
                return tick_fn(p_stage, stage, t0 + i, feed_buf[i],
                               h_in, ob), None

            (h, out_buf), _ = lax.scan(tick, (h, out_buf), jnp.arange(G))
            t0 += G
        # Drain phase: S-1 ticks with no feed.
        zero_feed = jnp.zeros(mb_shape, x_shard.dtype)

        def drain_tick(carry, i, t0=t0):
            h_in, ob = carry
            return tick_fn(p_stage, stage, t0 + i, zero_feed, h_in, ob), None

        (h, out_buf), _ = lax.scan(drain_tick, (h, out_buf), jnp.arange(S - 1))

        # Output delivery: ship each owner its G-slice straight from the
        # last stage (no all-stage psum broadcast).  parts[j] is non-zero
        # only on stage j (unaddressed ppermute destinations read zeros, and
        # out_buf is zeros off the last stage), so the sum keeps exactly
        # this stage's shard.
        parts = []
        for j in range(S):
            sl = lax.dynamic_slice_in_dim(out_buf, j * G, G, axis=0)
            parts.append(sl if j == S - 1
                         else lax.ppermute(sl, axis, [(S - 1, j)]))
        return sum(parts)

    if not sharded_io:
        repl_io = (P() if io_batch_axis is None else P(None, io_batch_axis))
        return shard_map(
            body_replicated, mesh=mesh,
            in_specs=(param_specs_in, repl_io), out_specs=repl_io,
            check_vma=False, **sm_kwargs)
    return shard_map(
        body_sharded, mesh=mesh,
        in_specs=(param_specs_in, io_spec), out_specs=io_spec,
        check_vma=False,
        **sm_kwargs)


# ------------------------------------------------------------------- 1F1B
#
# GPipe (above, via AD of the forward scan) runs all M forwards, then all M
# backwards: every stage stashes M micro-batch activations.  1F1B
# (PipeDream-flush) interleaves — each stage starts backwards as soon as the
# last stage can, capping the stash at ~S instead of M.  AD cannot produce
# that interleaving from a forward scan, so the 1F1B step is built
# explicitly: a static schedule (computed by a tiny Python simulator at
# trace time) says, per (tick, stage), which micro-batch to forward and
# which to backward; the scan body executes the scheduled ops under
# ``lax.cond`` (stage-varying predicates are fine because stage_fn is
# collective-free) and hands activations/gradients to neighbours with
# unconditional ppermutes.


def schedule_1f1b(S: int, M: int, combined: bool = False):
    """Simulate the 1F1B schedule, synchronous hand-off (results usable
    next tick).

    ``combined=False`` (the cond-gated executed body): one op (fwd OR bwd
    of one micro-batch) per stage per tick — the classic alternating
    1F1B, stash <= S+1, T ~= 2M + 2(S-1) ticks.

    ``combined=True`` (the cond-free executed body, which computes BOTH
    slots every tick and masks): up to one fwd AND one bwd per stage per
    tick.  Because an idle slot still costs its compute in that body, the
    policy packs both slots greedily; full throughput under the 1-tick
    hand-off latency needs the in-flight window opened to ``2(S-s)``
    (a micro-batch's bwd returns to stage ``s`` ~``2(S-s)`` ticks after
    its fwd leaves), giving T ~= M + 2S - 1 at a stash bound of
    ``2S - 1`` — still M-independent, the 1F1B point.

    Returns ``(fwd_sched, bwd_sched, max_stash)``: two (T, S) int arrays
    (-1 = idle) and the high-water count of activations any stage holds
    between its forward and backward of a micro-batch — the memory bound
    the schedule exists to cap (vs M for GPipe).
    """
    fwd_ready = [set(range(M)) if s == 0 else set() for s in range(S)]
    bwd_ready = [set() for _ in range(S)]
    fwd_next = [0] * S
    bwd_next = [0] * S
    depth = (lambda s: 2 * (S - s)) if combined else (lambda s: S - s)
    warmup = [min(depth(s), M) for s in range(S)]
    fwd_rows, bwd_rows = [], []
    max_stash = 0
    limit = 4 * (M + S) + 8
    while any(b < M for b in bwd_next):
        if len(fwd_rows) > limit:
            raise RuntimeError(f"1F1B schedule did not converge (S={S}, M={M})")
        f_row, b_row = [-1] * S, [-1] * S
        # Decide from the last stage down so each stage knows whether its
        # downstream fwd-link buffer is being consumed this tick (credit-
        # based flow control: a send needs a free — or freeing — buffer).
        # The upstream bwd link (decided later in the sweep) is gated
        # conservatively on its tick-start state in alternating mode; the
        # combined policy bets one deep on same-tick consumption (the
        # send/consume ordering inside the executed tick permits it) and
        # the effects phase below still hard-asserts the single buffer.
        for s in reversed(range(S)):
            can_f = fwd_next[s] < M and fwd_next[s] in fwd_ready[s]
            if can_f and s + 1 < S and fwd_ready[s + 1]:
                can_f = f_row[s + 1] == next(iter(fwd_ready[s + 1]))
            can_b = bwd_next[s] < M and bwd_next[s] in bwd_ready[s]
            if can_b and s - 1 >= 0 and bwd_ready[s - 1]:
                can_b = combined and len(bwd_ready[s - 1]) == 1
            if combined:
                if can_b:
                    b_row[s] = bwd_next[s]
                inflight = fwd_next[s] + 1 - bwd_next[s] - (b_row[s] >= 0)
                if can_f and inflight <= warmup[s]:
                    f_row[s] = fwd_next[s]
            elif can_b and (fwd_next[s] >= warmup[s] or not can_f):
                b_row[s] = bwd_next[s]
            elif can_f:
                f_row[s] = fwd_next[s]
        # Consumptions free the (single) link buffers before this tick's
        # sends land in them.
        for s in range(S):
            if f_row[s] >= 0 and s > 0:
                fwd_ready[s].discard(f_row[s])
            if b_row[s] >= 0 and s < S - 1:
                bwd_ready[s].discard(b_row[s])
        for s in range(S):
            if f_row[s] >= 0:
                m = f_row[s]
                fwd_next[s] += 1
                if s + 1 < S:
                    # The executed pipeline holds ONE in-flight activation
                    # per neighbour link (a single scan-carry buffer); the
                    # policy must consume before the next send.
                    if fwd_ready[s + 1]:
                        raise RuntimeError(
                            f"1F1B schedule needs >1 fwd buffer at stage "
                            f"{s + 1} (S={S}, M={M})")
                    fwd_ready[s + 1].add(m)
                else:
                    bwd_ready[s].add(m)     # last stage: bwd follows its fwd
            if b_row[s] >= 0:
                m = b_row[s]
                bwd_next[s] += 1
                if s - 1 >= 0:
                    if bwd_ready[s - 1]:
                        raise RuntimeError(
                            f"1F1B schedule needs >1 bwd buffer at stage "
                            f"{s - 1} (S={S}, M={M})")
                    bwd_ready[s - 1].add(m)
        fwd_rows.append(f_row)
        bwd_rows.append(b_row)
        max_stash = max(max_stash,
                        max(fwd_next[s] - bwd_next[s] for s in range(S)))
    return np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32), max_stash


def pipeline_stats(S: int, M: int, mode: str = "1f1b") -> dict:
    """Schedule analytics: tick count, bubble fraction (idle stage-ticks /
    total stage-ticks), and per-stage activation stash bound.

    GPipe (this module's AD path): 2(M + S - 1) ticks, stash = M.
    1F1B: measured from the simulated schedule, stash <= S + 1.
    1f1b-combined: the cond-free body's packed schedule, stash <= 2S - 1,
    ticks ~= M + 2S - 1 (every tick pays fwd+bwd compute, so its bubble
    fraction counts both slots: idle slot-ticks / 2T).
    """
    if mode == "gpipe":
        ticks = 2 * (M + S - 1)
        return {"ticks": ticks,
                "bubble_fraction": 1.0 - (2.0 * M) / ticks,
                "max_stash": M}
    if mode not in ("1f1b", "1f1b-combined"):
        raise ValueError(
            f"mode must be 'gpipe', '1f1b' or '1f1b-combined', got {mode!r}")
    fs, bs, stash = schedule_1f1b(S, M, combined=(mode == "1f1b-combined"))
    ticks = fs.shape[0]
    # Alternating: one op-slot per tick (2M useful ops in T slots).
    # Combined: two op-slots per tick (the cond-free body pays both).
    slots = 2 * ticks if mode == "1f1b-combined" else ticks
    return {"ticks": ticks,
            "bubble_fraction": 1.0 - (2.0 * M) / slots,
            "max_stash": stash}


def make_1f1b_step(
    mesh: Mesh,
    stage_fn: StageFn,
    loss_fn: Callable[..., jax.Array],
    n_microbatches: int,
    axis: str = AXIS_PP,
    loss_params_example: Any = None,
    return_dx: bool = False,
    auto_other_axes: bool = False,
    manual_axes: Optional[Sequence[str]] = None,
    param_in_specs: Any = None,
    io_batch_axis: Optional[str] = None,
    loss_param_specs: Any = None,
    manual_schedule: str = "combined",
):
    """Build a 1F1B training-gradient function.

    Base form: ``fn(params_stacked, x, targets) -> (mean_loss,
    grads_stacked)`` with ``loss_fn(h_last, target_mb) -> scalar``.

    Two hooks let a full model (embed + pipeline + head) train through the
    schedule (the llama-over-1F1B composition):

    * ``loss_params_example`` — a pytree template: ``loss_fn`` becomes
      ``loss_fn(loss_params, h_last, target_mb)`` and the step signature
      gains ``loss_params`` after ``params_stacked``; the returned tuple
      gains ``loss_grads`` (the mean d loss/d loss_params — the head and
      final-norm gradients, accumulated at the last stage and psum-shared).
    * ``return_dx=True`` — the returned tuple additionally ends with
      ``dx``: (M, mb, d) gradients of the pipeline *input*, accumulated at
      stage 0 (what an embedding's scatter-add needs).

    ``x``: (M, mb, d) micro-batched input; ``targets``: (M, ...) per-micro-
    batch targets; both replicated across stages (the activation stash, not
    the input buffer, is what 1F1B bounds).  In the base form ``stage_fn``
    has no manual axes to write collectives over; the hand-sharded form
    below hosts explicit collectives in EITHER schedule.
    ``auto_other_axes=True`` leaves non-``axis`` mesh axes to GSPMD, which
    MAY place collectives inside the scheduled branches — legal here
    because every predicate depends only on (tick, stage) and is therefore
    uniform along the auto axes, so all auto peers of a stage take the
    same branch.

    ``manual_axes`` + ``param_in_specs`` (+ ``io_batch_axis``) instead run
    a HAND-sharded stage under the schedule — the long-context 3-D form,
    where ``stage_fn`` writes its own Megatron psums over the extra manual
    axes and calls the Pallas flash kernels on its local head shard (GSPMD
    cannot partition a custom call; see ``make_pipeline_fn``).
    ``manual_schedule`` picks the tick discipline:

    * ``"combined"`` (default) — a COND-FREE body: both slots (stage fwd +
      stage vjp) execute unconditionally every tick and idle slots are
      masked out, so every collective inside ``stage_fn`` runs on every
      device every tick, trivially matched.  Because an idle slot still
      costs its compute, the schedule packs one fwd AND one bwd per tick
      (``schedule_1f1b(combined=True)``): T ~= M + 2S - 1 ticks at a
      stash bound of 2S - 1.  Best wall-clock (a combined tick costs
      fwd+bwd once vs the alternating form's max-synced op over 2x the
      ticks).
    * ``"alternating"`` — the classic cond-GATED one-op-per-tick 1F1B
      with the stash bound at S + 1, the memory-optimal form.  The
      explicit collectives sit under the scheduled ``lax.cond`` — legal
      because every predicate depends only on (tick, stage) and is
      therefore uniform across each tp/dp group, so all group peers take
      the same branch and the collectives execute matched (the round-4
      "psums cannot live under the cond" diagnosis was the in-region vjp
      transpose problem, fixed by the f/g markers, not the cond itself).

    In both manual schedules, ``stage_fn``'s vjp must be correct when
    taken PER DEVICE — explicit psums need Megatron f/g ``custom_vjp``
    markers (identity-fwd/psum-bwd at each block input) so the in-body
    ``jax.vjp`` yields true input cotangents; under ``"combined"``,
    ``stage_fn`` must additionally tolerate zero-filled inputs on idle
    ticks (no data-dependent NaNs — the cond-free body computes always
    and masks).  ``loss_fn`` stays cond-gated to the
    last stage yet MAY contain explicit collectives over the manual axes:
    every schedule predicate depends only on (tick, stage), so it is
    uniform across each tp/dp group and group collectives inside the
    branch execute matched (a tp-vocab-sharded cross-entropy rides this
    — its vjp needs the same per-device-correctness discipline as
    ``stage_fn``'s).  With ``io_batch_axis`` loss_fn sees the per-device
    batch shard and all returned values are reduced as means over the
    batch axis.  ``loss_param_specs`` (default: fully replicated) gives
    the loss-param pytree's per-leaf specs — both the entry sharding and
    the returned loss-grad sharding (leaves sharded over non-reduced axes
    come back per-shard, e.g. a vocab-sharded head's grads).

    Backward is explicit (``jax.vjp`` per scheduled op), not AD-through-
    scan, so parameter gradients come back stage-stacked, ready for
    ``optax``/SGD on the same sharding as the parameters.
    """
    S = mesh.shape[axis]
    M = n_microbatches
    manual = manual_axes is not None
    if manual_schedule not in ("combined", "alternating"):
        raise ValueError("manual_schedule must be 'combined' or "
                         "'alternating'")
    cond_free = manual and manual_schedule == "combined"
    if manual and param_in_specs is None:
        raise ValueError("manual_axes needs param_in_specs (per-leaf "
                         "stacked-param specs)")
    if manual and auto_other_axes:
        raise ValueError("manual_axes and auto_other_axes are exclusive")
    if io_batch_axis is not None and (
            not manual or io_batch_axis not in manual_axes):
        raise ValueError("io_batch_axis must name one of manual_axes")
    fs, bs, stash_hw = schedule_1f1b(S, M, combined=cond_free)
    T = fs.shape[0]
    K = stash_hw + 1                       # stash slots (m % K is unique)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]
    fsched = jnp.asarray(fs)               # (T, S)
    bsched = jnp.asarray(bs)
    with_lp = loss_params_example is not None

    def body(params_local, loss_params, x, targets):
        p_stage = _check_one_stage_per_device(params_local, S)
        stage = lax.axis_index(axis)
        is_last = stage == S - 1
        mb_shape = x.shape[1:]

        def apply_loss(h_out, tgt):
            """(loss, dseed, d loss_params) for one micro-batch."""
            if with_lp:
                loss_m, (dlp, dseed) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1))(loss_params, h_out, tgt)
            else:
                loss_m, dseed = jax.value_and_grad(loss_fn)(h_out, tgt)
                dlp = None
            return loss_m, dseed, dlp

        def zeros_lp():
            return (jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                 loss_params) if with_lp else None)

        def tick(carry, t):
            (h_fwd_in, g_bwd_in, in_stash, seed_stash, acc, lp_acc,
             dx_buf, loss_acc) = carry
            m_f = fsched[t, stage]
            m_b = bsched[t, stage]
            do_f = m_f >= 0
            do_b = m_b >= 0
            mf = jnp.clip(m_f, 0, M - 1)
            mb_ = jnp.clip(m_b, 0, M - 1)

            # ---- forward op (scheduled): stage compute + loss seed at the
            # last stage; stash the input for the later backward.
            feed = x[mf]
            h_in = jnp.where(stage == 0, feed, h_fwd_in)

            # Loss work (incl. the (d_model, vocab) head backward when
            # loss_params are in play) only exists on the LAST stage —
            # gate it there so the other S-1 stages skip it at runtime
            # instead of computing and discarding it every tick.
            def with_loss(h_out):
                loss_m, dseed, dlp = apply_loss(h_out, targets[mf])
                # f32 to match the skip branch whatever loss_fn's
                # compute dtype is.
                return (loss_m.astype(jnp.float32), dseed,
                        dlp if with_lp else 0)

            def no_loss(_):
                return (jnp.zeros((), jnp.float32),
                        jnp.zeros(mb_shape, x.dtype),
                        jax.tree.map(jnp.zeros_like, loss_params)
                        if with_lp else 0)

            if cond_free:
                # Stage collectives must run unconditionally: compute
                # every tick, mask idle slots.  The loss stays cond-gated
                # to the last stage — it MAY contain manual-axis
                # collectives (e.g. the tp-sharded CE's pmax/psums)
                # because its predicate depends only on (tick, stage) and
                # is therefore uniform across each tp/dp group.
                h_full = stage_fn(p_stage, h_in)
                loss_m, dseed, dlp = lax.cond(do_f & is_last, with_loss,
                                              no_loss, h_full)
                h_out = jnp.where(do_f, h_full, jnp.zeros(mb_shape, x.dtype))
            else:
                def run_fwd(_):
                    h_out = stage_fn(p_stage, h_in)
                    loss_m, dseed, dlp = lax.cond(is_last, with_loss,
                                                  no_loss, h_out)
                    return h_out, loss_m, dseed, dlp

                def skip_fwd(_):
                    z = jnp.zeros(mb_shape, x.dtype)
                    return (z,) + no_loss(None)

                h_out, loss_m, dseed, dlp = lax.cond(do_f, run_fwd,
                                                     skip_fwd, None)
            if with_lp:
                on_lp = do_f & is_last
                lp_acc = jax.tree.map(
                    lambda a, g: a + jnp.where(on_lp, g, 0).astype(a.dtype),
                    lp_acc, dlp)
            slot_f = mf % K

            def upd(buf, val, on):
                cur = lax.dynamic_slice_in_dim(buf, slot_f, 1, 0)[0]
                return lax.dynamic_update_slice_in_dim(
                    buf, jnp.where(on, val, cur)[None], slot_f, axis=0)

            in_stash = upd(in_stash, h_in, do_f)
            seed_stash = upd(seed_stash, dseed, do_f & is_last)
            loss_acc = loss_acc + jnp.where(do_f & is_last,
                                            loss_m.astype(jnp.float32), 0.0)

            # ---- backward op (scheduled): re-form the vjp from the stashed
            # input; grad seed comes from the loss (last stage) or the
            # neighbour hand-off.
            slot_b = mb_ % K
            h_saved = lax.dynamic_slice_in_dim(in_stash, slot_b, 1, 0)[0]
            g_seed = lax.dynamic_slice_in_dim(seed_stash, slot_b, 1, 0)[0]
            g_in = jnp.where(is_last, g_seed, g_bwd_in)

            def run_bwd(_):
                _, vjp = jax.vjp(stage_fn, p_stage, h_saved)
                dp, dh = vjp(g_in)
                return dp, dh

            def skip_bwd(_):
                return (jax.tree.map(jnp.zeros_like, p_stage),
                        jnp.zeros(mb_shape, x.dtype))

            if cond_free:
                dp, dh = run_bwd(None)
                dp = jax.tree.map(lambda g: jnp.where(do_b, g, 0), dp)
                dh = jnp.where(do_b, dh, jnp.zeros(mb_shape, x.dtype))
            else:
                dp, dh = lax.cond(do_b, run_bwd, skip_bwd, None)
            acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc, dp)
            if return_dx:
                # Stage 0's dh is d loss/d x[mb_] — bank it by micro-batch.
                on_dx = do_b & (stage == 0)
                cur = lax.dynamic_slice_in_dim(dx_buf, mb_, 1, 0)[0]
                dx_buf = lax.dynamic_update_slice_in_dim(
                    dx_buf, jnp.where(on_dx, dh.astype(dx_buf.dtype),
                                      cur)[None], mb_, axis=0)

            # ---- neighbour hand-offs.  The ppermute runs every tick (SPMD);
            # a receiver only *latches* the payload when the schedule says
            # its neighbour actually sent, so idle-tick zeros never clobber
            # a not-yet-consumed activation/gradient (the simulator asserts
            # at most one is outstanding per link).
            h_recv = lax.ppermute(jnp.where(do_f, h_out, 0), axis, fwd_perm)
            g_recv = lax.ppermute(jnp.where(do_b, dh, 0), axis, bwd_perm)
            prev_sent = (fsched[t, jnp.maximum(stage - 1, 0)] >= 0) & (stage > 0)
            next_sent = (bsched[t, jnp.minimum(stage + 1, S - 1)] >= 0) & (
                stage < S - 1)
            h_fwd_next = jnp.where(prev_sent, h_recv, h_fwd_in)
            g_bwd_next = jnp.where(next_sent, g_recv, g_bwd_in)
            return (h_fwd_next, g_bwd_next, in_stash, seed_stash,
                    acc, lp_acc, dx_buf, loss_acc), None

        z = jnp.zeros(mb_shape, x.dtype)
        stash0 = jnp.zeros((K,) + mb_shape, x.dtype)
        acc0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p_stage)
        dx0 = jnp.zeros((M,) + mb_shape, jnp.float32)
        carry0 = (z, z, stash0, stash0, acc0, zeros_lp(), dx0,
                  jnp.zeros((), jnp.float32))
        (_, _, _, _, acc, lp_acc, dx_buf, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T))
        # Mean over micro-batches; loss lives on the last stage only, so one
        # scalar psum shares it (gradients are already where they belong;
        # loss-param grads and dx live on one stage each and psum-replicate
        # the same way — every other stage contributes zeros).  With a
        # manual batch axis, per-device values are per-shard means: the
        # global mean additionally averages over that axis (loss/lp/dx sum
        # the batch axis in; stage grads stay per-tp-shard but average
        # their batch-shard contributions).
        bsz = mesh.shape[io_batch_axis] if io_batch_axis else 1
        batch_axes = (io_batch_axis,) if bsz > 1 else ()
        denom = M * bsz
        # The aggregation psums below are GRADIENT wires (stage grads over
        # the batch axis, loss-param grads, dx) — they ride the
        # backend-gated manual wire dtype (tp.resolve_wire_dtype: bf16 on
        # TPU at half the f32 bytes, f32 elsewhere).  The scalar loss psum
        # stays f32: one element, and the reported loss should not round.
        from . import tp as _tp

        wire = _tp.resolve_wire_dtype()

        def wire_psum(a, axes):
            return lax.psum(a.astype(wire), axes).astype(a.dtype)

        loss = lax.psum(loss_acc, (axis,) + batch_axes) / denom
        if batch_axes:
            grads = jax.tree.map(
                lambda a: (wire_psum(a, batch_axes) / denom)[None], acc)
        else:
            grads = jax.tree.map(lambda a: (a / denom)[None], acc)
        out = [loss, grads]
        if with_lp:
            out.append(jax.tree.map(
                lambda a: wire_psum(a, (axis,) + batch_axes) / denom,
                lp_acc))
        if return_dx:
            # dx stays batch-sharded (each device's rows are its shard's);
            # only the stage axis reduces (stage 0 holds the values).
            out.append(wire_psum(dx_buf, axis) / denom)
        return tuple(out)

    io_spec = P() if io_batch_axis is None else P(None, io_batch_axis)
    lp_specs = P() if loss_param_specs is None else loss_param_specs
    out_specs = [P(), param_in_specs if manual else P(axis)]
    if with_lp:
        out_specs.append(lp_specs)
    if return_dx:
        out_specs.append(io_spec if manual else P())
    # auto_other_axes: dp (and tp) stay GSPMD's while pp is manual — legal
    # under the scheduled lax.conds because every predicate is uniform
    # along the auto axes (it depends only on (tick, stage)), so all auto
    # peers of a stage take the same branch and any collective GSPMD
    # places inside a branch executes consistently.
    if manual:
        sm_kwargs = dict(axis_names={axis, *manual_axes})
    elif auto_other_axes:
        sm_kwargs = dict(axis_names={axis})
    else:
        sm_kwargs = {}
    inner = shard_map(
        body, mesh=mesh,
        in_specs=(param_in_specs if manual else P(axis), lp_specs,
                  io_spec, io_spec),
        out_specs=tuple(out_specs),
        check_vma=False, **sm_kwargs)

    if with_lp:
        return inner

    def compat(params_stacked, x, targets):
        return inner(params_stacked, None, x, targets)

    return compat


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """(B, d) -> (M, B/M, d)."""
    B = x.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(f"batch {B} not divisible into {n_microbatches} micro-batches")
    return x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])


def unmicrobatch(y: jax.Array) -> jax.Array:
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])
