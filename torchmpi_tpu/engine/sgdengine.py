"""AllReduceSGDEngine — the training engine (reference:
torchmpi/engine/sgdengine.lua, a torchnet SGDEngine subclass whose hooks
inject the distributed machinery: initial parameter broadcast, per-step
gradient allreduce, barrier-fenced sampling, iterator prefetch).

Three execution modes, all sharing the hook protocol:

* ``compiled`` (default, the TPU-idiomatic fast path): the entire step —
  forward, backward, ``pmean`` of grads over the replica axis, optimizer
  update — is one pjit'd program over the communicator's mesh.  XLA
  overlaps the gradient collectives with backward compute, subsuming the
  reference's hand-pipelined async backward (nn.lua:112-213) *and* the sync
  path in a single compiled form.  Parameters live replicated on the mesh;
  the batch is sharded along the replica axis.
* ``eager_sync``: parameters are rank-major (one slice per replica); each
  step computes per-replica grads then calls
  ``mpinn.synchronize_gradients`` (bucketed eager allreduce) — the
  reference's synchronous engine loop (sgdengine.lua:126-131).
* ``eager_async``: same, but grads are dispatched with
  ``mpinn.async_.register_async_backward`` and drained before the update —
  the reference's async engine (sgdengine.lua:128-130).

Hooks (reference: tnt.SGDEngine hook table, wrapped at sgdengine.lua:82-135):
``on_start, on_start_epoch, on_sample, on_forward, on_backward, on_update,
on_end_epoch, on_end`` — each called with the mutable engine ``state``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import _startup
from .. import nn as mpinn
from ..collectives import eager
from ..obs import numerics as _numerics
from ..obs import serve as _obs_serve
from ..obs import tracer as _obs
from ..data import pipeline as _data_pipe
from ..data.staging import Staged as _Staged
from ..data.staging import stage_rank_major as _stage
from ..runtime import communicator as _comm_mod
from ..runtime.communicator import RANK_AXIS
from ..utils.meters import AverageValueMeter

LossFn = Callable[[Any, Tuple[jax.Array, jax.Array]], jax.Array]
Hooks = Dict[str, Callable[[Dict[str, Any]], None]]

MODES = ("compiled", "eager_sync", "eager_async")


def _step_correlation(t) -> Optional[int]:
    """Cluster correlation id for step ``t``
    (``tracer.cluster_correlation``): derived from the step number alone,
    so every rank of an SPMD job stamps the SAME id on step t's span —
    the cross-rank join key for merged traces and the straggler
    detector.  None with tracing off (inherit/allocate never runs then,
    and the off path must not pay a hash per step)."""
    if not _obs.enabled():
        return None
    return _obs.cluster_correlation("engine.step", int(t))


def _ended(state) -> bool:
    """A step boundary has ended the loop (``step_boundaries``)."""
    return bool(state.get("departed") or state.get("resized"))


def sgd_update(params, grads, lr):
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def sample_array(state, flatten: bool = False):
    """Hook ergonomics (docs/data.md): the ``(x, y)`` payloads of
    ``state["sample"]`` with the input-pipeline wrapper unwrapped —
    ``Staged`` batches yield their global device ``.array``, raw
    payloads pass through untouched.  Hooks stop hand-unwrapping
    ``state["sample"]`` with ``hasattr(xb, "array")`` dances that break
    the moment ``data_pipeline`` flips.

    ``flatten=True`` additionally views a RAW rank-major host batch
    ``(p, b, ...)`` as the global ``(p*b, ...)`` batch (what a
    ``Staged.array`` already is), so a hook consuming the data gets one
    uniform layout in both pipeline modes.  Accepts the engine ``state``
    dict or a bare ``(x, y)`` sample pair."""
    sample = state["sample"] if isinstance(state, dict) else state
    xb, yb = sample

    def unwrap(a):
        if isinstance(a, _Staged):
            return a.array
        if flatten and getattr(a, "ndim", 0) >= 2:
            return np.reshape(np.asarray(a), (-1,) + tuple(a.shape[2:]))
        return a

    return unwrap(xb), unwrap(yb)


# ------------------------------------------------------------ the run record

RUN_RING = 4096         # steps and completions a record keeps (the newest)
RUNS_KEPT = 16          # records ``runs()`` keeps (the newest)

_RUNS: "deque[RunRecord]" = deque(maxlen=RUNS_KEPT)
_OPEN: Optional["RunRecord"] = None     # the record compilations go to


def runs() -> List["RunRecord"]:
    """This process's most recent :class:`RunRecord` s, oldest first, at
    most ``RUNS_KEPT``: one for every ``AllReduceSGDEngine.train()`` call,
    the one still open included.  For a reader that has neither the engine
    nor the state ``train()`` returned (docs/observability.md)."""
    return list(_RUNS)


def _compiled(row) -> None:
    """A finished ``compile`` row of the start-up account (``_startup.py``,
    the program's one set of ``jax.monitoring`` listeners): JAX records one
    round every compilation of a new program, whether the backend compiles
    it or the persistent cache supplies it."""
    rec = _OPEN
    if rec is not None:
        rec.compiles.append((rec.steps, (row.t1 - row.t0) / 1e9))


_startup.ACCOUNT.compile_sinks.append(_compiled)


def _open_run(rec: "RunRecord") -> Optional["RunRecord"]:
    """Make ``rec`` the record compilations go to; returns the one that was
    (a hook may train another engine inside a call)."""
    global _OPEN
    outer, _OPEN = _OPEN, rec
    _RUNS.append(rec)
    return outer


def _close_run(rec: "RunRecord", outer: Optional["RunRecord"]) -> None:
    global _OPEN
    _OPEN = outer
    rec.t_return = time.monotonic_ns()


class RunRecord:
    """The engine's own account of one ``train()`` call.  Always kept, with
    no knob and independent of ``obs_trace`` and the metrics feed, as
    ``data/device.py:StageStats`` is: plain Python, a handful of clock reads
    a step.  Reached as ``state["run"]``, ``engine.last_run`` and
    :func:`runs`.

    Every stamp is ``time.monotonic_ns()``, the clock of ``obs/tracer.py``;
    a stamp plus ``epoch_offset_ns`` is on the clock of ``time.time_ns()``,
    which a profiler capture's ``profile_start_time`` is given in.  A step
    is numbered within its call, from 0 (``start_step`` is the global
    number of step 0).

    * ``t_enter``, ``t_return``: the call.  ``t_first_batch``: the
      iterator's first yield (pipeline start-up and the first staging end
      here).  ``t_first_dispatch``: the first step's call into the step
      program has returned (start-up ends here).
    * ``step_stamps``, the newest ``RUN_RING``: ``(step, t_batch,
      t_stepped, t_end, wait_ns, hook_ns)``: top of the loop body; the
      step function has returned; the iteration's last controller has;
      time inside ``block_until_ready`` of the in-flight bound; time
      inside the user's hooks.  ``t_batch`` less the step before's
      ``t_end`` is the wait for input, ``t_end - t_batch - wait_ns -
      hook_ns`` the engine's own host time.  After those six, what the
      step function stamped, the only clock it reads: ``t_entry, t_staged,
      t_dispatched, t_sync, t_synced, t_done, blocked_ns``: it has opened
      its ``engine.step`` span; staging has ended; the call into the step
      program (eager: the gradient function) has returned; the two ends of
      its one wait (compiled: the in-flight bound; eager: the gradient
      sync); its last statement inside the span; and, under eager_async
      alone (else ``None``), the time the drain spent inside handle waits.
      The live feed and the phase spans are derived from these
      (``obs/serve.py:engine_step``).
    * ``completions``, same ring: ``(step, stamp)``, the moment the host
      saw step ``step`` finished, taken where the in-flight bound blocks on
      its loss.  The last ``window`` steps of a call have none: the host
      does not wait for them inside the call, and a record is not written
      to after its call has returned.  The eager modes fence within the
      step and have none at all.
    * ``compiles``: ``(step, seconds)`` of every program compiled (or
      loaded from the persistent cache) while the call was open, on any
      thread, from the start-up account's ``compile`` rows
      (``mpi.startup()``); one past step 0 is a recompile.
    * ``steps``, ``mode``, ``window`` (the in-flight bound in force,
      negative for none)."""

    def __init__(self, mode: str, window: int, start_step: int):
        self.mode = mode
        self.window = window
        self.start_step = start_step
        self.steps = 0
        self.t_enter = time.monotonic_ns()
        self.epoch_offset_ns = time.time_ns() - self.t_enter
        self.t_first_batch: Optional[int] = None
        self.t_first_dispatch: Optional[int] = None
        self.t_return: Optional[int] = None
        self.step_stamps: deque = deque(maxlen=RUN_RING)
        self.completions: deque = deque(maxlen=RUN_RING)
        self.compiles: deque = deque(maxlen=RUN_RING)
        # Sums of the step that is open, moved into step_stamps at its end.
        self._wait_ns = 0
        self._hook_ns = 0
        self.profiler = None    # the StepWindowProfiler closed in the call

    @property
    def device(self) -> Optional[Dict[str, Any]]:
        """``utils/profiler.py:StepProfile.summary()`` of the capture, its idle
        gaps named by these stamps, parsed when read; ``None`` without one."""
        profile = self.profiler and self.profiler.profile()
        return profile and dict(profile.summary(), gaps=profile.gaps(
            self.step_stamps, self.epoch_offset_ns))

    def summary(self) -> Dict[str, Any]:
        """The arithmetic, once.  ``step_ms_p50/p90/p95``: percentiles of
        the intervals between the completions of consecutive steps; a
        percentile is given only with ten samples beyond it (``None``
        under 20, 100, 200 intervals).  ``host_ms_p50``: the engine's own
        host time a step.  ``input_wait_ms_p50``: the loop's wait for its
        next batch.  ``start_ms``, ``first_batch_ms``: from ``t_enter`` to
        ``t_first_dispatch``, ``t_first_batch``.  ``recompiles``:
        compilations past step 0."""
        done = list(self.completions)
        gaps = [(b[1] - a[1]) / 1e6 for a, b in zip(done, done[1:])
                if b[0] == a[0] + 1]
        stamps = list(self.step_stamps)
        host = [(s[3] - s[1] - s[4] - s[5]) / 1e6 for s in stamps]
        waits = [(b[1] - a[3]) / 1e6 for a, b in zip(stamps, stamps[1:])]

        def since_enter(t):
            return None if t is None else (t - self.t_enter) / 1e6

        def pct(values, q, least):
            if len(values) < least:
                return None
            return float(np.percentile(values, q))

        return {
            "steps": self.steps,
            "intervals": len(gaps),
            "step_ms_p50": pct(gaps, 50, 20),
            "step_ms_p90": pct(gaps, 90, 100),
            "step_ms_p95": pct(gaps, 95, 200),
            "host_ms_p50": pct(host, 50, 1),
            "input_wait_ms_p50": pct(waits, 50, 1),
            "start_ms": since_enter(self.t_first_dispatch),
            "first_batch_ms": since_enter(self.t_first_batch),
            "recompiles": sum(1 for step, _ in self.compiles if step > 0),
        }


class AllReduceSGDEngine:
    """Distributed SGD training loop (reference: tnt.AllReduceSGDEngine)."""

    def __init__(
        self,
        loss_fn: LossFn,
        lr: float = 0.01,
        optimizer=None,          # optional optax GradientTransformation
        comm=None,
        mode: str = "compiled",
        hooks: Optional[Hooks] = None,
        sync_parameters_on_start: bool = True,
        check_frequency: int = 0,  # steps between check_with_allreduce; 0=off
        zero1: bool = False,
        accum_steps: int = 1,
    ):
        """``zero1`` (compiled mode, with an optimizer): shard the optimizer
        state over the replica axis — ZeRO-1 / optimizer-state sharding.
        Each leaf whose leading dim divides the replica count lives sharded;
        GSPMD then lowers the gradient sync to reduce-scatter into the local
        shard, updates locally, and all-gathers the parameters — the same
        collective volume as allreduce but 1/p the optimizer memory (for
        Adam at 8B scale, that is the difference between fitting and not).

        ``accum_steps`` (compiled mode): gradient accumulation — each batch
        is split into that many equal slices scanned inside the compiled
        step, gradients accumulating in f32, with ONE optimizer update per
        batch.  Grows effective batch beyond what activations allow in HBM;
        numerically equal to the unaccumulated step on the same global
        batch (equal slice sizes make mean-of-means exact)."""
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}")
        if zero1 and mode != "compiled":
            raise ValueError("zero1 requires compiled mode")
        if zero1 and optimizer is None:
            raise ValueError(
                "zero1 shards optimizer state; pass an optax optimizer "
                "(plain SGD keeps no state to shard)")
        if accum_steps < 1:
            raise ValueError("accum_steps must be >= 1")
        if accum_steps > 1 and mode != "compiled":
            raise ValueError("accum_steps requires compiled mode")
        self.loss_fn = loss_fn
        self.lr = lr
        self.optimizer = optimizer
        self._comm = comm
        self.mode = mode
        self.hooks = hooks or {}
        self.sync_parameters_on_start = sync_parameters_on_start
        self.check_frequency = check_frequency
        self.zero1 = zero1
        self.accum_steps = accum_steps
        self._compiled_step = None
        self._compiled_for = None   # cache key the compiled step was built for
        self._batch_sh = None       # staging sharding, hoisted per compile
        self._eager_grad_fn = None
        self._eager_grad_for = None
        # Whether the CURRENT compiled step carries the numerics plane's
        # in-graph sentinels (set beside the compile key: a mode change
        # rebuilds).
        self._sentinels_on = False
        # Compute-efficiency feed: the compiled step's analytical FLOPs
        # (XLA cost model), probed once per compile when telemetry is on.
        self._step_flops = None
        self._flops_probed = False
        self._test_fns = {}   # (metric_fn, mode) -> jitted eval, like the
        #                       compiled-step cache: a second test() epoch
        #                       must not retrace
        self._inflight = []   # dispatch-depth window (see _bound_inflight):
        #                       (loss, the RunRecord it was queued under, step)
        self._run = None      # the RunRecord of the train() call in progress
        self.last_run = None  # ... and of the newest call, open or returned
        # Step-boundary planes attach here from outside
        # (resize.engine_boundary, retune.maybe_install,
        # numerics.Auditor.step_boundary): callables of the engine ``state``,
        # run in order once a step after ``on_update``, outside hook time,
        # where no collective is in flight.  One that sets
        # ``state["departed"]`` or ``state["resized"]`` ends train() there:
        # those after it are skipped, ``on_end_epoch`` and ``on_end`` too.
        self.step_boundaries: List[Callable[[Dict[str, Any]], None]] = []

    @property
    def comm(self):
        return self._comm if self._comm is not None else _comm_mod.stack.current()

    def _bound_inflight(self, marker) -> None:
        """Bound host run-ahead: keep at most ``engine_max_inflight_steps``
        dispatched steps outstanding, blocking on the OLDEST step's loss
        when the window fills.  In steady state that step completed long
        ago, so the wait is ~free while the pipeline stays ``window``
        steps deep.  Knob 0 = auto = 8 on every backend.  The multi-device
        CPU backend needs the bound (unbounded run-ahead starves its
        collective rendezvous into the fatal stuck-detector).  The TPU was
        once left unbounded because a readiness check cost ~60 ms on the
        rounds 2-5 set-up; on a v5e chip of the chip tool's machine (PR 21)
        the check costs 0.4 us and a window of 8 runs ResNet-50 at batch
        128 as fast as none: 46.400 against 46.407 ms/step resident, 47.367
        against 47.415 streamed (medians of three 48-step windows).  So the
        chip runs the path the CPU tests run.  A negative knob is
        unbounded."""
        window = self._inflight_window()
        if window < 0:
            return
        rec = self._run
        self._inflight.append((marker, rec, 0 if rec is None else rec.steps))
        while len(self._inflight) > window:
            oldest, queued_under, step = self._inflight.pop(0)
            t0 = time.monotonic_ns()
            oldest.block_until_ready()
            if rec is None:             # test(): no record
                continue
            t1 = time.monotonic_ns()
            rec._wait_ns += t1 - t0
            # ``_inflight`` outlives a call, so a call's first waits are on
            # steps of the call before: the caller has fenced since, and
            # this is not the moment they finished.
            if queued_under is rec:
                rec.completions.append((step, t1))

    @staticmethod
    def _inflight_window() -> int:
        from ..runtime import config as _config

        return int(_config.get("engine_max_inflight_steps")) or 8

    def _hook(self, name: str, state: Dict[str, Any]) -> None:
        fn = self.hooks.get(name)
        if fn is None:
            return
        rec = self._run
        if rec is None:
            fn(state)
            return
        t0 = time.monotonic_ns()
        try:
            fn(state)
        finally:
            rec._hook_ns += time.monotonic_ns() - t0

    # ------------------------------------------------------------- compiled

    def _opt_state_shardings(self, mesh, opt_state):
        """ZeRO-1 sharding pytree: leaves whose leading dim divides the
        replica count shard there; scalars/small leaves replicate."""
        p = mesh.shape[RANK_AXIS]
        repl = NamedSharding(mesh, P())
        rowsh = NamedSharding(mesh, P(RANK_AXIS))

        def leaf(a):
            shape = getattr(a, "shape", ())
            if len(shape) >= 1 and shape[0] >= p and shape[0] % p == 0:
                return rowsh
            return repl

        return jax.tree.map(leaf, opt_state)

    def _build_compiled_step(self, comm, opt_state_example=None):
        """One pjit'd step over the communicator mesh: the whole reference
        hook pipeline (forward/criterion/backward/allreduce/update) fused
        into a single XLA program (SURVEY.md §7: idiomatic TPU form).

        With ``use_pallas_collectives`` set (and no zero1), the gradient
        sync executes the custom ring kernel instead of GSPMD's lowering:
        grads are computed per-device inside a shard_map region and reduced
        by ``pallas_ring.inner_ring_allreduce`` — the TPU analogue of the
        reference preferring its p2p rings over NCCL (nn.lua:18-27,
        README.md:104-106).  zero1 keeps GSPMD: its reduce-scatter-into-
        shard + allgather fusion is exactly what the explicit ring would
        have to re-create."""
        from ..runtime import config as _config

        mesh = comm.mesh()
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        lr = self.lr
        # The knob switches the step's structure even at p=1 (the ring
        # itself shortcuts) so single-chip A/Bs measure the shard_map
        # restructure overhead honestly.
        use_rings = (bool(_config.get("use_pallas_collectives"))
                     and not self.zero1)

        A = self.accum_steps

        def accum_scan(params, xs, ys):
            """Shared accumulation core: scan the A slices, accumulate in
            f32, return (mean loss, mean grads) — used by both the GSPMD
            and the ring path so the two can never diverge numerically."""
            def acc(carry, sl):
                g_acc, l_acc = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, sl)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, l_acc + loss.astype(jnp.float32)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g, l), _ = lax.scan(acc, (zeros, jnp.zeros((), jnp.float32)),
                                 (xs, ys))
            grads = jax.tree.map(lambda a, p: (a / A).astype(p.dtype),
                                 g, params)
            return l / A, grads

        def grads_of(params, xb, yb):
            if A == 1:
                return jax.value_and_grad(loss_fn)(params, (xb, yb))
            # Gradient accumulation: scan A equal slices, accumulate in f32,
            # one update per batch.  Slices are cut *device-locally* — slice
            # a takes sub-block a of every replica's existing shard — so the
            # split moves no data between devices (a plain
            # reshape(A, B//A) would make slice 0 = global rows [0, B/A),
            # i.e. an all-to-all every step).  Gradients average over all
            # slices, so slice composition does not affect the result.
            B = xb.shape[0]
            p_sz = mesh.shape[RANK_AXIS]
            if B % (A * p_sz):
                raise ValueError(
                    f"global batch {B} must be divisible by accum_steps * "
                    f"replicas = {A} * {p_sz}")
            sl_sh = NamedSharding(mesh, P(None, RANK_AXIS))

            def split(a):
                rest = a.shape[1:]
                out = (a.reshape(p_sz, A, B // (A * p_sz), *rest)
                        .swapaxes(0, 1)
                        .reshape(A, B // A, *rest))
                return lax.with_sharding_constraint(out, sl_sh)

            xs, ys = split(xb), split(yb)
            return accum_scan(params, xs, ys)

        def local_grads_of(params, xb, yb):
            """Per-device loss/grads on the LOCAL batch shard (runs inside
            the ring path's shard_map body).  Accumulation slices the local
            shard directly — already device-local, no resharding games."""
            if A == 1:
                return jax.value_and_grad(loss_fn)(params, (xb, yb))
            b = xb.shape[0]
            if b % A:
                raise ValueError(
                    f"per-replica batch {b} must be divisible by "
                    f"accum_steps = {A}")
            xs = xb.reshape(A, b // A, *xb.shape[1:])
            ys = yb.reshape(A, b // A, *yb.shape[1:])
            return accum_scan(params, xs, ys)

        def ring_synced_grads(params, xb, yb):
            """Explicit DP sync through the pallas ring.

            Large leaves (>= the ``small_allreduce_size_gpu`` element
            cutoff) ring INDIVIDUALLY — a flattened view, no concatenate;
            the p=1 decomposition measured the all-leaves pack at
            +0.6 ms/step over GSPMD and the per-leaf form at GSPMD level
            (BASELINE.md round 4) — while small leaves still pack into one
            flat tail bucket per dtype so tiny tensors don't each pay ring
            latency (the reference's bucketed nn sync, nn.lua:49-56).

            The rings are independent data-flow-wise, so without care XLA
            may launch them concurrently — and ring-skewed devices with
            two kernels on one barrier semaphore deadlock (pallas_ring's
            documented unsupported case).  Two guards: rotating DISTINCT
            collective ids (independent semaphores), and an
            optimization_barrier threading ring i's output into ring
            i+1's input so they also run one at a time (serial rings use
            the full ICI links instead of halving them)."""
            from ..collectives import pallas_ring

            p_sz = mesh.shape[RANK_AXIS]
            cutoff = int(_config.get("small_allreduce_size_gpu"))

            def body(params, xb, yb):
                loss, grads = local_grads_of(params, xb, yb)
                leaves, treedef = jax.tree.flatten(grads)
                synced = list(leaves)
                chain = [None, 0]      # [prev ring output, ring counter]

                @jax.named_scope("grad_sync")
                def ring(flat):
                    prev, n = chain
                    if prev is not None:
                        flat, _ = lax.optimization_barrier((flat, prev))
                    out = pallas_ring.inner_ring_allreduce(
                        flat, p_sz, mean=True,
                        collective_id=(
                            pallas_ring.CALLER_COLLECTIVE_ID_BASE + n % 8))
                    chain[0], chain[1] = out, n + 1
                    return out

                small_by_dtype: Dict[Any, list] = {}
                for i, leaf in enumerate(leaves):
                    if leaf.size >= cutoff:
                        synced[i] = ring(leaf.reshape(-1)).reshape(leaf.shape)
                    else:
                        small_by_dtype.setdefault(leaf.dtype, []).append(i)
                for dt, idxs in small_by_dtype.items():
                    flat = jnp.concatenate(
                        [leaves[i].reshape(-1) for i in idxs])
                    flat = ring(flat)
                    off = 0
                    for i in idxs:
                        sz = leaves[i].size
                        synced[i] = flat[off:off + sz].reshape(
                            leaves[i].shape)
                        off += sz
                return (lax.pmean(loss, RANK_AXIS),
                        jax.tree.unflatten(treedef, synced))

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(RANK_AXIS), P(RANK_AXIS)),
                out_specs=(P(), P()), check_vma=False,
            )(params, xb, yb)

        # In-step numerics sentinels (obs/numerics.py): with the knob on,
        # the step additionally returns fused in-graph statistics over
        # the SYNCED gradients and the applied update.  "off" is the
        # pre-numerics step bit-for-bit — same outputs, same graph
        # (pinned by tests/test_numerics.py).
        sentinels_on = (str(_config.get("numerics_mode"))
                        in _numerics.SENTINEL_MODES)

        def step(params, opt_state, xb, yb):
            # xb, yb sharded on the replica axis; params replicated;
            # opt_state replicated, or ZeRO-1 sharded (see __init__).
            if use_rings:
                # Grads come back already mean-reduced by the explicit ring
                # inside the shard_map region — no further sync below.
                loss, grads = ring_synced_grads(params, xb, yb)
            else:
                # Gradient sync: mean over replicas.  Inside jit this lowers
                # to fused psums XLA overlaps with backward (replaces
                # nn.lua's per-layer async pipeline); under zero1 GSPMD
                # instead reduce-scatters into the optimizer shard and
                # all-gathers the updated parameters.
                loss, grads = grads_of(params, xb, yb)
            # Names in the device program (docs/observability.md): metadata
            # only.  ``grad_sync`` names the explicit rings above; GSPMD's
            # all-reduces are put in by the partitioner and carry the name
            # of the backward operation whose result they sum.
            with jax.named_scope("optimizer"):
                if optimizer is not None:
                    updates, opt_state = optimizer.update(grads, opt_state,
                                                          params)
                    new_params = jax.tree.map(lambda p, u: p + u, params,
                                              updates)
                else:
                    updates = None
                    new_params = sgd_update(params, grads, lr)
            if sentinels_on:
                if updates is None:
                    updates = jax.tree.map(lambda q, p: q - p,
                                           new_params, params)
                stats = _numerics.sentinel_stats(params, grads, updates)
                return new_params, opt_state, loss, stats
            return new_params, opt_state, loss

        batch_sharding = NamedSharding(mesh, P(RANK_AXIS))
        repl = NamedSharding(mesh, P())
        if self.zero1 and self.optimizer is not None:
            opt_sh = self._opt_state_shardings(mesh, opt_state_example)
        else:
            opt_sh = repl
        out_sh = ((repl, opt_sh, repl, repl) if sentinels_on
                  else (repl, opt_sh, repl))
        return jax.jit(
            step,
            in_shardings=(repl, opt_sh, batch_sharding, batch_sharding),
            out_shardings=out_sh,
            donate_argnums=(0, 1),
        )

    def step_text(self, state: Dict[str, Any]) -> str:
        """The text of the compiled step on ``state``'s arrays, which a device
        capture is joined by: one lowering and compile, no execution."""
        batch = [_stage(b, self._batch_sh).array for b in state["sample"]]
        return self._compiled_step.lower(
            state["params"], state["opt_state"], *batch).compile().as_text()

    # ---------------------------------------------------------------- eager

    def _build_eager_grad_fn(self):
        """Per-replica loss/grad over the rank-major leading axis: a vmapped
        value_and_grad, jitted so each device computes its own replica's
        backward locally (the reference's per-process compute)."""
        loss_fn = self.loss_fn

        def per_replica(params, xb, yb):
            return jax.value_and_grad(loss_fn)(params, (xb, yb))

        return jax.jit(jax.vmap(per_replica))

    # ---------------------------------------------------------------- train

    def train(
        self,
        params: Any,
        iterator,
        epochs: int = 1,
        opt_state: Any = None,
        start_step: int = 0,
    ) -> Dict[str, Any]:
        """Run the training loop; returns the final engine state.

        ``params``: plain pytree (compiled mode) or rank-major pytree
        (eager modes).  ``iterator``: yields rank-major batches
        ``(x:(p,b,...), y:(p,b))`` per step (ShardedIterator).
        ``start_step`` seeds the global step counter — pass the step from
        ``checkpoint.resume_or_init`` so schedules and checkpoint cadence
        continue instead of restarting.
        """
        rec = RunRecord(self.mode, self._inflight_window(), int(start_step))
        outer, mine = _open_run(rec), self._run
        self._run = self.last_run = rec
        try:
            return self._train(rec, params, iterator, epochs, opt_state,
                               start_step)
        finally:
            self._run = mine
            _close_run(rec, outer)

    def _train(self, rec, params, iterator, epochs, opt_state, start_step):
        comm = self.comm
        state: Dict[str, Any] = {
            "params": params,
            "opt_state": opt_state,
            "epoch": 0,
            "t": int(start_step),        # global step (reference: state.t)
            "loss_meter": AverageValueMeter(),
            "engine": self,
            "training": True,
            "comm": comm,
            "run": rec,
        }

        if self.mode == "compiled":
            state["params"] = jax.tree.map(
                lambda a: jax.device_put(a, NamedSharding(comm.mesh(), P())), params)
            if self.optimizer is not None and opt_state is None:
                if self.zero1:
                    # Born sharded: shardings are derived from the abstract
                    # state (eval_shape) and baked into a jitted init, so
                    # the moments never exist replicated — at Adam-at-8B
                    # scale the replicated form would OOM before resharding.
                    abstract = jax.eval_shape(self.optimizer.init,
                                              state["params"])
                    opt_sh = self._opt_state_shardings(comm.mesh(), abstract)
                    state["opt_state"] = jax.jit(
                        self.optimizer.init, out_shardings=opt_sh)(
                            state["params"])
                else:
                    state["opt_state"] = self.optimizer.init(state["params"])
            elif self.zero1 and opt_state is not None:
                # Caller-provided state (e.g. checkpoint restore): reshard.
                state["opt_state"] = jax.tree.map(
                    jax.device_put, state["opt_state"],
                    self._opt_state_shardings(comm.mesh(), state["opt_state"]))
            # Build the pjit'd step once and reuse it across train() calls —
            # repeated training phases (warmup/timed epochs, resumed runs)
            # must not re-trace/re-compile (the reference keeps one compiled
            # module per process for the engine's lifetime).  The key covers
            # everything the step closes over, so mutating lr/optimizer/
            # loss_fn between phases still takes effect.
            # Under zero1 the in/out shardings are baked from the optimizer
            # state's leaf shapes, so those join the key (same structure
            # with different shapes must rebuild, not reuse).
            opt_shapes = (tuple((tuple(l.shape), str(l.dtype))
                                for l in jax.tree.leaves(state["opt_state"])
                                if hasattr(l, "shape"))
                          if self.zero1 else None)
            from ..runtime import config as _config
            # ring_key: None = GSPMD sync (also when zero1 ignores the
            # flag — no rebuild on a toggle that changes nothing); else the
            # geometry knobs the ring bakes in at trace time, so mutating
            # them between train() calls rebuilds like every other input.
            ring_key = None
            if bool(_config.get("use_pallas_collectives")) and not self.zero1:
                ring_key = (int(_config.get("min_buffer_size")),
                            int(_config.get("max_buffer_size")),
                            int(_config.get("num_buffers_per_collective")),
                            int(_config.get("max_num_buffers_per_collective_tpu")),
                            int(_config.get("small_allreduce_size_gpu")))
            # Numerics sentinels change the step's outputs, so the mode
            # joins the key (a knob flip between train() calls rebuilds
            # like every other traced-in input).
            num_mode = str(_config.get("numerics_mode"))
            if num_mode not in _numerics.MODES:
                raise ValueError(
                    f"numerics_mode must be one of {_numerics.MODES}, "
                    f"got {num_mode!r}")
            key = (comm, self.lr, self.optimizer, self.loss_fn, self.zero1,
                   self.accum_steps, opt_shapes, ring_key, num_mode)
            if self._compiled_step is None or self._compiled_for != key:
                self._compiled_step = self._build_compiled_step(
                    comm, state["opt_state"])
                self._compiled_for = key
                # Hoisted out of the per-step path (staging target for every
                # batch of every train() call against this compiled step).
                self._batch_sh = NamedSharding(comm.mesh(), P(RANK_AXIS))
                # A fresh executable means fresh cost analysis.
                self._step_flops = None
                self._flops_probed = False
            self._sentinels_on = num_mode in _numerics.SENTINEL_MODES
            # Streaming input plane (torchmpi_tpu/data, docs/data.md):
            # bare host iterators wrap in the background pipeline per the
            # data_pipeline knob, so batches arrive as pre-staged Staged
            # pairs and the engine.stage span collapses to a handoff.
            # "off" returns the iterator untouched — the seed staging
            # path bit-for-bit (pinned by tests/test_data_pipeline.py).
            # NOTE: with the pipeline active, state["sample"] holds the
            # (Staged, Staged) pair, not the rank-major host batch —
            # hooks inspecting it read .array (docs/data.md).
            iterator = _data_pipe.engine_wrap(iterator, comm.mesh())
        else:
            # Initial parameter synchronization: all replicas start from
            # rank 0's weights (reference: sgdengine.lua:140-144 initial
            # synchronizeParameters).
            if self.sync_parameters_on_start:
                state["params"] = mpinn.synchronize_parameters(params, comm)
            # Cached across train() calls like the compiled step (which keys
            # on self.loss_fn): a second phase (warmup-then-timed bench,
            # resumed run) must not retrace the vmapped grad function, but a
            # swapped-out loss_fn must rebuild — the builder closes over it.
            if (self._eager_grad_fn is None
                    or self._eager_grad_for is not self.loss_fn):
                self._eager_grad_fn = self._build_eager_grad_fn()
                self._eager_grad_for = self.loss_fn

        self._hook("on_start", state)
        try:
            for epoch in range(epochs):
                state["epoch"] = epoch
                state["loss_meter"].reset()
                self._hook("on_start_epoch", state)
                for xb, yb in iterator:
                    t_batch = time.monotonic_ns()
                    if rec.t_first_batch is None:
                        rec.t_first_batch = t_batch
                    rec._wait_ns = rec._hook_ns = 0
                    ended = False       # a step boundary may end the loop
                    state["sample"] = (xb, yb)
                    # Reference fences each sample with a barrier + device
                    # sync (sgdengine.lua:111-114); under SPMD the single
                    # compiled dispatch already orders replicas, so the
                    # barrier is only kept for the eager modes' first step.
                    self._hook("on_sample", state)
                    if self.mode == "compiled":
                        stepped = self._train_step_compiled(state, xb, yb)
                    else:
                        stepped = self._train_step_eager(state, xb, yb)
                    t_stepped = time.monotonic_ns()
                    state["t"] += 1
                    if (self.check_frequency and self.mode != "compiled"
                            and state["t"] % self.check_frequency == 0):
                        mpinn.check_with_allreduce(state["params"], comm)
                    self._hook("on_update", state)
                    for boundary in self.step_boundaries:
                        boundary(state)
                        ended = _ended(state)
                        if ended:
                            break
                    rec.step_stamps.append(
                        (rec.steps, t_batch, t_stepped, time.monotonic_ns(),
                         rec._wait_ns, rec._hook_ns) + stepped)
                    rec.steps += 1
                    if ended:
                        break
                if _ended(state):
                    break
                self._hook("on_end_epoch", state)
            if not _ended(state):
                self._hook("on_end", state)
        finally:
            # A loop that ENDED (cleanly or by a recoverable fault the
            # elastic driver will handle) must not leave a stale
            # engine_step health mark reading as stalled on /healthz.
            _obs_serve.health.clear("engine_step")
        return state

    def _train_step_compiled(self, state, xb, yb):
        """One compiled step; returns its stamps (``RunRecord.step_stamps``,
        positions 6 on).  ``engine.step`` is a live span because a hook's
        host collectives and parameter-server traffic inherit its
        correlation id through the context; the id is the CLUSTER
        correlation of this step number, identical on every rank with no
        coordination.  The phase spans inside it and the live feed are
        derived from the stamps (``obs/serve.py:engine_step``)."""
        now = time.monotonic_ns
        # A pre-staged pair (``data/device.py``) carries the pipeline's
        # measured consumer wait: it happened between steps, outside these
        # stamps, and staging such a pair below is a pure handoff.
        wait_s = xb.wait_s if isinstance(xb, _Staged) else 0.0
        nstats = None
        step = state["t"]
        with _obs.span("engine.step", step=step,
                       correlation=_step_correlation(step)) as corr:
            t_entry = now()
            # Rank-major host batches (p, b, ...) are flattened and placed
            # on the replica axis; ``Staged`` batches pass through untouched.
            sh = self._batch_sh
            xb = _stage(xb, sh).array
            yb = _stage(yb, sh).array
            t_staged = now()
            if not self._flops_probed and _obs_serve.metrics_feed():
                # One-time compute-efficiency probe per compiled step
                # (obs/numerics.py): XLA's analytical FLOPs via lower()
                # — a re-trace, no compile, no execution — feeding the
                # tmpi_step_flops / tmpi_mfu_estimate gauges.  Before
                # dispatch on purpose: this step's donation has not
                # consumed the argument buffers yet.
                self._flops_probed = True
                self._step_flops = _numerics.probe_step_flops(
                    self._compiled_step,
                    (state["params"], state["opt_state"], xb, yb))
            out = self._compiled_step(
                state["params"], state["opt_state"], xb, yb)
            t_dispatched = now()
            if self._run.t_first_dispatch is None:
                self._run.t_first_dispatch = t_dispatched
            if self._sentinels_on:
                params, opt_state, loss, nstats = out
            else:
                params, opt_state, loss = out
            state["params"], state["opt_state"] = params, opt_state
            # Keep the loss a device scalar: float()-ing here would block
            # the host on the whole fused step and serialize input prep
            # with compute.
            state["loss"] = loss
            state["loss_meter"].add(loss)
            t_wait = now()
            self._bound_inflight(loss)
            # The blocked window closes HERE: hook time below is the
            # user's, not staging or sync block.
            t_waited = now()
            self._hook("on_forward", state)
            self._hook("on_backward", state)
            t_done = now()
        stamps = (t_entry, t_staged, t_dispatched, t_wait, t_waited, t_done,
                  None)
        _obs_serve.engine_step(stamps, self.mode, step, corr, xb, yb,
                               wait_s=wait_s, numerics=nstats,
                               flops=self._step_flops)
        return stamps

    def _train_step_eager(self, state, xb, yb):
        """One eager step; as ``_train_step_compiled``.  No
        ``_bound_inflight`` here by design: the eager modes synchronize
        gradients within the step (eager collectives block_until_ready; the
        async form drains its handles before the update below), so host
        run-ahead is already <= 1 step."""
        now = time.monotonic_ns
        comm = state["comm"]
        blocked_ns = None
        step = state["t"]
        with _obs.span("engine.step", step=step, mode=self.mode,
                       correlation=_step_correlation(step)) as corr:
            t_entry = now()
            xb = eager.shard(comm, xb)
            yb = eager.shard(comm, yb)
            t_staged = now()
            losses, grads = self._eager_grad_fn(state["params"], xb, yb)
            t_grad = now()
            if self._run.t_first_dispatch is None:
                self._run.t_first_dispatch = t_grad
            state["loss"] = losses
            state["loss_meter"].add(jnp.mean(losses))
            self._hook("on_forward", state)
            # Gradient synchronization (reference hook 'onBackward',
            # sgdengine.lua:126-131).
            t_sync = now()
            if self.mode == "eager_async":
                from ..runtime import config as _config

                reg = mpinn.async_.register_async_backward(
                    grads, comm, step=step)
                self._hook("on_backward", state)
                if str(_config.get("engine_async_drain")) == "barrier":
                    # A/B baseline: the old post-backward barrier.
                    grads = mpinn.async_.synchronize_gradients(reg)
                    state["params"] = sgd_update(state["params"], grads,
                                                 self.lr)
                else:
                    # Drain at the optimizer boundary: each bucket's
                    # parameters update the moment its collective
                    # completes, while later buckets stay in flight
                    # (nn.async_.drain_at_optimizer — the
                    # registerAsyncMPIBackward pipeline).
                    lr = self.lr
                    state["params"] = mpinn.async_.drain_at_optimizer(
                        reg, state["params"],
                        lambda p, g: p - lr * g)
                # Real blocked time: only what the host spent INSIDE
                # handle waits — ready-order update work between
                # waits is overlap, not block.
                blocked_ns = int(reg.blocked_s * 1e9)
            else:
                grads = mpinn.synchronize_gradients(grads, comm)
                self._hook("on_backward", state)
            t_synced = now()
            if self.mode != "eager_async":
                state["params"] = sgd_update(state["params"], grads,
                                             self.lr)
            t_done = now()
        stamps = (t_entry, t_staged, t_grad, t_sync, t_synced, t_done,
                  blocked_ns)
        _obs_serve.engine_step(stamps, self.mode, step, corr, xb, yb)
        return stamps

    # ----------------------------------------------------------------- test

    def test(self, params: Any, iterator, metric_fn: LossFn) -> float:
        """Evaluation loop (reference: tnt.SGDEngine:test); returns the mean
        metric over the iterator."""
        comm = self.comm
        meter = AverageValueMeter()
        # Device scalars go straight into the meter (it accumulates lazily):
        # a float() here would block the host every batch and serialize
        # input staging with compute — the exact stall the train path avoids
        # (_train_step_compiled keeps the loss a device scalar too).  The
        # one host sync happens at the final meter read.
        # Identity-keyed on purpose: keying on __code__ would alias two
        # closures that share code but capture different values (jit bakes
        # captures at trace time — silent wrong results).  A loop passing
        # a FRESH lambda per eval epoch instead pays a retrace and rolls
        # the bounded cache (oldest out), so nothing accumulates.
        key = (metric_fn, self.mode)
        fn = self._test_fns.get(key)
        if fn is None and len(self._test_fns) >= 8:
            self._test_fns.pop(next(iter(self._test_fns)))
        if self.mode == "compiled":
            mesh = comm.mesh()
            sh = NamedSharding(mesh, P(RANK_AXIS))
            if fn is None:
                fn = self._test_fns[key] = jax.jit(metric_fn)
            # Same input plane as train(): the pipeline pre-stages eval
            # batches in the background, so the _stage calls below become
            # passthroughs instead of the old per-batch blocking copies
            # (data_pipeline=off restores those exactly).
            for xb, yb in _data_pipe.engine_wrap(iterator, mesh):
                val = fn(params, (_stage(xb, sh).array,
                                  _stage(yb, sh).array))
                meter.add(val)
                self._bound_inflight(val)
        else:
            if fn is None:
                fn = self._test_fns[key] = jax.jit(
                    jax.vmap(lambda p, x, y: metric_fn(p, (x, y))))
            for xb, yb in iterator:
                vals = fn(params, eager.shard(comm, xb), eager.shard(comm, yb))
                m = jnp.mean(vals)
                meter.add(m)
                self._bound_inflight(m)
        return meter.mean
