"""Runner `engine_images`: an image classifier trained through the entry
points a user calls: `mpi.start()` -> `AllReduceSGDEngine(mode="compiled")`
-> one `engine.train` over an input iterator -> `mpi.stop()`.

The timed window is ONE `engine.train` call over a source that cycles the
seeded host batches through the engine's input pipeline and stops itself
when the seconds are up; steps are counted by the `on_update` hook and the
clock stops at the fence on the final state.  Everything else (weights, host
batches, the reference check, the one-device comparison of a multi-chip
cell, compilation, warm-up) is set-up.
"""

import time

import numpy as np

# Four devices against one, step for step (bf16, another order of summation):
# chip_smoke.py's LOSS_RTOL, with its reason.
DP_LOSS_RTOL = 2e-2
DP_STEPS = 4


def _model(cfg):
    """The program's static architecture, built from the configuration file
    and checked against it, so the file is what runs."""
    from torchmpi_tpu.models import resnet

    model = resnet.config(
        depth=cfg["depth"], n_classes=cfg["num_classes"],
        in_channels=cfg["in_channels"],
        width_multiplier=cfg["stage_widths"][0] / 64,
        stem_space_to_depth=cfg["stem_space_to_depth"])
    widths = [w for w, n in zip(cfg["stage_widths"], cfg["stage_blocks"])
              for _ in range(n)]
    if (list(model.widths) != widths or model.stem_width != cfg["stem_width"]
            or model.expansion != cfg["bottleneck_expansion"]):
        raise ValueError(f"the program builds {model}, the configuration "
                         f"file says otherwise")
    return model


class _Hooks:
    """Engine hooks: count steps, keep each loss on the device, keep a host
    span round every step and every fetch, and open and close the profiler's
    window by step number."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.losses = []
        self._span = None           # (name, start) of the open host span
        self.trace_at = self.trace_steps = None
        self.fence_each = False
        self.step_times = []
        self._t_last = None

    def _swap(self, name):
        """Close the open host span and open `name` (None: none)."""
        now = time.time_ns()
        if self._span is not None:
            self.ctx.spans.append((*self._span, now))
        self._span = (name, now) if name is not None else None

    def on_sample(self, state):
        self._swap("bench.engine_step")

    def on_update(self, state):
        import jax

        self.losses.append(state["loss"])
        if self.fence_each:                  # warm-up only
            jax.block_until_ready(state["loss"])
            now = time.perf_counter()
            if self._t_last is not None:
                self.step_times.append(now - self._t_last)
            self._t_last = now
        n = len(self.losses)
        if self.trace_at is not None:
            if n == self.trace_at:
                self._swap(None)
                self.ctx.start_trace()
            elif n == self.trace_at + self.trace_steps:
                self._swap(None)
                # The steps still in flight belong to the window.
                jax.block_until_ready(state["loss"])
                self.ctx.stop_trace()
                self.trace_at = None
        self._swap("bench.next_batch")

    def reset(self):
        self._swap(None)
        self.losses = []
        self._t_last = None

    def table(self):
        return {"on_sample": self.on_sample, "on_update": self.on_update}


def _cycle_until(batches, deadline):
    """The source of a window: the seeded batches, cycled, until the clock
    passes `deadline()`.  It runs on the input pipeline's own thread."""
    i = 0
    while time.perf_counter() < deadline():
        yield batches[i % len(batches)]
        i += 1


def _losses(engine, hooks, params, batches):
    """Train over `batches` and return the per-step losses as floats."""
    engine.train(params, batches)
    losses = [float(x) for x in hooks.losses]
    hooks.reset()
    return losses


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.data import DataPipeline
    from torchmpi_tpu.data.staging import stage_rank_major
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import resnet
    from torchmpi_tpu.runtime.communicator import RANK_AXIS
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import compare
    import harness
    import traffic as traffic_mod

    cfg, mix = ctx.cfg, ctx.traffic
    devices = jax.devices()[:ctx.chips]
    model = _model(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    loss_fn = resnet.make_loss_fn(model)
    hooks = _Hooks(ctx)

    def engine_on(world):
        # mpi.start() settles the compile cache, so it comes before the
        # first program of the run.
        mpi.start(devices=world)
        return AllReduceSGDEngine(loss_fn, lr=cfg["lr"], mode="compiled",
                                  comm=mpi.stack.current(), hooks=hooks.table())

    engine = engine_on(devices[:1])
    host = traffic_mod.images(mix, cfg, ctx.seed, ctx.chips)
    ctx.mark(f"{len(host)} host batches")
    with ctx.compiling("seeded weights"):
        params0 = jax.device_get(jax.jit(
            lambda key: resnet.init(key, model, dtype=dtype)[0])(
                jax.random.PRNGKey(ctx.seed)))

    # (a) the system against the plain reference, on one device.
    reference = ctx.module("reference")
    sx, sy = traffic_mod.images(mix, cfg, ctx.seed + 1, 1, n_batches=1,
                                per_chip=cfg["check_sample"]["images"])[0]

    def system(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p, s)
        return loss, resnet.apply(model, p, s[0], train=True), grads

    with ctx.compiling("reference check"):
        ctx.counters["reference_check"] = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s),
            jax.device_put(params0, devices[0]),
            (jnp.asarray(sx[0]), jnp.asarray(sy[0])), reference.TOLERANCE,
            getattr(reference, "LEAF_AXES", None))

    # (d) what exists only across chips, against one device: the same
    # images on every chip give, under sync batch norm, one device's
    # statistics, mean loss and gradient.
    if ctx.chips > 1:
        x0, y0 = host[0][0][:1], host[0][1][:1]
        with ctx.compiling("one-device engine step"):
            one = _losses(engine, hooks, params0, [(x0, y0)] * DP_STEPS)
        mpi.stop()
        engine = engine_on(devices)
    mesh = engine.comm.mesh()
    with ctx.compiling("engine step"):
        state = engine.train(params0, host[:1])
        jax.block_until_ready(state["loss"])
    hooks.reset()
    if ctx.chips > 1:
        copies = (np.repeat(x0, ctx.chips, axis=0),
                  np.repeat(y0, ctx.chips, axis=0))
        many = _losses(engine, hooks, params0, [copies] * DP_STEPS)
        diff = max(abs(a - b) / max(abs(a), abs(b)) for a, b in zip(one, many))
        ctx.counters["dp_check"] = {
            "one_device": one, "all_devices": many, "rel_diff": diff,
            "ok": bool(diff <= DP_LOSS_RTOL)}

    # Warm up through the input path until two fenced steps agree.
    hooks.fence_each = True

    def warm_source():
        for i in range(harness.WARM_UP_MAX_STEPS):
            if harness.warmed_up(hooks.step_times):
                return
            yield host[i % len(host)]

    state = engine.train(state["params"], DataPipeline(warm_source(), mesh))
    jax.block_until_ready(state["loss"])
    warm_step_s = min(hooks.step_times)
    hooks.fence_each = False
    hooks.reset()
    ctx.mark(f"warmed up, {len(hooks.step_times)} fenced steps")

    # The step program as compiled: the memory plan and the collectives.
    rows = NamedSharding(mesh, P(RANK_AXIS))
    x, y = host[0]
    compiled = engine._compiled_step.lower(
        state["params"], None,
        jax.ShapeDtypeStruct((x.shape[0] * x.shape[1],) + x.shape[2:], x.dtype,
                             sharding=rows),
        jax.ShapeDtypeStruct((y.shape[0] * y.shape[1],), y.dtype,
                             sharding=rows)).compile()
    ctx.counters["program_bytes"] = harness.program_bytes(compiled)
    stats = hlo_collective_stats(compiled.as_text())
    ctx.counters["collective_calls"] = stats["total"]
    ctx.counters["collective_bytes"] = sum(stats["operand_bytes"].values())

    # In a traced run the seconds are split: streamed steps with the
    # profiler's window inside, then as many steps on device-resident
    # batches through the engine, and again through a bare loop over the
    # same compiled step.
    share = 1.0
    if ctx.trace:
        share = 0.6
        hooks.trace_at = mix["trace"]["after_steps"]
        hooks.trace_steps = mix["trace"]["steps"]
        resident = [(stage_rank_major(x, rows), stage_rank_major(y, rows))
                    for x, y in host]
        n = max(8, int((1 - share) / 2 * ctx.seconds / warm_step_s))
        repeated = [resident[i % len(resident)] for i in range(n)]
    deadline = float("inf")
    pipeline = DataPipeline(_cycle_until(host, lambda: deadline), mesh)
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + share * ctx.seconds
        state = engine.train(state["params"], pipeline)
        jax.block_until_ready((state["loss"], state["params"]))
        window_s = time.perf_counter() - t0
        losses = hooks.losses
        hooks.reset()
        ctx.counters["stage_stats"] = pipeline.stats.snapshot()

        if ctx.trace:
            t1 = time.perf_counter()
            state = engine.train(state["params"], repeated)
            jax.block_until_ready((state["loss"], state["params"]))
            ctx.counters["engine_step_s"] = (time.perf_counter() - t1) / n
            hooks.reset()
            # The engine is done: its parameters go to the bare loop, which
            # donates them as the engine did.
            p, step = state["params"], engine._compiled_step
            t2 = time.perf_counter()
            for xb, yb in repeated:
                p, _, loss = step(p, None, xb.array, yb.array)
            jax.block_until_ready((loss, p))
            ctx.counters["bare_step_s"] = (time.perf_counter() - t2) / n

    values = np.asarray(jax.device_get(losses), np.float32)
    mpi.stop()
    samples = len(losses) * mix["per_chip_batch"] * ctx.chips
    return {
        # One `engine.train` call is the window, start and drain with it: the
        # engine gives the host no moment at which a single step has ended.
        "samples_per_s": samples / window_s,
        "window_s": window_s,
        "attempted": len(losses),
        "failed": int(np.sum(~np.isfinite(values))),
        "first_loss": float(values[0]), "last_loss": float(values[-1]),
        "program_bytes": ctx.counters["program_bytes"],
        "devices": devices,
    }
