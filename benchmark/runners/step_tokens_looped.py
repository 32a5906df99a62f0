"""Runner `step_tokens_looped`: the `step_tokens_adamw` protocol (a decoder
language model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step(optimizer=AdamW)` -> `mpi.stop()` on seeded token
batches resident on the device; one step queued behind the one that runs; the
rate from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check before the optimizer's state exists,
compilation and warm-up in set-up) for a looped configuration: one stack of
layers run `total_ut_steps` times with shared weights, sandwich norms, a head
and an exit gate at every recurrent step, the expected-exit loss.  It builds
`llama.Config` from the configuration file with the fields such a model needs,
so a program that lacks them fails at once with a `TypeError`.

`step_tokens_adamw.py` gives it `_optimizer`, through `harness.load_module`.
Its list of scopes is a module constant there that its join reads, its
`scope_ms` gives up where no `moe.` scope joins and its `run` builds a
mixture's `Config` and counts expert units, so the join (`instruction_scopes`,
`scope_ms`) and the loop of `run` are written again here, the scopes an
argument (PERF.md section 7: a `benchmark` issue's merge).

`correct` compares what the window drives, in two parts
(`ctx.counters["reference_check"]` holds both; the limits and why are in
`reference/<config>.py`):

* before the optimizer's state exists, `compare.check` on the configuration's
  `check_sample`, which has the timed batch's rows and at least two chunks of
  the head's `loss_chunk`: the loss, every recurrent step's logits and every
  leaf's gradient norm against the plain reference;
* after the window, so that the reference's seconds are in no `setup_s`: the
  timed executable once more, from the seeded weights and a new optimizer
  state on the first timed batch.  Its loss against the reference's on that
  whole batch (`loss_only`), and the norm of every leaf's change against
  AdamW's first step as the reference writes it (`adamw_first_step`), applied
  to the gradient the program's loss gives on that batch.

What it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `ut_steps`: the recurrent steps of the configuration as run.
* `recomputed_layer_applications`: layer applications a step runs a second
  time for its backward pass, counted from the forward flash kernels that the
  text of the executable holds under `rematted_computation` (one inside the
  layer scan counts for every layer); `None` where the text holds no forward
  kernel at all.
* `scope_ms` (`--trace 1` only): device self time a step under each
  `jax.named_scope` of the step program (`attn`, `ffn`, `final_norm`,
  `exit_gate`, `head_loss`, `optimizer`, `embed`), forward, backward and
  recomputed alike, as `step_tokens_adamw.py` joins them.  Where no event joins
  one of them (an executable from a compile cache written before the names
  existed, or a program that lacks them) that is logged and nothing is left,
  so the readers return `None`, never zero.
"""

import os
import re
import time

import numpy as np

SCOPES = ("exit_gate", "final_norm", "optimizer", "head_loss", "attn", "ffn",
          "embed")


def _model(cfg):
    """`llama.Config` from the configuration file."""
    from torchmpi_tpu.models import llama

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("llama.Config derives head_dim as hidden_size / "
                         "num_attention_heads; the file says otherwise")
    if cfg["early_exit_threshold"] != 1:
        raise ValueError("apply returns the last step's logits: an early "
                         "exit below threshold 1 is not implemented")
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], ut_steps=cfg["total_ut_steps"],
        sandwich_norm=True, exit_gate=True,
        exit_entropy_coef=cfg["exit_entropy_coef"])


def recomputed_layer_applications(hlo_text, n_layers):
    """Layer applications the executable runs again for a backward pass: its
    `flash_fwd` kernels under `rematted_computation`, one inside a `while`
    body (the layer scan) counted `n_layers` times.  `None` where the text
    has no `flash_fwd` kernel."""
    kernels = [line for line in hlo_text.splitlines()
               if "tpu_custom_call" in line and "flash_fwd" in line]
    if not kernels:
        return None
    return sum(n_layers if "while/body" in line else 1
               for line in kernels if "rematted_computation" in line)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def instruction_scopes(hlo_text, scopes=SCOPES):
    """{instruction name: scope} from the text of an executable: the first of
    `scopes` that the instruction's `op_name` holds as a path component; for a
    fusion without a name of its own, the scope most instructions of its fused
    computation carry."""
    own, calls, inside, where = {}, {}, {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"%?([\w.\-]+) ", line)
            where = m.group(1) if m and line.rstrip().endswith("{") else None
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        parts = set(re.split(r"[/()]", op.group(1))) if op else ()
        scope = next((s for s in scopes if s in parts), None)
        own[m.group(1)] = scope
        called = _CALLS.search(line)
        if called:
            calls[m.group(1)] = called.group(1)
        if scope and where:
            votes = inside.setdefault(where, {})
            votes[scope] = votes.get(scope, 0) + 1
    out = {}
    for name, scope in own.items():
        if scope is None and calls.get(name) in inside:
            votes = inside[calls[name]]
            scope = max(votes, key=votes.get)
        if scope:
            out[name] = scope
    return out


def scope_ms(trace, hlo_text, trace_reduce):
    """{scope: device self ms a step} over the whole steps of a capture
    (`trace_reduce.load`'s plain lists), mean over its devices; "unnamed"
    holds what joined no scope.  {} where no event joins a scope."""
    scopes = instruction_scopes(hlo_text)
    per_device = []
    for lines in trace["devices"].values():
        steps = trace_reduce.whole_steps(lines.get(trace_reduce.MODULES_LINE, []))
        if steps is None:
            continue
        t0, t1, n = steps
        ops = [(name, max(s, t0), min(s + d, t1) - max(s, t0))
               for name, s, d in lines.get(trace_reduce.OPS_LINE, [])
               if s < t1 and s + d > t0]
        found = {}
        for name, ns in trace_reduce.self_times(ops):
            m = re.match(r"%?([\w.\-]+)", name)
            scope = scopes.get(m.group(1) if m else name, "unnamed")
            found[scope] = found.get(scope, 0.0) + ns / n / 1e6
        per_device.append(found)
    if not any(s in d for d in per_device for s in SCOPES):
        return {}
    return {s: sum(d.get(s, 0.0) for d in per_device) / len(per_device)
            for s in sorted(set().union(*per_device))}


def change_norms(after, before, keep_axes):
    """compare.py's leaf norms of `after - before`, taken in float32."""
    import jax
    import jax.numpy as jnp

    import compare

    f32 = lambda a: a.astype(jnp.float32)
    return compare.leaf_norms(
        jax.tree.map(lambda a, b: f32(a) - f32(b), after, before), keep_axes)


def step_differences(loss, loss_reference, changed, changed_reference):
    """The two numbers of the timed step's comparison, on plain data: the
    relative difference of the losses, and of the norms of each leaf's change
    (`change_norms` of both sides, as numpy) the largest, with its leaf."""
    worst_leaf, worst = "", 0.0
    for name, want in changed_reference.items():
        got = np.asarray(changed[name], np.float64)
        want = np.asarray(want, np.float64)
        d = float(np.max(np.abs(got - want) / np.maximum(
            np.maximum(np.abs(got), np.abs(want)), 1e-30)))
        if worst == worst and not d <= worst:     # a NaN is worst, and stays
            worst_leaf, worst = name, d
    return {"step_loss_rel": abs(loss - loss_reference) / max(
                abs(loss), abs(loss_reference), 1e-30),
            "update_norm_rel_max": worst, "update_worst_leaf": worst_leaf,
            "step_loss_system": loss, "step_loss_reference": loss_reference}


# ------------------------------------------------------------------ the run

def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import make_mesh
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import compare
    import harness
    import trace_reduce
    import traffic as traffic_mod

    cfg, mix, how = ctx.cfg, ctx.traffic, ctx.cfg["run"]
    model = _model(cfg)
    devices = jax.devices()[:ctx.chips]
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])
    check = cfg["check_sample"]
    if (check["batch"] != mix["batch"]
            or check["seq_len"] < 2 * how["loss_chunk"]):
        raise ValueError("the check sample has the timed batch's rows and at "
                         "least two chunks of the head, or it does not drive "
                         "what the window drives")

    mpi.start(devices=devices)
    mesh = make_mesh(mix["mesh"], devices=devices)
    init = jax.jit(lambda key: llama.init(key, model, dtype=dtype))
    seeded = lambda: llama.shard_params(init(jax.random.PRNGKey(ctx.seed)),
                                        mesh, model)
    with ctx.compiling("seeded weights"):
        params = seeded()
        jax.block_until_ready(params)

    # (a) the system against the plain reference, all recurrent steps' logits,
    # before the optimizer's state takes its share of the memory.
    reference = ctx.module("reference")
    sample = tuple(jnp.asarray(a) for a in traffic_mod.tokens(
        mix, cfg, ctx.seed + 1, n_batches=1, batch=check["batch"],
        seq_len=check["seq_len"])[0])
    loss_fn = llama.make_loss_fn(model, mesh, loss_chunk=how["loss_chunk"],
                                 **kinds)

    def system(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p, s)
        return loss, llama.apply(model, p, s[0], mesh=mesh, all_steps=True,
                                 **kinds), reference.compared(grads)

    def plain(p, s):
        loss, logits, grads = reference.loss_and_grads(cfg, p, s)
        return loss, logits, reference.compared(grads)

    with ctx.compiling("reference check"):
        found = compare.check(system, plain, params, sample,
                              reference.TOLERANCE, reference.LEAF_AXES)

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    ctx.counters["ut_steps"] = model.ut_steps

    optimizer = harness.load_module(
        "runners", "step_tokens_adamw")._optimizer(how["optimizer"])
    new_state = jax.jit(optimizer.init)
    opt_state = new_state(params)
    step = llama.make_train_step(model, mesh, optimizer=optimizer,
                                 loss_chunk=how["loss_chunk"], **kinds)
    with ctx.compiling("train step"):
        compiled = step.lower(params, opt_state, *batches[0]).compile()
    hlo = compiled.as_text()
    keep = os.environ.get("BENCHMARK_KEEP_TRACE")
    if keep:                # beside the capture the harness keeps there
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, ctx.cell["name"] + ".hlo.txt"), "w") as fh:
            fh.write(hlo)
    ctx.counters["kernel_calls"] = hlo.count("tpu_custom_call")
    ctx.counters["recomputed_layer_applications"] = \
        recomputed_layer_applications(hlo, model.n_layers)
    ctx.counters["program_bytes"] = harness.program_bytes(compiled)
    stats = hlo_collective_stats(hlo)
    ctx.counters["collective_calls"] = stats["total"]
    ctx.counters["collective_bytes"] = sum(stats["operand_bytes"].values())

    warm = []
    while not harness.warmed_up(warm) and len(warm) < harness.WARM_UP_MAX_STEPS:
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, *batches[0])
        jax.block_until_ready(loss)
        warm.append(time.perf_counter() - t0)
    ctx.mark(f"warmed up, {len(warm)} fenced steps; "
             f"{ctx.counters['recomputed_layer_applications']} layer "
             f"applications recomputed")

    trace_at = trace_end = None
    if ctx.trace:
        trace_at = mix["trace"]["after_steps"]
        trace_end = trace_at + mix["trace"]["steps"]
    losses, done = [], []       # done[i]: host clock when step i was seen finished
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            n = len(losses)
            if n == trace_at:
                ctx.start_trace()
            with ctx.span("bench.step_call"):
                params, opt_state, loss = compiled(params, opt_state,
                                                   *batches[n % len(batches)])
            losses.append(loss)
            if n >= 1:
                # One step runs, one is queued: wait for the one before.
                with ctx.span("bench.wait_previous"):
                    jax.block_until_ready(losses[n - 1])
                done.append(time.perf_counter())
            if n + 1 == trace_end:
                with ctx.span("bench.fence"):
                    jax.block_until_ready(loss)
                ctx.stop_trace()
        jax.block_until_ready((loss, params))
        done.append(time.perf_counter())
        window_s = done[-1] - t0

    tokens_per_step = mix["batch"] * mix["seq_len"]
    intervals = np.diff(done)
    step_s = harness.median_step_s(done) or window_s / len(losses)
    ctx.mark(f"step intervals: median {1e3 * step_s:.3f} ms, min "
             f"{1e3 * intervals.min(initial=step_s):.3f}, max "
             f"{1e3 * intervals.max(initial=step_s):.3f}, "
             f"{int(np.sum(intervals > 1.01 * step_s))} of {len(intervals)} "
             f"over 1.01 medians; whole window "
             f"{len(losses) * tokens_per_step / window_s:.1f} tokens/s")

    if ctx.trace:
        ctx.stop_trace()        # a window shorter than the traced steps
        capture = trace_reduce.newest_xplane(ctx.trace_dir)
        joined = (scope_ms(trace_reduce.load(capture), hlo, trace_reduce)
                  if capture else {})
        if joined:
            ctx.counters["scope_ms"] = joined
            ctx.mark("device self ms a step by scope: " + ", ".join(
                f"{k} {v:.3f}" for k, v in joined.items()))
        else:
            harness.log("NO EVENT OF THE CAPTURE JOINS A SCOPE: the executable "
                        "carries no names (loaded from a compile cache written "
                        "before they existed?) or there is no capture; "
                        "ut_stack_ms, ut_stack_roofline, ut_exit_ms, "
                        "head_loss_ms and optimizer_ms are left out")

    values = np.asarray(jax.device_get(losses), np.float32)
    del params, opt_state, loss, losses

    # (b) the timed executable against the reference, with the window closed:
    # one step from the seeded weights on the first timed batch.
    t0 = time.perf_counter()
    params = seeded()
    stepped, opt_state, loss = compiled(params, new_state(params), *batches[0])
    del opt_state
    params = seeded()           # the step took the others for its own
    loss_reference = jax.jit(lambda p, s: reference.loss_only(
        cfg, p, s, how["loss_chunk"]))(params, batches[0])
    # The reference's weights are a program's result of their own: where their
    # norm is taken in the program that makes them, the chip's compiler keeps
    # the sum in float32 and the norm is of a step no weight's type holds (7%
    # off at these weights; my chip run, PR 30).
    wanted = jax.jit(lambda p, g: reference.adamw_first_step(
        p, g, how["optimizer"]))(
            params, jax.jit(jax.grad(loss_fn))(params, batches[0]))
    changed, changed_reference = jax.jit(lambda p, p1, p2: (
        change_norms(p1, p, reference.LEAF_AXES),
        change_norms(p2, p, reference.LEAF_AXES)))(params, stepped, wanted)
    found.update(step_differences(float(loss), float(loss_reference),
                                  jax.device_get(changed),
                                  jax.device_get(changed_reference)))
    found["ok"] = bool(found["ok"] and all(
        np.isfinite(found[k]) and found[k] <= limit
        for k, limit in reference.STEP_TOLERANCE.items()))
    ctx.counters["reference_check"] = found
    ctx.mark(f"the timed step against the reference, after the window: "
             f"{time.perf_counter() - t0:.2f} s")
    del params, stepped, wanted
    mpi.stop()
    return {
        "samples_per_s": tokens_per_step / step_s,
        "window_s": window_s,
        "attempted": len(values),
        "failed": int(np.sum(~np.isfinite(values))),
        "first_loss": float(values[0]), "last_loss": float(values[-1]),
        "program_bytes": ctx.counters["program_bytes"],
        "devices": devices,
    }
