"""Runner `step_tokens_adamw`: the `step_tokens` protocol (a decoder language
model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step` -> `mpi.stop()` on seeded token batches resident on
the device; one step queued behind the one that runs; the rate from the
median interval between completions, `harness.median_step_s`; weights,
batches, the reference check, compilation and warm-up in set-up) for a
configuration that trains with a real optimizer: AdamW through the
`optimizer=` argument of `make_train_step`, its state donated with the
weights.  It builds `llama.Config` from the configuration file with the
fields a dropless, QK-normed mixture of experts needs, so a program that
lacks them fails at once with a `TypeError`.

Two things it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `expert_unit_counts`: the routed units of every expert of every layer on
  the first timed batch, from the program's own router
  (`llama.expert_unit_counts`), read once in set-up: no device-to-host read
  is in a step.
* `scope_ms` (`--trace 1` only): device self time a step under each
  `jax.named_scope` of the step program (`moe.router`, `moe.dispatch`,
  `moe.experts`, `moe.combine`, `attn`, `head_loss`, `optimizer`, `embed`),
  forward, backward and recomputed alike.  An `XLA Ops` event of the capture
  is named by its instruction; the instruction's `op_name` in the text of the
  executable this run compiled carries the scopes (`docs/observability.md`);
  with `BENCHMARK_KEEP_TRACE` set that text is kept beside the capture.
  An executable that the persistent compile cache hands back may have been
  compiled before the names existed (the cache leaves metadata out of its
  key): if no event joins a `moe.` scope, that is logged and nothing is left,
  so the readers return `None`, never zero.
"""

import os
import re
import time

import numpy as np

SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine",
          "optimizer", "head_loss", "attn", "embed")
# XLA's TPU compiler turns `lax.ragged_dot` into a Mosaic grouped matmul of
# its own (and a small kernel that lays out its tiles) and names both itself,
# dropping the scope the product was written under.
KERNEL_SCOPES = {"ragged-dot-none": "moe.experts",
                 "ragged-dot-metadata": "moe.experts"}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _model(cfg):
    """`llama.Config` from the configuration file."""
    from torchmpi_tpu.models import llama

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("llama.Config derives head_dim as hidden_size / "
                         "num_attention_heads; the file says otherwise")
    if cfg["clip_qkv"] is not None:
        raise ValueError("clip_qkv is not implemented")
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], n_experts=cfg["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        capacity_factor=None,                   # dropless, as published
        moe_renormalize=cfg["norm_topk_prob"],
        moe_aux_coef=cfg["router_aux_loss_coef"],
        moe_z_coef=cfg["router_z_loss_coef"], qk_norm=True)


def _optimizer(how):
    """`optax.adamw` with both moments held in `moments_dtype`: optax takes
    the second moment's type from the parameters it is shown, so it is shown
    them, and the gradients, in that type."""
    import jax
    import jax.numpy as jnp
    import optax

    how = dict(how)
    moments = jnp.dtype(how.pop("moments_dtype"))
    adamw = optax.adamw(**how)
    cast = lambda tree: jax.tree.map(lambda a: a.astype(moments), tree)

    def update(grads, state, params):
        updates, state = adamw.update(cast(grads), state, cast(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    return optax.GradientTransformation(lambda p: adamw.init(cast(p)), update)


# ------------------------------------------------- the join, on plain data

def instruction_scopes(hlo_text):
    """{instruction name: scope} from the text of an executable: the first
    of `SCOPES` that the instruction's `op_name` holds as a path component;
    for a fusion without a name of its own, the scope most instructions of
    its fused computation carry."""
    own, calls, inside, where = {}, {}, {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"%?([\w.\-]+) ", line)
            where = m.group(1) if m and line.rstrip().endswith("{") else None
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        scope = _scope_of(op.group(1)) if op else None
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


def _scope_of(op_name):
    if op_name in KERNEL_SCOPES:
        return KERNEL_SCOPES[op_name]
    parts = set(re.split(r"[/()]", op_name))
    return next((s for s in SCOPES if s in parts), None)


def scope_ms(trace, hlo_text, trace_reduce):
    """{scope: device self ms a step} over the whole steps of a capture
    (`trace_reduce.load`'s plain lists), mean over its devices; "unnamed"
    holds what joined no scope.  {} where no event joins a `moe.` scope that
    the program wrote."""
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
    # A scope the program wrote, not the compiler's name of its own kernel.
    if not any(s in d for d in per_device
               for s in ("moe.router", "moe.dispatch", "moe.combine")):
        return {}
    return {s: sum(d.get(s, 0.0) for d in per_device) / len(per_device)
            for s in sorted(set().union(*per_device))}


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
    devices = jax.devices()[:ctx.chips]
    model = _model(cfg)
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])

    mpi.start(devices=devices)
    mesh = make_mesh(mix["mesh"], devices=devices)
    with ctx.compiling("seeded weights"):
        params = llama.shard_params(
            jax.jit(lambda key: llama.init(key, model, dtype=dtype))(
                jax.random.PRNGKey(ctx.seed)), mesh, model)
        jax.block_until_ready(params)

    # (a) the system against the plain reference, before the optimizer's
    # state takes its share of the memory.
    reference = ctx.module("reference")
    check = cfg["check_sample"]
    sample = tuple(jnp.asarray(a) for a in traffic_mod.tokens(
        mix, cfg, ctx.seed + 1, n_batches=1, batch=check["batch"],
        seq_len=check["seq_len"])[0])
    loss_fn = llama.make_loss_fn(
        model, mesh, loss_chunk=min(how["loss_chunk"], check["seq_len"]), **kinds)

    def system(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p, s)
        return loss, llama.apply(model, p, s[0], mesh=mesh, **kinds), grads

    with ctx.compiling("reference check"):
        ctx.counters["reference_check"] = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s),
            params, sample, reference.TOLERANCE,
            getattr(reference, "LEAF_AXES", None))

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    with ctx.compiling("expert unit counts"):
        counts = jax.jit(lambda p, t: llama.expert_unit_counts(
            model, p, t, mesh=mesh, attn=how["attn"]))(params, batches[0][0])
        ctx.counters["expert_unit_counts"] = np.asarray(counts).tolist()

    optimizer = _optimizer(how["optimizer"])
    opt_state = jax.jit(optimizer.init)(params)
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
    ctx.mark(f"warmed up, {len(warm)} fenced steps")

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
        found = (scope_ms(trace_reduce.load(capture), hlo, trace_reduce)
                 if capture else {})
        if found:
            ctx.counters["scope_ms"] = found
            ctx.mark("device self ms a step by scope: " + ", ".join(
                f"{k} {v:.3f}" for k, v in found.items()))
        else:
            harness.log("NO EVENT OF THE CAPTURE JOINS A moe. SCOPE: the "
                        "executable carries no names (loaded from a compile "
                        "cache written before they existed?) or there is no "
                        "capture; moe_ms, moe_experts_ms, "
                        "moe_experts_roofline and optimizer_ms are left out")

    values = np.asarray(jax.device_get(losses), np.float32)
    del params, opt_state
    mpi.stop()
    return {
        "samples_per_s": tokens_per_step / step_s,
        "window_s": window_s,
        "attempted": len(losses),
        "failed": int(np.sum(~np.isfinite(values))),
        "first_loss": float(values[0]), "last_loss": float(values[-1]),
        "program_bytes": ctx.counters["program_bytes"],
        "devices": devices,
    }
