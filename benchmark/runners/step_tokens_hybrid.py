"""Runner `step_tokens_hybrid`: the `step_tokens` protocol with AdamW (a
decoder language model trained through `mpi.start()` -> `parallel.make_mesh`
-> `llama.make_train_step(optimizer=AdamW)` -> `mpi.stop()` on seeded token
batches resident on the device; one step queued behind the one that runs; the
rate from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check before the optimizer's state exists,
compilation and warm-up in set-up) for a stack that is not homogeneous: KDA
linear-attention layers among latent-attention ones, a dense first layer, then
sigmoid-routed experts beside a shared one, of which this chip holds a share.
It builds `llama.Config` from the configuration file with the fields such a
model needs, so a program that lacks them fails at once (`AttributeError`,
`TypeError`), before anything touches the device's memory.

Taken from the runners that have them, through `harness.load_module`:
`_optimizer` (`step_tokens_adamw.py`); `instruction_scopes`, which takes the
scopes as an argument, `change_norms` and `step_differences`
(`step_tokens_looped.py`).  Written again here: `scope_ms` (the looped
runner's joins against its own module constant and gives up unless one of its
own scopes joins) and the loop of `run` (both others fix their model, their
counters and a step of three results in it); PERF.md section 7 has the merge.

`correct` compares what the window drives, in two parts
(`ctx.counters["reference_check"]` holds both; the limits and why are in
`reference/<config>.py`), and needs a third thing:

* before the optimizer's state exists, `compare.check` on the configuration's
  `check_sample` (the timed batch's rows, at least two chunks of the head's
  `loss_chunk`): the loss, the logits and every leaf's gradient norm against
  the plain reference; the selection biases are leaves like the others, and
  the reference's gradient for them is exactly zero, so anything else reads
  1.0 there;
* after the window: the timed executable once more, from the seeded weights
  and a new optimizer state on the first timed batch.  Its loss against the
  reference's on that whole batch (`loss_only`), the norm of every leaf's
  change against AdamW's first step as the reference writes it, and every
  selection bias unchanged to the bit.  The gradient that step took is read
  from the first moment it left (`mu / (1 - b1)`, exact in float32), so no
  second program differentiates the 16k batch (61 s of compilation and 71
  MiB of cache; PERF.md section 6).

What it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `expert_unit_counts`: the routed units of each HELD expert of each expert
  layer on the first timed batch, what the step's tiles see
  (`moe_max_load` reads it); `routed_units_all`: the same over all published
  experts; `moe_local_share`: units kept here over k * tokens, a layer
  (3.1% under uniform routing); from the program's own router
  (`llama.expert_unit_counts`), read once in set-up.  `moe_local_share_end`:
  the same from the weights the window leaves, read after it: a layer past
  the rows of one pass of the held experts (`llama.held_pass_rows`, 12.5% of
  k * tokens) took a second pass by then.
* `kda_chunks`: chunks of the recurrence a step runs forward (sequences x
  chunks a sequence x KDA layers); `kernel_calls`.
* `scope_ms` (`--trace 1` only): device self time a step under each
  `jax.named_scope` of the step program, the innermost of `SCOPES` an
  instruction carries (`kda` and `mla` lie inside `attn`), forward, backward
  and recomputed alike.  Where no event joins a scope that is logged and
  nothing is left, so the readers return `None`, never zero.
"""

import os
import re
import time

import numpy as np

# Inner scopes first: an instruction under `attn/kda` is `kda`'s.
SCOPES = ("kda", "mla", "moe.shared", "moe.router", "moe.dispatch",
          "moe.experts", "moe.combine", "optimizer", "head_loss", "final_norm",
          "attn", "ffn", "embed")


def _model(cfg):
    """`llama.Config` from the configuration file."""
    from torchmpi_tpu.models import llama

    lin = cfg["linear_attn_config"]
    for key, want in (("q_lora_rank", None), ("mla_use_nope", True),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("num_nextn_predict_layers", 0),
                      ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("rope_scaling", None)):
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r} is not implemented "
                             f"(the program has {want!r})")
    n = cfg["num_hidden_layers"]
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        max_seq=cfg["model_max_length"], norm_eps=cfg["rms_norm_eps"],
        n_experts=cfg["published"]["num_experts"],
        expert_top_k=cfg["num_experts_per_token"], capacity_factor=None,
        moe_aux_coef=0.0, moe_renormalize=cfg["moe_renormalize"],
        n_shared_experts=cfg["num_shared_experts"],
        router_act=cfg["moe_router_activation_func"], router_bias=True,
        routed_scale=cfg["routed_scaling_factor"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        layer_kinds=llama.layer_kinds(n, lin["kda_layers"],
                                      lin["full_attn_layers"],
                                      cfg["first_k_dense_replace"]),
        experts_held=(cfg["experts_held_first"], cfg["num_experts"]))


def scope_ms(trace, scopes, trace_reduce):
    """{scope: device self ms a step} over the whole steps of a capture
    (`trace_reduce.load`'s plain lists), mean over its devices, from
    `scopes` ({instruction name: scope}, `instruction_scopes` of the
    executable's text); "unnamed" holds what joined no scope.  {} where no
    event joins one."""
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


def bias_leaves(params):
    """Every selection bias of a parameter tree, in order."""
    return [run["router_bias"] for run in params["layers"]
            if "router_bias" in run]


# ------------------------------------------------------------------ the run

def run(ctx):
    cfg, mix, how = ctx.cfg, ctx.traffic, ctx.cfg["run"]
    model = _model(cfg)         # a program without the fields stops here

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.ops import kda
    from torchmpi_tpu.parallel import make_mesh
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import compare
    import harness
    import trace_reduce
    import traffic as traffic_mod

    looped = harness.load_module("runners", "step_tokens_looped")
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

    # (a) the system against the plain reference, before the optimizer's
    # state takes its share of the memory.
    reference = ctx.module("reference")
    sample = tuple(jnp.asarray(a) for a in traffic_mod.tokens(
        mix, cfg, ctx.seed + 1, n_batches=1, batch=check["batch"],
        seq_len=check["seq_len"])[0])
    loss_fn = llama.make_loss_fn(model, mesh, loss_chunk=how["loss_chunk"],
                                 **kinds)
    grad_fn = jax.value_and_grad(loss_fn)

    def system(p, s):
        loss, grads = grad_fn(p, s)
        return loss, llama.apply(model, p, s[0], mesh=mesh, **kinds), grads

    with ctx.compiling("reference check"):
        found = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s), params,
            sample, reference.TOLERANCE, reference.LEAF_AXES)

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    first, held = model.experts_held
    unit_counts = jax.jit(lambda p, t: llama.expert_unit_counts(
        model, p, t, mesh=mesh, attn=how["attn"]))
    local_share = lambda counts: (
        counts[:, first:first + held].sum(axis=1)
        / (model.expert_top_k * tokens_per_step)).tolist()
    with ctx.compiling("expert unit counts"):
        counts = np.asarray(unit_counts(params, batches[0][0]))
    ctx.counters["routed_units_all"] = counts.tolist()
    ctx.counters["expert_unit_counts"] = counts[:, first:first + held].tolist()
    ctx.counters["moe_local_share"] = local_share(counts)
    ctx.counters["kda_chunks"] = (
        mix["batch"] * kda.n_chunks(mix["seq_len"])
        * sum(mixer == "kda" for mixer, _ in model.layer_kinds))

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
    ctx.mark(f"warmed up, {len(warm)} fenced steps; held experts see "
             f"{[f'{100 * s:.2f}%' for s in ctx.counters['moe_local_share']]} "
             f"of the routed units")

    trace_at = trace_end = None
    if ctx.trace:
        trace_at = mix["trace"]["after_steps"]
        trace_end = trace_at + mix["trace"]["steps"]
    losses, done = [], []               # done[i]: step i seen finished
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            n = len(losses)
            if n == trace_at:
                ctx.start_trace()
            with ctx.span("bench.step_call"):
                params, opt_state, loss = compiled(
                    params, opt_state, *batches[n % len(batches)])
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
        joined = (scope_ms(trace_reduce.load(capture),
                           looped.instruction_scopes(hlo, SCOPES), trace_reduce)
                  if capture else {})
        if joined:
            ctx.counters["scope_ms"] = joined
            ctx.mark("device self ms a step by scope: " + ", ".join(
                f"{k} {v:.3f}" for k, v in joined.items()))
        else:
            harness.log("NO EVENT OF THE CAPTURE JOINS A SCOPE: the executable "
                        "carries no names (loaded from a compile cache written "
                        "before they existed?) or there is no capture; the "
                        "metrics read from scope_ms are left out")

    values = np.asarray(jax.device_get(losses), np.float32)
    ctx.counters["moe_local_share_end"] = local_share(np.asarray(
        unit_counts(params, batches[0][0])))
    pass_share = (llama.held_pass_rows(model, tokens_per_step)
                  / (model.expert_top_k * tokens_per_step))
    ctx.mark(f"held experts see "
             f"{[f'{100 * s:.2f}%' for s in ctx.counters['moe_local_share_end']]}"
             f" of the routed units after the window; a pass takes "
             f"{100 * pass_share:.2f}%")
    del params, opt_state, loss, losses

    # (b) the timed executable against the reference, with the window closed:
    # one step from the seeded weights on the first timed batch.
    t0 = time.perf_counter()
    params = seeded()
    stepped, opt_state, loss = compiled(params, new_state(params),
                                        *batches[0])
    params = seeded()           # the step took the others for its own
    loss_reference, units_reference = jax.jit(lambda p, s: reference.loss_only(
        cfg, p, s, how["loss_chunk"]))(params, batches[0])
    # The reference's stepped weights are a program's result of their own
    # (PR 30: taken in the program that makes them, the norm is of a step no
    # weight's type holds), from the gradient the step itself took.
    wanted = jax.jit(lambda p, mu: reference.adamw_first_step(
        p, jax.tree.map(lambda m: m / (1 - how["optimizer"]["b1"]), mu),
        how["optimizer"]))(params, optax.tree_utils.tree_get(opt_state, "mu"))
    del opt_state
    axes = reference.LEAF_AXES
    changed, changed_reference, bias_kept = jax.jit(lambda p, p1, p2: (
        looped.change_norms(p1, p, axes), looped.change_norms(p2, p, axes),
        jnp.all(jnp.stack([jnp.all(a == b) for a, b in zip(
            bias_leaves(p1), bias_leaves(p))]))))(params, stepped, wanted)
    found.update(looped.step_differences(
        float(loss), float(loss_reference), jax.device_get(changed),
        jax.device_get(changed_reference)))
    found["bias_unchanged"] = bool(bias_kept)
    # The program's router against the reference's on that batch, at the
    # seeded weights: the units that go to another expert, of a layer's k * T.
    found["routing_l1_max"] = float(np.max(np.sum(np.abs(
        counts - np.asarray(units_reference)), axis=1))
        / (2 * model.expert_top_k * tokens_per_step))
    found["ok"] = bool(
        found["ok"] and found["bias_unchanged"]
        and all(np.isfinite(found[k]) and found[k] <= limit
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
