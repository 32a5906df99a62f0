"""Runner `step_tokens_latent`: the `step_tokens_hybrid` protocol (a decoder
language model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step(optimizer=AdamW)` -> `mpi.stop()` on seeded token
batches resident on the device; one step queued behind the one that runs; the
rate from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check before the optimizer's state exists,
compilation and warm-up in set-up) for a stack of runs whose latent-attention
layers may be rotated and take their queries through a latent, and which may
end in a multi-token-prediction module: GLM-4.7-Flash.  It builds
`llama.Config` from the configuration file with the fields such a model
needs, so a program that lacks them fails at once (`TypeError`), before
anything touches the device's memory.

`_model` reads either file's key names (`n_routed_experts` or `num_experts`,
`num_experts_per_tok` or `num_experts_per_token`, `max_position_embeddings` or
`model_max_length`, `topk_method` `noaux_tc` or `moe_router_activation_func`,
`mla_use_nope`, a `linear_attn_config` or none), so the Kimi Linear file could
name this runner and `step_tokens_hybrid.py` be retired (a `benchmark`
issue's: PERF.md section 7).

Taken from the runners that have them, through `harness.load_module`:
`_optimizer` (`step_tokens_adamw.py`); `instruction_scopes`, `change_norms`
and `step_differences` (`step_tokens_looped.py`); `SCOPES` and `bias_leaves`
(`step_tokens_hybrid.py`).  Written here: `self_ms`, the join of a capture
with ANY labelling of the executable's instructions (the hybrid runner's
`scope_ms` gives up unless one of its own scopes joins, and this runner joins
the same capture three times), and the loop of `run`.

`correct` compares what the window drives, as the hybrid runner does and with
the module in every part (`ctx.counters["reference_check"]` holds all of it;
the limits and why are in `reference/<config>.py`):

* before the optimizer's state exists, `compare.check` on the configuration's
  `check_sample`: the loss (both terms), the main logits AND the module's (2 B
  sequences of rows), every leaf's gradient norm, the module's leaves among
  them; a selection bias's gradient is exactly zero on both sides;
* after the window: the timed executable once more, from the seeded weights
  and a new optimizer state on the first timed batch: its loss against the
  reference's on that whole batch, the norm of every leaf's change against
  AdamW's first step as the reference writes it on the gradient the step
  itself took (`mu / (1 - b1)`), every selection bias, the module's too,
  unchanged to the bit, and the program's routed units an expert against the
  reference's over all the routers, the module's the last (`routing_l1_max`).

What it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `expert_unit_counts`, `routed_units_all`, `moe_local_share`,
  `moe_local_share_end`: as the hybrid runner, with the module's router as one
  more row; `kernel_calls`; `kda_chunks` where the stack has KDA layers.
* `main_nll`, `mtp_nll` and `main_nll_end`, `mtp_nll_end`: the two terms of
  the loss, unweighted, on the first timed batch at the seeded weights and at
  the weights the window leaves, from the program's own loss parts
  (`llama.mtp_loss_parts`), one forward pass each, outside the window.
* with `--trace 1`, three joins of the one capture with the executable's text:
  `scope_ms`, the innermost of the hybrid runner's `SCOPES` an instruction
  carries (the module's layer counts under `mla`, `moe.*`, `head_loss` like
  every other: the flops functions count six layers and two head passes);
  `mtp_scope_ms`, the same events by the OUTER name `mtp` alone (`mtp_ms`);
  `mla_flash_kernel_ms`, the Mosaic kernels `flash_fwd` and `flash_bwd` under
  `mla` by kernel (`mla_flash_ms`).  Where no event joins, that is logged and
  nothing is left, so the readers return `None`, never zero.
"""

import os
import re
import time

import numpy as np

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
FLASH_KERNELS = ("flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv")


_REQUIRED = object()


def _either(cfg, *names, default=_REQUIRED):
    """The value of the first of `names` the file has."""
    for name in names:
        if name in cfg:
            return cfg[name]
    if default is _REQUIRED:
        raise KeyError(f"the configuration has none of {names}")
    return default


def _model(cfg):
    """`llama.Config` from the configuration file, GLM-4.7-Flash's key names
    or Kimi Linear's."""
    from torchmpi_tpu.models import llama

    for names, want in ((("n_group", "num_expert_group"), 1),
                        (("topk_group",), 1), (("moe_layer_freq",), 1),
                        (("num_nextn_predict_layers",), (0, 1)),
                        (("tie_word_embeddings",), False),
                        (("attention_bias",), False),
                        (("partial_rotary_factor",), 1),
                        (("hidden_act",), "silu"), (("rope_scaling",), None)):
        got = _either(cfg, *names, default=want)
        if got != want and not (isinstance(want, tuple) and got in want):
            raise ValueError(f"{names[0]} = {got!r} is not implemented (the "
                             f"program has {want!r})")
    router = _either(cfg, "moe_router_activation_func", default={
        "noaux_tc": "sigmoid"}.get(cfg.get("topk_method"), ""))
    n = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    lin = cfg.get("linear_attn_config")
    if lin:
        kinds = llama.layer_kinds(n, lin["kda_layers"],
                                  lin["full_attn_layers"], dense)
        kda = dict(kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
                   kda_conv=lin["short_conv_kernel_size"])
    else:
        kinds = tuple(("mla", "dense" if i < dense else "moe")
                      for i in range(n))
        kda = {}
    experts = ("n_routed_experts", "num_experts")
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        max_seq=_either(cfg, "max_position_embeddings", "model_max_length"),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        n_experts=_either(cfg["published"], *experts),
        expert_top_k=_either(cfg, "num_experts_per_tok",
                             "num_experts_per_token"),
        capacity_factor=None, moe_aux_coef=0.0,
        moe_renormalize=_either(cfg, "norm_topk_prob", "moe_renormalize"),
        n_shared_experts=_either(cfg, "n_shared_experts",
                                 "num_shared_experts"),
        router_act=router, router_bias=True,
        routed_scale=cfg["routed_scaling_factor"], **kda,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], q_lora_rank=cfg["q_lora_rank"] or 0,
        mla_rope=not cfg.get("mla_use_nope", False), layer_kinds=kinds,
        mtp_layers=cfg["num_nextn_predict_layers"],
        **({"mtp_coef": cfg["mtp_loss_weight"]} if "mtp_loss_weight" in cfg
           else {}),
        experts_held=(cfg["experts_held_first"], _either(cfg, *experts)))


# ------------------------------------------------- the joins, on plain data

def kernel_instructions(hlo_text, scope, kernels=FLASH_KERNELS):
    """{instruction name: kernel name} of an executable's Mosaic kernel calls
    whose `op_name` holds `scope` and one of `kernels` as path components."""
    out = {}
    for line in hlo_text.splitlines():
        m, op = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if not m or not op or "tpu_custom_call" not in line:
            continue
        parts = set(re.split(r"[/()\[\]= ]", op.group(1)))
        kernel = next((k for k in kernels if k in parts), None)
        if kernel and scope in parts:
            out[m.group(1)] = kernel
    return out


def self_ms(trace, labels, trace_reduce):
    """{label: device self ms a step} over the whole steps of a capture
    (`trace_reduce.load`'s plain lists), mean over its devices, from `labels`
    ({instruction name: label}); "unnamed" holds what carries none.  {} where
    no event carries one."""
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
            label = labels.get(m.group(1) if m else name, "unnamed")
            found[label] = found.get(label, 0.0) + ns / n / 1e6
        per_device.append(found)
    if not any(label != "unnamed" for d in per_device for label in d):
        return {}
    return {s: sum(d.get(s, 0.0) for d in per_device) / len(per_device)
            for s in sorted(set().union(*per_device))}


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
    from torchmpi_tpu.parallel import make_mesh
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import compare
    import harness
    import trace_reduce
    import traffic as traffic_mod

    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    devices = jax.devices()[:ctx.chips]
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])
    chunk = how["loss_chunk"]
    check = cfg["check_sample"]
    if check["batch"] != mix["batch"] or check["seq_len"] < 2 * chunk:
        raise ValueError("the check sample has the timed batch's rows and at "
                         "least two chunks of the head, or it does not drive "
                         "what the window drives")

    def bias_leaves(params):
        """The stack's selection biases and the module's."""
        leaves = hybrid.bias_leaves(params)
        module = params.get("mtp", {}).get("layer", {})
        if "router_bias" in module:
            leaves.append(module["router_bias"])
        return leaves

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
    loss_fn = llama.make_loss_fn(model, mesh, loss_chunk=chunk, **kinds)
    grad_fn = jax.value_and_grad(loss_fn)
    module_too = lambda s: s[1] if model.mtp_layers else None

    def system(p, s):
        loss, grads = grad_fn(p, s)
        logits = llama.apply(model, p, s[0], mesh=mesh,
                             mtp_tokens=module_too(s), **kinds)
        return (loss, jnp.concatenate(logits) if model.mtp_layers else logits,
                grads)

    with ctx.compiling("reference check"):
        found = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s), params,
            sample, reference.TOLERANCE, reference.LEAF_AXES)

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    first, held = model.experts_held
    unit_counts = jax.jit(lambda p, b: llama.expert_unit_counts(
        model, p, b[0], mesh=mesh, attn=how["attn"], mtp_tokens=module_too(b)))
    local_share = lambda counts: (
        counts[:, first:first + held].sum(axis=1)
        / (model.expert_top_k * tokens_per_step)).tolist()
    with ctx.compiling("expert unit counts"):
        counts = np.asarray(unit_counts(params, batches[0]))
    ctx.counters["routed_units_all"] = counts.tolist()
    ctx.counters["expert_unit_counts"] = counts[:, first:first + held].tolist()
    ctx.counters["moe_local_share"] = local_share(counts)
    if any(mixer == "kda" for mixer, _ in model.layer_kinds):
        from torchmpi_tpu.ops import kda

        ctx.counters["kda_chunks"] = (
            mix["batch"] * kda.n_chunks(mix["seq_len"])
            * sum(mixer == "kda" for mixer, _ in model.layer_kinds))
    loss_parts = None
    if model.mtp_layers:
        loss_parts = jax.jit(lambda p, b: llama.mtp_loss_parts(
            model, p, b, mesh=mesh, attn=how["attn"], loss_chunk=chunk))
        with ctx.compiling("loss parts"):
            ctx.counters["main_nll"], ctx.counters["mtp_nll"] = (
                float(x) for x in loss_parts(params, batches[0]))

    optimizer = harness.load_module(
        "runners", "step_tokens_adamw")._optimizer(how["optimizer"])
    new_state = jax.jit(optimizer.init)
    opt_state = new_state(params)
    step = llama.make_train_step(model, mesh, optimizer=optimizer,
                                 loss_chunk=chunk, **kinds)
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
             f"{len(losses) * tokens_per_step / window_s:.1f} tokens/s; each, "
             f"ms: {[round(1e3 * float(x)) for x in intervals]}")

    if ctx.trace:
        ctx.stop_trace()        # a window shorter than the traced steps
        capture = trace_reduce.newest_xplane(ctx.trace_dir)
        loaded = trace_reduce.load(capture) if capture else None
        for counter, labels in (
                ("scope_ms", looped.instruction_scopes(hlo, hybrid.SCOPES)),
                ("mtp_scope_ms", looped.instruction_scopes(hlo, ("mtp",))),
                ("mla_flash_kernel_ms", kernel_instructions(hlo, "mla"))):
            joined = self_ms(loaded, labels, trace_reduce) if loaded else {}
            if joined:
                ctx.counters[counter] = joined
                ctx.mark(f"device self ms a step, {counter}: " + ", ".join(
                    f"{k} {v:.3f}" for k, v in joined.items()))
            else:
                harness.log(f"NO EVENT OF THE CAPTURE JOINS {counter}: the "
                            "executable carries no such names (loaded from a "
                            "compile cache written before they existed?) or "
                            "there is no capture; the metrics read from it "
                            "are left out")

    values = np.asarray(jax.device_get(losses), np.float32)
    ctx.counters["moe_local_share_end"] = local_share(np.asarray(
        unit_counts(params, batches[0])))
    if loss_parts is not None:
        ctx.counters["main_nll_end"], ctx.counters["mtp_nll_end"] = (
            float(x) for x in loss_parts(params, batches[0]))
        ctx.mark("main and module's NLL on the first timed batch: "
                 f"{ctx.counters['main_nll']:.4f}, "
                 f"{ctx.counters['mtp_nll']:.4f} seeded; "
                 f"{ctx.counters['main_nll_end']:.4f}, "
                 f"{ctx.counters['mtp_nll_end']:.4f} after the window")
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
    loss_reference, units_reference, *_ = jax.jit(
        lambda p, s: reference.loss_only(cfg, p, s, chunk))(params, batches[0])
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
    found["bias_leaves"] = len(bias_leaves(params))
    # The program's routers against the reference's on that batch, at the
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
