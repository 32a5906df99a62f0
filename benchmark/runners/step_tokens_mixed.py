"""Runner `step_tokens_mixed`: the `step_tokens_latent` protocol (a decoder
language model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step(optimizer=AdamW)` -> `mpi.stop()` on seeded token
batches resident on the device; one step queued behind the one that runs; the
rate from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check before the optimizer's state exists,
compilation and warm-up in set-up) for a stack whose softmax layers are of two
kinds, window and full, with head counts and rotations of their own, a head
width that is not the state's over the heads, and a gate on the attention
output: Laguna-S-2.1.  It builds `llama.Config` from the configuration file
with the fields such a model needs, so a program that lacks them fails at once
(`TypeError`), before anything touches the device's memory.

Taken from the runners that have them, through `harness.load_module`:
`_optimizer` (`step_tokens_adamw.py`); `instruction_scopes`, `change_norms`
and `step_differences` (`step_tokens_looped.py`); `SCOPES`
(`step_tokens_hybrid.py`); `self_ms` and `kernel_instructions`
(`step_tokens_latent.py`).  Written here: `_model`, the four joins and the
loop of `run`.

`correct` compares what the window drives (`ctx.counters["reference_check"]`
holds all of it; the limits and why are in `reference/<config>.py`):

* before the optimizer's state exists, `compare.check` on the configuration's
  `check_sample`: the loss, the logits, every leaf's gradient norm, the gate's
  (`wg`) among them;
* still before it, `band_rows_wrong`: one sliding layer of the model alone
  (its first window layer's mixer, a dense FFN, the embedding and the head:
  a row of its logits sees the row's own token and the `sliding_window - 1`
  before it and no other) on the check sample and on the same sample with two
  tokens changed, through `llama.apply`; the rows whose logits changed, to
  the bit, against the rows the reference's change on its own pass: a window
  one key wider, which rounding hides from every norm above, changes one row
  more for each token;
* after the window: the timed executable once more, from the seeded weights
  and a new optimizer state on the first timed batch: its loss against the
  reference's on that whole batch, the norm of every leaf's change against
  AdamW's first step as the reference writes it on the gradient the step
  itself took (`mu / (1 - b1)`), and the program's routed units an expert
  against the reference's over all the routers (`routing_l1_max`).

What it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `expert_unit_counts`, `routed_units_all`, `moe_local_share`,
  `moe_local_share_end`: as the hybrid runner, one row a router;
  `kernel_calls`;
* `flash_blocks`, from the shapes, once in set-up: for a sliding and for a
  full layer the tile the program chose and the K blocks a Q block meets
  (the most any does and the mean), read from the index maps the kernels are
  given (`ops.flash_attention.blocks_met`);
* with `--trace 1`, four joins of the one capture with the executable's text:
  `scope_ms`, the innermost of `SCOPES` an instruction carries (`swa` and
  `attn.gate` before the hybrid runner's, so `attn` there is what is left of
  the mixers: norms' share, projections, rotations, the full layers'
  kernels); `attn_scope_ms`, the same events by the OUTER name `attn` alone
  (`attn_ms`); `swa_flash_kernel_ms` and `full_flash_kernel_ms`, the Mosaic
  flash kernels under `swa`, and under `attn` outside `swa`, by kernel.  Where
  no event joins, that is logged and nothing is left, so the readers return
  `None`, never zero.
"""

import os
import time

import numpy as np

SCOPES_FIRST = ("swa", "attn.gate")


def _model(cfg):
    """`llama.Config` from the configuration file: the first
    `num_hidden_layers` entries of its per-layer lists are the layers that
    run."""
    from torchmpi_tpu.models import llama

    for name, want in (("decoder_sparse_step", 1),
                       ("tie_word_embeddings", False),
                       ("attention_bias", False), ("gating", "per-head"),
                       ("moe_apply_router_weight_on_input", False),
                       ("moe_router_logit_softcapping", 0)):
        if cfg[name] != want:
            raise ValueError(f"{name} = {cfg[name]!r} is not implemented (the "
                             f"program has {want!r})")
    n = cfg["num_hidden_layers"]
    kinds = llama.window_layer_kinds(cfg["layer_types"][:n],
                                     cfg["mlp_layer_types"][:n])
    if [i for i, (_, ffn) in enumerate(kinds) if ffn == "dense"] != [
            i for i in cfg["mlp_only_layers"] if i < n]:
        raise ValueError("mlp_only_layers and mlp_layer_types disagree")
    heads = {mixer: {h for h, (m, _) in zip(
        cfg["num_attention_heads_per_layer"], kinds) if m == mixer}
        for mixer in ("attn", "swa")}
    if heads["attn"] != {cfg["num_attention_heads"]} or len(heads["swa"]) > 1:
        raise ValueError("one head count for the full layers "
                         "(num_attention_heads) and one for the sliding ones "
                         f"is what the program has; the file gives {heads}")
    if set(cfg["gating_types"][:n]) != {"per_head"}:
        raise ValueError("every layer's gate is per_head in the program")
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"],
            sliding["partial_rotary_factor"]) != ("yarn", "default", 1):
        raise ValueError("the program rotates the full layers with YaRN and "
                         "the sliding ones whole and unscaled")
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], norm_eps=cfg["rms_norm_eps"],
        n_experts=cfg["published"]["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"], capacity_factor=None,
        moe_aux_coef=0.0, moe_renormalize=cfg["norm_topk_prob"],
        n_shared_experts=(cfg["shared_expert_intermediate_size"]
                          // cfg["moe_intermediate_size"]),
        router_act="sigmoid", router_bias=False,
        routed_scale=cfg["moe_routed_scaling_factor"],
        swa_heads=next(iter(heads["swa"]), 0),
        swa_window=cfg["sliding_window"], swa_rope_theta=sliding["rope_theta"],
        rope_theta=full["rope_theta"],
        rope_fraction=full["partial_rotary_factor"],
        rope_yarn=(full["factor"], full["original_max_position_embeddings"],
                   full["beta_fast"], full["beta_slow"],
                   full["attention_factor"]),
        attn_gate=True, layer_kinds=kinds,
        experts_held=(cfg["experts_held_first"], cfg["num_experts"]))


def band_rows_wrong(*args):
    """How many rows the program's window and the reference's disagree on:
    the rows of `band_rows`' two answers that differ."""
    ours, theirs = band_rows(*args)
    return int((ours != theirs).sum())


def band_rows(model, cfg, reference, mesh, kinds, seed, dtype, seq_len):
    """(the program's, the reference's): which of `seq_len` rows of one
    sliding layer's logits two changed tokens move.  A model of one sliding layer (the mixer of `model`'s window
    layers with a dense FFN between the embedding and the head) is run twice
    through `llama.apply`, on seeded tokens and on the same with two of them
    changed, one an eighth of the way in and one at the middle, where a tile
    of the kernels ends; a row of the logits either changed somewhere or is
    the same to the bit: a key outside the row's band is masked or never
    fetched, and weighs exactly 0.  The reference, given the same one-layer
    file and weights, names the rows ITS logits change on."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama

    W = cfg["sliding_window"]
    at = (seq_len // 8 + 3, seq_len // 2)
    if at[0] + W > at[1] or at[1] + W >= seq_len:
        raise ValueError(f"{seq_len} rows do not hold two bands of {W} keys "
                         "apart and a row past the second")
    one = dataclasses.replace(model, n_layers=1,
                              layer_kinds=(("swa", "dense"),))
    file = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"],
                mlp_layer_types=["dense"],
                num_attention_heads_per_layer=[llama.softmax_heads(one,
                                                                   "swa")])
    params = llama.shard_params(
        jax.jit(lambda key: llama.init(key, one, dtype=dtype))(
            jax.random.PRNGKey(seed)), mesh, one)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, seq_len), 0,
                                one.vocab)
    other = tokens.at[0, jnp.asarray(at)].set(
        (tokens[0, jnp.asarray(at)] + 1) % one.vocab)
    # One executable for both samples on each side, so that a row neither
    # token reaches is computed by the same instructions on the same values:
    # two copies of the model in one program are not compiled alike (on the
    # chip they differed in every row, PERF.md section 6, PR 40).
    return tuple(
        np.asarray(jnp.any(f(params, tokens) != f(params, other), axis=-1)[0])
        for f in (jax.jit(lambda p, t: llama.apply(one, p, t, mesh=mesh,
                                                   **kinds)),
                  jax.jit(lambda p, t: reference.loss_fn(file, p, t, t)[1])))


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
    from torchmpi_tpu.ops.flash_attention import blocks_met
    from torchmpi_tpu.parallel import make_mesh
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import compare
    import harness
    import trace_reduce
    import traffic as traffic_mod

    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    latent = harness.load_module("runners", "step_tokens_latent")
    devices = jax.devices()[:ctx.chips]
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])
    chunk = how["loss_chunk"]
    check = cfg["check_sample"]
    if check["batch"] != mix["batch"] or check["seq_len"] < 2 * chunk:
        raise ValueError("the check sample has the timed batch's rows and at "
                         "least two chunks of the head, or it does not drive "
                         "what the window drives")
    if check["seq_len"] < 2 * cfg["sliding_window"]:
        raise ValueError("the check sample is at least two windows deep, or "
                         "the sliding layers' band is the whole triangle")

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
    grad_fn = jax.value_and_grad(
        llama.make_loss_fn(model, mesh, loss_chunk=chunk, **kinds))

    def system(p, s):
        loss, grads = grad_fn(p, s)
        return loss, llama.apply(model, p, s[0], mesh=mesh, **kinds), grads

    with ctx.compiling("reference check"):
        found = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s), params,
            sample, reference.TOLERANCE, reference.LEAF_AXES)

    with ctx.compiling("band probe"):
        found["band_rows_wrong"] = band_rows_wrong(
            model, cfg, reference, mesh, kinds, ctx.seed, dtype,
            check["seq_len"])

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    first, held = model.experts_held
    unit_counts = jax.jit(lambda p, b: llama.expert_unit_counts(
        model, p, b[0], mesh=mesh, attn=how["attn"]))
    local_share = lambda counts: (
        counts[:, first:first + held].sum(axis=1)
        / (model.expert_top_k * tokens_per_step)).tolist()
    with ctx.compiling("expert unit counts"):
        counts = np.asarray(unit_counts(params, batches[0]))
    ctx.counters["routed_units_all"] = counts.tolist()
    ctx.counters["expert_unit_counts"] = counts[:, first:first + held].tolist()
    ctx.counters["moe_local_share"] = local_share(counts)
    ctx.counters["flash_blocks"] = {
        "swa": blocks_met(mix["seq_len"], model.swa_window),
        "full": blocks_met(mix["seq_len"])}
    ctx.mark(f"flash blocks: {ctx.counters['flash_blocks']}")

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
        swa = latent.kernel_instructions(hlo, "swa")
        full = {name: kernel for name, kernel in
                latent.kernel_instructions(hlo, "attn").items()
                if name not in swa}
        for counter, labels in (
                ("scope_ms", looped.instruction_scopes(
                    hlo, SCOPES_FIRST + hybrid.SCOPES)),
                ("attn_scope_ms", looped.instruction_scopes(hlo, ("attn",))),
                ("swa_flash_kernel_ms", swa), ("full_flash_kernel_ms", full)):
            joined = (latent.self_ms(loaded, labels, trace_reduce)
                      if loaded else {})
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
    loss_reference, units_reference = jax.jit(
        lambda p, s: reference.loss_only(cfg, p, s, chunk))(params, batches[0])
    # The reference's stepped weights are a program's result of their own
    # (PR 30: taken in the program that makes them, the norm is of a step no
    # weight's type holds), from the gradient the step itself took.
    wanted = jax.jit(lambda p, mu: reference.adamw_first_step(
        p, jax.tree.map(lambda m: m / (1 - how["optimizer"]["b1"]), mu),
        how["optimizer"]))(params, optax.tree_utils.tree_get(opt_state, "mu"))
    del opt_state
    axes = reference.LEAF_AXES
    changed, changed_reference = jax.jit(lambda p, p1, p2: (
        looped.change_norms(p1, p, axes), looped.change_norms(p2, p, axes)))(
            params, stepped, wanted)
    found.update(looped.step_differences(
        float(loss), float(loss_reference), jax.device_get(changed),
        jax.device_get(changed_reference)))
    # The program's routers against the reference's on that batch, at the
    # seeded weights: the units that go to another expert, of a layer's k * T.
    found["routing_l1_max"] = float(np.max(np.sum(np.abs(
        counts - np.asarray(units_reference)), axis=1))
        / (2 * model.expert_top_k * tokens_per_step))
    found["ok"] = bool(
        found["ok"] and all(np.isfinite(found[k]) and found[k] <= limit
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
