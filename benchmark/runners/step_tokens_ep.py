"""Runner `step_tokens_ep`: the `step_tokens_mixed` protocol (a decoder
language model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step(optimizer=AdamW)` -> `mpi.stop()` on seeded token
batches resident on the device; one step queued behind the one that runs; the
rate from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check before the optimizer's state exists,
compilation and warm-up in set-up) on a mesh of MORE THAN ONE device, the
first token runner that builds one: the traffic file's `mesh` names an `ep`
axis, every chip holds its share of each layer's experts and its rows of the
batch (`llama.batch_spec`), and the step exchanges the routed units over the
axis.  The model is a stack of window and full softmax layers with sparse
experts in every layer and no shared one: Mellum2-12B-A2.5B.  It builds
`llama.Config` from the configuration file, so a program that lacks a field
fails at once (`TypeError`), and `_model` asks for `llama.batch_spec` and
`llama.ep_pass_rows` first, so a program without the exchange fails there
(`AttributeError`), before a device is touched.

Taken from the runners that have them, through `harness.load_module`:
`_optimizer` (`step_tokens_adamw.py`); `instruction_scopes`, `change_norms`
and `step_differences` (`step_tokens_looped.py`); `SCOPES`
(`step_tokens_hybrid.py`); `self_ms` and `kernel_instructions`
(`step_tokens_latent.py`); `band_rows_wrong` (`step_tokens_mixed.py`).
Written here: `_model`, `reduced` and `check_reduced`, `exchange_ms`,
`delivered_counters` and the loop of `run`.

How it rehearses: `--rehearse` with
`XLA_FLAGS=--xla_force_host_platform_device_count=4` gives the CPU backend
four devices; the mesh, the shardings, the exchange and every check below run
as on the chip, at the files' `rehearse` sizes.

`correct` compares what the window drives (`ctx.counters["reference_check"]`
holds all of it; the limits and why are in `reference/<config>.py`):

* before the optimizer's state exists, `compare.check`'s four differences on
  the configuration's `check_sample`, whose rows are a multiple of the chips
  (a row on every chip, so the compared step crossed the exchange): the
  system on the mesh against the plain reference on ONE device, each reduced
  where it ran (`reduced`, `check_reduced`): the loss, the logits of every
  `logit_stride`-th row, every leaf's gradient norm;
* still before it, `band_rows_wrong` on one chip: one sliding layer alone on
  `band_seq_len` tokens and on the same with two tokens changed; the rows
  whose logits changed, to the bit, against the reference's;
* in EVERY timed step, the units the exchange delivered, counted in its
  passes where the rows move (a sender the rows its gather filled, a receiver
  the rows its experts ran, a block the lesser; the step's fourth result,
  read after the window) against `k x tokens x layers`, the routers' count:
  `moe_units_dropped`, 0 or the run is not correct.  A pass too few or a mask
  that leaves a row out reads above 0
  (`benchmark/tests/test_mellum2.py::test_a_pass_too_few_is_not_correct`);
* after the window: the timed executable once more, from the seeded weights
  and a new optimizer state on the first timed batch: its loss against the
  reference's on that whole batch on one device, the norm of every leaf's
  change against AdamW's first step as the reference writes it on the
  gradient the step itself took (`mu / (1 - b1)`), and the program's routed
  units an expert against the reference's (`routing_l1_max`).

What it leaves in `ctx.counters` beside what `step_tokens` leaves:

* `expert_unit_counts`: one row a router, all 64 experts, on the first timed
  batch (`llama.expert_unit_counts` on the mesh); `kernel_calls`;
  `flash_blocks` (as the mixed runner); `ep_pass_rows`;
* from every timed step's `delivered` (ep, ep): `moe_units_dropped` (a list, a
  step each), `moe_rank_max_load` (the fullest rank's units over the mean,
  the largest over the steps), `moe_exchange_rows` (units a chip sent to
  other chips in a layer's exchange, mean over chips, layers and steps) and
  `moe_pair_max_load` (the fullest pair of ranks over the uniform share,
  which with `ep_pass_rows` says how many passes an exchange took);
* with `--trace 1`, joins of the one capture with the executable's text:
  `scope_ms` (the innermost of `SCOPES` an instruction carries,
  `moe.exchange` and `swa` first), `attn_scope_ms`, `swa_flash_kernel_ms`,
  `full_flash_kernel_ms` as the mixed runner, and `exchange_ms`: the time a
  step in which an instruction under `moe.exchange` was under way on a device
  (synchronous ones on the TensorCore's line, asynchronous ones from start to
  done) and the part of it during which no other operation ran there.  Where
  no event joins, that is logged and nothing is left, so the readers return
  `None`, never zero.
"""

import os
import re
import time

import numpy as np

SCOPES_FIRST = ("moe.exchange", "swa")


def _model(cfg):
    """`llama.Config` from the configuration file: the first
    `num_hidden_layers` entries of its per-layer lists are the layers that
    run."""
    from torchmpi_tpu.models import llama

    # A program without the exchange stops here, before a device is touched.
    llama.batch_spec, llama.ep_pass_rows
    for name, want in (("tie_word_embeddings", False),
                       ("attention_bias", False), ("hidden_act", "silu"),
                       ("use_sliding_window", True)):
        if cfg[name] != want:
            raise ValueError(f"{name} = {cfg[name]!r} is not implemented (the "
                             f"program has {want!r})")
    n = cfg["num_hidden_layers"]
    kinds = llama.window_layer_kinds(cfg["layer_types"][:n],
                                     cfg["mlp_layer_types"][:n])
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") or any(
            "partial_rotary_factor" in rope for rope in (full, sliding)):
        raise ValueError("the program rotates the full layers with YaRN and "
                         "the sliding ones unscaled, the whole head in both")
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["max_position_embeddings"], norm_eps=cfg["rms_norm_eps"],
        n_experts=cfg["num_experts"], expert_top_k=cfg["num_experts_per_tok"],
        capacity_factor=None, moe_aux_coef=0.0,
        moe_renormalize=cfg["norm_topk_prob"],
        swa_window=cfg["sliding_window"], swa_rope_theta=sliding["rope_theta"],
        rope_theta=full["rope_theta"],
        rope_yarn=(full["factor"], full["original_max_position_embeddings"],
                   full["beta_fast"], full["beta_slow"],
                   full["attention_factor"]),
        layer_kinds=kinds)


def reduced(fn, params, sample, keep_axes):
    """One side of `compare.check`, where its arguments live (the system on
    the mesh its parameters are sharded over, the reference on the one device
    that holds the whole model): `fn(params, sample)` reduced in one jitted
    program to the loss, the logits and every leaf's norms
    (`compare.leaf_norms`), as `compare.py` reduces them, on the host."""
    import jax
    import jax.numpy as jnp

    import compare

    def program(p, s):
        loss, logits, grads = fn(p, s)
        return (loss.astype(jnp.float32), logits.astype(jnp.float32),
                compare.leaf_norms(grads, keep_axes))

    return jax.device_get(jax.jit(program)(params, sample))


def check_reduced(system_side, reference_side, tolerance, keep_axes):
    """`compare.check`'s differences of two sides already `reduced`: it is
    handed the reduced values as its parameters and functions that pick a
    side.  A norm's norm is the norm, and a dictionary keyed by a leaf's path
    names the leaf as the tree did, so its formulas see what they always
    see."""
    import compare

    return compare.check(lambda sides, _: sides[0], lambda sides, _: sides[1],
                         (system_side, reference_side), None, tolerance,
                         keep_axes)


def exchange_ms(trace, scopes, trace_reduce):
    """(ms a step under way, ms of it with nothing else running) of the
    instructions that `scopes` ({instruction name: scope}) puts under
    `moe.exchange`, over the whole steps of a capture, mean over its devices;
    None where no event of the capture is one of them."""
    name_of = lambda text: (re.match(r"%?([\w.\-]+)", text) or [None, text])[1]
    under = lambda text: scopes.get(name_of(text)) == "moe.exchange"
    found = []
    for lines in trace["devices"].values():
        steps = trace_reduce.whole_steps(
            lines.get(trace_reduce.MODULES_LINE, []))
        if steps is None:
            continue
        t0, t1, n = steps
        clip = lambda line: [(name, max(s, t0), min(s + d, t1))
                             for name, s, d in lines.get(line, [])
                             if s < t1 and s + d > t0]
        ops = clip(trace_reduce.OPS_LINE)
        mine = trace_reduce.union(
            [(s, e) for name, s, e in ops + clip(trace_reduce.ASYNC_LINE)
             if under(name)])
        if not mine:
            continue
        others = trace_reduce.union(
            (s, e) for name, s, e in ops if not under(name)
            and not name.lstrip("%").startswith(("while", "conditional")))
        found.append((trace_reduce.total(mine) / n / 1e6,
                      trace_reduce.total(trace_reduce.subtract(mine, others))
                      / n / 1e6))
    if not found:
        return None
    return tuple(sum(part) / len(found) for part in zip(*found))


def delivered_counters(delivered, units_a_layer, layers):
    """The counters of the timed steps' exchanges from `delivered` (steps, ep,
    ep): [r, s] the units of rank s that reached the experts of rank r and ran
    there, counted in the passes and summed over the step's `layers` expert
    layers."""
    delivered = np.asarray(delivered, np.int64)
    ep = delivered.shape[-1]
    by_rank = delivered.sum(axis=2)
    away = (delivered.sum(axis=(1, 2))
            - np.trace(delivered, axis1=1, axis2=2))
    return {
        "moe_units_dropped": (layers * units_a_layer
                              - delivered.sum(axis=(1, 2))).tolist(),
        "moe_rank_max_load": float(np.max(
            by_rank.max(axis=1) / by_rank.mean(axis=1))),
        "moe_pair_max_load": float(np.max(
            delivered.max(axis=(1, 2)) / (layers * units_a_layer / ep ** 2))),
        "moe_exchange_rows": float(np.mean(away) / (ep * layers)),
    }


# ------------------------------------------------------------------ the run

def run(ctx):
    cfg, mix, how = ctx.cfg, ctx.traffic, ctx.cfg["run"]
    model = _model(cfg)         # a program without the fields stops here

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.ops.flash_attention import blocks_met
    from torchmpi_tpu.parallel import make_mesh
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import harness
    import trace_reduce
    import traffic as traffic_mod

    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    latent = harness.load_module("runners", "step_tokens_latent")
    mixed = harness.load_module("runners", "step_tokens_mixed")
    devices = jax.devices()[:ctx.chips]
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])
    chunk = how["loss_chunk"]
    check = cfg["check_sample"]
    ep = mix["mesh"]["ep"]
    if check["batch"] % len(devices) or check["seq_len"] < 2 * chunk:
        raise ValueError("the check sample has a row on every chip and at "
                         "least two chunks of the head, or it does not drive "
                         "what the window drives")
    if check["seq_len"] < 2 * cfg["sliding_window"]:
        raise ValueError("the check sample is at least two windows deep, or "
                         "the sliding layers' band is the whole triangle")

    mpi.start(devices=devices)
    mesh = make_mesh(mix["mesh"], devices=devices)
    one_chip = make_mesh({"dp": 1}, devices=devices[:1])
    # The whole model from the seed on the first device, which is what the
    # plain reference is given, then each chip's share of it.
    init = jax.jit(lambda key: llama.init(key, model, dtype=dtype))
    with ctx.compiling("seeded weights"):
        whole = jax.block_until_ready(init(jax.random.PRNGKey(ctx.seed)))

    # (a) the system on the mesh against the plain reference on one device,
    # before the optimizer's state takes its share of the memory; the
    # reference first, while the first device holds nothing but the model.
    reference = ctx.module("reference")
    batch_sharding = NamedSharding(mesh, llama.batch_spec(model, mesh))
    sample = traffic_mod.tokens(mix, cfg, ctx.seed + 1, n_batches=1,
                                batch=check["batch"],
                                seq_len=check["seq_len"])[0]
    with ctx.compiling("reference check, the reference on one device"):
        reference_side = reduced(
            lambda p, s: reference.loss_and_grads(cfg, p, s), whole,
            jax.device_put(sample, devices[0]), reference.LEAF_AXES)
    with ctx.compiling("each chip's share of the weights"):
        params = llama.shard_params(whole, mesh, model)
        jax.block_until_ready(params)
    del whole
    sample = tuple(jax.device_put(a, batch_sharding) for a in sample)
    grad_fn = jax.value_and_grad(
        llama.make_loss_fn(model, mesh, loss_chunk=chunk, **kinds))
    stride = check.get("logit_stride", 1)

    def system(p, s):
        loss, grads = grad_fn(p, s)
        h = llama.apply(model, p, s[0], mesh=mesh, return_hidden=True, **kinds)
        return loss, (h[:, ::stride] @ p["head"]).astype(jnp.float32), grads

    with ctx.compiling("reference check, the system on the mesh"):
        found = check_reduced(
            reduced(system, params, sample, reference.LEAF_AXES),
            reference_side, reference.TOLERANCE, reference.LEAF_AXES)
    del reference_side

    with ctx.compiling("band probe"):
        found["band_rows_wrong"] = mixed.band_rows_wrong(
            model, cfg, reference, one_chip, kinds, ctx.seed, dtype,
            check["band_seq_len"])

    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    units_a_layer = model.expert_top_k * tokens_per_step
    layers = sum(ffn == "moe" for _, ffn in model.layer_kinds)
    with ctx.compiling("expert unit counts"):
        counts = np.asarray(jax.jit(lambda p, t: llama.expert_unit_counts(
            model, p, t, mesh=mesh, attn=how["attn"]))(params, batches[0][0]))
    ctx.counters["expert_unit_counts"] = counts.tolist()
    ctx.counters["ep_pass_rows"] = llama.ep_pass_rows(
        model, tokens_per_step // len(devices), ep)
    ctx.counters["flash_blocks"] = {
        "swa": blocks_met(mix["seq_len"], model.swa_window),
        "full": blocks_met(mix["seq_len"])}
    ctx.mark(f"flash blocks: {ctx.counters['flash_blocks']}; a pass of the "
             f"exchange: {ctx.counters['ep_pass_rows']} rows a peer")

    optimizer = harness.load_module(
        "runners", "step_tokens_adamw")._optimizer(how["optimizer"])
    # The moments are born where their weights live: zeros follow no input's
    # sharding, under `jit` or outside it, and 64 experts' float32 moments on
    # every chip are 17 GB.
    new_state = jax.jit(
        optimizer.init, out_shardings=optax.tree_utils.tree_map_params(
            optimizer, lambda _, weight: weight.sharding,
            jax.eval_shape(optimizer.init, params), params,
            transform_non_params=lambda _: NamedSharding(
                mesh, jax.sharding.PartitionSpec())))
    opt_state = new_state(params)
    ctx.mark("optimizer state: " + ", ".join(sorted({
        f"{a.dtype} {a.sharding.spec}" for a in jax.tree.leaves(opt_state)
        if a.ndim})))
    step = llama.make_train_step(model, mesh, optimizer=optimizer,
                                 loss_chunk=chunk, with_delivered=True,
                                 **kinds)
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
        params, opt_state, loss, _ = compiled(params, opt_state, *batches[0])
        jax.block_until_ready(loss)
        warm.append(time.perf_counter() - t0)
    ctx.mark(f"warmed up, {len(warm)} fenced steps")

    trace_at = trace_end = None
    if ctx.trace:
        trace_at = mix["trace"]["after_steps"]
        trace_end = trace_at + mix["trace"]["steps"]
    losses, delivered, done = [], [], []    # done[i]: step i seen finished
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            n = len(losses)
            if n == trace_at:
                ctx.start_trace()
            with ctx.span("bench.step_call"):
                params, opt_state, loss, units = compiled(
                    params, opt_state, *batches[n % len(batches)])
            losses.append(loss)
            delivered.append(units)
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
    delivered = np.asarray(jax.device_get(delivered))
    ctx.counters.update(delivered_counters(delivered, units_a_layer, layers))
    by_rank = delivered.sum(axis=2)
    ctx.mark(f"the exchange: dropped {ctx.counters['moe_units_dropped']}; "
             f"fullest rank over the mean, a step: "
             f"{np.round(by_rank.max(axis=1) / by_rank.mean(axis=1), 3).tolist()}"
             f"; fullest pair over the uniform share "
             f"{ctx.counters['moe_pair_max_load']:.3f}; "
             f"{ctx.counters['moe_exchange_rows']:.0f} units a chip sent away "
             f"in a layer's exchange")

    if ctx.trace:
        ctx.stop_trace()        # a window shorter than the traced steps
        capture = trace_reduce.newest_xplane(ctx.trace_dir)
        loaded = trace_reduce.load(capture) if capture else None
        scopes = looped.instruction_scopes(hlo, SCOPES_FIRST + hybrid.SCOPES)
        swa = latent.kernel_instructions(hlo, "swa")
        full = {name: kernel for name, kernel in
                latent.kernel_instructions(hlo, "attn").items()
                if name not in swa}
        for counter, labels in (
                ("scope_ms", scopes),
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
        # No metric lists this cell for `collective_ms` (it moves the image
        # cells' rate): what that reader's list of names finds here is
        # logged.  On the chip that is the gradients' all-reduces alone: the
        # exchange's instructions are named `all_to_all.N` there, which the
        # list does not match.
        by_name = trace_reduce.reduce(loaded) if loaded else None
        if by_name:
            ctx.mark("collectives by instruction name, ms a step: under way "
                     f"{1e3 * by_name['collective_s'] / by_name['steps']:.3f}"
                     ", no compute beside them "
                     f"{1e3 * by_name['collective_exposed_s'] / by_name['steps']:.3f}"
                     f"; {ctx.counters['collective_calls']} instructions, "
                     f"{ctx.counters['collective_bytes'] / 1e6:.1f} MB of "
                     "operands as compiled (a loop's body once)")
        under_way = exchange_ms(loaded, scopes, trace_reduce) if loaded else None
        if under_way:
            ctx.counters["exchange_ms"] = {"under_way": under_way[0],
                                           "exposed": under_way[1]}
            ctx.mark(f"the exchange, ms a step: under way {under_way[0]:.3f}, "
                     f"nothing beside it {under_way[1]:.3f}")

    values = np.asarray(jax.device_get(losses), np.float32)
    del params, opt_state, loss, losses, units

    # (b) the timed executable against the reference, with the window closed:
    # one step from the seeded weights on the first timed batch.
    t0 = time.perf_counter()

    def seeded(also=lambda whole: None):
        whole = init(jax.random.PRNGKey(ctx.seed))
        return llama.shard_params(whole, mesh, model), also(whole)

    # The reference on the whole batch first, on the first device, while it
    # holds nothing but the model.
    params, (loss_reference, units_reference) = seeded(
        lambda whole: jax.device_get(jax.jit(
            lambda p, s: reference.loss_only(cfg, p, s, chunk))(
                whole, jax.device_put(batches[0], devices[0]))))
    stepped, opt_state, loss, _ = compiled(params, new_state(params),
                                           *batches[0])
    # The gradient the step took is all that is kept of its state: the second
    # moments go before the seeded model stands on the first device again.
    mu = optax.tree_utils.tree_get(opt_state, "mu")
    del opt_state
    params, _ = seeded()        # the step took the others for its own
    # The reference's stepped weights are a program's result of their own
    # (PR 30: taken in the program that makes them, the norm is of a step no
    # weight's type holds), from the gradient the step itself took.
    wanted = jax.jit(lambda p, mu: reference.adamw_first_step(
        p, jax.tree.map(lambda m: m / (1 - how["optimizer"]["b1"]), mu),
        how["optimizer"]))(params, mu)
    del mu
    axes = reference.LEAF_AXES
    changed, changed_reference = jax.jit(lambda p, p1, p2: (
        looped.change_norms(p1, p, axes), looped.change_norms(p2, p, axes)))(
            params, stepped, wanted)
    found.update(looped.step_differences(
        float(loss), float(loss_reference), jax.device_get(changed),
        jax.device_get(changed_reference)))
    # The program's routers (four ranks, each on its own tokens) against the
    # reference's on that batch, at the seeded weights: the units that go to
    # another expert, of a layer's k * T.
    found["routing_l1_max"] = float(np.max(np.sum(np.abs(
        counts - np.asarray(units_reference)), axis=1))
        / (2 * units_a_layer))
    found["moe_units_dropped"] = int(np.max(np.abs(
        ctx.counters["moe_units_dropped"])))
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
