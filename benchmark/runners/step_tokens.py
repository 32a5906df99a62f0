"""Runner `step_tokens`: a decoder language model trained through
`mpi.start()` -> `parallel.make_mesh` -> `llama.make_train_step` ->
`mpi.stop()`, on seeded token batches resident on the device.  Engine and
input pipeline are bypassed: the cell is the model step.

The timed window is a loop over whole steps with one step queued behind the
one that runs, so the device never waits for the host and the loop stops
within a step of the deadline.  The host stamps the moment it sees each step
finished; with the device never idle, the interval between two stamps is one
step on the device.  The rate is the tokens of a step over the MEDIAN of those
intervals (18 in a 10 s window), not the steps over the whole window: the
driver's first check of PR 22 saw windows of this cell 4-6% slow in one set
of six runs where twelve others agreed to 0.002%, and a median over the
window's steps does not move when a few of them are slow.  The whole-window
rate is logged beside it.  Weights, batches, the reference check, compilation
and warm-up are set-up.
"""

import time

import numpy as np


def _model(cfg):
    """`llama.Config` from the configuration file (not from a preset of the
    program, which a later PR may edit)."""
    from torchmpi_tpu.models import llama

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("llama.Config derives head_dim as hidden_size / "
                         "num_attention_heads; the file says otherwise")
    run = cfg["run"]
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], n_experts=cfg["num_local_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        capacity_factor=run["capacity_factor"],
        moe_aux_coef=cfg["router_aux_loss_coef"],
        moe_group_size=run["moe_group_size"])


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

    # (a) the system against the plain reference.
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
    step = llama.make_train_step(model, mesh, lr=how["lr"],
                                 loss_chunk=how["loss_chunk"], **kinds)
    with ctx.compiling("train step"):
        compiled = step.lower(params, None, *batches[0]).compile()
    hlo = compiled.as_text()
    ctx.counters["kernel_calls"] = hlo.count("tpu_custom_call")
    ctx.counters["program_bytes"] = harness.program_bytes(compiled)
    stats = hlo_collective_stats(hlo)
    ctx.counters["collective_calls"] = stats["total"]
    ctx.counters["collective_bytes"] = sum(stats["operand_bytes"].values())

    warm = []
    while not harness.warmed_up(warm) and len(warm) < harness.WARM_UP_MAX_STEPS:
        t0 = time.perf_counter()
        params, _, loss = compiled(params, None, *batches[0])
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
                params, _, loss = compiled(params, None,
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

    values = np.asarray(jax.device_get(losses), np.float32)
    del params
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
