"""The part of a `step_tokens*` runner's `run` that does not know the model:
compiling the step and counting what its text holds, the warm-up, the timed
window with its capture (one step queued behind the one that runs; the rate
from the median interval between completions, `harness.median_step_s`), the
joins of the capture with the executable's names, and the result.  A runner
loads it through `harness.load_module("runners", "token_loop")` and keeps
its model, its optimizer state and its checks to itself: the step is handed
over as `one_step(n) -> loss`, a closure over whatever state it carries.

The runners older than this file each hold a copy of these lines in their
`run` (they are the accepted benchmark's, a `benchmark` issue's to fold in);
`step_tokens_ssm.py` was the first written on it.
"""

import os
import time

import numpy as np


def compile_step(ctx, step, *args):
    """-> (the executable of `step` for `args`, its text); counted under
    "train step", with `kernel_calls`, `program_bytes`, `collective_calls`
    and `collective_bytes` left in `ctx.counters` and, with
    `BENCHMARK_KEEP_TRACE`, the text beside the capture the harness keeps."""
    from torchmpi_tpu.runtime.topology import hlo_collective_stats

    import harness

    with ctx.compiling("train step"):
        compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    keep = os.environ.get("BENCHMARK_KEEP_TRACE")
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, ctx.cell["name"] + ".hlo.txt"), "w") as fh:
            fh.write(hlo)
    ctx.counters["kernel_calls"] = hlo.count("tpu_custom_call")
    ctx.counters["program_bytes"] = harness.program_bytes(compiled)
    stats = hlo_collective_stats(hlo)
    ctx.counters["collective_calls"] = stats["total"]
    ctx.counters["collective_bytes"] = sum(stats["operand_bytes"].values())
    return compiled, hlo


def warm_up(one_step):
    """Fenced steps until `harness.warmed_up`; -> how many."""
    import jax

    import harness

    warm = []
    while not harness.warmed_up(warm) and len(warm) < harness.WARM_UP_MAX_STEPS:
        t0 = time.perf_counter()
        jax.block_until_ready(one_step(0))
        warm.append(time.perf_counter() - t0)
    return len(warm)


def window(ctx, mix, one_step, tokens_per_step, state=lambda: ()):
    """Whole steps until `ctx.seconds` are over, step n through
    `one_step(n)`; with `ctx.trace`, the capture round the steps that
    `mix["trace"]` names.  `state()` is what the last step leaves beside its
    loss, fenced with it.  -> (the losses as float32, seconds a step, the
    window's seconds)."""
    import jax

    import harness

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
                loss = one_step(n)
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
        jax.block_until_ready((loss, state()))
        done.append(time.perf_counter())
        window_s = done[-1] - t0
    if ctx.trace:
        ctx.stop_trace()        # a window shorter than the traced steps

    intervals = np.diff(done)
    step_s = harness.median_step_s(done) or window_s / len(losses)
    ctx.mark(f"step intervals: median {1e3 * step_s:.3f} ms, min "
             f"{1e3 * intervals.min(initial=step_s):.3f}, max "
             f"{1e3 * intervals.max(initial=step_s):.3f}, "
             f"{int(np.sum(intervals > 1.01 * step_s))} of {len(intervals)} "
             f"over 1.01 medians; whole window "
             f"{len(losses) * tokens_per_step / window_s:.1f} tokens/s; each, "
             f"ms: {[round(1e3 * float(x)) for x in intervals]}")
    return np.asarray(jax.device_get(losses), np.float32), step_s, window_s


def join(ctx, self_ms, joins):
    """The joins of the run's one capture: `joins` is {counter: {instruction:
    label}}, `self_ms(loaded capture, labels, trace_reduce)` the device's
    self ms a step by label (`step_tokens_latent.py` has it).  Where no event
    joins, that is logged and nothing is left, so the readers return `None`,
    never zero."""
    import harness
    import trace_reduce

    capture = trace_reduce.newest_xplane(ctx.trace_dir)
    loaded = trace_reduce.load(capture) if capture else None
    for counter, labels in joins.items():
        joined = self_ms(loaded, labels, trace_reduce) if loaded else {}
        if joined:
            ctx.counters[counter] = joined
            ctx.mark(f"device self ms a step, {counter}: " + ", ".join(
                f"{k} {v:.3f}" for k, v in joined.items()))
        else:
            harness.log(f"NO EVENT OF THE CAPTURE JOINS {counter}: the "
                        "executable carries no such names (loaded from a "
                        "compile cache written before they existed?) or "
                        "there is no capture; the metrics read from it are "
                        "left out")


def result(ctx, losses, tokens_per_step, step_s, window_s, devices):
    """What `run` hands back to the harness."""
    return {
        "samples_per_s": tokens_per_step / step_s,
        "window_s": window_s,
        "attempted": len(losses),
        "failed": int(np.sum(~np.isfinite(losses))),
        "first_loss": float(losses[0]), "last_loss": float(losses[-1]),
        "program_bytes": ctx.counters["program_bytes"],
        "devices": devices,
    }
