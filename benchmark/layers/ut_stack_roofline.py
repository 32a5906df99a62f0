"""`ut_stack_roofline` (kernels): the least time the chip could take for the
looped stack's products and scores a step requires, over `ut_stack_ms`.  The
least time is the larger of required FLOPs / peak FLOP/s and required bytes /
peak HBM bytes/s (`flops/<config>.py:stack_required`: every layer application
of every recurrent step, forward and both gradients, nothing recomputed);
FLOPs bound it.  What recomputation and the loop's copies cost shows here."""

import harness


def read(obs):
    stack_ms = harness.load_module("layers", "ut_stack_ms").read(obs)
    if stack_ms is None or not obs["peaks"]:
        return None
    flops, nbytes = obs["flops"].stack_required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (stack_ms / 1e3)
