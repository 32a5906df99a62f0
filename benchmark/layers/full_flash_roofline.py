"""`full_flash_roofline` (kernels): the least time the chip could take for the
causal scores the full layers of a step require, over `full_flash_ms`.  The
least time is the larger of required FLOPs / peak FLOP/s and required bytes /
peak HBM bytes/s (`flops/<config>.py:full_scores_required`: the causal
triangle, QK^T and PV forward, the four gradient products backward, nothing
recomputed); FLOPs bound it by far.  `None` where `full_flash_ms` is, or the
configuration's flops file has no such function."""

import harness


def read(obs):
    ms = harness.load_module("layers", "full_flash_ms").read(obs)
    required = getattr(obs["flops"], "full_scores_required", None)
    if ms is None or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
