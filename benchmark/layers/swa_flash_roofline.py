"""`swa_flash_roofline` (kernels): the least time the chip could take for the
band of scores the sliding layers of a step require, over `swa_flash_ms`.  The
least time is the larger of required FLOPs / peak FLOP/s and required bytes /
peak HBM bytes/s (`flops/<config>.py:window_scores_required`: min(i + 1,
window) keys a row, QK^T and PV forward, the four gradient products backward,
nothing recomputed, no edge block counted whole); FLOPs bound it.  The kernels
execute whole blocks at the band's two edges and form the scores again in the
backward pass, so this reads low by exactly that: it is what a smaller or
smarter tile would win.  `None` where `swa_flash_ms` is, or the configuration's
flops file has no such function."""

import harness


def read(obs):
    ms = harness.load_module("layers", "swa_flash_ms").read(obs)
    required = getattr(obs["flops"], "window_scores_required", None)
    if ms is None or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
