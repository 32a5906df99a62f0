"""`flash_roofline` (kernels): the least time the chip could take for the
attention a step requires, over `flash_ms`.  The least time is the larger of
required FLOPs / peak FLOP/s and required bytes / peak HBM bytes/s
(`flops/<config>.py:flash_required`: causal, forward 2 products and backward
4, nothing recomputed).  At these lengths FLOPs bound it by far (intensity
over 1,000 FLOP/byte against the chip's 240)."""


def read(obs):
    t = obs["trace"]
    if not t or not t["steps"] or not t["mosaic_s"] or not obs["peaks"]:
        return None
    flops, nbytes = obs["flops"].flash_required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["mosaic_s"] / t["steps"])
