"""`mla_flash_roofline` (kernels): the least time the chip could take for the
causal scores the latent layers of a step require, over `mla_flash_ms`.  The
least time is the larger of required FLOPs / peak FLOP/s and required bytes /
peak HBM bytes/s (`flops/<config>.py:scores_required`: QK^T and PV forward,
the four gradient products backward, nothing recomputed: the backward kernel
forms the scores a second time, which is executed and not required).  FLOPs
bound it.  `None` where `mla_flash_ms` is, or the configuration's flops file
has no such function."""



def read(obs):
    by_kernel = obs["counters"].get("mla_flash_kernel_ms") or {}
    ms = sum(v for k, v in by_kernel.items() if k != "unnamed")
    required = getattr(obs["flops"], "scores_required", None)
    if not ms or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
