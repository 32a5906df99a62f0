"""`mla_roofline` (kernels): the least time the chip could take for what the
latent-attention layers of a step require, over `mla_ms`.  The least time is
the larger of required FLOPs / peak FLOP/s and required bytes / peak HBM
bytes/s (`flops/<config>.py:mla_required`: the four projections and the
causal scores over keys of 192 and values of 128, forward and both
gradients).  FLOPs bound it: the scores alone are three quarters of them.
`None` where `mla_ms` is, or the configuration's flops file has no such
function."""


def read(obs):
    ms = (obs["counters"].get("scope_ms") or {}).get("mla")
    required = getattr(obs["flops"], "mla_required", None)
    if not ms or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
