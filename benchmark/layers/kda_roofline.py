"""`kda_roofline` (kernels): the least time the chip could take for the KDA
recurrence a step requires, over `kda_ms`.  The least time is the larger of
required FLOPs / peak FLOP/s and required bytes / peak HBM bytes/s
(`flops/<config>.py:kda_required`: the chunked form's products forward and
both gradients, its inputs, outputs, gradients and chunk-entry states moved
once in each direction).  Bytes bound it: the recurrence's products are small
(64 x 128 x 128) and its state is float32.  `None` where `kda_ms` is, or the
configuration's flops file has no such function."""


def read(obs):
    ms = (obs["counters"].get("scope_ms") or {}).get("kda")
    required = getattr(obs["flops"], "kda_required", None)
    if not ms or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
