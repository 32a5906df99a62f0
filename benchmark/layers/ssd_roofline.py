"""`ssd_roofline` (kernels): the least time the chip could take for the
state-space scans a step requires, over `ssd_ms`.  The least time is the
larger of required FLOPs / peak FLOP/s and required bytes / peak HBM bytes/s
(`flops/<config>.py:ssd_required`: the chunked form's products forward and
both gradients, the causal half of the chunk-local ones; inputs, outputs,
gradients and chunk-entry states moved once in each direction, whatever
implements the scan).  Bytes bound it: the products are small (128 x 128 x
256) and the state is float32.  `None` where `ssd_ms` is, or the
configuration's flops file has no such function."""


def read(obs):
    ms = (obs["counters"].get("scope_ms") or {}).get("ssd")
    required = getattr(obs["flops"], "ssd_required", None)
    if not ms or not obs["peaks"] or required is None:
        return None
    flops, nbytes = required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
