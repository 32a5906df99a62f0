"""`moe_experts_roofline` (kernels): the least time the chip could take for
the expert products a step requires, over `moe_experts_ms`: the grouped
matmul's share of its roofline, whatever form it took.  The least time is the
larger of required FLOPs / peak FLOP/s and required bytes / peak HBM bytes/s
(`flops/<config>.py:experts_required`: the k experts a token is routed to,
forward and both gradients, nothing recomputed, no row padded to a tile);
FLOPs bound it."""


def read(obs):
    ms = (obs["counters"].get("scope_ms") or {}).get("moe.experts")
    if not ms or not obs["peaks"]:
        return None
    flops, nbytes = obs["flops"].experts_required(obs["cfg"], obs["traffic"])
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
