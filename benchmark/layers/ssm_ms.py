"""`ssm_ms` (model step): device self time a step under the `ssm` scope
outside `ssd`: a two-branch layer's state-space branch round its scan: the one
projection to [gate | x | B | C | dt] and its multipliers, the causal
convolution and SiLU (`ssm.conv`), dt's softplus, the gated norm a group
(`ssm.norm`) and the way out, forward, backward and recomputed alike, all
layers together.  `ssd_ms` beside it is the scan alone.  From the runner's
join (`runners/step_tokens_ssm.py`, inner scopes first, so what stands under
`ssm/ssd` is not counted here); `None` where it found nothing, or the program
has no such scope."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("ssm")
