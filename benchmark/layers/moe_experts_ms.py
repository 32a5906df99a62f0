"""`moe_experts_ms` (kernels): device self time a step under `moe.experts`:
the grouped matmuls of gate, up and down with the SwiGLU between them,
forward, both gradients and the recomputed forward, from the runner's join
(`runners/step_tokens_adamw.py:scope_ms`).  `None` where it found nothing."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("moe.experts")
