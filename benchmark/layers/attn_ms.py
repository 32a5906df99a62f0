"""`attn_ms` (model step): device self time a step of every instruction whose
scope path holds `attn`, the name round a layer's mixer: its norm, the q, k, v
and gate projections, both rotations, the gate (`attn.gate`), the flash
kernels of the sliding (`swa`) and of the full layers, the output projection,
forward, backward and recomputed alike, all layers together.  `swa_flash_ms`
and `full_flash_ms` are parts of it.  From the runner's join of the capture by
the outer name alone (`runners/step_tokens_mixed.py`,
`instruction_scopes(hlo, ("attn",))`); `None` where it found nothing."""


def read(obs):
    return (obs["counters"].get("attn_scope_ms") or {}).get("attn")
