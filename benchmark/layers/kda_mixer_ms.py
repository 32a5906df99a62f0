"""`kda_mixer_ms` (kernels): device self time a step under the `attn` scope
outside `kda` and `mla`: in a stack with KDA layers, those layers round their
recurrence: the q, k, v, decay, gate and output projections, and the passes
between them and the recurrence (short convolutions, SiLU, a head's L2 norm,
the decay, the output's norm a head and its gate: since PR 35 the kernels
`kda_pre`, `kda_post` and their backward kernels,
`torchmpi_tpu/ops/kda_mixer.py`), forward, backward and recomputed alike.
`kda_ms` beside it is the recurrence alone.  From the runner's join
(`runners/step_tokens_hybrid.py:scope_ms`, inner scopes first, so what stands
under `attn/kda` or `mla` is not counted here); `None` where it found
nothing, or the program has no such scope."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("attn")
