"""`kda_ms` (kernels): device self time a step under the `kda` scope: the
chunked gated-delta-rule recurrence of every KDA layer alone
(`torchmpi_tpu/ops/kda.py`: the chunk-local products, the scan over the
chunks, forward and backward), without the projections, convolutions and
gates round it, which are `attn`'s.  From the runner's join
(`runners/step_tokens_hybrid.py:scope_ms`); `None` where it found nothing, or
the program has no such scope."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("kda")
