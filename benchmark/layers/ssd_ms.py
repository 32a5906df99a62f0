"""`ssd_ms` (kernels): device self time a step under the `ssd` scope: the
chunked state-space scan of every two-branch layer alone
(`torchmpi_tpu/ops/ssd.py`: the decay sums, `C B^T` under the decay
differences, its product with `dt * x`, the chunk-entry states carried chunk
to chunk, `C S` for what entered, the skip; forward and the hand-written
backward), without the projection, convolution and gated norm round it, which
are `ssm_ms`'s.  From the runner's join (`runners/step_tokens_ssm.py`);
`None` where it found nothing, or the program has no such scope."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("ssd")
