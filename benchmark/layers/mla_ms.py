"""`mla_ms` (kernels): device self time a step under the `mla` scope: the
whole latent-attention mixer of every such layer, its projections, the
latent's norm and the flash kernels over keys of 192 and values of 128,
forward and backward.  It stands where `flash_ms` stands in other cells: that
reader counts every Mosaic kernel, and here the grouped matmuls are Mosaic
kernels too.  From the runner's join
(`runners/step_tokens_hybrid.py:scope_ms`); `None` where it found nothing, or
the program has no such scope."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("mla")
