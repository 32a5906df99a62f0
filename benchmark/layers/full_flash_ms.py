"""`full_flash_ms` (kernels): device time a step in the Mosaic flash kernels
under `attn` and outside `swa`: `flash_fwd` and `flash_bwd` of every full
(causal) softmax layer of a stack that has window layers beside them, none of
the grouped matmuls' kernels, which `flash_ms` would count too.  From the
runner's join of the capture with the executable's kernel calls by name
(`runners/step_tokens_mixed.py`); `None` where it found none."""


def read(obs):
    by_kernel = obs["counters"].get("full_flash_kernel_ms") or {}
    ms = sum(v for k, v in by_kernel.items() if k != "unnamed")
    return ms or None
