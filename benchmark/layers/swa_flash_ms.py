"""`swa_flash_ms` (kernels): device time a step in the Mosaic flash kernels
under the `swa` scope: `flash_fwd` and `flash_bwd` of every sliding-window
layer (and `flash_bwd_dq`, `flash_bwd_dkv` where the backward streams), which
run the blocks of the band alone.  A kernel that masked the window and
skipped nothing would read 72/48 of `full_flash_ms` a layer.  From the
runner's join of the capture with the executable's kernel calls by name
(`runners/step_tokens_mixed.py`); `None` where it found none."""


def read(obs):
    by_kernel = obs["counters"].get("swa_flash_kernel_ms") or {}
    ms = sum(v for k, v in by_kernel.items() if k != "unnamed")
    return ms or None
