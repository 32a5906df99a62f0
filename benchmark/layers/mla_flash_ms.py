"""`mla_flash_ms` (kernels): device time a step in the Mosaic flash kernels
under the `mla` scope: `flash_fwd` and `flash_bwd` of every latent layer, a
multi-token-prediction module's among them (and `flash_bwd_dq`,
`flash_bwd_dkv` where the backward streams), none of the grouped matmuls'
kernels, which `flash_ms` would count too.  From the runner's join of the
capture with the executable's kernel calls by name
(`runners/step_tokens_latent.py:kernel_instructions`); `None` where it found
none."""


def read(obs):
    by_kernel = obs["counters"].get("mla_flash_kernel_ms") or {}
    ms = sum(v for k, v in by_kernel.items() if k != "unnamed")
    return ms or None
