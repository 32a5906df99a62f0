"""`flash_ms` (kernels): time a step in Mosaic kernels, from the trace.  In
these cells every Mosaic kernel is a flash-attention kernel (forward, dQ,
dK/dV, and the forward again under `remat="dots"`)."""


def read(obs):
    t = obs["trace"]
    if not t or not t["steps"] or not t["mosaic_s"]:
        return None
    return 1e3 * t["mosaic_s"] / t["steps"]
