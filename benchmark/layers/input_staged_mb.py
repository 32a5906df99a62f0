"""`input_staged_mb` (input): bytes the input pipeline put on the devices, a
step: `StageStats.staged_bytes` / batches."""


def read(obs):
    s = obs["counters"].get("stage_stats")
    if not s or not s["batches"]:
        return None
    return s["staged_bytes"] / s["batches"] / 1e6
