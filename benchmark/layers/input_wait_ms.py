"""`input_wait_ms` (input): how long the training loop's thread blocked for
its next batch, a step: `StageStats.wait_s` / batches of the streamed
window, measured by the program's own consumer (`data/device.py`)."""


def read(obs):
    s = obs["counters"].get("stage_stats")
    if not s or not s["batches"]:
        return None
    return 1e3 * s["wait_s"] / s["batches"]
