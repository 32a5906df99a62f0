"""`device_idle_share` (device): 1 - busy union / window of the trace, mean
over the devices."""


def read(obs):
    t = obs["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
