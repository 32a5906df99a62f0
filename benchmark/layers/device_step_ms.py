"""`device_step_ms` (model step): time in which an operation ran on the
device, a step: the busy union of the trace over the step programs it
holds, mean over the devices."""


def read(obs):
    t = obs["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["busy_s"] / t["steps"]
