"""`collective_ms` (collectives): time a step in which a collective
operation was under way on a device (union of their intervals in the
trace, asynchronous ones from start to done), mean over the devices."""


def read(obs):
    t = obs["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["collective_s"] / t["steps"]
