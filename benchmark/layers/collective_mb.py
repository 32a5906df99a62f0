"""`collective_mb` (collectives): operand bytes of the collective
instructions in the step as compiled, a step and a device
(`runtime/topology.py:hlo_collective_stats` on the executable that ran)."""


def read(obs):
    c = obs["counters"]
    if not c.get("collective_calls"):
        return None
    return c["collective_bytes"] / 1e6
