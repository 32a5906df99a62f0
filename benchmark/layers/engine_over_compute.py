"""`engine_over_compute` (engine loop): seconds a step through
`engine.train` on device-resident batches, over seconds a step of the same
compiled program called in a bare loop on the same batches, both in this run
(the arithmetic of `bench.py`).  1.0: the loop adds nothing."""


def read(obs):
    c = obs["counters"]
    if not c.get("engine_step_s") or not c.get("bare_step_s"):
        return None
    return c["engine_step_s"] / c["bare_step_s"]
