"""`engine_host_ms` (engine loop): the engine's own host time a step, measured
where it is spent: from the top of the loop body to the iteration's last
controller, less the time blocked on the in-flight bound and the time in
the user's hooks; `host_ms_p50` of the record the engine keeps of the
streamed window's `train()` call (see `engine_step_ms.py` for which
record).  Staging an already staged batch, the dispatch of the compiled
step and the loop's bookkeeping are in it; the wait for input is not.  The
host runs eight steps ahead, so this reaches the device only where it
exceeds a step."""


def read(obs):
    from torchmpi_tpu.engine import sgdengine

    runs = [r for r in getattr(sgdengine, "runs", list)()  # none: no record
            if r.t_first_dispatch and r.t_return]
    if not runs:
        return None
    window = max(runs, key=lambda r: r.t_return - r.t_first_dispatch)
    return window.summary()["host_ms_p50"]
