"""`engine_start_ms` (engine loop): from the first line of `engine.train()`
to the return of the first step's call into the compiled program: placing
the parameters, wrapping the source, the input pipeline's start and its
first staged batch, the first dispatch; `start_ms` of the record the engine
keeps of the streamed window's `train()` call (see `engine_step_ms.py` for
which record).  The device runs no step of this call before it ends, and
the cell's rate is steps over the seconds of the whole call."""


def read(obs):
    from torchmpi_tpu.engine import sgdengine

    runs = [r for r in getattr(sgdengine, "runs", list)()  # none: no record
            if r.t_first_dispatch and r.t_return]
    if not runs:
        return None
    window = max(runs, key=lambda r: r.t_return - r.t_first_dispatch)
    return window.summary()["start_ms"]
