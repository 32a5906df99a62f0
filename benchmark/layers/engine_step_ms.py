"""`engine_step_ms` (engine loop): the median interval between the moments
the engine's host loop saw consecutive steps finished, where its in-flight
bound blocks on the loss of the step eight back: `step_ms_p50` of
`RunRecord.summary()` (`torchmpi_tpu/engine/sgdengine.py`).  The program
keeps a record of every `engine.train()` call, always, and
`sgdengine.runs()` hands back the newest.  The window's is the one that ran
longest past its first dispatch: the streamed window is 6 s of a traced run
and 10 s of another, against 1.5 s on resident batches and under a second
of warm-up, and a call that compiles does so inside its first dispatch.
(Not the one with the most steps: on four chips the profiler's start and
stop leave a traced window 39 steps, fewer than the 40 resident ones.)
Beside `device_step_ms` it says what the host adds to a step; a few slow
steps do not move it.  `None` under 20 intervals, or where the program keeps
no record."""


def read(obs):
    from torchmpi_tpu.engine import sgdengine

    runs = [r for r in getattr(sgdengine, "runs", list)()  # none: no record
            if r.t_first_dispatch and r.t_return]
    if not runs:
        return None
    window = max(runs, key=lambda r: r.t_return - r.t_first_dispatch)
    return window.summary()["step_ms_p50"]
