"""`trace_lower_s` (entry): seconds the process spent tracing Python functions
to jaxprs and lowering jaxprs to MLIR, `trace_s + lower_s` of the program's
start-up account (`torchmpi_tpu/_startup.py`, reached as `mpi.startup()`;
`docs/observability.md`, "The start-up account"): Python work that no compile
cache holds, paid again by every process; a Pallas kernel's body is lowered
to Mosaic here.  Each row's OWN time is summed (a jit traced inside another's
trace counts once), so this and `backend_compile_s` add up to time spent.
The sum is the whole process's, not the set-up's alone: the program sees no
timed window in a token cell (the runner calls an AOT executable), and the
Ouro and Kimi runners make a few programs of the benchmark's own after the
window, for the timed step's check against the reference (PR 34 saw 4 of the
Ouro cell's 11 programs and 3 of the Kimi cell's 11 there;
`mpi.startup().summary(until_ns)` leaves them out).  `None` where the program
keeps no account (a parent of PR 34)."""


def read(obs):
    import sys

    mpi = sys.modules.get("torchmpi_tpu")       # the runner imported it
    startup = getattr(mpi, "startup", None)     # none: no account
    if startup is None:
        return None
    s = startup().summary()
    return s["trace_s"] + s["lower_s"]
