"""`cache_load_s` (entry): the part of `backend_compile_s` spent fetching
executables from the persistent compile cache and deserialising them (JAX's
`cache_retrieval_time_sec`, of the programs the cache had); from the
program's start-up account (`torchmpi_tpu/_startup.py`, reached as
`mpi.startup()`; `docs/observability.md`, "The start-up account").  0 in a
run from an empty cache.  The sum is the whole process's, not the set-up's
alone: the program sees no timed window in a token cell (the runner calls an
AOT executable), and the Ouro and Kimi runners make a few programs of the
benchmark's own after the window, for the timed step's check against the
reference (PR 34 saw 4 of the Ouro cell's 11 programs and 3 of the Kimi
cell's 11 there; `mpi.startup().summary(until_ns)` leaves them out).  `None`
where the program keeps no account (a parent of PR 34)."""


def read(obs):
    import sys

    mpi = sys.modules.get("torchmpi_tpu")       # the runner imported it
    startup = getattr(mpi, "startup", None)     # none: no account
    if startup is None:
        return None
    return startup().summary()["cache_load_s"]
