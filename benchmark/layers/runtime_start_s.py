"""`runtime_start_s` (entry): `mpi.start()` from entry to return, summed over
the process's calls (the four-chip runner starts the runtime once for one
device and once for four), `start_s` of the program's start-up account (`torchmpi_tpu/_startup.py`, reached as
`mpi.startup()`; `docs/observability.md`, "The start-up account"): process
group, the first `jax.devices()` (the backend is up already under the
harness, which asks first: `backend_was_up`), the communicators, the
collective selector, and the planes started after the runtime is up
(`obs.serve`, journal, history); the account's summary gives each part.
`None` where the program keeps no account (a parent of PR 34), or
`mpi.start()` has not returned."""


def read(obs):
    import sys

    mpi = sys.modules.get("torchmpi_tpu")       # the runner imported it
    startup = getattr(mpi, "startup", None)     # none: no account
    if startup is None:
        return None
    return startup().summary()["start_s"]
