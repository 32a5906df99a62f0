"""`import_s` (entry): the package's import, from the first statement of
`torchmpi_tpu/__init__.py` to its last, as the program's start-up account
(`torchmpi_tpu/_startup.py`, reached as `mpi.startup()`;
`docs/observability.md`, "The start-up account") stamps it.  The harness asks
JAX for its devices before a runner imports the package, so `jax` is imported
already (`jax_preloaded` in the account says so) and this is the package's
own modules and what else they import.  `None` where the program keeps no
account (a parent of PR 34)."""


def read(obs):
    import sys

    mpi = sys.modules.get("torchmpi_tpu")       # the runner imported it
    startup = getattr(mpi, "startup", None)     # none: no account
    if startup is None:
        return None
    return startup().summary()["import_s"]
