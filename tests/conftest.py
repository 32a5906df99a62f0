"""Test fixture: an 8-device virtual CPU mesh stands in for a TPU pod slice,
the way ``mpirun -n K`` on one host stands in for a cluster in the reference
(reference: scripts/test_cpu.sh:17-31; SURVEY.md §4 testing ideas).

Environment must be set before jax import, hence the module-level setup.
"""

import os

# 8 virtual devices on 2 virtual "hosts" worth of topology; tests that need
# multi-host semantics key communicators on explicit keys instead.  The
# collective timeout is raised for loaded single-core CI hosts, where the
# 8-thread rendezvous can exceed XLA-CPU's default before all threads get
# scheduled.
# Single definition for every test process (parent and spawned workers);
# test modules import it so a future timeout change edits one place.
COLLECTIVE_TIMEOUT_FLAG = "--xla_cpu_collective_timeout_seconds=300"

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8 "
    + COLLECTIVE_TIMEOUT_FLAG
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compile cache under test, here or in any worker a test
# spawns: mpi.start() would otherwise point it at <checkout>/.jax_cache
# (runtime/lifecycle.py use_compile_cache), and a compile-only TPU program written there
# cannot be read back without a chip, so tests/test_aot_compile.py would
# warn on its next run.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
# numpy.testing's import-time SVE probe runs `lscpu` in a subprocess
# (numpy gh-22982).  Import it HERE — single-threaded, before jax spawns
# its runtime threads — because under the sanitizer drill
# (scripts/sanitize_drill.py, TSAN preloaded) a fork taken while another
# thread holds a TSAN runtime lock deadlocks the whole test process; the
# lazy import inside the first assert_allclose is exactly such a fork.
import numpy.testing  # noqa: E402, F401
import pytest  # noqa: E402

import jax  # noqa: E402

import torchmpi_tpu as mpi  # noqa: E402
from torchmpi_tpu.runtime import config  # noqa: E402


# ------------------------------------------------------------- CI timing
# Per-file wall time at the end of every run: the suite has grown past 15
# minutes and this names the files to mark `heavy` next (the fast loop is
# `pytest -m "not heavy"`).

_file_seconds = {}


def pytest_runtest_logreport(report):
    f = report.nodeid.split("::", 1)[0]
    _file_seconds[f] = _file_seconds.get(f, 0.0) + report.duration


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _file_seconds:
        return
    tr = terminalreporter
    tr.write_sep("-", "per-file wall time")
    for f, s in sorted(_file_seconds.items(), key=lambda kv: -kv[1]):
        tr.write_line(f"{s:8.1f}s  {f}")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def world(devices):
    """A started runtime with the world communicator over 8 devices."""
    if mpi.started():
        mpi.stop()
    config.reset()
    mpi.start(with_tpu=False, devices=devices)
    yield mpi.stack.world()
    mpi.stop()
    config.reset()


@pytest.fixture()
def fresh_config():
    config.reset()
    yield config
    config.reset()
