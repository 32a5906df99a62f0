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

# The driver runs the suite under xdist with `--dist loadfile`, six workers on
# eight cores and a time limit the suite stands near: a file is one unit of
# work, and a worker takes the next file in the queue when it runs dry.
# xdist would queue the files with the most TESTS first, so the few files
# that hold most of the SECONDS start late and end the run.  The queue here
# (seconds and cores are a whole run's on this sandbox, PR 47):
# the kernels the chip's compiler is asked for (15 cases, four cores); every
# file not named below, in collection order (two thirds of the cases in a
# ninth of the work); the long files, the three longest and then those with
# the most cases a second first, so that a worker is never left alone with a
# long file of many cases; and LAST the three files of steps compiled for a
# described TPU, which hold four to five cores each while the compiler works
# (a quarter of the run's CPU time in 9 cases, three a file: a worker is
# handed its next file when two cases are left to it, and took two files of
# two) and so fill the cores that the last workers leave.
_FIRST = ("test_aot_compile.py",)
_LONG = (
    "test_kimi_linear_kernels.py", "test_glm_flash.py", "test_llama.py",
    "test_sequence.py", "test_pallas_ring.py", "test_ops.py",
    "test_nn_engine.py", "test_laguna.py", "test_ouro.py",
    "test_llama_decode.py", "test_kda_mixer.py", "test_llama_pipeline.py",
    "test_models.py", "test_olmoe.py", "test_kimi_linear.py",
    "test_mellum2.py", "test_laguna_stack.py", "test_examples.py",
    "test_mellum2_passes.py", "test_kimi_linear_remat.py",
    "test_kimi_linear_stack.py", "test_examples_models.py",
)
_LAST = ("test_aot_steps_glm_ouro.py", "test_aot_steps_laguna_kimi.py",
         "test_aot_steps_mellum2_kimi_mesh.py")


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False      # the queue as collected


def pytest_collection_modifyitems(items):
    rank = {f: -1 for f in _FIRST}
    rank.update({f: 1 + i for i, f in enumerate(_LONG + _LAST)})
    items.sort(key=lambda it: rank.get(
        it.nodeid.split("::", 1)[0].rsplit("/", 1)[-1], 0))


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


@pytest.fixture(scope="module")
def quick_compiles():
    """For a module whose time is XLA's CPU compiler on toy programs that then
    run for milliseconds (the model families' files: three fifths of the
    suite's worker time was ``backend_compile``, PR 47): the compiler is asked
    for little optimisation, LLVM at -O0 without its expensive passes, which
    takes a quarter off such a module's CPU time.  The programs' HLO and the
    precision of their arithmetic are what they were; the order of a
    vectorised sum is not, so a test that pins BITS takes
    ``full_optimisation`` as well.  Compiles for a described TPU do not read
    the flag.  A module asks with ``pytestmark =
    pytest.mark.usefixtures("quick_compiles")``; nothing compiled otherwise
    enters the module (two programs of one test compiled differently are
    not equal to the bit: ``tests/test_laguna_stack.py``'s shares) and
    nothing compiled so leaves it, for its worker's next file."""
    jax.clear_caches()
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()


@pytest.fixture()
def full_optimisation():
    """Inside a ``quick_compiles`` module: this test's programs are compiled
    as the rest of the suite's are, none taken from the module's cache."""
    quick = jax.config.read("jax_disable_most_optimizations")
    jax.clear_caches()
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", quick)
    if quick:
        jax.clear_caches()


@pytest.fixture()
def fresh_config():
    config.reset()
    yield config
    config.reset()
