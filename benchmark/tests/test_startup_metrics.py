"""The six readers of the program's start-up account (`mpi.startup()`,
`torchmpi_tpu/_startup.py`) and their places in `BENCHMARK.json`."""

import pytest

import harness
import torchmpi_tpu as mpi

SUMMARY = {"import_s": 1.5, "start_s": 0.25, "trace_s": 20.0, "lower_s": 12.5,
           "backend_compile_s": 9.0, "cache_load_s": 4.0, "cache_misses": 3}
READS = {"import_s": 1.5, "runtime_start_s": 0.25, "trace_lower_s": 32.5,
         "backend_compile_s": 9.0, "cache_load_s": 4.0,
         "compile_cache_misses": 3}


class StubAccount:
    def summary(self):
        return dict(SUMMARY)


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_on_a_stub_account_and_none_without_one(name, monkeypatch):
    read = harness.load_module("layers", name).read
    monkeypatch.setattr(mpi, "startup", StubAccount)
    assert read({"counters": {}}) == READS[name]
    monkeypatch.delattr(mpi, "startup")         # the parent's program
    assert read({"counters": {}}) is None


def test_the_readers_on_the_program_s_own_account():
    """Finite, and `cache_load_s` a part of `backend_compile_s`."""
    import jax

    jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
    if not mpi.started():
        mpi.start(with_tpu=False)
    got = {name: harness.load_module("layers", name).read({})
           for name in READS}
    assert all(harness.finite(v) for v in got.values()), got
    assert got["import_s"] > 0 and got["runtime_start_s"] > 0
    assert got["trace_lower_s"] > 0 and got["backend_compile_s"] > 0
    assert 0 <= got["cache_load_s"] <= got["backend_compile_s"]


def test_their_entries_in_the_benchmark():
    """At the end of `per_layer`, under `entry`, moving `setup_s`, in every
    cell: they list no `workloads`."""
    spec = harness.load_json("BENCHMARK.json", base=harness.ROOT)
    entries = {m["name"]: m for m in spec["per_layer"] if m["name"] in READS}
    assert sorted(entries) == sorted(READS)
    for name, entry in entries.items():
        counter = name == "compile_cache_misses"
        assert entry == {"name": name, "unit": "programs" if counter else "s",
                         "better": "lower", "layer": "entry",
                         "moves": "setup_s", "source":
                         "program_counter" if counter else "program_span"}
    for cell in spec["workloads"]:
        reported = harness.metrics_of(spec, "per_layer", cell["name"])
        assert all(e in reported for e in entries.values())
