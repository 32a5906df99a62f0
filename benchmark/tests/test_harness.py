"""The harness: `BENCHMARK.json` against its contract, the last line's
schema, the errors off the TPU, and a cell and a metric added as files."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


def _run(root, *args, env=None):
    """`run.py` of the benchmark under `root`, as the driver starts it."""
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "false", **(env or {})})


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------ BENCHMARK.json

def test_benchmark_json_meets_the_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert all(not w.startswith("/") and ".." not in w for w in spec["command"])
    assert 1 <= spec["run_seconds"] <= 51
    cells = spec["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    configs = {c["name"]: c for c in spec["configs"]}
    assert {c["config"] for c in cells} == set(configs)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
        assert os.path.isfile(os.path.join(harness.HERE, "traffic",
                                           c["traffic"] + ".json"))
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["source"]) <= 200
        data = harness.load_json(c["file"], base=harness.ROOT)
        assert data["reduced"] == c["reduced"]
        for kind in ("runners", "reference", "flops"):
            name = data["runner"] if kind == "runners" else c["name"]
            assert os.path.isfile(os.path.join(harness.HERE, kind, name + ".py"))
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    cell_names = {c["name"] for c in cells}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert os.path.isfile(os.path.join(harness.HERE, "layers",
                                           m["name"] + ".py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cell_names
    # Every cell reports setup_s, another end-to-end metric and a per-layer
    # one, and a per-layer metric only where the metric it moves is.
    for cell in cell_names:
        ends = {m["name"] for m in harness.metrics_of(spec, "end_to_end", cell)}
        assert "setup_s" in ends and len(ends) >= 2
        layers = harness.metrics_of(spec, "per_layer", cell)
        assert layers and all(m["moves"] in ends for m in layers)
    assert len(json.dumps(spec)) < 64 * 1024


def test_roofline_metrics_are_named_by_the_contract(spec):
    for m in spec["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


# ------------------------------------------------------------ errors, schema

def test_no_tpu_is_an_error_and_prints_no_result():
    proc = _run(harness.ROOT, "--workload", "resnet50-b128-stream",
                "--seed", "0", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_without_the_program_is_an_error_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path), "--workload", "mixtral-8x7b-l4096", "--seed",
                "0", "--seconds", "1", "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "torchmpi_tpu" in proc.stderr


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Unknown:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Unknown()])
    with pytest.raises(harness.BenchmarkError, match="not in peaks.json"):
        harness.device_info(1, rehearse=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [Unknown(), Unknown()])
    with pytest.raises(harness.BenchmarkError, match="asks for 1 chip"):
        harness.device_info(1, rehearse=False)


def test_peaks_have_sources():
    for kind, row in harness.load_json("peaks.json").items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 \
            and row["hbm_bytes_per_s"] > 0, kind
    assert harness.load_json("peaks.json")["TPU v5 lite"]["bf16_flops_per_s"] \
        == 197e12


@pytest.mark.parametrize("workload, devices", [
    ("resnet50-b128-stream", 1), ("resnet50-b512-dp4", 4),
    ("mixtral-8x7b-l4096", 1), ("mixtral-8x7b-l16k", 1)])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, workload, devices, trace):
    """Every cell end to end at its rehearsal sizes: the last line has the
    contract's keys, names the CPU, is never correct and holds no metric."""
    proc = _run(harness.ROOT, "--workload", workload, "--seed", "3",
                "--seconds", "2", "--trace", str(trace), "--rehearse",
                env={"XLA_FLAGS":
                     f"--xla_force_host_platform_device_count={devices}"})
    line = _last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": devices}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True, proc.stderr[-3000:]
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in harness.metrics_of(spec, group, workload)}
    assert set(line["rehearsal"]["would_report"]) <= listed
    if not trace:   # mfu needs a peak, which a CPU has not
        assert set(line["rehearsal"]["would_report"]) == listed - {"mfu"}


# ------------------------------------------------------- the median step

def test_median_step_does_not_move_with_a_few_slow_steps():
    """19 steps of 0.5 s, three of them 40% slow (a 6% slower window): the
    median interval is a step; steps over seconds would read 6% low."""
    t, done = 10.0, []
    for i in range(19):
        t += 0.7 if i in (5, 6, 12) else 0.5
        done.append(t)
    assert harness.median_step_s(done) == pytest.approx(0.5)
    assert (done[-1] - done[0]) / 18 == pytest.approx(0.5 + 0.6 / 18)
    assert harness.median_step_s([1.0, 1.4, 2.0]) == pytest.approx(0.5)
    assert harness.median_step_s([1.0]) is None
    assert harness.median_step_s([]) is None


# ------------------------------------------------- a cell added as files only

DUMMY_RUNNER = '''
def run(ctx):
    ctx.counters["reference_check"] = {"ok": True}
    ctx.counters["widgets"] = ctx.traffic["widgets"] * ctx.cfg["scale"]
    with ctx.window():
        pass
    return {"samples_per_s": 5.0, "window_s": 2.0, "attempted": 5, "failed": 0,
            "first_loss": 1.0, "last_loss": 0.5, "program_bytes": 0,
            "devices": []}
'''


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path, spec):
    """A configuration, its runner and FLOP count, a traffic mix and a
    per-layer metric arrive as new files and new entries of BENCHMARK.json;
    no file that was there changes, and the harness finds them by name."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digests(root / "benchmark")

    bench = root / "benchmark"
    (bench / "configs" / "dummy.json").write_text(json.dumps({
        "runner": "dummy", "throughput_metric": "widgets_per_s_chip",
        "scale": 3, "reduced": []}))
    (bench / "traffic" / "few.json").write_text(json.dumps({"widgets": 7}))
    (bench / "runners" / "dummy.py").write_text(DUMMY_RUNNER)
    (bench / "flops" / "dummy.py").write_text(
        "def required_flops_per_sample(cfg, traffic):\n    return 1.0\n")
    (bench / "layers" / "widgets_made.py").write_text(
        "def read(obs):\n    return float(obs['counters']['widgets'])\n")
    (bench / "layers" / "never_there.py").write_text(
        "def read(obs):\n    return obs['counters'].get('absent')\n")
    new = json.loads(json.dumps(spec))
    new["configs"].append({"name": "dummy", "source": "none", "reduced": [],
                           "file": "benchmark/configs/dummy.json", "why": "x"})
    new["workloads"].append({"name": "dummy-few", "config": "dummy",
                             "traffic": "few", "chips": 1, "why": "x"})
    new["end_to_end"].append({"name": "widgets_per_s_chip", "unit": "widgets/s",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock", "workloads": ["dummy-few"]})
    for name in ("widgets_made", "never_there"):
        new["per_layer"].append({
            "name": name, "unit": "widgets", "better": "higher",
            "source": "program_counter", "layer": "dummy",
            "moves": "widgets_per_s_chip", "workloads": ["dummy-few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    def would_report(trace):
        return set(_last_line(_run(
            str(root), "--workload", "dummy-few", "--seed", "0", "--seconds",
            "1", "--trace", str(trace), "--rehearse"))["rehearsal"]["would_report"])

    assert would_report(0) == {"widgets_per_s_chip", "setup_s"}
    # `never_there` read nothing and is left out; so are the metrics of all
    # cells whose counters this runner does not keep.
    assert would_report(1) == {"widgets_made"}
    after = _digests(root / "benchmark")
    assert {k: after[k] for k in before} == before
