"""The `mellum2-12b-a2.5b` configuration's files: the cell's rehearsal on four
host devices, the cell and its metrics as the issue names them, the file
against the catalog's keys, the FLOP and byte counts against the issue's
figures, the runner's `Config` from the file and its failure on a program
without the exchange, a rehearsal whose exchange runs a pass too few (not
correct, by the units it dropped), the exchange's intervals from a capture,
the counters of the timed steps' deliveries, and the four new readers with
those that list the cell."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run
from test_olmoe import _ns

CELL = "mellum2-12b-a2.5b-ep4-l8k"
NEW = ("moe_exchange_ms", "moe_exchange_exposed_ms", "moe_exchange_roofline",
       "moe_rank_max_load")
GAINED = ("tokens_per_s_chip", "moe_ms", "moe_experts_ms",
          "moe_experts_roofline",
          "moe_max_load", "optimizer_ms", "head_loss_ms", "kernel_calls",
          "attn_ms", "swa_flash_ms", "swa_flash_roofline", "full_flash_ms",
          "full_flash_roofline")
FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "mellum2-12b-a2.5b.json")


@pytest.fixture(scope="module")
def mix():
    return harness.load_json("traffic", "ep4-l8k.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_ep")


@pytest.fixture(scope="module")
def flops():
    return harness.load_module("flops", "mellum2-12b-a2.5b")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line_on_four_host_devices(spec, trace):
    """The cell end to end at its rehearsal sizes on four host devices (a
    mesh of ep = 4, 8 experts 2 a chip, the exchange, AdamW, the reference on
    one device against the system on the mesh, on the check sample and on the
    timed step, every timed step's deliveries): the checks hold, the last line
    names the CPU's four devices and holds no metric."""
    line = _last_line(_run(harness.ROOT, "--workload", CELL, "--seed",
                           "4400000019", "--seconds", "2", "--trace",
                           str(trace), "--rehearse", env=FOUR))
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in harness.metrics_of(spec, group, CELL)}
    reported = set(line["rehearsal"]["would_report"])
    assert reported <= listed
    if trace:       # a CPU capture has no device plane: the counters only
        assert {"hbm_program_gb", "compile_s", "kernel_calls", "moe_max_load",
                "moe_rank_max_load"} <= reported
        assert set(NEW) | set(GAINED[1:]) <= listed
        assert not {"flash_ms", "flash_roofline", "mla_ms", "kda_ms"} & listed
    else:
        assert reported == listed - {"mfu"}


A_PASS_SHORT = """import os, runpy, sys
sys.path[:0] = [os.path.join({root!r}, "benchmark"), {root!r}]
from torchmpi_tpu.models import llama

def a_pass_short(units, rows, axis, whole=llama._pass_plan):
    plan = whole(units, rows, axis)
    return (*plan[:3], plan[3] - 1)

llama._pass_plan = a_pass_short
runpy.run_path(os.path.join({root!r}, "benchmark", "run.py"),
               run_name="__main__")
"""


def test_a_pass_too_few_is_not_correct(tmp_path):
    """The rehearsal with one fault planted in the program: every exchange
    runs one pass fewer than its fullest pair of ranks needs.  The routers
    still count `k x tokens` a layer; the units the passes delivered fall
    short in the timed steps, `moe_units_dropped` reads them, and the checks
    do not pass."""
    planted = tmp_path / "a_pass_short.py"
    planted.write_text(A_PASS_SHORT.format(root=harness.ROOT))
    done = subprocess.run(
        [sys.executable, str(planted), "--workload", CELL, "--seed",
         "4400000023", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "false", **FOUR})
    line = _last_line(done)
    assert line["rehearsal"]["checks_passed"] is False
    assert line["attempted"] > 0
    dropped = re.findall(r"the exchange: dropped \[([0-9, ]+)\]", done.stderr)
    assert dropped and all(int(n) > 0 for n in dropped[0].split(","))
    assert int(re.findall(r"'moe_units_dropped': (\d+)", done.stderr)[0]) > 0


def test_a_rehearsal_without_four_devices_says_so():
    done = _run(harness.ROOT, "--workload", CELL, "--seconds", "1",
                "--rehearse", env={"XLA_FLAGS": ""})
    assert done.returncode == 1 and "needs 4 devices" in done.stderr


def test_the_cell_is_the_issues(spec):
    """By name, not by position: a later PR appends."""
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b", "ep4-l8k", 4)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/mellum2-12b-a2.5b.json"
    assert config["source"] == ("https://huggingface.co/JetBrains/Mellum2-12B"
                                "-A2.5B-Instruct/blob/main/config.json")
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    # two of ten cells ask for four chips: a quarter, rounded down
    assert len(spec["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 2 <= len(
        spec["workloads"]) // 4
    assert sum(w["config"] == "mellum2-12b-a2.5b"
               for w in spec["workloads"]) == 1
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tokens_per_s_chip"
    for name in NEW[:3]:
        assert (metrics[name]["source"], metrics[name]["layer"]) == (
            "device_trace", "collectives")
    assert (metrics["moe_rank_max_load"]["source"],
            metrics["moe_rank_max_load"]["layer"]) == (
        "program_counter", "model step")
    assert (metrics["moe_exchange_roofline"]["unit"],
            metrics["moe_exchange_roofline"]["better"]) == ("%", "higher")
    for name in GAINED:
        assert CELL in metrics[name]["workloads"], name
    # `collective_ms` and its two siblings move `images_per_s_chip`, which a
    # token cell does not report: the cell is in none of their lists (the
    # exchange has readers of its own; a `benchmark` issue can split them).
    for name in ("collective_ms", "collective_exposed_ms", "collective_mb"):
        assert metrics[name]["moves"] == "images_per_s_chip"
        assert CELL not in metrics[name]["workloads"]
    assert sorted(m for m, entry in metrics.items()
                  if CELL in entry.get("workloads", ())) == sorted(
                      NEW + GAINED)


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    published["layer_types"] *= 7
    published["mlp_layer_types"] = ["sparse"] * 28
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert cfg["num_hidden_layers"] == 4
    # the layers that run: one whole period, the full layer last
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert "4 chips share each layer" in cfg["deployment"]
    assert "six further four-chip groups" in cfg["deployment"]
    assert "934.9 M" in cfg["why_reduced"]
    assert "39.9%" in cfg["why"] and "9% in the 28-layer model" in cfg["why"]
    assert cfg["run"]["optimizer"]["moments_dtype"] == "bfloat16"
    assert cfg["run"]["optimizer"]["learning_rate"] == 3e-6
    assert cfg["run"]["remat"] == "full"
    assert cfg["check_sample"]["batch"] == 4      # a row on every chip
    assert cfg["check_sample"]["seq_len"] == 2048
    assert {"mtp", "context", "optimizer", "moments", "master_weights",
            "initial_scales", "rotary", "attention_factor", "mask",
            "aux_loss", "qk_norm"} <= set(cfg["assumed"])
    small = harness.rehearsed(cfg)
    assert small["num_hidden_layers"] == 4
    assert (small["num_experts"], small["num_experts_per_tok"]) == (8, 2)
    assert small["check_sample"]["seq_len"] >= 2 * small["sliding_window"]


def test_flops_reproduce_the_issues_figures(cfg, mix, flops):
    """One period at L = 8192: forward MFLOP a token, part by part, as the
    issue gives them; 55.8 TFLOP a chip a step; 453 MB out an exchange."""
    assert (mix["batch"], mix["seq_len"], mix["mesh"]) == (8, 8192,
                                                           {"ep": 4})
    parts = flops.forward_flops_per_token(cfg, 8192)
    D = 2304
    mixer = 2 * D * 32 * 128 + 2 * D * 4 * 128
    assert mixer == 21_233_664
    assert parts["projections"] == 4 * 2 * mixer
    assert parts["full_scores"] == 32 * 256 * 8193
    band = (1024 * 1025 / 2 + (8192 - 1024) * 1024) / 8192
    assert round(band, 1) == 960.1
    assert parts["swa_scores"] == 3 * 32 * 4 * 128 * band
    assert parts["experts"] == 4 * 8 * 3 * 2 * D * 896
    assert parts["head"] == 2 * D * 98304
    assert parts["router"] == 4 * 2 * D * 64
    total = sum(parts.values())
    assert round(total / 1e6) == 1135
    share = lambda key: round(100 * parts[key] / total, 1)
    assert (share("experts"), share("head"), share("projections"),
            share("full_scores"), share("swa_scores")) == (
        34.9, 39.9, 15.0, 5.9, 4.2)
    assert flops.required_flops_per_sample(cfg, mix) == 3 * total
    assert round(3 * total * 8 * 8192 / 4 / 1e12, 1) == 55.8
    # in the 28-layer model the head is 9% of required FLOPs
    deep = flops.forward_flops_per_token({**cfg, "num_hidden_layers": 28},
                                         8192)
    assert round(100 * deep["head"] / sum(deep.values())) == 9
    held, used = flops.parameters(cfg, ep=4)
    assert held == 934_891_776 and used == 736_710_912
    assert round(held * 12 / 1e9, 1) == 11.2
    assert round(held * 8 / 1e9, 2) == 7.48
    # a chip's share of a step
    tokens = 8 * 8192 / 4
    f_flops, f_bytes = flops.full_scores_required(cfg, mix)
    assert f_flops == 3 * tokens * parts["full_scores"]
    assert f_flops / 197e12 > 20 * f_bytes / 819e9       # bound by FLOPs
    w_flops, w_bytes = flops.window_scores_required(cfg, mix)
    assert w_flops == 3 * tokens * parts["swa_scores"]
    assert w_bytes == 2 * tokens * 3 * 2 * (32 + 4) * 128 * 2
    e_flops, _ = flops.experts_required(cfg, mix)
    assert e_flops == 3 * tokens * parts["experts"]
    # the exchange: 131,072 units a chip a layer, three quarters leave it
    assert flops.exchange_rows_uniform(cfg, mix) == 131072 * 3 / 4
    assert round(flops.exchange_rows_uniform(cfg, mix) * D * 2 / 1e6) == 453
    assert flops.exchange_required(cfg, mix) == 4 * 4 * 98304 * D * 2
    assert flops.exchange_required(cfg, mix, 1000) == 4 * 4 * 1000 * D * 2


def test_the_parameters_are_the_programs(cfg, runner, flops):
    import jax
    from torchmpi_tpu.models import llama

    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                               runner._model(cfg)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == flops.parameters(cfg)[0] == 2_123_976_960


def test_the_runner_builds_the_model_from_the_file(cfg, runner):
    import dataclasses
    from torchmpi_tpu.models import llama

    model = runner._model(cfg)
    assert (model.d_model, model.n_layers, model.vocab) == (2304, 4, 98304)
    assert (model.n_experts, model.experts_held, model.expert_top_k,
            model.capacity_factor) == (64, None, 8, None)
    assert llama.layer_runs(model) == (("swa", "moe", 3), ("attn", "moe", 1))
    published = llama.mellum2_12b_a2_5b()
    assert model == dataclasses.replace(
        published, n_layers=4, layer_kinds=published.layer_kinds[:4])
    with pytest.raises(ValueError, match="attention_bias"):
        runner._model({**cfg, "attention_bias": True})
    with pytest.raises(ValueError, match="the whole head"):
        runner._model({**cfg, "rope_parameters": {
            **cfg["rope_parameters"], "sliding_attention": {
                "rope_type": "default", "rope_theta": 500000,
                "partial_rotary_factor": 0.5}}})


def test_a_program_without_the_exchange_fails_at_once(spec, cfg, runner,
                                                      monkeypatch):
    """On the commit before this configuration the benchmark has no such
    cell; with this benchmark's files laid over it, its program has no
    `batch_spec`, and the runner stops in `_model`, before a device is
    touched."""
    with pytest.raises(harness.BenchmarkError, match="no workload"):
        harness.find_cell({**spec, "workloads": spec["workloads"][:-1]}, CELL)
    from torchmpi_tpu.models import llama

    monkeypatch.delattr(llama, "batch_spec")
    with pytest.raises(AttributeError, match="batch_spec"):
        runner._model(cfg)


HLO = """HloModule jit_step

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kCustom, metadata={op_name="jit(step)/jvp(shard_map)/moe.dispatch/gather"}
  %all-to-all.2 = bf16[8]{0} all-to-all(%fusion.1), metadata={op_name="jit(step)/jvp(shard_map)/while/body/moe.exchange/all_to_all"}
  %custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(shard_map)/while/body/moe.experts/gmm"}
  %all-to-all-start.4 = bf16[8]{0} all-to-all-start(%x), metadata={op_name="jit(step)/transpose(jvp(shard_map))/while/body/moe.exchange/all_to_all"}
  %all-to-all-done.4 = bf16[8]{0} all-to-all-done(%all-to-all-start.4), metadata={op_name="jit(step)/transpose(jvp(shard_map))/while/body/moe.exchange/all_to_all"}
  %all-reduce.5 = bf16[8]{0} all-reduce(%x), metadata={op_name="jit(step)/transpose(jvp(attn))/dot_general"}
  ROOT %copy.6 = bf16[8]{0} copy(%x)
}
"""
CAPTURE = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_step(1)", 0, 900), ("jit_step(1)", 1000, 1000),
                    ("jit_step(1)", 2000, 1000)],
    "XLA Ops": [(name, 1000 + step * 1000 + start, dur) for step in (0, 1)
                for name, start, dur in [
        ("%fusion.1 = bf16[8]{0} fusion(%x), kind=kCustom", 0, 100),
        ("%all-to-all.2 = bf16[8]{0} all-to-all(%fusion.1)", 100, 200),
        ("%custom-call.3 = bf16[8]{0} custom-call(%x)", 300, 300),
        ("%all-to-all-start.4 = bf16[8]{0} all-to-all-start(%x)", 600, 10),
        ("%copy.6 = bf16[8]{0} copy(%x)", 610, 90),
        ("%all-to-all-done.4 = bf16[8]{0} all-to-all-done(%x)", 800, 10),
        ("%all-reduce.5 = bf16[8]{0} all-reduce(%x)", 810, 50)]],
    "Async XLA Ops": [(name, 1000 + step * 1000 + start, dur)
                      for step in (0, 1) for name, start, dur in [
        ("%all-to-all-start.4 = bf16[8]{0} all-to-all-start(%x)", 600, 210)]],
}}}


def test_the_exchange_by_scope_under_way_and_exposed(runner):
    """By the scope the program wrote, not by the instruction's name: the
    synchronous all-to-all while it runs, the asynchronous one from start to
    done, no all-reduce; exposed is what no other operation runs beside (the
    copy hides 90 us of the asynchronous one's 210)."""
    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    scopes = looped.instruction_scopes(HLO, runner.SCOPES_FIRST + hybrid.SCOPES)
    assert scopes == {"fusion.1": "moe.dispatch",
                      "all-to-all.2": "moe.exchange",
                      "custom-call.3": "moe.experts",
                      "all-to-all-start.4": "moe.exchange",
                      "all-to-all-done.4": "moe.exchange",
                      "all-reduce.5": "attn"}
    under_way, exposed = runner.exchange_ms(_ns(CAPTURE), scopes, trace_reduce)
    assert round(under_way * 1000) == 200 + 210
    assert round(exposed * 1000) == 200 + 210 - 90
    assert runner.exchange_ms(_ns(CAPTURE), {}, trace_reduce) is None
    # the accepted readers' list counts the all-reduce with them
    reduced = trace_reduce.reduce(_ns(CAPTURE))
    assert round(reduced["collective_s"] * 1e6 / reduced["steps"]) == 460


def test_the_counters_of_the_timed_steps_deliveries(runner):
    """From `delivered` (steps, ep, ep): nothing dropped in a step that
    delivered every unit, the fullest rank over the mean, the fullest pair
    over the uniform share, and the units a chip sent away a layer."""
    even = np.full((4, 4), 100)
    lopsided = np.array([[100, 100, 100, 100], [50, 50, 50, 50],
                         [250, 150, 150, 50], [100, 100, 100, 100]])
    assert lopsided.sum() == even.sum() == 1600
    found = runner.delivered_counters(np.stack([even, lopsided, even - 1]),
                                    units_a_layer=800, layers=2)
    assert found["moe_units_dropped"] == [0, 0, 16]
    assert found["moe_rank_max_load"] == 600 / 400
    assert found["moe_pair_max_load"] == 250 / 100
    # off the diagonal: 1,200 of a step's 1,600, by 4 chips and 2 layers
    assert round(found["moe_exchange_rows"], 1) == round(
        (1200 + 1200 + 1188) / 3 / 8, 1)


def test_the_new_readers(cfg, mix, flops):
    read = lambda name, obs: harness.load_module("layers", name).read(obs)
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    obs = {"cfg": cfg, "traffic": mix, "flops": flops, "peaks": peaks,
           "counters": {"exchange_ms": {"under_way": 200.0, "exposed": 150.0},
                        "moe_exchange_rows": 98304.0,
                        "moe_rank_max_load": 1.125}}
    assert read("moe_exchange_ms", obs) == 200.0
    assert read("moe_exchange_exposed_ms", obs) == 150.0
    assert read("moe_rank_max_load", obs) == 1.125
    # 7.25 GB a chip a step over 200 GB/s is 36.2 ms of the 200
    assert round(read("moe_exchange_roofline", obs), 2) == round(
        100 * 4 * 4 * 98304 * 2304 * 2 / 200e9 / 0.2, 2) == 18.12
    # a parent without the scope or the counters: nothing, and no raise
    bare = {**obs, "counters": {}}
    for name in NEW:
        assert read(name, bare) is None
    older = {**obs, "flops": object()}
    assert read("moe_exchange_roofline", older) is None
