"""The `laguna-s-2.1` configuration's files: the cell's rehearsal, the cell and
its metrics as the issue names them, the file against the catalog's keys, the
FLOP counts against the issue's shares, the runner's `Config` from the file
and its failure on a program without the fields, the four joins of one
capture, and the five new readers with those that list the cell."""

import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run
from test_olmoe import _ns

CELL = "laguna-s-2.1-l16k"
NEW = ("attn_ms", "swa_flash_ms", "swa_flash_roofline", "full_flash_ms",
       "full_flash_roofline")
GAINED = ("tokens_per_s_chip", "moe_ms", "moe_experts_ms",
          "moe_experts_roofline", "moe_max_load", "optimizer_ms",
          "head_loss_ms", "kernel_calls")


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "laguna-s-2.1.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_mixed")


@pytest.fixture(scope="module")
def flops():
    return harness.load_module("flops", "laguna-s-2.1")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (five layers of both kinds,
    8 of 32 experts, AdamW, the reference on the check sample and on the timed
    step): the checks hold, the last line names the CPU and holds no metric."""
    line = _last_line(_run(harness.ROOT, "--workload", CELL, "--seed",
                           "3000000019", "--seconds", "2", "--trace",
                           str(trace), "--rehearse"))
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in harness.metrics_of(spec, group, CELL)}
    reported = set(line["rehearsal"]["would_report"])
    assert reported <= listed
    if trace:       # a CPU capture has no device plane: the counters only
        assert {"hbm_program_gb", "compile_s", "kernel_calls",
                "moe_max_load"} <= reported
        assert set(NEW) | set(GAINED[1:]) <= listed
        assert not {"flash_ms", "flash_roofline", "mla_ms", "kda_ms"} & listed
    else:
        assert reported == listed - {"mfu"}


def test_the_cell_is_the_issues(spec):
    """By name, not by position: a later PR appends."""
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "l16k", 1)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/laguna-s-2.1.json"
    assert config["source"] == ("https://huggingface.co/poolside/"
                                "Laguna-S-2.1/blob/main/config.json")
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert sum(w["config"] == "laguna-s-2.1" for w in spec["workloads"]) == 1
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tokens_per_s_chip"
        assert metrics[name]["source"] == "device_trace"
    assert metrics["attn_ms"]["layer"] == "model step"
    for name in NEW[1:]:
        assert metrics[name]["layer"] == "kernels"
    for name in ("swa_flash_roofline", "full_flash_roofline"):
        assert (metrics[name]["unit"], metrics[name]["better"]) == (
            "%", "higher")
    for name in GAINED:
        assert CELL in metrics[name]["workloads"], name
    assert sorted(m for m, entry in metrics.items()
                  if CELL in entry.get("workloads", ())) == sorted(
                      NEW + GAINED)


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention"] * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["experts_held_first"]) == (5, 8, 12544, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["vocab_size"] / 128 == 98          # whole tiles
    # the layers that run: the dense full layer and one whole period
    assert cfg["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert "32 chips share each layer" in cfg["deployment"]
    assert "811.0 M" in cfg["why_reduced"]
    assert cfg["run"]["optimizer"]["moments_dtype"] == "bfloat16"
    assert cfg["run"]["remat"] == "full"
    assert cfg["check_sample"] == {"batch": 1, "seq_len": 2048}
    assert {"gate", "scores", "selection_bias", "qk_norm",
            "shared_expert_gate", "attention_factor", "rotary", "mask",
            "aux_loss", "initial_scales", "optimizer", "moments",
            "master_weights", "context"} <= set(cfg["assumed"])
    small = harness.rehearsed(cfg)
    assert small["num_hidden_layers"] == 5
    assert (small["num_experts"], small["published"]["num_experts"]) == (8, 32)
    assert small["check_sample"]["seq_len"] >= 2 * small["sliding_window"]


def test_flops_reproduce_the_issues_shares(cfg, flops):
    """Two full and three sliding layers, 8 of 256 experts and 12,544 rows at
    L=16384: forward MFLOP a token, part by part, as the issue gives them;
    811.0 M parameters."""
    mix = harness.load_json("traffic", "l16k.json")
    parts = flops.forward_flops_per_token(cfg, 16384)
    D = 3072
    full, sliding = (D * h * 128 + 2 * D * 8 * 128 + D * h + h * 128 * D
                     for h in (48, 72))
    assert (full, sliding) == (44_187_648, 63_135_744)
    assert parts["full_scores"] == 2 * 48 * 256 * 16385
    # a row of the band meets min(i + 1, 512) keys: 504 on average
    band = (512 * 513 / 2 + (16384 - 512) * 512) / 16384
    assert round(band, 1) == 504.0
    assert parts["swa_scores"] == 3 * 72 * 4 * 128 * band
    assert parts["projections"] == 2 * (2 * full + 3 * sliding)
    assert parts["dense_ffn"] == 3 * 2 * D * 12288
    assert parts["shared_expert"] == 4 * 3 * 2 * D * 1024
    assert parts["routed_experts_held"] == (4 * (10 * 8 / 256)
                                            * 3 * 2 * D * 1024)
    assert parts["head"] == 2 * D * 12544
    assert parts["router"] == 4 * 2 * D * 256
    mflop = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mflop == {"projections": 555.6, "full_scores": 402.7,
                     "swa_scores": 55.7, "dense_ffn": 226.5, "router": 6.3,
                     "routed_experts_held": 23.6, "shared_expert": 75.5,
                     "head": 77.1}
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 1422.9
    assert flops.required_flops_per_sample(cfg, mix) == 3 * total
    assert round(3 * total * 16384 / 1e12, 1) == 69.9
    share = lambda *keys: round(100 * sum(parts[k] for k in keys) / total, 1)
    assert (share("full_scores"), share("swa_scores"), share("projections"),
            share("dense_ffn"), share("shared_expert"),
            share("routed_experts_held"), share("head")) == (
        28.3, 3.9, 39.0, 15.9, 5.3, 1.7, 5.4)
    assert round(share("full_scores", "swa_scores", "projections")) == 71
    # a kernel that masked the window and did not skip: the triangle at 72
    # heads, where the band is 2.7 TFLOP
    masked = 3 * 72 * 256 * 16385
    assert round(3 * masked * 16384 / 1e12, 1) == 44.5
    held, used = flops.parameters(cfg)
    assert held == 811_017_216 and used < held
    assert round(held * 12 / 1e9, 2) == 9.73
    f_flops, f_bytes = flops.full_scores_required(cfg, mix)
    assert f_flops == 3 * 16384 * parts["full_scores"]
    assert f_flops / 197e12 > 20 * f_bytes / 819e9       # bound by FLOPs
    w_flops, w_bytes = flops.window_scores_required(cfg, mix)
    assert w_flops == 3 * 16384 * parts["swa_scores"]
    assert w_bytes == 2 * 16384 * 3 * 2 * (72 + 8) * 128 * 2
    assert w_flops / 197e12 > 2 * w_bytes / 819e9        # FLOPs still, by less
    e_flops, _ = flops.experts_required(cfg, mix)
    assert e_flops == 3 * 16384 * parts["routed_experts_held"]


def test_the_parameters_are_the_programs(cfg, runner, flops):
    """`flops.parameters` against the program's own tree, at the published
    widths, by shape alone."""
    import jax
    import numpy as np
    from torchmpi_tpu.models import llama

    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                               runner._model(cfg)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == flops.parameters(cfg)[0]


def test_the_runner_builds_the_model_from_the_file(cfg, runner):
    from torchmpi_tpu.models import llama

    model = runner._model(cfg)
    assert (model.d_model, model.n_layers, model.vocab) == (3072, 5, 12544)
    assert (model.n_experts, model.experts_held, model.expert_top_k) == (
        256, (0, 8), 10)
    assert llama.layer_runs(model) == (
        ("attn", "dense", 1), ("swa", "moe", 3), ("attn", "moe", 1))
    assert dict(vars(model), vocab=0, n_layers=0, layer_kinds=None,
                experts_held=None) == dict(
        vars(llama.laguna_s_2_1()), vocab=0, n_layers=0, layer_kinds=None,
        experts_held=None)
    with pytest.raises(ValueError, match="gating"):
        runner._model({**cfg, "gating": "per-channel"})
    with pytest.raises(ValueError, match="softcapping"):
        runner._model({**cfg, "moe_router_logit_softcapping": 30})
    with pytest.raises(ValueError, match="one head count"):
        runner._model({**cfg, "num_attention_heads_per_layer":
                       [48, 72, 64, 72, 48]})
    with pytest.raises(ValueError, match="mlp_only_layers"):
        runner._model({**cfg, "mlp_only_layers": [0, 1]})


def test_a_program_without_the_fields_fails_at_once(cfg, runner, monkeypatch):
    """On the commit before this configuration `llama` has no window layer
    kind and `Config` no head width of its own: the runner stops before it
    touches the device."""
    import dataclasses
    from torchmpi_tpu.models import llama

    fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.Config)
              if f.name not in ("head_dim", "swa_heads", "swa_window",
                                "swa_rope_theta", "rope_fraction", "rope_yarn",
                                "attn_gate")]
    older = dataclasses.make_dataclass("Config", fields, frozen=True)
    monkeypatch.setattr(llama, "Config", older)
    with pytest.raises(TypeError, match="head_dim"):
        runner._model(cfg)
    monkeypatch.delattr(llama, "window_layer_kinds")
    with pytest.raises(AttributeError, match="window_layer_kinds"):
        runner._model(cfg)


HLO = """HloModule jit_step

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(attn)/attn.gate/mul"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = bf16[8]{0} fusion(%x), kind=kOutput, metadata={op_name="jit(step)/jvp(attn)/dot_general"}
  %custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(attn))/swa/flash_bwd"}
  %custom-call.4 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/swa/flash_fwd"}
  %custom-call.5 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(moe.experts)/gmm"}
  %custom-call.6 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/flash_fwd"}
  %fusion.7 = bf16[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/jvp(head_loss)/dot_general"}
  ROOT %copy.8 = bf16[8]{0} copy(%x)
}
"""
T0 = 1000
CAPTURE = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_step(1)", 0, 900), ("jit_step(1)", T0, 1000),
                    ("jit_step(1)", T0 + 1000, 1000)],
    "XLA Ops": [(name, T0 + step * 1000 + start, dur) for step in (0, 1)
                for name, start, dur in [
        ("%fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop", 0, 100),
        ("%fusion.2 = bf16[8]{0} fusion(%x), kind=kOutput", 100, 200),
        ("%custom-call.3 = bf16[8]{0} custom-call(%x)", 300, 150),
        ("%custom-call.4 = bf16[8]{0} custom-call(%x)", 450, 50),
        ("%custom-call.5 = bf16[8]{0} custom-call(%x)", 500, 20),
        ("%custom-call.6 = bf16[8]{0} custom-call(%x)", 520, 60),
        ("%fusion.7 = bf16[8]{0} fusion(%x), kind=kLoop", 580, 70),
        ("%copy.8 = bf16[8]{0} copy(%x)", 650, 30)]],
}}}


def test_the_four_joins_of_one_capture(runner):
    """Innermost first with `swa` and `attn.gate` before the hybrid runner's
    scopes; by the outer name `attn` alone; the flash kernels under `swa` by
    kernel; and those under `attn` outside `swa`, the grouped matmul's in
    neither."""
    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    latent = harness.load_module("runners", "step_tokens_latent")
    ms = lambda labels: {k: round(v * 1000) for k, v in latent.self_ms(
        _ns(CAPTURE), labels, trace_reduce).items()}
    inner = looped.instruction_scopes(HLO, runner.SCOPES_FIRST + hybrid.SCOPES)
    assert inner == {"m.1": "attn.gate", "fusion.1": "attn.gate",
                     "fusion.2": "attn", "custom-call.3": "swa",
                     "custom-call.4": "swa", "custom-call.5": "moe.experts",
                     "custom-call.6": "attn", "fusion.7": "head_loss"}
    assert ms(inner) == {"attn.gate": 100, "attn": 260, "swa": 200,
                         "moe.experts": 20, "head_loss": 70, "unnamed": 30}
    outer = looped.instruction_scopes(HLO, ("attn",))
    assert sorted(outer) == ["custom-call.3", "custom-call.4",
                             "custom-call.6", "fusion.1", "fusion.2", "m.1"]
    assert ms(outer) == {"attn": 560, "unnamed": 120}
    swa = latent.kernel_instructions(HLO, "swa")
    assert swa == {"custom-call.3": "flash_bwd", "custom-call.4": "flash_fwd"}
    assert ms(swa) == {"flash_bwd": 150, "flash_fwd": 50, "unnamed": 480}
    full = {name: kernel for name, kernel in
            latent.kernel_instructions(HLO, "attn").items() if name not in swa}
    assert full == {"custom-call.6": "flash_fwd"}
    assert ms(full) == {"flash_fwd": 60, "unnamed": 620}


def test_the_readers_on_a_recorded_join(cfg, flops):
    """On a program without the scopes, as the parent of this PR is, a reader
    finds nothing, returns `None` and does not raise, and the metric is left
    out; so do the two roofline shares beside a flops file without their
    functions (another configuration's)."""
    read = {name: harness.load_module("layers", name).read
            for name in NEW + GAINED[1:]}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "traffic": None,
           "flops": None, "trace": None}
    assert all(r(obs) is None for r in read.values())
    mix = harness.load_json("traffic", "l16k.json")
    obs = {"counters": {"scope_ms": {"attn": 430.0, "swa": 60.0,
                                     "attn.gate": 10.0, "moe.experts": 20.0,
                                     "moe.shared": 40.0, "moe.router": 5.0,
                                     "head_loss": 30.0, "optimizer": 20.0},
                        "attn_scope_ms": {"attn": 500.0, "unnamed": 200.0},
                        "swa_flash_kernel_ms": {"flash_fwd": 20.0,
                                                "flash_bwd": 40.0,
                                                "unnamed": 640.0},
                        "full_flash_kernel_ms": {"flash_fwd": 50.0,
                                                 "flash_bwd": 110.0,
                                                 "unnamed": 540.0},
                        "expert_unit_counts": [[800, 480] + [640] * 6],
                        "kernel_calls": 46},
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"], "cfg": cfg,
           "traffic": mix, "trace": None, "flops": flops}
    assert read["attn_ms"](obs) == 500.0
    assert read["swa_flash_ms"](obs) == 60.0
    assert read["full_flash_ms"](obs) == 160.0
    # 19.79 TFLOP at 197 TFLOP/s are 100.5 ms of the 160.
    assert read["full_flash_roofline"](obs) == pytest.approx(62.79, abs=0.01)
    # 2.74 TFLOP are 13.9 ms of the 60.
    assert read["swa_flash_roofline"](obs) == pytest.approx(23.18, abs=0.01)
    assert read["moe_ms"](obs) == 65.0
    assert 0 < read["moe_experts_roofline"](obs) < 100
    assert read["moe_max_load"](obs) == pytest.approx(800 * 8 / 5120)
    assert read["kernel_calls"](obs) == 46
    obs["flops"] = harness.load_module("flops", "olmoe-1b-7b")
    assert read["swa_flash_roofline"](obs) is None
    assert read["full_flash_roofline"](obs) is None
