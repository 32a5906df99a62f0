"""The `glm-4.7-flash` configuration's files: the cell's rehearsal, the cell
and its metrics as the issue names them, the file against the catalog's keys,
the FLOP counts against the issue's shares, the runner's `Config` from either
file's key names and its failure on a program without the fields, the three
joins of one capture, and the three new readers with those that list the
cell."""

import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run
from test_olmoe import _ns

CELL = "glm-4.7-flash-l16k"
NEW = ("mtp_ms", "mla_flash_ms", "mla_flash_roofline")
GAINED = ("tokens_per_s_chip", "moe_ms", "moe_experts_ms",
          "moe_experts_roofline", "moe_max_load", "optimizer_ms",
          "head_loss_ms", "kernel_calls", "mla_ms", "mla_roofline")


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "glm-4.7-flash.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_latent")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (five layers and the module,
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
        assert not {"flash_ms", "flash_roofline", "kda_ms"} & listed
    else:
        assert reported == listed - {"mfu"}


def test_the_cell_is_the_issues(spec):
    """By name, not by position: a later PR appends."""
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash", "l16k", 1)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/glm-4.7-flash.json"
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tokens_per_s_chip"
        assert metrics[name]["source"] == "device_trace"
    assert metrics["mtp_ms"]["layer"] == "model step"
    assert metrics["mla_flash_roofline"]["layer"] == "kernels"
    assert metrics["mla_flash_roofline"]["unit"] == "%"
    for name in GAINED:
        assert metrics[name]["workloads"][-1] == CELL, name
    assert sorted(m for m, entry in metrics.items()
                  if CELL in entry.get("workloads", ())) == sorted(
                      NEW + GAINED)


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "vocab_size": 154880}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held_first"]) == (5, 8, 19360, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["vocab_size"] / 128 == 151.25         # a ragged last tile
    assert "8 chips share each layer" in cfg["deployment"]
    assert cfg["run"]["optimizer"]["moments_dtype"] == "float32"
    assert cfg["check_sample"] == {"batch": 1, "seq_len": 2048}
    assert cfg["mtp_loss_weight"] == 0.3
    assert {"scores", "selection_bias", "grouped_topk", "mtp_form",
            "mtp_input", "mtp_loss_weight", "rotary", "aux_loss",
            "initial_scales", "optimizer", "moments", "master_weights",
            "context"} <= set(cfg["assumed"])
    small = harness.rehearsed(cfg)
    assert small["num_hidden_layers"] == 5
    assert (small["n_routed_experts"],
            small["published"]["n_routed_experts"]) == (8, 32)


def test_flops_reproduce_the_issues_shares(cfg):
    """Six latent layers (the module's the sixth), 8 of 64 experts and 19,360
    rows at L=16384: forward MFLOP a token, part by part, as the issue gives
    them; 706.5 M parameters."""
    flops = harness.load_module("flops", "glm-4.7-flash")
    mix = harness.load_json("traffic", "l16k.json")
    parts = flops.forward_flops_per_token(cfg, 16384)
    D = 2048
    assert parts["mla_scores"] == 6 * 20 * (256 + 256) * 16385
    assert parts["mla_projections"] == 6 * 2 * (
        D * 768 + 768 * 20 * 256 + D * 576 + 512 * 20 * 448 + 20 * 256 * D)
    assert parts["head"] == 2 * 2 * D * 19360
    assert parts["dense_ffn"] == 3 * 2 * D * 10240
    assert parts["shared_expert"] == 5 * 3 * 2 * D * 1536
    assert parts["routed_experts_held"] == 5 * (4 * 8 / 64) * 3 * 2 * D * 1536
    assert parts["mtp_projection"] == 2 * 2 * D * D
    assert parts["router"] == 5 * 2 * D * 64
    mflop = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mflop == {"mla_scores": 1006.7, "mla_projections": 261.1,
                     "head": 158.6, "dense_ffn": 125.8, "shared_expert": 94.4,
                     "routed_experts_held": 47.2, "mtp_projection": 16.8,
                     "router": 1.3}
    total = sum(parts.values())
    assert round(total / 1e6) == 1712
    assert flops.required_flops_per_sample(cfg, mix) == 3 * total
    assert round(3 * total * 16384 / 1e12, 1) == 84.1
    share = lambda *keys: round(100 * sum(parts[k] for k in keys) / total, 1)
    assert (share("mla_scores"), share("mla_projections"), share("head"),
            share("dense_ffn"), share("shared_expert"),
            share("routed_experts_held"), share("mtp_projection")) == (
        58.8, 15.3, 9.3, 7.4, 5.5, 2.8, 1.0)
    assert share("mla_scores", "mla_projections") == 74.1
    # the module: a latent expert layer, W_eh and one pass over the head
    module = (parts["mla_scores"] / 6 + parts["mla_projections"] / 6
              + parts["shared_expert"] / 5 + parts["routed_experts_held"] / 5
              + parts["router"] / 5 + parts["mtp_projection"]
              + parts["head"] / 2)
    assert round(100 * module / total, 1) == 19.6
    held, used = flops.parameters(cfg)
    assert round(held / 1e6, 1) == 706.5 and used < held
    assert round(held * 12 / 1e9, 2) == 8.48
    s_flops, s_bytes = flops.scores_required(cfg, mix)
    assert s_flops == 3 * 16384 * parts["mla_scores"]
    assert s_flops / 197e12 > 20 * s_bytes / 819e9       # bound by FLOPs
    m_flops, m_bytes = flops.mla_required(cfg, mix)
    assert m_flops == 3 * 16384 * (parts["mla_projections"]
                                   + parts["mla_scores"])
    assert m_flops / 197e12 > m_bytes / 819e9
    e_flops, _ = flops.experts_required(cfg, mix)
    assert e_flops == 3 * 16384 * parts["routed_experts_held"]


def test_the_parameters_are_the_programs(cfg, runner):
    """`flops.parameters` against the program's own tree, at the published
    widths, by shape alone."""
    import jax
    import numpy as np
    from torchmpi_tpu.models import llama

    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                               runner._model(cfg)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == harness.load_module(
        "flops", "glm-4.7-flash").parameters(cfg)[0]


def test_the_runner_builds_the_model_from_either_file(cfg, runner):
    from torchmpi_tpu.models import llama

    model = runner._model(cfg)
    assert (model.d_model, model.n_layers, model.vocab) == (2048, 5, 19360)
    assert (model.n_experts, model.experts_held, model.expert_top_k) == (
        64, (0, 8), 4)
    assert llama.layer_runs(model) == (("mla", "dense", 1), ("mla", "moe", 4))
    assert (model.q_lora_rank, model.kv_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim, model.d_ff,
            model.dense_d_ff, model.routed_scale, model.rope_theta,
            model.mla_rope, model.mtp_layers, model.mtp_coef,
            model.router_act, model.n_heads) == (
        768, 512, 192, 64, 256, 1536, 10240, 1.8, 1e6, True, 1, 0.3,
        "sigmoid", 20)
    assert dict(vars(model), vocab=0, n_layers=0, layer_kinds=None,
                experts_held=None, n_experts=0) == dict(
        vars(llama.glm_4_7_flash()), vocab=0, n_layers=0, layer_kinds=None,
        experts_held=None, n_experts=0)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        runner._model({**cfg, "partial_rotary_factor": 0.5})
    with pytest.raises(ValueError, match="n_group"):
        runner._model({**cfg, "n_group": 4})
    # the Kimi Linear file's key names build what its own runner builds
    kimi = harness.load_json("configs", "kimi-linear-48b-a3b.json")
    import dataclasses
    assert dataclasses.replace(       # nothing of Kimi Linear's is rotated
        runner._model(kimi), rope_theta=llama.Config.rope_theta
    ) == harness.load_module("runners", "step_tokens_hybrid")._model(kimi)


def test_a_program_without_the_fields_fails_at_once(cfg, runner, monkeypatch):
    """On the commit before this configuration `llama.Config` knows no query
    latent: the runner stops before it touches the device."""
    import dataclasses
    from torchmpi_tpu.models import llama

    fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.Config)
              if f.name not in ("q_lora_rank", "mla_rope", "mtp_layers",
                                "mtp_coef")]
    older = dataclasses.make_dataclass("Config", fields, frozen=True)
    monkeypatch.setattr(llama, "Config", older)
    with pytest.raises(TypeError, match="q_lora_rank"):
        runner._model(cfg)


HLO = """HloModule jit_step

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(mtp)/attn/mla/mul"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = bf16[8]{0} fusion(%x), kind=kOutput, metadata={op_name="jit(step)/jvp(attn)/mla/dot_general"}
  %custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(attn))/mla/flash_bwd"}
  %custom-call.4 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mtp)/attn/mla/flash_fwd"}
  %custom-call.5 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mtp)/moe.experts/gmm"}
  %fusion.6 = bf16[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(mtp))/head_loss/dot_general"}
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
        ("%fusion.6 = bf16[8]{0} fusion(%x), kind=kLoop", 520, 60),
        ("%fusion.7 = bf16[8]{0} fusion(%x), kind=kLoop", 580, 70),
        ("%copy.8 = bf16[8]{0} copy(%x)", 650, 30)]],
}}}


def test_the_three_joins_of_one_capture(runner):
    """Innermost first over the hybrid runner's scopes (the module's layer is
    `mla`'s, `moe.experts`', `head_loss`'s); by the outer name `mtp` alone;
    and the flash kernels under `mla` by kernel, the grouped matmul's not."""
    looped = harness.load_module("runners", "step_tokens_looped")
    hybrid = harness.load_module("runners", "step_tokens_hybrid")
    ms = lambda labels: {k: round(v * 1000) for k, v in runner.self_ms(
        _ns(CAPTURE), labels, trace_reduce).items()}
    inner = looped.instruction_scopes(HLO, hybrid.SCOPES)
    assert inner == {"m.1": "mla", "fusion.1": "mla", "fusion.2": "mla",
                     "custom-call.3": "mla", "custom-call.4": "mla",
                     "custom-call.5": "moe.experts", "fusion.6": "head_loss",
                     "fusion.7": "head_loss"}
    assert ms(inner) == {"mla": 500, "moe.experts": 20, "head_loss": 130,
                         "unnamed": 30}
    outer = looped.instruction_scopes(HLO, ("mtp",))
    assert sorted(outer) == ["custom-call.4", "custom-call.5", "fusion.1",
                             "fusion.6", "m.1"]
    assert ms(outer) == {"mtp": 230, "unnamed": 450}
    kernels = runner.kernel_instructions(HLO, "mla")
    assert kernels == {"custom-call.3": "flash_bwd",
                       "custom-call.4": "flash_fwd"}
    assert ms(kernels) == {"flash_bwd": 150, "flash_fwd": 50, "unnamed": 480}
    assert runner.self_ms(_ns(CAPTURE), {}, trace_reduce) == {}


def test_the_readers_on_a_recorded_join(cfg):
    """On a program without the scopes, as the parent of this PR is, a reader
    finds nothing, returns `None` and does not raise, and the metric is left
    out; so does `mla_flash_roofline` beside a flops file without
    `scores_required` (another configuration's)."""
    read = {name: harness.load_module("layers", name).read
            for name in NEW + GAINED[1:]}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "traffic": None,
           "flops": None, "trace": None}
    assert all(r(obs) is None for r in read.values())
    mix = harness.load_json("traffic", "l16k.json")
    obs = {"counters": {"scope_ms": {"mla": 560.0, "attn": 20.0,
                                     "moe.experts": 20.0, "moe.shared": 40.0,
                                     "moe.router": 5.0, "head_loss": 70.0,
                                     "optimizer": 15.0},
                        "mtp_scope_ms": {"mtp": 170.0, "unnamed": 700.0},
                        "mla_flash_kernel_ms": {"flash_fwd": 120.0,
                                                "flash_bwd": 280.0,
                                                "unnamed": 470.0},
                        "expert_unit_counts": [[1200, 848] + [1024] * 6],
                        "kernel_calls": 57},
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"], "cfg": cfg,
           "traffic": mix, "trace": None,
           "flops": harness.load_module("flops", "glm-4.7-flash")}
    assert read["mtp_ms"](obs) == 170.0
    assert read["mla_flash_ms"](obs) == 400.0
    # 49.48 TFLOP at 197 TFLOP/s are 251.2 ms of the 400.
    assert read["mla_flash_roofline"](obs) == pytest.approx(62.79, abs=0.01)
    # 62.31 TFLOP are 316.3 ms of the 560.
    assert read["mla_ms"](obs) == 560.0
    assert read["mla_roofline"](obs) == pytest.approx(56.49, abs=0.01)
    assert read["moe_ms"](obs) == 65.0
    assert 0 < read["moe_experts_roofline"](obs) < 100
    assert read["moe_max_load"](obs) == pytest.approx(1200 * 8 / 8192)
    assert read["kernel_calls"](obs) == 57
    obs["flops"] = harness.load_module("flops", "olmoe-1b-7b")
    assert read["mla_flash_roofline"](obs) is None
    assert read["mla_roofline"](obs) is None
