"""The `kimi-linear-48b-a3b` configuration's files: the cell's rehearsal, the
file against the catalog's keys, the FLOP and byte counts against a hand
count, the runner's `Config` from the file and its refusal of a program
without the fields, the join with its inner scopes, and the three new readers
with those that list the cell."""

import json

import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run
from test_olmoe import _ns

CELL = "kimi-linear-48b-a3b-l16k"
NEW = ("kda_ms", "kda_roofline", "mla_ms", "mla_roofline")


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "kimi-linear-48b-a3b.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_hybrid")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (five layers of all three
    kinds, 8 of 32 experts, AdamW, the reference on the check sample and on
    the timed step): the checks hold, the last line names the CPU and holds
    no metric."""
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
        assert set(NEW) | {"moe_ms", "moe_experts_ms", "moe_experts_roofline",
                           "head_loss_ms", "optimizer_ms"} <= listed
        assert not {"flash_ms", "flash_roofline"} & listed
    else:
        assert reported == listed - {"mfu"}


def test_the_cell_is_the_issues(spec):
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "l16k", 1)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert spec["workloads"][-1] == cell and spec["configs"][-1] == config
    assert [m["name"] for m in spec["per_layer"][-len(NEW):]] == list(NEW)
    for m in spec["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_chip"


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["experts_held_first"]) == (5, 8, 20480, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["run"]["optimizer"]["moments_dtype"] == "float32"
    assert cfg["check_sample"] == {"batch": 1, "seq_len": 2048}
    assert {"short_conv", "qk_l2_norm", "decay", "output_gate",
            "selection_bias", "aux_loss", "initial_scales", "optimizer",
            "moments", "master_weights", "context"} <= set(cfg["assumed"])
    small = harness.rehearsed(cfg)
    assert small["num_hidden_layers"] == 5
    assert (small["num_experts"], small["published"]["num_experts"]) == (8, 32)


def test_flops_against_a_hand_count(cfg):
    """Five layers, 8 of 256 experts and 20,480 rows at L=16384, by hand."""
    flops = harness.load_module("flops", "kimi-linear-48b-a3b")
    mix = harness.load_json("traffic", "l16k.json")
    parts = flops.forward_flops_per_token(cfg, 16384)
    D, HK = 2304, 32 * 128
    assert parts["kda_projections"] == 4 * 2 * (
        4 * D * HK + 2 * (D * 128 + 128 * HK) + D * 32)
    # a chunk of 64 tokens and a head of 128: five C x C x d products, three
    # C x d x d, the triangular inverse; 32 heads, four layers
    chunk = 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128 + 2 * 64 ** 3 // 3
    assert parts["kda_recurrence"] == 4 * 32 * chunk / 64
    assert parts["mla_projections"] == 2 * (D * 32 * 192 + D * 576
                                            + 512 * 32 * 256 + 32 * 128 * D)
    assert parts["mla_scores"] == 32 * (192 + 128) * 16385
    assert parts["dense_ffn"] == 3 * 2 * D * 9216
    assert parts["routed_experts_held"] == 4 * (8 * 8 / 256) * 3 * 2 * D * 1024
    assert parts["shared_expert"] == 4 * 3 * 2 * D * 1024
    assert parts["head"] == 2 * D * 20480
    total = sum(parts.values())
    assert round(total / 1e6) == 862
    assert flops.required_flops_per_sample(cfg, mix) == 3 * total
    share = lambda k: round(100 * parts[k] / total, 1)
    assert (share("mla_scores"), share("head"), share("kda_recurrence"),
            share("routed_experts_held")) == (19.5, 10.9, 2.7, 1.6)
    held, used = flops.parameters(cfg)
    assert held == 602_450_816 and used < held
    k_flops, k_bytes = flops.kda_required(cfg, mix)
    assert k_flops == 3 * 16384 * parts["kda_recurrence"]
    assert k_bytes / 819e9 > k_flops / 197e12            # bound by bytes
    assert k_bytes / 819e9 == pytest.approx(16.4e-3, rel=0.01)
    m_flops, m_bytes = flops.mla_required(cfg, mix)
    assert m_flops == 3 * 16384 * (parts["mla_projections"]
                                   + parts["mla_scores"])
    assert m_flops / 197e12 > m_bytes / 819e9            # bound by FLOPs
    e_flops, _ = flops.experts_required(cfg, mix)
    assert e_flops == 3 * 16384 * parts["routed_experts_held"]


def test_the_runner_builds_the_model_from_the_file(cfg, runner):
    from torchmpi_tpu.models import llama

    model = runner._model(cfg)
    assert (model.d_model, model.n_layers, model.vocab) == (2304, 5, 20480)
    assert (model.n_experts, model.experts_held, model.expert_top_k) == (
        256, (0, 8), 8)
    assert [n for *_, n in llama.layer_runs(model)] == [1, 2, 1, 1]
    assert (model.kda_heads, model.kda_head_dim, model.kda_conv,
            model.kv_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim, model.d_ff,
            model.dense_d_ff, model.routed_scale) == (
        32, 128, 4, 512, 128, 64, 128, 1024, 9216, 2.446)
    with pytest.raises(ValueError, match="q_lora_rank"):
        runner._model({**cfg, "q_lora_rank": 1536})


def test_a_program_without_the_fields_fails_at_once(cfg, runner, monkeypatch):
    """On the commit before this configuration `llama` has no `layer_kinds`:
    the runner stops before it touches JAX."""
    from torchmpi_tpu.models import llama

    monkeypatch.delattr(llama, "layer_kinds")
    with pytest.raises(AttributeError, match="layer_kinds"):
        runner._model(cfg)


HLO = """HloModule jit_step

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(attn)/kda/while/body/mul"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = bf16[8]{0} fusion(%x), kind=kOutput, metadata={op_name="jit(step)/jvp(attn)/dot_general"}
  %custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(attn))/mla/flash_bwd/pallas_call"}
  %fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/jvp(moe.shared)/dot_general"}
  %fusion.5 = bf16[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(attn))/kda/transpose(jvp())/mul"}
  ROOT %copy.6 = bf16[8]{0} copy(%x)
}
"""
T0, US = 1000, 1000
CAPTURE = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_step(1)", 0, 900), ("jit_step(1)", T0, 1000),
                    ("jit_step(1)", T0 + 1000, 1000)],
    "XLA Ops": [(name, T0 + step * 1000 + start, dur) for step in (0, 1)
                for name, start, dur in [
        ("%fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop", 0, 200),
        ("%fusion.2 = bf16[8]{0} fusion(%x), kind=kOutput", 200, 300),
        ('%custom-call.3 = bf16[8]{0} custom-call(%x)', 500, 100),
        ("%fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop", 600, 40),
        ("%fusion.5 = bf16[8]{0} fusion(%x), kind=kLoop", 640, 60),
        ("%copy.6 = bf16[8]{0} copy(%x)", 700, 30)]],
}}}


def test_the_join_takes_the_inner_scope(runner):
    """`kda` and `mla` lie inside `attn`: an instruction is its innermost
    listed scope's, forward and backward."""
    looped = harness.load_module("runners", "step_tokens_looped")
    scopes = looped.instruction_scopes(HLO, runner.SCOPES)
    assert scopes == {"m.1": "kda", "fusion.1": "kda", "fusion.2": "attn",
                      "custom-call.3": "mla", "fusion.4": "moe.shared",
                      "fusion.5": "kda"}
    found = runner.scope_ms(_ns(CAPTURE), scopes, trace_reduce)
    assert {k: round(v * 1000) for k, v in found.items()} == {
        "kda": 260, "attn": 300, "mla": 100, "moe.shared": 40, "unnamed": 30}
    assert runner.scope_ms(_ns(CAPTURE), {}, trace_reduce) == {}


def test_the_readers_on_a_recorded_join(cfg):
    """On a program without the scopes, as the parent of this PR is, a reader
    finds nothing and the metric is left out; so does `kda_roofline` beside a
    flops file without `kda_required` (another configuration's)."""
    read = {name: harness.load_module("layers", name).read for name in NEW + (
        "moe_ms", "moe_experts_ms", "moe_experts_roofline", "head_loss_ms",
        "optimizer_ms", "moe_max_load", "kernel_calls")}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "traffic": None,
           "flops": None, "trace": None}
    assert all(r(obs) is None for r in read.values())
    mix = harness.load_json("traffic", "l16k.json")
    obs = {"counters": {"scope_ms": {"kda": 205.0, "mla": 100.0, "attn": 250.0,
                                     "moe.experts": 10.0, "moe.shared": 30.0,
                                     "moe.router": 4.0, "head_loss": 45.0,
                                     "optimizer": 20.0},
                        "expert_unit_counts": [[600, 400, 512, 512, 512, 512,
                                                512, 536]],
                        "kernel_calls": 46},
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"], "cfg": cfg,
           "traffic": mix, "trace": None,
           "flops": harness.load_module("flops", "kimi-linear-48b-a3b")}
    assert read["kda_ms"](obs) == 205.0 and read["mla_ms"](obs) == 100.0
    # 13.45 GB at 819 GB/s are 16.42 ms of the 205.
    assert read["kda_roofline"](obs) == pytest.approx(8.01, abs=0.01)
    # 11.11 TFLOP at 197 TFLOP/s are 56.39 ms of the 100.
    assert read["mla_roofline"](obs) == pytest.approx(56.39, abs=0.01)
    assert read["moe_ms"](obs) == 44.0
    assert read["moe_experts_roofline"](obs) == pytest.approx(35.3, abs=0.1)
    assert read["moe_max_load"](obs) == pytest.approx(600 * 8 / 4096)
    assert read["kernel_calls"](obs) == 46
    obs["flops"] = harness.load_module("flops", "olmoe-1b-7b")
    assert read["kda_roofline"](obs) is None
    assert read["mla_roofline"](obs) is None
