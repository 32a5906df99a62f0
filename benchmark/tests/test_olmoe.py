"""The `olmoe-1b-7b` configuration's files: the cell's rehearsal, the FLOP
count against a hand count, the plain reference against the system at the
rehearsal sizes, the runner's join of a capture to the scopes of its
executable, and the five per-layer readers."""

import json

import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run

CELL = "olmoe-1b-7b-l4096"


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "olmoe-1b-7b.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_adamw")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (E=8 > k=4 > 1, QK-norm,
    no renormalisation, two layers, AdamW): the checks hold, the last line
    names the CPU and holds no metric."""
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
        assert {"moe_max_load", "hbm_program_gb", "compile_s"} <= reported
    else:
        assert reported == listed - {"mfu"}


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 1024,
                 "max_position_embeddings": 4096, "model_type": "olmoe",
                 "norm_topk_prob": False, "num_attention_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "num_hidden_layers": 16, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
                 "tie_word_embeddings": False, "vocab_size": 50304}
    differs = [k for k, v in published.items() if cfg[k] != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 16}
    small = harness.rehearsed(cfg)
    assert small["num_experts"] > small["num_experts_per_tok"] > 1
    assert small["num_hidden_layers"] == 2 and not small["norm_topk_prob"]


def test_flops_against_a_hand_count(cfg):
    """Two layers at L=4096, by hand from the shapes."""
    flops = harness.load_module("flops", "olmoe-1b-7b")
    mix = harness.load_json("traffic", "l4096.json")
    parts = flops.forward_flops_per_token(cfg, 4096)
    assert parts["head"] == 2 * 2048 * 50304 == 206_045_184
    assert parts["experts"] == 2 * 8 * 3 * 2 * 2048 * 1024 == 201_326_592
    assert parts["attention_projections"] == 2 * 4 * 2 * 2048 * 2048
    assert parts["attention_scores"] == 2 * 2 * 2048 * 4097
    assert parts["router"] == 2 * 2 * 2048 * 64
    per_token = flops.required_flops_per_sample(cfg, mix)
    assert per_token == 3 * 508_567_552
    assert round(100 * parts["head"] / sum(parts.values()), 1) == 40.5
    total, active = flops.parameters(cfg)
    layer = 64 * 3 * 2048 * 1024 + 4 * 2048 * 2048 + 2048 * 64 + 4 * 2048
    assert total == 2 * layer + 2 * 50304 * 2048 + 2048 == 1_045_186_560
    assert active == total - 2 * 56 * 3 * 2048 * 1024
    e_flops, e_bytes = flops.experts_required(cfg, mix)
    assert e_flops == 2 * 9 * 2 * 131072 * 2048 * 1024
    assert e_flops / 197e12 > e_bytes / 819e9           # bound by FLOPs
    f_flops, _ = flops.flash_required(cfg, mix)
    assert f_flops == 2 * 6 * 2 * 128 * (4 * 16 * 4096 * 4097 // 2)


def test_reference_against_the_system_at_rehearsal_sizes(cfg, runner):
    """The comparison the runner makes on the chip, here in float32: the
    differences are rounding, far inside what bf16 is allowed."""
    import jax
    import jax.numpy as jnp

    import compare
    import traffic
    from torchmpi_tpu.models import llama

    small = harness.rehearsed(cfg)
    model = runner._model(small)
    assert model.capacity_factor is None and model.qk_norm
    assert not model.moe_renormalize and model.moe_z_coef == 0.001
    params = llama.init(jax.random.PRNGKey(5), model)
    sample = tuple(jnp.asarray(a) for a in traffic.tokens(
        {}, small, 6, n_batches=1, batch=1, seq_len=128)[0])
    reference = harness.load_module("reference", "olmoe-1b-7b")
    loss_fn = llama.make_loss_fn(model, attn="flash", remat="dots", loss_chunk=64)

    def system(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p, s)
        return loss, llama.apply(model, p, s[0], attn="flash"), grads

    found = compare.check(system,
                          lambda p, s: reference.loss_and_grads(small, p, s),
                          params, sample, reference.TOLERANCE,
                          reference.LEAF_AXES)
    assert found["ok"], found
    assert all(found[k] < 1e-4 for k in reference.TOLERANCE), found


# A step program in little: two scopes of the expert layer, the optimizer, a
# fusion that has no name of its own, XLA's own grouped matmul, and a loop.
HLO = '''HloModule jit_step

%fused_computation.3 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(moe.combine)/mul"}
  ROOT %a.1 = bf16[8]{0} add(%m.1, %p), metadata={op_name="jit(step)/jvp(moe.combine)/add"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %sort.1 = s32[8]{0} sort(%x), metadata={op_name="jit(step)/jvp()/while/body/checkpoint/rematted_computation/moe.dispatch/sort"}
  %ragged-dot-none.2 = bf16[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.3 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3
  %fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/optimizer/mul"}
  %fusion.5 = bf16[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(step)/transpose(jvp(attn))/dot_general"}
  ROOT %while.6 = bf16[8]{0} while(%x), condition=%c, body=%b
}
'''
T0, US = 5000, 1000
CAPTURE = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_step(1)", 0, 900), ("jit_step(1)", T0, 1000),
                    ("jit_step(1)", T0 + 1000, 1000)],
    "XLA Ops": [(name, T0 + step * 1000 + start, dur) for step in (0, 1)
                for name, start, dur in [
        ("%while.6 = bf16[8]{0} while(%x), condition=%c, body=%b", 0, 400),
        ("%sort.1 = s32[8]{0} sort(%x)", 10, 90),
        ('%ragged-dot-none.2 = bf16[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call"', 100, 250),
        ("%fusion.3 = bf16[8]{0} fusion(%x), kind=kLoop", 400, 100),
        ("%fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop", 500, 200),
        ("%fusion.5 = bf16[8]{0} fusion(%x), kind=kOutput", 700, 50),
        ("%copy.7 = bf16[8]{0} copy(%x)", 750, 30)]],
}}}


def _ns(capture):
    return {"profile_start_ns": 0, "devices": {
        plane: {line: [(n, s * US, d * US) for n, s, d in events]
                for line, events in lines.items()}
        for plane, lines in capture["devices"].items()}}


def test_the_join_of_a_capture_to_its_scopes(runner):
    scopes = runner.instruction_scopes(HLO)
    assert scopes == {"m.1": "moe.combine", "a.1": "moe.combine",
                      "sort.1": "moe.dispatch", "ragged-dot-none.2": "moe.experts",
                      "fusion.3": "moe.combine", "fusion.4": "optimizer",
                      "fusion.5": "attn"}
    found = runner.scope_ms(_ns(CAPTURE), HLO, trace_reduce)
    # Two whole steps (the first execution is left out); self times a step in
    # microseconds: the loop's own 400 - 90 - 250, the copy's 30.
    assert {k: round(v * 1000) for k, v in found.items()} == {
        "moe.dispatch": 90, "moe.experts": 250, "moe.combine": 100,
        "optimizer": 200, "attn": 50, "unnamed": 60 + 30}
    # An executable without the names (the compile-cache trap): nothing.
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    assert runner.scope_ms(_ns(CAPTURE), bare, trace_reduce) == {}


def test_the_readers_return_none_where_there_is_nothing(cfg):
    """On a program without the scopes, as the parent of the PR that brought
    them is, a reader finds nothing and the metric is left out."""
    read = {name: harness.load_module("layers", name).read for name in (
        "moe_ms", "moe_experts_ms", "moe_experts_roofline", "optimizer_ms",
        "moe_max_load")}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "traffic": None,
           "flops": None}
    assert all(r(obs) is None for r in read.values())
    obs = {"counters": {"scope_ms": {"moe.router": 1.0, "moe.experts": 100.0,
                                     "moe.combine": 9.0, "optimizer": 20.0,
                                     "attn": 40.0},
                        "expert_unit_counts": [[10, 30], [20, 20]]},
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"], "cfg": cfg,
           "traffic": harness.load_json("traffic", "l4096.json"),
           "flops": harness.load_module("flops", "olmoe-1b-7b")}
    assert read["moe_ms"](obs) == 110.0
    assert read["moe_experts_ms"](obs) == 100.0 and read["optimizer_ms"](obs) == 20.0
    assert read["moe_max_load"](obs) == 1.5
    # 9.896 TFLOP at 197 TFLOP/s are 50.23 ms of the 100.
    assert read["moe_experts_roofline"](obs) == pytest.approx(50.23, abs=0.01)
    assert json.dumps({k: r(obs) for k, r in read.items()})
