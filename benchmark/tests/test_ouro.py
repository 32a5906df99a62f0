"""The `ouro-2.6b` configuration's files: the cell's rehearsal, the file
against the catalog's keys, the FLOP count against a hand count, the plain
reference against the system at the rehearsal sizes, the runner's join of a
capture to the scopes of its executable, the recomputed layer applications
read off an executable's text, the timed step's two differences on plain data,
and the per-layer readers that list the cell."""

import json

import pytest

import harness
import trace_reduce
from test_harness import _last_line, _run
from test_olmoe import _ns

CELL = "ouro-2.6b-b2-l4096"


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "ouro-2.6b.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_looped")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (five layers, so the layer
    scan runs, four recurrent steps, a remat policy for each, AdamW): the
    checks hold, the last line names the CPU and holds no metric."""
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
        assert {"hbm_program_gb", "compile_s"} <= reported
        assert {"ut_stack_ms", "ut_stack_roofline", "ut_exit_ms",
                "head_loss_ms", "optimizer_ms", "flash_ms",
                "flash_roofline"} <= listed
    else:
        assert reported == listed - {"mfu"}


def test_the_cell_is_the_issues(spec):
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "b2-l4096", 1)
    mix = harness.load_json("traffic", "b2-l4096.json")
    assert {k: mix[k] for k in ("generator", "batch", "seq_len",
                                "distinct_batches", "mesh", "trace")} == {
        "generator": "tokens", "batch": 2, "seq_len": 4096,
        "distinct_batches": 4, "mesh": {"dp": 1},
        "trace": {"after_steps": 2, "steps": 2}}
    assert config["reduced"] == ["num_hidden_layers"]


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    differs = [k for k, v in published.items() if cfg[k] != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 8
    assert cfg["run"]["optimizer"]["moments_dtype"] == "float32"
    assert {"sandwich_norm", "final_norm", "exit_gate", "loss",
            "exit_entropy_coef", "attention_bias", "rope", "optimizer",
            "context"} <= set(cfg["assumed"])
    small = harness.rehearsed(cfg)
    assert small["total_ut_steps"] == 4 == len(small["run"]["remat"])
    assert small["num_hidden_layers"] > 4        # the layer scan, as the cell


def test_flops_against_a_hand_count(cfg):
    """Eight layers run four times at L=4096, by hand from the shapes."""
    flops = harness.load_module("flops", "ouro-2.6b")
    mix = harness.load_json("traffic", "b2-l4096.json")
    parts = flops.forward_flops_per_token(cfg, 4096)
    assert parts["head"] == 4 * 2 * 2048 * 49152 == 805_306_368
    assert parts["swiglu"] == 32 * 3 * 2 * 2048 * 5632 == 2_214_592_512
    assert parts["attention_projections"] == 32 * 4 * 2 * 2048 * 2048
    assert parts["attention_scores"] == 32 * 2 * 2048 * 4097
    assert parts["exit_gate"] == 4 * 2 * 2048
    assert sum(parts.values()) == 4_630_659_072
    assert flops.required_flops_per_sample(cfg, mix) == 3 * 4_630_659_072
    share = lambda k: round(100 * parts[k] / sum(parts.values()), 1)
    assert (share("head"), share("swiglu"), share("attention_projections"),
            share("attention_scores")) == (17.4, 47.8, 23.2, 11.6)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    total, active = flops.parameters(cfg)
    assert total == active == 8 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1 \
        == 612_438_017
    s_flops, s_bytes = flops.stack_required(cfg, mix)
    assert s_flops == 3 * 8192 * (parts["attention_projections"]
                                  + parts["attention_scores"]
                                  + parts["swiglu"])
    assert s_flops / 197e12 > s_bytes / 819e9            # bound by FLOPs
    f_flops, _ = flops.flash_required(cfg, mix)
    assert f_flops == 32 * 6 * 2 * 128 * (2 * 16 * 4096 * 4097 // 2)
    assert f_flops == 3 * 8192 * parts["attention_scores"]


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
    assert (model.ut_steps, model.sandwich_norm, model.exit_gate,
            model.exit_entropy_coef) == (4, True, True, 0.1)
    params = llama.init(jax.random.PRNGKey(5), model)
    sample = tuple(jnp.asarray(a) for a in traffic.tokens(
        {}, small, 6, n_batches=1, **small["check_sample"])[0])
    assert sample[0].shape == (2, 128)      # the timed rows, two head chunks
    reference = harness.load_module("reference", "ouro-2.6b")
    kinds = dict(attn="flash", remat=small["run"]["remat"])
    loss_fn = llama.make_loss_fn(model, loss_chunk=64, **kinds)

    def system(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p, s)
        return (loss, llama.apply(model, p, s[0], all_steps=True, **kinds),
                reference.compared(grads))

    def plain(p, s):
        loss, logits, grads = reference.loss_and_grads(small, p, s)
        return loss, logits, reference.compared(grads)

    found = compare.check(system, plain, params, sample, reference.TOLERANCE,
                          reference.LEAF_AXES)
    assert found["ok"], found
    assert all(found[k] < 1e-4 for k in reference.TOLERANCE), found
    assert set(reference.LEAF_AXES) == {"layers/" + k for k in params["layers"]}


# A looped step in little: a recurrent step's layer scan with both kernels
# and a recomputed forward, an inlined recomputed kernel, the per-step norm,
# the gate, the head, the optimizer, and a copy under no scope.
HLO = '''HloModule jit_step

%fused_computation.2 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(final_norm)/mul"}
  ROOT %a.1 = bf16[8]{0} add(%m.1, %p), metadata={op_name="jit(step)/jvp(final_norm)/add"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %custom-call.1 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call"}
  %custom-call.2 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn/flash_fwd/pallas_call"}
  %custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/flash_bwd/pallas_call"}
  %custom-call.4 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn/flash_fwd/pallas_call"}
  %fusion.2 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %fusion.5 = bf16[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(step)/jvp()/while/body/closed_call/ffn/dot_general"}
  %fusion.6 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/transpose(jvp(exit_gate))/mul"}
  %fusion.7 = bf16[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step)/jvp(head_loss)/while/body/dot_general"}
  ROOT %fusion.8 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(step)/optimizer/mul"}
}
'''
T0 = 5000
CAPTURE = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_step(1)", 0, 900), ("jit_step(1)", T0, 1000),
                    ("jit_step(1)", T0 + 1000, 1000)],
    "XLA Ops": [(name, T0 + step * 1000 + start, dur) for step in (0, 1)
                for name, start, dur in [
        ('%custom-call.1 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call"', 0, 100),
        ('%custom-call.3 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call"', 100, 200),
        ("%fusion.2 = bf16[8]{0} fusion(%x), kind=kLoop", 300, 40),
        ("%fusion.5 = bf16[8]{0} fusion(%x), kind=kOutput", 340, 360),
        ("%fusion.6 = bf16[8]{0} fusion(%x), kind=kLoop", 700, 10),
        ("%fusion.7 = bf16[8]{0} fusion(%x), kind=kOutput", 710, 150),
        ("%fusion.8 = bf16[8]{0} fusion(%x), kind=kLoop", 860, 50),
        ("%copy.9 = bf16[8]{0} copy(%x)", 910, 30)]],
}}}


def test_the_join_of_a_capture_to_its_scopes(runner):
    scopes = runner.instruction_scopes(HLO)
    assert scopes["fusion.2"] == "final_norm" and scopes["fusion.6"] == "exit_gate"
    assert scopes["custom-call.3"] == "attn" and scopes["fusion.5"] == "ffn"
    found = runner.scope_ms(_ns(CAPTURE), HLO, trace_reduce)
    # Two whole steps (the first execution is left out); self times a step in
    # microseconds.
    assert {k: round(v * 1000) for k, v in found.items()} == {
        "attn": 300, "final_norm": 40, "ffn": 360, "exit_gate": 10,
        "head_loss": 150, "optimizer": 50, "unnamed": 30}
    # An executable without the names (the compile-cache trap): nothing.
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    assert runner.scope_ms(_ns(CAPTURE), bare, trace_reduce) == {}
    # The scopes are an argument: another list joins the same text otherwise.
    assert set(runner.instruction_scopes(HLO, ("optimizer",)).values()) == {
        "optimizer"}


def test_recomputed_layer_applications_from_the_text(runner):
    """One recomputed forward kernel inside a layer scan counts for every
    layer, an inlined one for itself; none where there is no forward kernel."""
    assert runner.recomputed_layer_applications(HLO, 8) == 8 + 1
    assert runner.recomputed_layer_applications("ENTRY %main {}", 8) is None


def test_the_timed_steps_differences_on_plain_data(runner):
    """The loss relative to the larger side; of the leaves' change norms the
    largest relative difference, a stack leaf layer by layer; NaN is worst."""
    import numpy as np

    want = {"embed": np.float32(2.0), "layers/wq": np.array([1.0, 4.0])}
    got = {"embed": np.float32(2.0), "layers/wq": np.array([1.0, 3.0])}
    found = runner.step_differences(10.0, 10.1, got, want)
    assert found["step_loss_rel"] == pytest.approx(0.1 / 10.1)
    assert found["update_norm_rel_max"] == pytest.approx(0.25)
    assert found["update_worst_leaf"] == "layers/wq"
    assert (found["step_loss_system"], found["step_loss_reference"]) == (
        10.0, 10.1)
    got["embed"] = np.float32("nan")
    found = runner.step_differences(10.0, 10.0, got, want)
    assert found["update_worst_leaf"] == "embed"
    assert not found["update_norm_rel_max"] <= 1e9


def test_the_readers_on_a_recorded_join(cfg):
    """On a program without the scopes, as the parent of the PR that brought
    them is, a reader finds nothing and the metric is left out; `flash_ms`
    and `flash_roofline` read the trace's Mosaic time, which in this cell is
    the flash kernels'."""
    read = {name: harness.load_module("layers", name).read for name in (
        "ut_stack_ms", "ut_stack_roofline", "ut_exit_ms", "head_loss_ms",
        "optimizer_ms", "flash_ms", "flash_roofline")}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "traffic": None,
           "flops": None, "trace": None}
    assert all(r(obs) is None for r in read.values())
    obs = {"counters": {"scope_ms": {"attn": 280.0, "ffn": 480.0,
                                     "final_norm": 8.0, "head_loss": 130.0,
                                     "exit_gate": 1.25, "optimizer": 20.0,
                                     "unnamed": 90.0}},
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"], "cfg": cfg,
           "traffic": harness.load_json("traffic", "b2-l4096.json"),
           "flops": harness.load_module("flops", "ouro-2.6b"),
           "trace": {"steps": 2, "mosaic_s": 0.32}}
    assert read["ut_stack_ms"](obs) == 768.0
    assert read["head_loss_ms"](obs) == 130.0
    assert read["optimizer_ms"](obs) == 20.0
    assert read["ut_exit_ms"](obs) == 1.25
    # 94.01 TFLOP at 197 TFLOP/s are 477.2 ms of the 768.
    assert read["ut_stack_roofline"](obs) == pytest.approx(62.14, abs=0.01)
    assert read["flash_ms"](obs) == pytest.approx(160.0)
    # 13.20 TFLOP of scores at 197 TFLOP/s are 67.0 ms of the 160.
    assert read["flash_roofline"](obs) == pytest.approx(41.9, abs=0.1)
    assert json.dumps({k: r(obs) for k, r in read.items()})
