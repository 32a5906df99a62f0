"""The `falcon-h1-34b` configuration's files: the cell's rehearsal, the cell,
its traffic and its metrics as the issue names them, the file against the
catalog's keys, the FLOP counts against hand values, `ssd_required` against a
count by hand at a small size, the runner's `Config` from the file and its
failure on a program without the fields, the checks' own arithmetic (a
dropped multiplier, a step that leaves the state unchanged, steps the wrong
way or skips a leaf, and bfloat16 in the scan's state, decay sums or step
sizes each read over their limit) and the three new readers."""

import dataclasses

import numpy as np
import pytest

import harness
from test_harness import _last_line, _run

CELL = "falcon-h1-34b-l8k"
NEW = ("ssm_ms", "ssd_ms", "ssd_roofline")
GAINED = ("tokens_per_s_chip", "optimizer_ms", "head_loss_ms", "kernel_calls",
          "attn_ms", "full_flash_ms", "full_flash_roofline")


@pytest.fixture(scope="module")
def spec():
    return harness.load_json("BENCHMARK.json", base=harness.ROOT)


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "falcon-h1-34b.json")


@pytest.fixture(scope="module")
def runner():
    return harness.load_module("runners", "step_tokens_ssm")


@pytest.fixture(scope="module")
def flops():
    return harness.load_module("flops", "falcon-h1-34b")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", "falcon-h1-34b")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(spec, trace):
    """The cell end to end at its rehearsal sizes (four two-branch layers,
    SGD, the reference on the check sample, the branches and the scan's
    probe): the checks hold, the last line names the CPU and holds no
    metric."""
    line = _last_line(_run(harness.ROOT, "--workload", CELL, "--seed",
                           "3000000023", "--seconds", "2", "--trace",
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
        assert {"hbm_program_gb", "compile_s", "kernel_calls"} <= reported
        assert set(NEW) | set(GAINED[1:]) <= listed
        assert not {"flash_ms", "flash_roofline", "kda_ms", "moe_ms"} & listed
    else:
        assert reported == listed - {"mfu"}


def test_the_cell_and_its_traffic_are_the_issues(spec):
    """By name, not by position: a later PR appends."""
    cell, config = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b", "l8k", 1)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == "benchmark/configs/falcon-h1-34b.json"
    assert config["source"] == ("https://huggingface.co/tiiuae/"
                                "Falcon-H1-34B-Instruct/blob/main/config.json")
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["config"] == "falcon-h1-34b" for w in spec["workloads"]) == 1
    # Eleven cells may have two on four chips (max(1, 11 // 4)).
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 4)
    mix = harness.load_json("traffic", "l8k.json")
    assert {k: mix[k] for k in ("generator", "batch", "seq_len",
                                "distinct_batches", "mesh", "trace")} == {
        "generator": "tokens", "batch": 1, "seq_len": 8192,
        "distinct_batches": 4, "mesh": {"dp": 1},
        "trace": {"after_steps": 3, "steps": 3}}
    assert (mix["rehearse"]["batch"], mix["rehearse"]["seq_len"]) == (1, 128)
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "tokens_per_s_chip"
        assert metrics[name]["source"] == "device_trace"
    assert metrics["ssm_ms"]["layer"] == "model step"
    assert metrics["ssd_ms"]["layer"] == metrics["ssd_roofline"]["layer"] \
        == "kernels"
    assert (metrics["ssd_roofline"]["unit"],
            metrics["ssd_roofline"]["better"]) == ("%", "higher")
    for name in GAINED:
        assert metrics[name]["workloads"][-1] == CELL, name
    assert sorted(m for m, entry in metrics.items()
                  if CELL in entry.get("workloads", ())) == sorted(
                      NEW + GAINED)


def test_the_file_holds_the_catalog_keys_at_every_width(cfg):
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    reduced = {"num_hidden_layers": 4, "vocab_size": 32640}
    assert cfg["reduced"] == list(reduced)
    assert cfg["published"] == {k: published[k] for k in reduced}
    for key, value in published.items():
        assert cfg[key] == reduced.get(key, value), key
    # An eighth of the vocabulary, whole tiles of 128; four layers, the floor.
    assert cfg["vocab_size"] * 8 == 261120 and cfg["vocab_size"] % 128 == 0
    for key in ("deployment", "why_reduced", "assumed", "why"):
        assert cfg[key], key
    assert cfg["run"] == {"dtype": "bfloat16", "attn": "flash",
                          "remat": cfg["run"]["remat"], "loss_chunk": 512,
                          "lr": 0.01}
    assert cfg["run"]["remat"] in ("dots", "full")
    assert cfg["check_sample"] == {"batch": 1, "seq_len": 512}


def test_flops_shares_against_hand_values(cfg, flops):
    """The issue's shares of a token's forward pass at L = 8,192."""
    parts = flops.forward_flops_per_token(cfg, 8192)
    total = sum(parts.values())
    assert round(total / 1e6) == 3962
    by_hand = {
        "ffn": 4 * 6 * 5120 * 21504,
        "ssm_projections": 4 * 2 * (5120 * 9248 + 4096 * 5120),
        "attn_projections": 4 * 2 * 5120 * 6144,
        "full_scores": 4 * 20 * 4 * 128 * 4096.5,
        "scan": 4 * 2 * (64.5 * (512 + 4096) + 2 * 32 * 128 * 256),
        "head": 2 * 5120 * 32640}
    assert parts == pytest.approx(by_hand)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"ffn": 66.7, "ssm_projections": 13.8, "head": 8.4,
                      "attn_projections": 6.4, "full_scores": 4.2,
                      "scan": 0.5}
    mix = harness.load_json("traffic", "l8k.json")
    step = 8192 * flops.required_flops_per_sample(cfg, mix)
    assert round(step / 1e12, 1) == 97.4
    assert flops.parameters(cfg) == 4 * 430_120_032 + 2 * 32640 * 5120 + 5120
    scores, moved = flops.full_scores_required(cfg, mix)
    assert scores == pytest.approx(3 * 8192 * by_hand["full_scores"])
    assert moved == 2 * 8192 * 4 * 2 * 24 * 128 * 2


def test_ssd_required_against_a_count_by_hand(flops):
    """Two heads of 4 channels in one group, a state of 8, chunks of 4, one
    layer, 8 tokens: counted product by product and array by array."""
    small = {"mamba_n_heads": 2, "mamba_d_head": 4, "mamba_n_groups": 1,
             "mamba_d_state": 8, "mamba_chunk_size": 4,
             "num_hidden_layers": 1}
    got_flops, got_bytes = flops.ssd_required(small, {"batch": 1, "seq_len": 8})
    # A chunk's rows meet 1, 2, 3, 4 rows: 10 pairs a chunk, two chunks.
    pairs = 2 * 10
    forward = (2 * pairs * 8            # C B^T, the group's, N = 8 wide
               + 2 * pairs * 2 * 4      # M U, two heads of P = 4
               + 8 * 2 * 2 * 4 * 8      # every token writes u B^T: H x P x N
               + 8 * 2 * 2 * 4 * 8)     # and reads S C
    assert got_flops == 3 * forward
    x = y = 8 * 2 * 4 * 2               # bfloat16
    b = c = 8 * 1 * 8 * 2
    dt = 8 * 2 * 4                      # float32
    states = 2 * 2 * 4 * 8 * 4          # two chunks' entry states, float32
    inputs = x + b + c + dt
    assert got_bytes == ((inputs + y + states)             # forward
                         + (inputs + y + states)           # backward reads
                         + inputs)                         # and writes


def test_the_published_scan_is_bound_by_bytes(cfg, flops):
    mix = harness.load_json("traffic", "l8k.json")
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    required, moved = flops.ssd_required(cfg, mix)
    assert moved / (8192 * 4) == 113024
    assert (moved / peaks["hbm_bytes_per_s"]
            > required / peaks["bf16_flops_per_s"])
    assert round(1e3 * moved / peaks["hbm_bytes_per_s"], 1) == 4.5


def test_the_runner_builds_the_models_config(cfg, runner):
    from torchmpi_tpu.models import llama

    model = runner._model(cfg)
    assert model == dataclasses.replace(
        llama.falcon_h1_34b(), vocab=32640, n_layers=4,
        layer_kinds=(("attn+ssm", "dense"),) * 4)
    assert runner.SCOPES.index("ssd") < runner.SCOPES.index("ssm")
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        runner._model(dict(cfg, mamba_norm_before_gate=True))


def test_a_program_without_the_fields_fails_at_once(cfg, runner, monkeypatch):
    """The parent of this configuration's PR: `llama.Config` takes no
    `ssm_heads`, and the runner stops before it touches the device."""
    from torchmpi_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Parent:
        vocab: int = 0
        d_model: int = 0

    monkeypatch.setattr(llama, "Config", Parent)
    with pytest.raises(TypeError, match="unexpected keyword"):
        runner._model(cfg)


def test_a_dropped_multiplier_reads_over_the_branch_limit(runner, reference):
    """`branch_errors` on made-up contributions: a branch as the reference
    has it reads 0, with its output multiplier dropped 1 / 0.088 - 1 of its
    own norm, left out 1: both far over the limit, where the branch's share
    of the residual, 0.088 of a unit product, would hide in the logits'."""
    rng = np.random.default_rng(0)
    theirs = {name: rng.standard_normal((4, 1, 16, 8)).astype(np.float32)
              for name in ("attn", "ssm", "ffn")}
    limit = reference.MORE_TOLERANCE["branch_rel_max"]
    assert max(runner.branch_errors(theirs, theirs).values()) == 0
    dropped = dict(theirs, ssm=theirs["ssm"] / 0.08838834764831845)
    found = runner.branch_errors(dropped, theirs)
    assert float(found["ssm.first"]) == pytest.approx(
        1 / 0.08838834764831845 - 1, rel=1e-5)
    assert found["ssm.last"] > limit and found["attn.first"] == 0
    absent = dict(theirs, ffn=np.zeros_like(theirs["ffn"]))
    assert runner.branch_errors(absent, theirs)["ffn.last"] == 1.0 > limit


# ------------------------------------------- the step that is timed, planted

@pytest.fixture(scope="module")
def one_step(cfg, runner, reference):
    """What the runner's check (c) sees at toy widths, for a `step` of the
    test's choosing: {name: `|ours - theirs| / |theirs - seeded|`} of one
    step from seeded weights against the reference's on its own gradient."""
    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import make_mesh

    small = harness.rehearsed(cfg)
    model, lr = runner._model(small), small["run"]["lr"]
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    seeded = lambda: llama.init(jax.random.PRNGKey(0), model,
                                dtype=jnp.bfloat16)
    rng = np.random.default_rng(1)
    sample = tuple(jnp.asarray(rng.integers(0, model.vocab, (1, 64)),
                               jnp.int32) for _ in range(2))
    wanted = jax.jit(lambda p: reference.sgd_first_step(
        p, reference.loss_and_grads(small, p, sample)[2], lr))(seeded())

    def errors(make_step=llama.make_train_step, lr=lr, floor=1 << 12,
               moved=1):
        step = make_step(model, mesh, lr=lr, attn="full", remat="full",
                         loss_chunk=32)
        stepped = step(seeded(), None, *sample)[0]
        old = runner.UPDATE_LEAF_MIN, runner.UPDATE_MOVED_MIN
        runner.UPDATE_LEAF_MIN, runner.UPDATE_MOVED_MIN = floor, moved
        try:
            found, numbers = runner.update_errors(
                seeded(), stepped, wanted, reference.LEAF_AXES)
            return {k: float(v) for k, v in runner.update_read(
                found, {k: int(n) for k, n in numbers.items()}).items()}
        finally:
            runner.UPDATE_LEAF_MIN, runner.UPDATE_MOVED_MIN = old

    return errors


def test_the_timed_steps_update_reads_under_its_limit(one_step, reference):
    """The step as it is: every large leaf and the whole tree under the
    limit, and far from 0 (bfloat16 weights at this rate move in a few of
    their numbers, and which ones rests on the last bit of a gradient)."""
    found = one_step()
    assert {"all", "embed", "head", "layers/0/w_down"} <= set(found)
    assert "layers/0/attn_norm" not in found
    assert max(found.values()) < reference.MORE_TOLERANCE["update_rel_max"]
    # a leaf the reference's step moves in fewer numbers than the floor is
    # not read by itself: one number over a rounding edge is no reading
    assert set(one_step(moved=1 << 30)) == {"all"}


@pytest.mark.parametrize("fault,reads", [
    ("unchanged", 1.0), ("wrong_sign", 2.0), ("a_leaf_left_out", 1.0)])
def test_a_planted_step_fault_reads_over_the_update_limit(
        one_step, reference, fault, reads):
    """A state handed back unchanged reads 1 everywhere, a step up the
    gradient 2 or nearly, a leaf left out of the step 1 on that leaf: `ok`
    comes out false by `update_rel_max`, and nothing else the runner compares
    ever sees the step."""
    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.models import llama

    def planted(model, mesh, lr, **kinds):
        if fault == "wrong_sign":
            return llama.make_train_step(model, mesh, lr=-lr, **kinds)
        step = llama.make_train_step(model, mesh, lr=lr, **kinds)

        def faulty(params, state, tokens, targets):
            before = jax.tree.map(jnp.copy, params)  # the step takes `params`
            stepped, state, loss = step(params, state, tokens, targets)
            if fault == "unchanged":
                return before, state, loss
            stepped["layers"][0]["wo"] = before["layers"][0]["wo"]
            return stepped, state, loss

        return faulty

    found = one_step(planted)
    limit = reference.MORE_TOLERANCE["update_rel_max"]
    worst = max(found, key=found.get)
    assert found[worst] == pytest.approx(reads, rel=0.25) and reads > limit
    if fault == "a_leaf_left_out":
        assert worst == "layers/0/wo" and found["all"] < limit
    else:   # (at toy widths some leaves move in none of their numbers: 0)
        assert found["all"] > limit
        assert min(v for v in found.values() if v) > limit


@pytest.mark.parametrize("fault,over", [
    ("unchanged", "update_rel_max"), ("wrong_sign", "update_rel_max"),
    ("dt_bf16", "scan_f32_rel_max")])
def test_the_harness_says_not_correct_with_a_fault_planted(reference, fault,
                                                           over):
    """The cell's rehearsal through `run.py` itself with one fault planted in
    the program (`planted_fault.py`, which the chip's controls run at the
    published widths): the checks do not pass, by the limit that is there
    for that fault."""
    import ast
    import os
    import re
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    done = subprocess.run(
        [sys.executable, os.path.join(here, "planted_fault.py"), fault,
         "--workload", CELL, "--seed", "3000000029", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=harness.ROOT, text=True,
        capture_output=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "false"})
    line = _last_line(done)
    assert line["rehearsal"]["checks_passed"] is False
    found = ast.literal_eval(re.search(
        r"^benchmark: reference check: (\{.*\})$", done.stderr,
        re.M).group(1))
    limits = {**reference.TOLERANCE, **reference.MORE_TOLERANCE}
    assert found[over] > limits[over]
    if fault != "dt_bf16":      # nothing else ever sees the step
        assert [k for k in limits if found[k] > limits[k]] == [over]


# --------------------------------------------------------- the scan's probe

FAULTS = ("states", "leaving", "decay_sums", "dt")


@pytest.fixture(scope="module")
def probed(cfg, runner, reference):
    """{fault or "sound": `scan_errors` of the probe at toy head shapes}."""
    import jax
    import jax.numpy as jnp

    from torchmpi_tpu.ops import ssd

    small = dict(cfg, mamba_n_heads=4, mamba_d_head=8, mamba_n_groups=2,
                 mamba_d_state=16)
    probe = tuple(jnp.asarray(a) for a in runner.scan_probe(
        small, 5, 512, jnp.bfloat16))
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    carried, local = ssd._entry_states, ssd._local

    def sums_rounded(*inputs):
        cum, _, u, cb, _ = local(*inputs)
        rows = jnp.moveaxis(rounded(cum), 2, -1)
        seen = jnp.tril(jnp.ones((rows.shape[-1],) * 2, bool))
        return (rounded(cum), rounded(cum)[:, :, -1], u, cb, jnp.exp(jnp.where(
            seen, rows[..., :, None] - rows[..., None, :], -jnp.inf)))

    patches = {
        "sound": {},
        "states": {"_entry_states": lambda last, wrote, reverse=False: (
            carried(last, wrote, True) if reverse
            else rounded(carried(last, wrote)))},
        "leaving": {"_entry_states": lambda last, wrote, reverse=False: (
            rounded(carried(last, wrote, True)) if reverse
            else carried(last, wrote))},
        "decay_sums": {"_local": sums_rounded},
        "dt": {},
    }
    found = {}
    for fault, patch in patches.items():
        scan = lambda x, dt, *more: ssd.ssd(
            x, rounded(dt) if fault == "dt" else dt, *more, chunk=16)
        try:
            for name, planted in patch.items():
                setattr(ssd, name, planted)
            found[fault] = {k: float(v) for k, v in runner.scan_errors(
                scan, reference.scan, probe).items()}
        finally:
            ssd._entry_states, ssd._local = carried, local
    return found


def test_the_probe_reads_float32s_own_rounding_on_the_scan_as_it_is(
        runner, reference, probed):
    limits = reference.MORE_TOLERANCE
    sound = probed["sound"]
    assert max(sound[k] for k in runner.SCAN_ROUNDED) * 10 \
        < limits["scan_rel_max"]
    assert max(sound[k] for k in runner.SCAN_FLOAT32) * 3 \
        < limits["scan_f32_rel_max"]
    # What the chunked form rounds by design reaches dB and dC alone.
    assert min(sound["dB"], sound["dC"]) > limits["scan_rel_max"]


def test_a_number_carried_over_a_rounding_edge_costs_the_noise_not_a_unit(
        runner):
    """`beyond_rounding`: theirs a hair under the edge between two bfloat16
    numbers, ours the upper neighbour (its own sum a hair over): twice the
    hair, not the unit in the last place; ours a unit off where theirs is no
    edge: the unit less twice the rounding; float32 ours: the difference."""
    import jax.numpy as jnp

    unit, hair = 2.0 ** -7, 2.0 ** -22              # bfloat16's at 1.0
    theirs = jnp.asarray([1 + unit / 2 - hair, 1 + unit / 8, 3.0],
                         jnp.float32)
    ours = jnp.asarray([1 + unit, 1 + unit, 3.0], jnp.bfloat16)
    found = np.asarray(runner.beyond_rounding(ours, theirs))
    assert found == pytest.approx([2 * hair, unit * 3 / 4, 0], rel=1e-6)
    assert np.asarray(runner.beyond_rounding(
        ours.astype(jnp.float32), theirs)) == pytest.approx(
            [unit / 2 + hair, unit * 7 / 8, 0], rel=1e-6)


@pytest.mark.parametrize("fault", FAULTS)
def test_bfloat16_in_the_scan_reads_over_both_limits(runner, reference,
                                                     probed, fault):
    """The state that enters a chunk, the cotangent of the state that leaves
    it, the decay sums and the step sizes, each rounded to bfloat16 where the
    program has float32: over the limit of the rounded outputs or of the
    float32 ones, and all but one over both."""
    limits, found = reference.MORE_TOLERANCE, probed[fault]
    rounded = max(found[k] for k in runner.SCAN_ROUNDED)
    float32 = max(found[k] for k in runner.SCAN_FLOAT32)
    assert float32 > 10 * limits["scan_f32_rel_max"]
    assert rounded > 3 * limits["scan_rel_max"]


def test_the_new_readers(cfg, flops):
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    mix = harness.load_json("traffic", "l8k.json")
    read = {name: harness.load_module("layers", name).read for name in NEW}
    obs = {"counters": {"scope_ms": {"ssd": 45.0, "ssm": 30.0, "attn": 60.0}},
           "flops": flops, "cfg": cfg, "traffic": mix, "peaks": peaks}
    assert read["ssm_ms"](obs) == 30.0 and read["ssd_ms"](obs) == 45.0
    # 3.70 GB over 819 GB/s is 4.52 ms: a tenth of 45.
    assert read["ssd_roofline"](obs) == pytest.approx(10.05, abs=0.01)
    # A parent has no such scope, a rehearsal no peaks: nothing, never zero.
    for counters in ({}, {"scope_ms": {"attn": 60.0}}):
        empty = dict(obs, counters=counters)
        assert all(read[name](empty) is None for name in NEW)
    assert read["ssd_roofline"](dict(obs, peaks=None)) is None
    assert read["ssd_roofline"](dict(obs, flops=object())) is None
