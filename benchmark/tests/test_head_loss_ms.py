"""`head_loss_ms`: the reader of the `head_loss` scope of the OLMoE runner's
join, and its place in `BENCHMARK.json`."""

import json

import harness

CELL = "olmoe-1b-7b-l4096"


def test_the_reader_returns_none_where_there_is_nothing():
    """On a run without a join (`--trace 0`, a CPU capture, an executable
    without the names) the metric is left out, never zero."""
    read = harness.load_module("layers", "head_loss_ms").read
    assert read({"counters": {}}) is None
    assert read({"counters": {"scope_ms": {}}}) is None
    assert read({"counters": {"scope_ms": {"optimizer": 20.0}}}) is None


def test_the_reader_takes_the_scope_of_the_join():
    read = harness.load_module("layers", "head_loss_ms").read
    obs = {"counters": {"scope_ms": {"moe.experts": 100.0, "optimizer": 20.0,
                                     "head_loss": 61.5, "attn": 40.0}}}
    assert read(obs) == 61.5
    assert json.dumps({"head_loss_ms": read(obs)})


def test_the_runner_joins_the_scope_it_reads():
    """`head_loss` is one of the scopes `step_tokens_adamw` attributes, and an
    instruction of the gradient products (`jvp(head_loss)/while/body/...`, as
    the program names them since they moved into the chunk's forward pass)
    joins it."""
    runner = harness.load_module("runners", "step_tokens_adamw")
    assert "head_loss" in runner.SCOPES
    assert runner._scope_of(
        "jit(step)/jvp(head_loss)/while/body/closed_call/"
        "bcd,bcv->dv/dot_general") == "head_loss"


def test_its_entry_in_the_benchmark():
    spec = harness.load_json("BENCHMARK.json", base=harness.ROOT)
    entry = spec["per_layer"][-1]
    assert entry == {"name": "head_loss_ms", "unit": "ms/step",
                     "better": "lower", "source": "device_trace",
                     "layer": "model step", "moves": "tokens_per_s_chip",
                     "workloads": [CELL]}
    assert entry in harness.metrics_of(spec, "per_layer", CELL)
    assert entry not in harness.metrics_of(spec, "per_layer",
                                           "mixtral-8x7b-l4096")
