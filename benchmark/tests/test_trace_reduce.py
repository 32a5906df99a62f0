"""`trace_reduce.py` on a trace small enough to work out by hand, and on a
recorded piece of a real one."""

import json
import os

import pytest

import trace_reduce as tr

US = 1000   # the hand-made trace is written in microseconds

# One device.  Times in microseconds, counted from T0 = 2000, where the first
# WHOLE step starts: the capture began inside a step, whose end (0-500, one
# loop fusion) is the first execution of the program and is left out.
#   0-100     A  output fusion (a matrix product)
#   150-450   a while loop spanning its body:
#   160-260     B  convolution
#   270-330     C  all-reduce, synchronous, nothing beside it
#   340-440     D  loop fusion
#   1000-1200 E  Mosaic kernel
#   1350-1500 F  loop fusion
#   1100-1400 an asynchronous all-reduce, from its start to its done
T0 = 2000
ORIGIN = 1_790_000_000_000_000      # profile_start_time, microseconds
HAND = {"/device:TPU:0": {
    "XLA Ops": [
        ('%fusion.9 = bf16[8]{0} fusion(%z), kind=kLoop, calls=%fused.9', -T0, 500),
        ('%fusion.1 = bf16[8,8]{1,0} fusion(%p.0, %p.1), kind=kOutput, calls=%fused', 0, 100),
        ('%while.1 = (s32[], bf16[8]{0}) while(%tuple.3), condition=%cond, body=%body', 150, 300),
        ('%convolution.2 = bf16[8,8]{1,0} convolution(%a, %b), dim_labels=bf_io->bf', 160, 100),
        ('%all-reduce.3 = f32[64]{0} all-reduce(%x), replica_groups={{0,1,2,3}}', 270, 60),
        ('%fusion.4 = bf16[8]{0} fusion(%y), kind=kLoop, calls=%fused.1', 340, 100),
        ('%checkpoint.5 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call"', 1000, 200),
        ('%fusion.8 = bf16[8]{0} fusion(%z), kind=kLoop, calls=%fused.2', 1350, 150),
    ],
    "Async XLA Ops": [
        ('%all-reduce-start.7 = f32[128]{0} all-reduce-start(%g)', 1100, 300),
    ],
    "XLA Modules": [("jit_step(123)", -T0, 500), ("jit_step(123)", 0, 450),
                    ("jit_step(123)", 1000, 500), ("jit_other(7)", 460, 1)],
}}
SPANS = [("bench.next_batch", 90, 160), ("bench.engine_step", 160, 900),
         ("bench.fence", 1190, 1400)]


def _us(devices):
    return {"profile_start_ns": ORIGIN * US, "devices": {
        p: {l: [(n, (s + T0) * US, d * US) for n, s, d in evs]
            for l, evs in lines.items()} for p, lines in devices.items()}}


def test_hand_made_trace():
    r = tr.reduce(_us(HAND), [(n, (ORIGIN + T0 + s) * US,
                               (ORIGIN + T0 + e) * US) for n, s, e in SPANS])
    us = lambda seconds: round(seconds * 1e6, 6)
    assert r["devices"] == 1 and r["steps"] == 2
    assert us(r["window_s"]) == 1500
    # A 100 + the while 300 + E 200 + F 150.
    assert us(r["busy_s"]) == 750
    # Self time: the while keeps 300 - (100 + 60 + 100) = 40.
    assert {k: us(v) for k, v in r["categories"].items()} == {
        "fusion: output": 100, "while": 40, "convolution": 100,
        "collective: all-reduce": 60, "fusion: loop": 250, "Mosaic kernel": 200}
    assert us(r["op_self_s"]) == 750
    assert us(r["matmul_conv_s"]) == 200        # A and B
    assert us(r["mosaic_s"]) == 200
    # C 270-330 and the asynchronous one 1100-1400.
    assert us(r["collective_s"]) == 360
    # C has no compute beside it (the while is no compute): 60.  Of the
    # asynchronous one, E hides 1100-1200 and F 1350-1400: 150 are left.
    assert us(r["collective_exposed_s"]) == 210
    # Gaps 100-150, 450-1000 and 1200-1350, each named by the span that
    # covers most of it.
    assert {k: us(v) for k, v in r["breakdown"]["idle_gaps"]} == {
        "bench.engine_step": 550, "bench.fence": 150, "bench.next_batch": 50}
    assert r["breakdown"]["device_ops"][0] == ["fusion: loop", pytest.approx(250e-6)]


def test_two_devices_are_averaged():
    two = _us(HAND)
    two["devices"]["/device:TPU:1"] = {
        "XLA Ops": [("%fusion.1 = f32[] fusion(), kind=kLoop", 0, 600 * US)],
        "XLA Modules": [("jit_step(123)", 0, 100 * US),
                        ("jit_step(123)", 100 * US, 500 * US)]}
    r = tr.reduce(two)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((750e-6 + 500e-6) / 2)
    assert r["window_s"] == pytest.approx((1500e-6 + 500e-6) / 2)
    assert r["breakdown"]["idle_gaps"] == [["no span of the runner",
                                            pytest.approx(750e-6 / 2)]]


def test_nothing_ran_on_a_device():
    empty = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {"XLA Ops": []}}}
    assert tr.reduce(empty) is None
    assert tr.reduce({"profile_start_ns": 0, "devices": {}}) is None
    one_step = {"profile_start_ns": 0, "devices": {"/device:TPU:0": {
        "XLA Ops": [("%a = f32[] add()", 0, 5)],
        "XLA Modules": [("jit_step(1)", 0, 5)]}}}
    assert tr.reduce(one_step) is None      # no whole step


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [(-5, 2), (8, 12)], [(2, 8)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 10)]) == [(0, 4), (5, 6)]


@pytest.mark.parametrize("name, category, matmul", [
    ("%convolution.12 = bf16[4,8]{1,0} convolution(%a, %b)", "convolution", True),
    ("%fusion.3 = bf16[4]{0} fusion(%a), kind=kOutput, calls=%f", "fusion: output", True),
    ("%convolution_fusion.1 = bf16[4]{0} fusion(%a), kind=kOutput", "convolution", True),
    ("%multiply_subtract_fusion.7 = bf16[4]{0} fusion(%a), kind=kLoop", "fusion: multiply_subtract", False),
    ("%all-reduce-start.1 = f32[4]{0} all-reduce-start(%a)", "collective: all-reduce-start", False),
    ("%copy-start.4 = (bf16[4]{0}) copy-start(%a)", "async DMA (copy/slice)", False),
    ('%closed_call.2 = bf16[4]{0} custom-call(%a), custom_call_target="tpu_custom_call"', "Mosaic kernel", False),
])
def test_categorize(name, category, matmul):
    assert tr.categorize(name) == category
    assert tr.is_matmul_or_conv(name) is matmul


RECORDED = os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json")


def test_recorded_trace_against_a_sweep():
    """A piece of a real capture (TPU v5 lite, PR 22): the interval arithmetic
    against a sweep over the sorted starts and ends that counts how many
    events cover each stretch."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    trace = {"profile_start_ns": rec["profile_start_ns"], "devices": {
        p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
        for p, lines in rec["devices"].items()}}
    r = tr.reduce(trace)
    lines = trace["devices"]["/device:TPU:0"]
    t0, t1, steps = tr.whole_steps(lines["XLA Modules"])
    assert steps == rec["steps"] == r["steps"]
    # The whole step is the second execution, 531 ms; the first is cut short.
    assert (t1 - t0) / 1e6 == pytest.approx(531.02, abs=0.01)
    assert lines["XLA Modules"][0][2] < t1 - t0
    edges = sorted([(max(s, t0), 1) for _, s, d in lines["XLA Ops"] if s + d > t0]
                   + [(min(s + d, t1), -1) for _, s, d in lines["XLA Ops"]
                      if s + d > t0])
    covered = depth = 0
    for (at, step), (nxt, _) in zip(edges, edges[1:]):
        depth += step
        if depth > 0:
            covered += nxt - at
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert r["busy_s"] == pytest.approx(covered / 1e9, rel=1e-9)
    # One core runs one operation at a time: self times add up to the union.
    assert r["op_self_s"] == pytest.approx(r["busy_s"], rel=1e-9)
    assert sum(r["categories"].values()) == pytest.approx(r["op_self_s"])
    # By hand from the capture: the four flash kernels of a step are 10.14,
    # 7.28, 6.00 and 5.99 ms, and nothing idles between operations.
    assert 1e3 * r["mosaic_s"] == pytest.approx(29.41, abs=0.02)
    assert 1 - r["busy_s"] / r["window_s"] < 1e-4
    assert r["collective_s"] == 0 and r["breakdown"]["idle_gaps"] == []
