"""The reader of a device capture (``utils/profiler.py``: ``instruction_labels``,
``step_profile``, ``StepProfile``) on plain data: a text of a dozen
instructions and a capture small enough to work out by hand, a piece cut from
the Kimi Linear cell's real executable, and the benchmark runners' join on the
same lists.  Nothing here starts a profiler."""

import importlib.util
import json
import os
import re

import pytest

from torchmpi_tpu.utils import profiler as prof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "step_profile_data")
US = 1000       # the hand-made capture is written in microseconds
T0 = 2000       # where the first WHOLE step starts
ORIGIN = 1_790_000_000_000_000      # profile_start_time, microseconds

J = "jit(step)/"
REMAT = J + "transpose(jvp(jvp()))/checkpoint/rematted_computation/"
BACK = J + "transpose(jvp(jvp()))/checkpoint/"


def _meta(op_name):
    return f'metadata={{op_name="{op_name}" stack_frame_id=7}}'


# A fusion that votes (two of `mla`, one of `attn`), one that holds two
# passes, one whose vote is tied, a `while` with a convolution, a synchronous
# all-reduce and a fusion in its body, a kernel in a replayed window layer, an
# all-to-all whose NAME is the primitive's, an asynchronous all-reduce, the
# optimizer, and a copy nobody named.
TEXT = f"""HloModule jit_step, is_scheduled=true

%fused.vote (p: bf16[8,8]) -> bf16[8,8] {{
  %p = bf16[8,8]{{1,0}} parameter(0)
  %dot.1 = bf16[8,8]{{1,0}} dot(%p, %p), {_meta(J + "jvp(attn)/mla/dot_general")}
  %mul.1 = bf16[8,8]{{1,0}} multiply(%dot.1, %p), {_meta(J + "jvp(attn)/mla/mul")}
  ROOT %add.1 = bf16[8,8]{{1,0}} add(%mul.1, %p), {_meta(J + "jvp(attn)/add")}
}}

%fused.two (p.1: bf16[8]) -> bf16[8] {{
  %p.1 = bf16[8]{{0}} parameter(0)
  %exp.1 = bf16[8]{{0}} exponential(%p.1), {_meta(REMAT + "ffn/exp")}
  ROOT %mul.2 = bf16[8]{{0}} multiply(%exp.1, %p.1), {_meta(BACK + "ffn/mul")}
}}

%fused.tie (p.2: bf16[8]) -> bf16[8] {{
  %p.2 = bf16[8]{{0}} parameter(0)
  %mul.3 = bf16[8]{{0}} multiply(%p.2, %p.2), {_meta(J + "jvp(ssm)/ssd/mul")}
  ROOT %add.3 = bf16[8]{{0}} add(%mul.3, %p.2), {_meta(J + "jvp(attn)/add")}
}}

%fused.opt (p.3: bf16[8]) -> bf16[8] {{
  %p.3 = bf16[8]{{0}} parameter(0)
  ROOT %sub.4 = bf16[8]{{0}} subtract(%p.3, %p.3), {_meta(J + "optimizer/sub")}
}}

%body (t: (s32[], bf16[8])) -> (s32[], bf16[8]) {{
  %convolution.2 = bf16[8,8]{{1,0:T(8,128)(2,1)}} convolution(%a, %b), dim_labels=bf_io->bf, {_meta(J + "jvp(ssm)/ssd/conv_general_dilated")}
  %all-reduce.3 = f32[64]{{0}} all-reduce(%x), replica_groups={{{{0,1}}}}, to_apply=%sum, {_meta(J + "transpose(jvp(moe.combine))/psum")}
  %fusion.4 = bf16[8]{{0}} fusion(%y), kind=kLoop, calls=%fused.two, {_meta(BACK + "ffn/mul")}
}}

ENTRY %main.1 (a.1: bf16[8,8]) -> bf16[8] {{
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%a.1), kind=kOutput, calls=%fused.vote
  %fusion.2 = bf16[8]{{0}} fusion(%c), kind=kLoop, calls=%fused.tie
  %while.1 = (s32[], bf16[8]{{0:T(8,128)S(1)}}) while(%tuple.3), condition=%cond, body=%body
  %flash_fwd.5 = (bf16[8]{{0}}, f32[8]{{0:T(8,128)}}) custom-call(%q), custom_call_target="tpu_custom_call", {_meta(REMAT + "attn/swa/flash_fwd/pallas_call")}, backend_config={{"custom_call_config":{{"body":"TUzvUg"}}}}
  %all_to_all.6 = bf16[8]{{0}} all-to-all(%r), replica_groups={{{{0,1}}}}, {_meta(J + "jvp(moe.exchange)/all_to_all")}
  %all-reduce-start.7 = f32[128]{{0}} all-reduce-start(%g), to_apply=%sum, {_meta(J + "transpose(jvp(head_loss))/psum")}
  %all-reduce-done.7 = f32[128]{{0}} all-reduce-done(%all-reduce-start.7), {_meta(J + "transpose(jvp(head_loss))/psum")}
  %fusion.8 = bf16[8]{{0}} fusion(%z), kind=kLoop, calls=%fused.opt, {_meta(J + "optimizer/sub")}
  ROOT %copy.9 = bf16[8]{{0}} copy(%w)
}}
"""

# Two chips.  Microseconds from T0; the capture began inside a step, whose
# end (one execution of 500, `fusion.8` alone) is left out.
#   0-100     fusion.1
#   150-450   while.1 spanning its body:
#   160-260     convolution.2     (chip 1: 160-290)
#   270-330     all-reduce.3      (chip 1: 300-335, the late arrival)
#   340-440     fusion.4
#   1000-1200 flash_fwd.5
#   1210-1260 all_to_all.6
#   1270-1300 copy.9
#   1350-1500 fusion.8
#   1100-1400 all-reduce-start.7 to its done  (chip 1: 1150-1400)


def _chip(conv_end, reduce_at, reduce_us, async_at):
    ev = lambda name, s, d: (f"%{name} = bf16[8]{{0}} op(%x)", s, d)
    return {
        "XLA Ops": [
            ev("fusion.8", -T0, 500), ev("fusion.1", 0, 100),
            ev("while.1", 150, 300),
            ev("convolution.2", 160, conv_end - 160),
            ev("all-reduce.3", reduce_at, reduce_us),
            ev("fusion.4", 340, 100), ev("flash_fwd.5", 1000, 200),
            ev("all_to_all.6", 1210, 50), ev("copy.9", 1270, 30),
            ev("fusion.8", 1350, 150)],
        "Async XLA Ops": [ev("all-reduce-start.7", async_at, 1400 - async_at)],
        "XLA Modules": [("jit_step(123)", -T0, 500), ("jit_step(123)", 0, 450),
                        ("jit_step(123)", 1000, 500), ("jit_other(7)", 460, 1)],
    }


def _us(devices):
    return {"profile_start_ns": ORIGIN * US, "devices": {
        p: {l: [(n, (s + T0) * US, d * US) for n, s, d in evs]
            for l, evs in lines.items()} for p, lines in devices.items()}}


ONE = _us({"/device:TPU:0": _chip(260, 270, 60, 1100)})
TWO = _us({"/device:TPU:0": _chip(260, 270, 60, 1100),
           "/device:TPU:1": _chip(290, 300, 35, 1150)})
STEPS = 2       # so a row is half of what the window holds


def us(ms_a_step):
    return round(ms_a_step * 1e3 * STEPS, 6)


@pytest.fixture(scope="module")
def labels():
    return prof.instruction_labels(TEXT)


@pytest.fixture(scope="module")
def one():
    return prof.step_profile(ONE, TEXT)


# ------------------------------------------------------------------- labels

@pytest.mark.parametrize("path, scope", [
    (J + "jvp(attn)/mla/dot_general", "mla"),           # `attn` round `mla`
    (J + "jvp(ssm)/ssd/exp", "ssd"),                    # `ssm` round `ssd`
    (J + "jvp(mtp)/attn/mla/mul", "mla"),               # three deep
    (BACK + "attn/swa/flash_bwd/pallas_call", "swa"),
    (J + "jvp(ssm)/ssm.conv/add;" + J + "jvp(attn)/mul", "attn"),
    (J + "jvp()/squeeze", None),
])
def test_innermost_name_of_the_path(path, scope):
    text = f"ENTRY %m {{\n  %x.1 = bf16[8]{{0}} add(%a, %b), {_meta(path)}\n}}"
    if scope is None:
        with pytest.raises(ValueError, match="no instruction"):
            prof.instruction_labels(text)
    else:
        assert prof.instruction_labels(text)["x.1"].scope == scope


@pytest.mark.parametrize("path, pass_", [
    (J + "jvp(attn)/mul", "forward"),
    (J + "jvp()/while/body/closed_call/attn/flash_fwd/pallas_call", "forward"),
    (J + "transpose(jvp(attn))/mul", "backward"),
    (BACK + "attn/flash_bwd/pallas_call", "backward"),
    (REMAT + "attn/mul", "recomputed"),
    (J + "optimizer/transpose/mul", "optimizer"),
    (J + "embed/gather", "other"),
    # A hand-written rule's gradient products inside the forward scan.
    (J + "jvp(head_loss)/while/body/dot_general", "forward"),
])
def test_pass_from_jax_marks(path, pass_):
    text = f"ENTRY %m {{\n  %x.1 = bf16[8]{{0}} add(%a, %b), {_meta(path)}\n}}"
    assert prof.instruction_labels(text)["x.1"].pass_ == pass_


def test_a_fusion_takes_its_computations_vote(labels):
    # Two of `mla` against one of `attn`, all forward, and no name of its own.
    assert labels["fusion.1"] == prof.Label("mla", "forward")
    # Its own name decides where it has one; two passes inside are reported.
    assert labels["fusion.4"] == prof.Label("ffn", "backward", mixed=True)
    # One of `ssd`, one of `attn`: the first seen, and reported.
    assert labels["fusion.2"] == prof.Label("ssd", "forward", mixed=True)
    assert labels["fusion.8"] == prof.Label("optimizer", "optimizer")
    assert labels["while.1"] == labels["copy.9"] == prof.Label(None)


def test_a_kernel_by_its_name(labels):
    assert labels["flash_fwd.5"] == prof.Label("swa", "recomputed", "flash_fwd")
    line = ('  %custom-call.3 = bf16[8]{0} custom-call(%q), '
            'custom_call_target="tpu_custom_call", ')
    gmm = prof.instruction_labels(
        "ENTRY %m {\n" + line
        + _meta(J + "jvp(moe.experts)/jit(gmm)/pallas_call") + "\n}")
    assert gmm["custom-call.3"] == prof.Label("moe.experts", "forward", "gmm")
    bare = prof.instruction_labels(
        TEXT + line.replace("custom-call.3", "ragged_dot.3")
        + _meta("ragged-dot-none"))
    assert bare["ragged_dot.3"] == prof.Label(None, "other", "ragged_dot")


def test_a_collective_by_its_opcode(labels):
    """`all_to_all.6` is the chip's name for the exchange (ROADMAP M9)."""
    assert {k: v.collective for k, v in labels.items() if v.collective} == {
        "all-reduce.3": "all-reduce", "all_to_all.6": "all-to-all",
        "all-reduce-start.7": "all-reduce-start"}
    assert labels["all_to_all.6"].scope == "moe.exchange"


def test_a_text_without_names_raises():
    bare = re.sub(r", metadata=\{[^}]*\}", "", TEXT)
    assert "op_name" not in bare
    with pytest.raises(ValueError, match="compile cache"):
        prof.instruction_labels(bare)
    with pytest.raises(ValueError, match="compile cache"):
        prof.step_profile(ONE, bare)


# -------------------------------------------------------------------- times

def test_self_time_under_a_while(one):
    """The `while` keeps 300 - (100 + 60 + 100) = 40 of its own; its body is
    counted once, where a sum of durations counts it twice."""
    rows = {k[1:]: us(v) for k, v in one.rows.items()}
    assert rows == {
        ("mla", "forward", None): 100,
        ("ssd", "forward", None): 100,
        ("moe.combine", "backward", None): 60,
        ("ffn", "backward", None): 100,
        ("swa", "recomputed", "flash_fwd"): 200,
        ("moe.exchange", "forward", None): 50,
        ("optimizer", "optimizer", None): 150,
        ("unnamed", "other", None): 40 + 30}
    durations = sum(d for _, s, d in ONE["devices"]["/device:TPU:0"]["XLA Ops"]
                    if s >= T0 * US) / US
    assert durations == 1090 and us(one.chips["/device:TPU:0"]["op_self_ms"]) == 830


def test_whole_steps_leave_the_partial_first_one_out(one):
    chip = one.chips["/device:TPU:0"]
    assert chip["steps"] == STEPS
    assert (chip["t0_ns"], chip["t1_ns"]) == (T0 * US, (T0 + 1500) * US)
    assert us(chip["window_ms"]) == 1500 and us(chip["busy_ms"]) == 830
    assert chip["idle_share"] == pytest.approx(1 - 830 / 1500)
    # The optimizer's 500 of the partial step are not in its 150.
    assert us(one.by("scope")["optimizer"]) == 150
    # Pauses of 20 us or more, on the capture's clock.
    assert [(s // US - T0, e // US - T0) for s, e in chip["idle"]] == [
        (100, 150), (450, 1000), (1300, 1350)]
    assert chip["unnamed"] == [
        ("%while.1 = bf16[8]{0} op(%x)", pytest.approx(0.020)),
        ("%copy.9 = bf16[8]{0} op(%x)", pytest.approx(0.015))]
    assert us(chip["mixed_ms"]) == 100          # fusion.4


def test_both_identities(one):
    two = prof.step_profile(TWO, TEXT)
    for profile in (one, two):
        for chip, c in profile.chips.items():
            scopes = profile.by("scope", chip=chip)
            cells = profile.by("scope", "pass_", chip=chip)
            for scope, ms in scopes.items():
                assert ms == pytest.approx(sum(
                    v for (s, _), v in cells.items() if s == scope), abs=1e-9)
            assert sum(scopes.values()) == pytest.approx(c["op_self_ms"],
                                                         abs=1e-9)
    assert {k: us(v) for k, v in one.by("pass_").items()} == {
        "forward": 250, "backward": 160, "recomputed": 200, "optimizer": 150,
        "other": 70}
    assert {k: us(v) for k, v in one.by("kernel").items()} == {
        None: 630, "flash_fwd": 200}
    # A broken row breaks the assertion, not the report.
    with pytest.raises(AssertionError):
        prof.StepProfile(0, {**one.rows, ("/device:TPU:0", "x", "other",
                                          None): 1.0}, one.chips, {})


def test_two_branches_in_one_layer_each_keep_their_own():
    """PR 48's trap: a join by ONE outer name gives `attn` every fusion that
    holds any instruction of it, 141.9 for 86.8."""
    text = TEXT.replace("calls=%fused.tie", "calls=%fused.branches").replace(
        "%fused.tie (", "%fused.branches (").replace(
        f'{_meta(J + "jvp(ssm)/ssd/mul")}\n',
        f'{_meta(J + "jvp(ssm)/ssd/mul")}\n  %exp.3 = bf16[8]{{0}} '
        f'exponential(%p.2), {_meta(J + "jvp(ssm)/ssd/exp")}\n')
    ev = lambda name, s, d: (f"%{name} = bf16[8]{{0}} op(%x)", s, d)
    capture = _us({"/device:TPU:0": {
        "XLA Ops": [ev("fusion.1", 0, 86.8), ev("fusion.2", 100, 55.1)],
        "XLA Modules": [("jit_step(1)", -T0, 500), ("jit_step(1)", 0, 200)]}})
    inner = prof.step_profile(capture, text)
    assert inner.by("scope") == pytest.approx({"mla": 0.0868, "ssd": 0.0551})
    outer = prof.step_profile(capture, text, scopes=("attn",))
    assert outer.by("scope") == pytest.approx({"attn": 0.1419})


def test_a_cpu_capture_raises(tmp_path):
    with pytest.raises(ValueError, match="no whole step"):
        prof.step_profile({"profile_start_ns": 0, "devices": {}}, TEXT)
    with pytest.raises(ValueError, match="xplane"):
        prof.load_capture(str(tmp_path))


# ------------------------------------------------------- the chips apart

def test_a_late_arrival_is_the_others_wait():
    """Chip 1 comes to `all-reduce.3` 30 us after chip 0 and to the
    asynchronous one 50 us after: chip 0 waits, chip 1 is late, and what is
    left of each is transfer."""
    two = prof.step_profile(TWO, TEXT)
    got = {k: {f: us(x) if f != "calls" else x for f, x in v.items()}
           for k, v in two.collectives.items()}
    row = lambda late, wait, transfer, early=0: {
        "calls": 0.5, "late_ms": late, "wait_ms": wait,
        "transfer_ms": transfer, "early_ms": early}
    # Chip 0 leaves the first 5 us before chip 1: what bounds the clocks.
    assert got == {
        ("/device:TPU:0", "moe.combine", "all-reduce"): row(0, 30, 30, 5),
        ("/device:TPU:1", "moe.combine", "all-reduce"): row(30, 0, 35),
        ("/device:TPU:0", "moe.exchange", "all-to-all"): row(0, 0, 50),
        ("/device:TPU:1", "moe.exchange", "all-to-all"): row(0, 0, 50),
        ("/device:TPU:0", "head_loss", "all-reduce"): row(0, 50, 250),
        ("/device:TPU:1", "head_loss", "all-reduce"): row(50, 0, 250)}
    # The chips apart, and their mean.
    assert us(two.by("scope", chip="/device:TPU:1")["ssd"]) == 130
    assert us(two.by("scope")["ssd"]) == 115
    s = two.summary()
    assert s["chips"] == 2 and us(s["collectives"]["moe.combine"]["wait_ms"]) == 15
    assert "moe.combine all-reduce on /device:TPU:1" in two.table()
    # One chip meets nobody.
    assert prof.step_profile(ONE, TEXT).collectives == {}


def test_chips_are_matched_from_the_windows_end():
    """The capture found chip 1 a step earlier than chip 0 (three whole steps
    for two, as in `resnet50-b512-dp4`'s capture): the k-th collective from
    the END is the same one on both, the k-th from the start is not."""
    early = _chip(290, 300, 35, 1150)
    ev = lambda name, s, d: (f"%{name} = bf16[8]{{0}} op(%x)", s, d)
    early["XLA Modules"][:1] = [("jit_step(123)", -T0, 500),
                                ("jit_step(123)", -1000, 450)]
    early["XLA Ops"][1:1] = [ev("while.1", -850, 300),
                             ev("all-reduce.3", -700, 35)]
    early["Async XLA Ops"].insert(0, ev("all-reduce-start.7", -900, 200))
    two = prof.step_profile(_us({"/device:TPU:0": _chip(260, 270, 60, 1100),
                                 "/device:TPU:1": early}), TEXT)
    assert [c["steps"] for c in two.chips.values()] == [2, 3]
    assert two.collectives == prof.step_profile(TWO, TEXT).collectives


# ------------------------------------------------------------- one clock

def _stamps(offset):
    """One step of a run record (`RunRecord.step_stamps`) round step B, on
    the clock of `time.monotonic_ns()`, which `offset` puts on the epoch."""
    at = lambda t: (ORIGIN + T0 + t) * US - offset
    # step, t_batch, t_stepped, t_end, wait_ns, hook_ns, then t_entry,
    # t_staged, t_dispatched, t_sync, t_synced, t_done, blocked_ns
    return [(7, at(440), at(1340), at(1345), 0, 0, at(445), at(460), at(990),
             at(995), at(1330), at(1335), None)]


def test_idle_gaps_are_named_by_the_run_records_stamps(one):
    offset = 1_789_999_000_000_000_000
    gaps = one.gaps(_stamps(offset), offset)
    chip = "/device:TPU:0"
    at = lambda t: (ORIGIN + T0 + t) * US
    assert gaps == [
        (chip, at(100), 50 * US, None, None),       # before the record
        (chip, at(450), 550 * US, 7, "t_dispatched"),
        (chip, at(1300), 50 * US, 7, "t_synced")]
    assert one.profile_start_ns + (T0 + 450) * US == at(450)


def test_device_events_stand_on_the_spans_clock(monkeypatch):
    """`tmpi-trace merge --xplane`: a span recorded round step B covers that
    step's device events, which the old zero-origin shift put at 0."""
    from torchmpi_tpu.obs import export

    offset = 1_789_999_000_000_000_000
    mono = lambda t: (ORIGIN + T0 + t) * US - offset
    spans = [{"name": "engine.step", "t0_ns": mono(-3000), "t1_ns": mono(-2500),
              "thread": 1, "correlation": 1, "attrs": {}},
             {"name": "engine.step", "t0_ns": mono(990), "t1_ns": mono(1510),
              "thread": 1, "correlation": 2, "attrs": {}},
             {"name": "profiler.window", "t0_ns": mono(-2900),
              "t1_ns": mono(1600), "thread": 1, "correlation": 0,
              "attrs": {"epoch_offset_ns": offset}}]
    monkeypatch.setattr(prof, "load_capture", lambda path: ONE)
    for given in (None, offset):
        trace = export.chrome_trace(spans, [], "a.xplane.pb", given)["traceEvents"]
        step = [e for e in trace if e.get("name") == "engine.step"][1]
        device = [e for e in trace if e.get("cat") == "device"]
        assert len(device) == 11 + 4
        in_b = [e for e in device if e["name"] in
                ("%flash_fwd.5", "%all_to_all.6", "%copy.9", "%all-reduce-start.7")]
        assert len(in_b) == 4
        for e in in_b:
            assert step["ts"] <= e["ts"] and (
                e["ts"] + e["dur"] <= step["ts"] + step["dur"])
        first = min(e["ts"] for e in device)
        assert first == pytest.approx((mono(-T0) - mono(-3000)) / 1e3)
    assert [e["args"]["name"] for e in trace if e["ph"] == "M"][-1] == (
        "device /device:TPU:0")


def test_the_run_record_reads_the_window_when_asked(one):
    from torchmpi_tpu.engine.sgdengine import RunRecord

    rec = RunRecord("compiled", 1, 0)
    assert rec.device is None

    class Window:
        reads = 0

        def profile(self):
            Window.reads += 1
            return one

    rec.profiler = Window()
    assert Window.reads == 0            # nothing is parsed unless it is read
    rec.epoch_offset_ns = 1_789_999_000_000_000_000
    rec.step_stamps.extend(_stamps(rec.epoch_offset_ns))
    device = rec.device
    assert Window.reads == 1
    assert device["by_pass"]["recomputed"] == pytest.approx(0.1)
    assert device["gaps"][1][3:] == (7, "t_dispatched")
    json.dumps(device)                  # plain data


# -------------------------------------------------------- the program's set

def test_every_named_scope_of_the_package_is_in_the_tuple():
    from torchmpi_tpu.models import SCOPES

    found = {}
    for sub in ("models", "ops", "parallel", "engine"):
        for base, _, files in os.walk(os.path.join(ROOT, "torchmpi_tpu", sub)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(base, name)) as fh:
                        for scope in re.findall(
                                r'named_scope\(\s*"([^"]+)"', fh.read()):
                            found.setdefault(scope, name)
    assert found and not set(found) - set(SCOPES), {
        s: f for s, f in found.items() if s not in SCOPES}
    assert not set(SCOPES) - set(found)     # and the tuple names nothing else
    assert len(set(SCOPES)) == len(SCOPES)


def test_import_does_not_bring_the_reader():
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", "import sys, torchmpi_tpu; print("
         "'torchmpi_tpu.utils.profiler' in sys.modules)"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "False", out.stderr[-400:]


# ------------------------------------------------------------------- parity

def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_parity_with_the_runners_join(one):
    """On one fixture the reader's sums by scope are the benchmark runners'
    (`step_tokens_latent.self_ms` over `step_tokens_looped.
    instruction_scopes`), where no two listed names nest."""
    looped = _by_path("pr51_looped", "runners", "step_tokens_looped.py")
    latent = _by_path("pr51_latent", "runners", "step_tokens_latent.py")
    trace_reduce = _by_path("pr51_trace_reduce", "trace_reduce.py")
    listed = ("mla", "ssd", "moe.combine", "moe.exchange", "ffn", "swa",
              "optimizer")
    for capture in (ONE, TWO):
        theirs = latent.self_ms(
            capture, looped.instruction_scopes(TEXT, listed), trace_reduce)
        mine = prof.step_profile(capture, TEXT).by("scope")
        assert set(theirs) == set(mine)
        for scope, ms in theirs.items():
            assert mine[scope] == pytest.approx(ms, abs=1e-9), scope


# ------------------------------------------------- a piece of the real text

def test_the_kimi_cells_marks():
    """Cut from the Kimi Linear cell's executable (`"full"` remat, Mosaic
    kernels under `custom_vjp` rules, the chunked head's scan): how `jax`'s
    three marks stand in a real text, which the pass rule follows."""
    with open(os.path.join(DATA, "kimi_cut.hlo.txt")) as fh:
        labels = prof.instruction_labels(fh.read())
    with open(os.path.join(DATA, "kimi_cut.json")) as fh:
        expected = json.load(fh)
    got = {name: [lab.scope, lab.pass_, lab.kernel]
           for name, lab in labels.items() if name in expected}
    assert got == expected
    passes = {tuple(v[1:]) for v in expected.values()}
    # A kernel is of the pass its rule runs in.  The held experts' backward
    # loop forms gate and up again with `gmm`: `backward`, not `recomputed`,
    # which holds what the policy replays alone (the KDA layers' passes).
    assert {("forward", "kda_fwd"), ("backward", "kda_bwd"),
            ("forward", "flash_fwd"), ("backward", "flash_bwd"),
            ("forward", "gmm"), ("backward", "gmm"), ("backward", "tgmm"),
            ("recomputed", "kda_pre"), ("recomputed", "kda_post")} <= passes
    assert {k for p, k in passes if p == "recomputed" and k} == {
        "kda_pre", "kda_post"}
    # The chunked head's gradient products stand in the forward scan.
    assert labels["constant_dynamic-slice_fusion.2"][:2] == (
        "head_loss", "forward")
