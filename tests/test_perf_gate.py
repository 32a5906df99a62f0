"""Perf-regression gate (scripts/perf_gate.py): seeded synthetic artifact
histories pin the three behaviours the gate exists for — a real
regression is flagged, noise inside the tolerance band is not, and
missing/torn artifacts are skipped with a note instead of crashing.
Plus the acceptance check: the gate runs green on the repo's REAL
artifact history."""

import importlib.util
import json
import os

import pytest

pytestmark = pytest.mark.obsserve

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "perf_gate", os.path.join(_REPO, "scripts", "perf_gate.py"))
perf_gate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(perf_gate)


def _bench(tmp_path, rnd, img_per_s, step_ms=None):
    tail = ""
    if step_ms is not None:
        tail = (f"bench: engine+resident   {img_per_s} img/s/chip "
                f"({step_ms} ms/step)  <- reported\n")
    (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps(
        {"parsed": {"value": img_per_s}, "tail": tail}))


def _bench_autotune(tmp_path, rnd, ab_ratio, ready_fraction=None):
    doc = {"autotune": {"ab": {"ratio": ab_ratio}}}
    if ready_fraction is not None:
        doc["autotune"]["overlap"] = {
            "ready": {"overlap_fraction": ready_fraction},
            "barrier": {"overlap_fraction": max(ready_fraction - 0.05, 0.0)}}
    (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps(doc))


def _bench_input(tmp_path, rnd, ratio, overlap, parsed=False):
    sec = {"streamed_over_compute": ratio, "overlap_fraction": overlap}
    doc = {"parsed": {"input": sec}} if parsed else {"input": sec}
    (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps(doc))


def _obs(tmp_path, rnd, delta_ms, name="OBS", marker="trace"):
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(
        {"verdict": "PASS",
         "overhead_16MiB_allreduce": {
             f"{marker}_off_ms": 20.0,
             f"{marker}_on_ms": 20.0 + delta_ms,
             "delta_ms": delta_ms}}))


def _numerics(tmp_path, rnd, overhead_ms, name="NUMERICS", parsed=False):
    sec = {"sentinel_overhead_ms": overhead_ms, "sentinel_off_ms": 1.0,
           "sentinel_on_ms": 1.0 + overhead_ms}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"numerics": sec}
    else:
        doc["numerics"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


def _check(report, metric):
    [c] = [c for c in report["checks"] if c["metric"] == metric]
    return c


class TestRegressionFlagged:
    def test_throughput_drop_beyond_tolerance(self, tmp_path):
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 1010.0)
        _bench(tmp_path, 3, 900.0)          # -11% vs best: regression
        report = perf_gate.evaluate(str(tmp_path), tolerance=0.05)
        assert report["verdict"] == "REGRESSION"
        c = _check(report, "img_per_s")
        assert c["status"] == "regression"
        assert c["best_prior"] == 1010.0 and c["latest"] == 900.0

    def test_step_ms_growth_beyond_tolerance(self, tmp_path):
        _bench(tmp_path, 1, 1000.0, step_ms=45.0)
        _bench(tmp_path, 2, 1000.0, step_ms=50.0)   # +11%: regression
        report = perf_gate.evaluate(str(tmp_path), tolerance=0.05)
        assert _check(report, "step_ms")["status"] == "regression"
        assert "step_ms" in report["regressions"]

    def test_guard_delta_blowout(self, tmp_path):
        _obs(tmp_path, 6, -1.0)
        _obs(tmp_path, 7, 4.5, name="OBS2")  # > best(-1.0) + 3ms band
        report = perf_gate.evaluate(str(tmp_path), guard_tolerance_ms=3.0)
        c = _check(report, "trace_off_guard_delta_ms")
        assert c["status"] == "regression"
        assert c["bar"] == pytest.approx(2.0)

    def test_cli_exit_1_on_regression(self, tmp_path, capsys):
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 800.0)
        rc = perf_gate.main(["--dir", str(tmp_path), "--json"])
        assert rc == 1
        out = capsys.readouterr().out
        assert json.loads(out)["verdict"] == "REGRESSION"


class TestAutotuneSeries:
    def test_ab_ratio_regression_exits_1(self, tmp_path):
        """Acceptance: a seeded autotune regression (the measured selector
        got SLOWER than the static table vs best-so-far, beyond the
        absolute band) must exit 1."""
        _bench_autotune(tmp_path, 10, 1.0)
        _bench_autotune(tmp_path, 11, 1.2)     # > best(1.0) + 0.10 band
        rc = perf_gate.main(["--dir", str(tmp_path), "--json"])
        assert rc == 1
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "autotune_ab_ratio")
        assert c["status"] == "regression"
        assert c["best_prior"] == 1.0 and c["latest"] == 1.2

    def test_overlap_fraction_drop_flagged(self, tmp_path):
        _bench_autotune(tmp_path, 10, 1.0, ready_fraction=0.30)
        _bench_autotune(tmp_path, 11, 1.0, ready_fraction=0.12)
        report = perf_gate.evaluate(str(tmp_path))   # 0.12 < 0.30 - 0.10
        c = _check(report, "overlap_ready_fraction")
        assert c["status"] == "regression"
        assert c["bar"] == pytest.approx(0.20)

    def test_ratio_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # A lucky 0.95 round must NOT ratchet the bar so that an honest
        # ~1.0 later fails: the band is absolute around the best, not
        # relative (the trace-guard rationale, applied to a ratio whose
        # healthy value is noise around 1.0).
        _bench_autotune(tmp_path, 10, 0.95)
        _bench_autotune(tmp_path, 11, 1.03)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "autotune_ab_ratio")["status"] == "pass"

    def test_within_band_and_missing_sections_skip(self, tmp_path):
        # Old-format BENCH rounds (no autotune key) are skipped with a
        # note — the series starts when the artifact does.
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 1001.0)
        _bench_autotune(tmp_path, 10, 1.0, ready_fraction=0.30)
        _bench_autotune(tmp_path, 11, 1.02, ready_fraction=0.295)
        report = perf_gate.evaluate(str(tmp_path))
        assert report["verdict"] == "PASS"
        assert _check(report, "autotune_ab_ratio")["rounds"] == 2
        assert any("metric absent" in n for n in report["notes"])


class TestInputSeries:
    """The streaming input plane's two series (docs/data.md): the
    non-resident streamed/compute ratio (lower-better, noise just above
    1.0) and the input overlap fraction (higher-better, absolute scale),
    each gated with the absolute band on its own trajectory."""

    def test_streamed_ratio_regression_flagged(self, tmp_path):
        _bench_input(tmp_path, 11, 1.04, 0.97)
        _bench_input(tmp_path, 12, 1.31, 0.96)   # > best(1.04) + 0.10
        report = perf_gate.evaluate(str(tmp_path), ab_tolerance=0.10)
        c = _check(report, "streamed_over_compute")
        assert c["status"] == "regression"
        assert "streamed_over_compute" in report["regressions"]

    def test_overlap_drop_flagged(self, tmp_path):
        _bench_input(tmp_path, 11, 1.04, 0.97)
        _bench_input(tmp_path, 12, 1.05, 0.62)   # < best(0.97) - 0.10
        report = perf_gate.evaluate(str(tmp_path), ab_tolerance=0.10)
        assert _check(report,
                      "input_overlap_fraction")["status"] == "regression"

    def test_noise_inside_band_passes(self, tmp_path):
        _bench_input(tmp_path, 11, 1.04, 0.97)
        _bench_input(tmp_path, 12, 1.09, 0.93)   # honest load noise
        report = perf_gate.evaluate(str(tmp_path), ab_tolerance=0.10)
        assert report["verdict"] == "PASS"
        assert _check(report, "streamed_over_compute")["status"] == "pass"
        assert _check(report, "input_overlap_fraction")["status"] == "pass"

    def test_section_found_under_parsed_wrapper(self, tmp_path):
        # TPU rounds wrap the bench stdout under "parsed"; the series
        # must read both artifact shapes as one trajectory.
        _bench_input(tmp_path, 11, 1.04, 0.97, parsed=True)
        _bench_input(tmp_path, 12, 1.05, 0.95)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "streamed_over_compute")["rounds"] == 2

    def test_pre_pipeline_rounds_skip_with_note(self, tmp_path):
        # Rounds that predate the input plane skip with a note, never
        # crash the gate (the autotune series' discipline).
        _bench(tmp_path, 5, 2800.0)
        _bench_input(tmp_path, 11, 1.04, 0.97)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report,
                      "input_overlap_fraction")["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])


class TestNumericsSeries:
    """numerics.sentinel_overhead_ms: one series over BOTH artifact
    shapes (the BENCH satellite section and the NUMERICS drill
    artifact), absolute band, skip-with-note on pre-numerics rounds."""

    def test_overhead_regression_flagged_and_exits_1(self, tmp_path):
        _numerics(tmp_path, 11, 0.4)
        _numerics(tmp_path, 12, 9.5)     # blows the 3 ms absolute band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "numerics_sentinel_overhead_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self, tmp_path):
        _numerics(tmp_path, 11, 0.4, name="BENCH")
        _numerics(tmp_path, 12, 0.6)     # NUMERICS_r12
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "numerics_sentinel_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "NUMERICS_r12.json"
        assert c["best_prior_artifact"] == "BENCH_r11.json"

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _numerics(tmp_path, 11, 0.4, name="BENCH", parsed=True)
        _numerics(tmp_path, 12, 0.5)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "numerics_sentinel_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_old_artifacts_skip_with_note(self, tmp_path):
        # Pre-numerics rounds carry no section: the series skips with a
        # note instead of crashing or flagging.
        _bench(tmp_path, 3, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "numerics_sentinel_overhead_ms")
        assert c["status"] == "skipped"
        assert any("BENCH_r03.json" in n for n in report["notes"])

    def test_single_round_skipped(self, tmp_path):
        _numerics(tmp_path, 12, 0.5)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "numerics_sentinel_overhead_ms")
        assert c["status"] == "skipped"

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # A lucky near-zero best must not ratchet the bar: 0.0 -> 2.9
        # stays inside the 3 ms absolute band.
        _numerics(tmp_path, 11, 0.0)
        _numerics(tmp_path, 12, 2.9)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "numerics_sentinel_overhead_ms")
        assert c["status"] == "pass"


def _journal(tmp_path, rnd, overhead_ms, name="RCA", parsed=False):
    sec = {"overhead_ms": overhead_ms, "journal_off_ms": 20.0,
           "journal_on_ms": 20.0 + overhead_ms,
           "events_per_s": 50000.0, "bytes_per_event": 180.0}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"journal": sec}
    else:
        doc["journal"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestJournalSeries:
    """journal.overhead_ms: one series over BOTH artifact shapes (the
    BENCH satellite section and the RCA drill artifact), absolute band
    (the hot path has no journal emit sites — the healthy delta is noise
    around zero), skip-with-note on pre-13 rounds."""

    def test_overhead_regression_flagged_and_exits_1(self, tmp_path):
        _journal(tmp_path, 12, 0.2)
        _journal(tmp_path, 13, 8.5)     # blows the 3 ms absolute band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "journal_overhead_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _journal(tmp_path, 12, 0.3, name="BENCH")
        _journal(tmp_path, 13, 0.5)     # RCA_r13
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "journal_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "RCA_r13.json"
        assert c["best_prior_artifact"] == "BENCH_r12.json"

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _journal(tmp_path, 12, 0.3, name="BENCH", parsed=True)
        _journal(tmp_path, 13, 0.4)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "journal_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_journal_rounds_skip_with_note(self, tmp_path):
        # Rounds that predate the journal plane carry no section: the
        # series skips with a note instead of crashing or flagging.
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "journal_overhead_ms")
        assert c["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # A lucky negative best (load shed mid-A/B) must not ratchet the
        # bar: -0.5 -> 2.3 stays inside the 3 ms absolute band.
        _journal(tmp_path, 12, -0.5)
        _journal(tmp_path, 13, 2.3)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "journal_overhead_ms")
        assert c["status"] == "pass"


def _scale(tmp_path, rnd, pause_ms, name="SCALE", parsed=False):
    sec = {"pause_ms": pause_ms}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"scale": sec}
    else:
        doc["scale"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestScaleSeries:
    """scale.pause_ms: the elastic-resize drill's worst train-loop pause
    across a resize window, its OWN absolute-band series over SCALE_r*
    (+ any BENCH round carrying the section) via load_multi — the pause
    is a real absolute cost (quiesce barrier + state ship), so a
    relative band off a lucky small-model round would ratchet."""

    def test_pause_regression_flagged_and_exits_1(self, tmp_path):
        _scale(tmp_path, 14, 40.0)
        _scale(tmp_path, 15, 900.0)    # blows the 250 ms absolute band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "scale_pause_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _scale(tmp_path, 14, 35.0, name="BENCH")
        _scale(tmp_path, 15, 60.0)     # SCALE_r15
        c = _check(perf_gate.evaluate(str(tmp_path)), "scale_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "SCALE_r15.json"
        assert c["best_prior_artifact"] == "BENCH_r14.json"

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _scale(tmp_path, 14, 35.0, name="BENCH", parsed=True)
        _scale(tmp_path, 15, 45.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "scale_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_resize_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "scale_pause_ms")["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # One lucky tiny-pause round must not ratchet the bar below an
        # honest pause: 5 -> 200 stays inside the 250 ms band.
        _scale(tmp_path, 14, 5.0)
        _scale(tmp_path, 15, 200.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "scale_pause_ms")
        assert c["status"] == "pass"

    def test_custom_band_flag(self, tmp_path):
        _scale(tmp_path, 14, 5.0)
        _scale(tmp_path, 15, 200.0)
        report = perf_gate.evaluate(str(tmp_path), pause_tolerance_ms=50.0)
        assert _check(report, "scale_pause_ms")["status"] == "regression"


def _alerts(tmp_path, rnd, eval_ms, name="ALERTS", parsed=False):
    sec = {"eval_overhead_ms": eval_ms, "overhead_ms": 0.01,
           "alerts_off_ms": 20.0, "alerts_on_ms": 20.0, "rules": 8}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"alerts": sec}
    else:
        doc["alerts"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestAlertsSeries:
    """alerts.eval_overhead_ms: one default-pack evaluator pass over a
    fully-populated history store, a single series over BOTH artifact
    shapes (BENCH satellite section + ALERTS drill artifact) with the
    trace guard's ABSOLUTE band — the evaluator runs on the sampler
    thread off the hot path, so the healthy value is a small constant
    and a relative band off a lucky round would ratchet until honest
    noise fails.  Pre-alerts rounds skip with a note."""

    def test_eval_regression_flagged_and_exits_1(self, tmp_path):
        _alerts(tmp_path, 14, 0.8)
        _alerts(tmp_path, 15, 9.0)     # blows the 3 ms absolute band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "alerts_eval_overhead_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _alerts(tmp_path, 14, 0.7, name="BENCH")
        _alerts(tmp_path, 15, 0.9)     # ALERTS_r15
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "alerts_eval_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "ALERTS_r15.json"
        assert c["best_prior_artifact"] == "BENCH_r14.json"

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _alerts(tmp_path, 14, 0.7, name="BENCH", parsed=True)
        _alerts(tmp_path, 15, 0.9)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "alerts_eval_overhead_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_alerts_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "alerts_eval_overhead_ms")
        assert c["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # A lucky fast pass must not ratchet the bar: 0.1 -> 2.5 stays
        # inside the 3 ms absolute band.
        _alerts(tmp_path, 14, 0.1)
        _alerts(tmp_path, 15, 2.5)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "alerts_eval_overhead_ms")
        assert c["status"] == "pass"


def _retune(tmp_path, rnd, pause_ms=None, ab_ratio=None, name="RETUNE",
            parsed=False):
    sec = {}
    if pause_ms is not None:
        sec["pause_ms"] = pause_ms
    if ab_ratio is not None:
        sec["ab"] = {"ratio": ab_ratio}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"retune": sec}
    else:
        doc["retune"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestRetuneSeries:
    """retune.pause_ms + retune.ab.ratio: the retune drill's worst
    train-loop step pause while an alert-triggered probe + apply ran
    mid-job (the controller's whole point is that the bench is off the
    hot path — a pause spike means it leaked onto it), and the
    post-retune over pre-retune steady step time (<= 1.0 means the
    retune helped; the band tolerates measurement noise, not a
    controller that makes jobs slower).  Both ride load_multi over
    RETUNE_r* + BENCH rounds carrying the section, absolute bands —
    same no-ratchet argument as the scale pause."""

    def test_pause_regression_flagged_and_exits_1(self, tmp_path):
        _retune(tmp_path, 15, pause_ms=30.0)
        _retune(tmp_path, 16, pause_ms=900.0)  # blows the 250 ms band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "retune_pause_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_ab_ratio_regression_flagged_and_exits_1(self, tmp_path):
        _retune(tmp_path, 15, ab_ratio=0.97)
        _retune(tmp_path, 16, ab_ratio=1.25)   # blows the 0.10 band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "retune_ab_ratio")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _retune(tmp_path, 15, pause_ms=25.0, ab_ratio=0.98, name="BENCH")
        _retune(tmp_path, 16, pause_ms=40.0, ab_ratio=1.01)  # RETUNE_r16
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "retune_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "RETUNE_r16.json"
        assert c["best_prior_artifact"] == "BENCH_r15.json"
        c = _check(report, "retune_ab_ratio")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _retune(tmp_path, 15, pause_ms=25.0, name="BENCH", parsed=True)
        _retune(tmp_path, 16, pause_ms=40.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "retune_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_retune_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "retune_pause_ms")["status"] == "skipped"
        assert _check(report, "retune_ab_ratio")["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # One lucky quiet-probe round must not ratchet the bar: 5 -> 200
        # stays inside the 250 ms band, 0.90 -> 0.99 inside the 0.10 one.
        _retune(tmp_path, 15, pause_ms=5.0, ab_ratio=0.90)
        _retune(tmp_path, 16, pause_ms=200.0, ab_ratio=0.99)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "retune_pause_ms")["status"] == "pass"
        assert _check(report, "retune_ab_ratio")["status"] == "pass"

    def test_custom_band_flag(self, tmp_path):
        _retune(tmp_path, 15, pause_ms=5.0)
        _retune(tmp_path, 16, pause_ms=200.0)
        report = perf_gate.evaluate(str(tmp_path), pause_tolerance_ms=50.0)
        assert _check(report, "retune_pause_ms")["status"] == "regression"


class TestNoiseTolerated:
    def test_within_band_passes(self, tmp_path):
        _bench(tmp_path, 1, 1000.0, step_ms=45.0)
        _bench(tmp_path, 2, 1010.0, step_ms=44.8)
        _bench(tmp_path, 3, 985.0, step_ms=45.9)   # ~-2.5% / +2.5%: noise
        _obs(tmp_path, 2, -1.2)
        _obs(tmp_path, 3, 0.8, name="OBS2")        # inside the 3ms band
        report = perf_gate.evaluate(str(tmp_path), tolerance=0.05)
        assert report["verdict"] == "PASS"
        assert all(c["status"] in ("pass", "skipped")
                   for c in report["checks"])
        assert {c["metric"] for c in report["checks"]
                if c["status"] == "pass"} == {
            "img_per_s", "step_ms", "trace_off_guard_delta_ms"}

    def test_http_and_trace_guards_are_separate_series(self, tmp_path):
        # The live drill's endpoint+scraper delta is a strictly larger
        # quantity than bare tracing: it must gate as its OWN series,
        # not breach the trace-guard band.
        _obs(tmp_path, 6, -1.0)
        _obs(tmp_path, 7, -0.3, name="OBS2")
        _obs(tmp_path, 9, 1.9, name="OBSLIVE", marker="http")
        report = perf_gate.evaluate(str(tmp_path), guard_tolerance_ms=3.0)
        assert report["verdict"] == "PASS"
        assert _check(report,
                      "trace_off_guard_delta_ms")["latest_round"] == 7
        # A single live round has no prior history yet: skipped, and the
        # next OBSLIVE round gates against this one.
        assert _check(report,
                      "endpoint_scrape_delta_ms")["status"] == "skipped"

    def test_scrape_series_gates_its_own_rounds(self, tmp_path):
        _obs(tmp_path, 9, 1.9, name="OBSLIVE", marker="http")
        _obs(tmp_path, 10, 9.0, name="OBSLIVE", marker="http")
        report = perf_gate.evaluate(str(tmp_path), guard_tolerance_ms=3.0)
        assert _check(report,
                      "endpoint_scrape_delta_ms")["status"] == "regression"

    def test_best_so_far_not_last_round(self, tmp_path):
        # A noisy dip in round 2 must not ratchet the bar down: round 3
        # is judged against the round-1 BEST, and fails.
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 700.0)     # earlier regression (its round)
        _bench(tmp_path, 3, 720.0)     # "recovered" vs r2 — still -28%
        report = perf_gate.evaluate(str(tmp_path), tolerance=0.05)
        c = _check(report, "img_per_s")
        assert c["status"] == "regression"
        assert c["best_prior"] == 1000.0


class TestMissingArtifactsHandled:
    def test_empty_directory_all_skipped(self, tmp_path):
        report = perf_gate.evaluate(str(tmp_path))
        assert report["verdict"] == "PASS"
        assert all(c["status"] == "skipped" for c in report["checks"])

    def test_single_round_skipped(self, tmp_path):
        _bench(tmp_path, 1, 1000.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "img_per_s")["status"] == "skipped"

    def test_analyze_artifact_skips_with_note(self, tmp_path):
        # static-analysis verdicts carry no perf series; the gate names
        # them skipped instead of silently ignoring the family
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 1005.0)
        (tmp_path / "ANALYZE_r18.json").write_text(
            json.dumps({"verdict": "PASS", "findings": []}))
        report = perf_gate.evaluate(str(tmp_path))
        assert report["verdict"] == "PASS"
        assert any("ANALYZE_r18.json" in n and "skipped" in n
                   for n in report["notes"])

    def test_torn_artifact_noted_not_fatal(self, tmp_path):
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 2, 1005.0)
        (tmp_path / "BENCH_r03.json").write_text("{torn")
        report = perf_gate.evaluate(str(tmp_path))
        assert report["verdict"] == "PASS"
        assert any("BENCH_r03.json" in n for n in report["notes"])
        # The torn round simply doesn't participate.
        assert _check(report, "img_per_s")["latest_round"] == 2

    def test_metric_absent_rounds_skipped(self, tmp_path):
        # r01's old format has no tail line: step_ms series starts at r04.
        _bench(tmp_path, 1, 1000.0)
        _bench(tmp_path, 4, 1001.0, step_ms=45.0)
        _bench(tmp_path, 5, 1002.0, step_ms=45.2)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "step_ms")
        assert c["status"] == "pass" and c["rounds"] == 2


def _election(tmp_path, rnd, pause_ms, name="ELECTION", parsed=False):
    sec = {"pause_ms": pause_ms}
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"election": sec}
    else:
        doc["election"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestElectionSeries:
    """election.pause_ms: the leader-election drill's worst train-loop
    pause across a failover (detect the dead leader over /healthz,
    claim the next epoch under the fence, rewire the survivors), its
    own absolute-band series over ELECTION_r* (+ any BENCH round
    carrying the section) via load_multi — the pause is a real absolute
    cost (detection probes + ring rewire), same no-ratchet argument as
    the scale pause."""

    def test_pause_regression_flagged_and_exits_1(self, tmp_path):
        _election(tmp_path, 17, 60.0)
        _election(tmp_path, 18, 900.0)  # blows the 250 ms absolute band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "election_pause_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _election(tmp_path, 17, 50.0, name="BENCH")
        _election(tmp_path, 18, 70.0)  # ELECTION_r18
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "election_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "ELECTION_r18.json"
        assert c["best_prior_artifact"] == "BENCH_r17.json"

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _election(tmp_path, 17, 50.0, name="BENCH", parsed=True)
        _election(tmp_path, 18, 70.0)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "election_pause_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_election_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "election_pause_ms")["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # One lucky instant-failover round must not ratchet the bar:
        # 5 -> 200 stays inside the 250 ms band.
        _election(tmp_path, 17, 5.0)
        _election(tmp_path, 18, 200.0)
        c = _check(perf_gate.evaluate(str(tmp_path)),
                   "election_pause_ms")
        assert c["status"] == "pass"

    def test_custom_band_flag(self, tmp_path):
        _election(tmp_path, 17, 5.0)
        _election(tmp_path, 18, 200.0)
        report = perf_gate.evaluate(str(tmp_path),
                                    pause_tolerance_ms=50.0)
        assert _check(report, "election_pause_ms")["status"] == \
            "regression"


def _serve(tmp_path, rnd, p99_ms=None, tokens_per_sec=None, name="SERVE",
           parsed=False):
    sec = {}
    if p99_ms is not None:
        sec["p99_ms"] = p99_ms
    if tokens_per_sec is not None:
        sec["tokens_per_sec"] = tokens_per_sec
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"serve": sec}
    else:
        doc["serve"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestServeSeries:
    """serve.p99_ms + serve.tokens_per_sec: the serving drill's
    baseline-leg tail latency (absolute band — queue-wait dominated,
    load-noisy, a relative band off one lucky quiet round would
    ratchet) and aggregate decode throughput (relative band, wider than
    the bench's: the drill shares its host with 200+ client threads).
    Both ride load_multi over SERVE_r* + BENCH rounds carrying the
    section."""

    def test_p99_regression_flagged_and_exits_1(self, tmp_path):
        _serve(tmp_path, 18, p99_ms=40.0)
        _serve(tmp_path, 19, p99_ms=400.0)   # blows the 100 ms band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "serve_p99_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_throughput_regression_flagged_and_exits_1(self, tmp_path):
        _serve(tmp_path, 18, tokens_per_sec=1500.0)
        _serve(tmp_path, 19, tokens_per_sec=900.0)  # > 25% drop
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "serve_tokens_per_sec")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _serve(tmp_path, 18, p99_ms=30.0, tokens_per_sec=1400.0,
               name="BENCH")
        _serve(tmp_path, 19, p99_ms=80.0, tokens_per_sec=1300.0)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "serve_p99_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "SERVE_r19.json"
        assert c["best_prior_artifact"] == "BENCH_r18.json"
        c = _check(report, "serve_tokens_per_sec")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _serve(tmp_path, 18, p99_ms=30.0, name="BENCH", parsed=True)
        _serve(tmp_path, 19, p99_ms=80.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "serve_p99_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_serving_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "serve_p99_ms")["status"] == "skipped"
        assert _check(report, "serve_tokens_per_sec")["status"] == \
            "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_p99_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # One lucky quiet round (5 ms tail) must not ratchet the bar:
        # 5 -> 90 stays inside the 100 ms band.
        _serve(tmp_path, 18, p99_ms=5.0)
        _serve(tmp_path, 19, p99_ms=90.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "serve_p99_ms")
        assert c["status"] == "pass"

    def test_custom_band_flags(self, tmp_path):
        _serve(tmp_path, 18, p99_ms=5.0, tokens_per_sec=1000.0)
        _serve(tmp_path, 19, p99_ms=90.0, tokens_per_sec=850.0)
        report = perf_gate.evaluate(str(tmp_path),
                                    serve_p99_tolerance_ms=50.0,
                                    serve_tolerance=0.10)
        assert _check(report, "serve_p99_ms")["status"] == "regression"
        assert _check(report, "serve_tokens_per_sec")["status"] == \
            "regression"


def _scale100(tmp_path, rnd, sweep_ms=None, step_rate=None,
              name="SCALE100", parsed=False):
    sec = {}
    if sweep_ms is not None:
        sec["sweep_ms"] = sweep_ms
    if step_rate is not None:
        sec["step_rate"] = step_rate
    doc = {"verdict": "PASS"}
    if parsed:
        doc["parsed"] = {"scale100": sec}
    else:
        doc["scale100"] = sec
    (tmp_path / f"{name}_r{rnd:02d}.json").write_text(json.dumps(doc))


class TestScale100Series:
    """scale100.sweep_ms + scale100.step_rate: the 64-256 rank churn
    drill's post-churn federated sweep (absolute band — backstop-
    bounded, so healthy values are noise around a small constant) and
    its under-churn per-rank step rate (relative band, wide: the fleet
    oversubscribes one host).  Both ride load_multi over SCALE100_r* +
    BENCH rounds carrying the section."""

    def test_sweep_regression_flagged_and_exits_1(self, tmp_path):
        _scale100(tmp_path, 19, sweep_ms=40.0)
        _scale100(tmp_path, 20, sweep_ms=1500.0)  # blows the 1 s band
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "scale100_sweep_ms")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_step_rate_regression_flagged_and_exits_1(self, tmp_path):
        _scale100(tmp_path, 19, step_rate=40.0)
        _scale100(tmp_path, 20, step_rate=15.0)  # > 50% drop
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "scale100_step_rate")
        assert c["status"] == "regression"
        assert report["verdict"] == "REGRESSION"
        assert perf_gate.main(["--dir", str(tmp_path)]) == 1

    def test_bench_and_drill_artifacts_merge_into_one_series(self,
                                                             tmp_path):
        _scale100(tmp_path, 19, sweep_ms=30.0, step_rate=38.0,
                  name="BENCH")
        _scale100(tmp_path, 20, sweep_ms=120.0, step_rate=30.0)
        report = perf_gate.evaluate(str(tmp_path))
        c = _check(report, "scale100_sweep_ms")
        assert c["status"] == "pass" and c["rounds"] == 2
        assert c["latest_artifact"] == "SCALE100_r20.json"
        assert c["best_prior_artifact"] == "BENCH_r19.json"
        c = _check(report, "scale100_step_rate")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_parsed_wrapper_shape_found(self, tmp_path):
        _scale100(tmp_path, 19, sweep_ms=30.0, name="BENCH", parsed=True)
        _scale100(tmp_path, 20, sweep_ms=120.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "scale100_sweep_ms")
        assert c["status"] == "pass" and c["rounds"] == 2

    def test_pre_scale100_rounds_skip_with_note(self, tmp_path):
        _bench(tmp_path, 5, 2800.0)
        report = perf_gate.evaluate(str(tmp_path))
        assert _check(report, "scale100_sweep_ms")["status"] == "skipped"
        assert _check(report, "scale100_step_rate")["status"] == "skipped"
        assert any("metric absent" in n for n in report["notes"])

    def test_sweep_band_is_absolute_no_lucky_ratchet(self, tmp_path):
        # One lucky quiet sweep (10 ms) must not ratchet the bar:
        # 10 -> 900 stays inside the 1000 ms band.
        _scale100(tmp_path, 19, sweep_ms=10.0)
        _scale100(tmp_path, 20, sweep_ms=900.0)
        c = _check(perf_gate.evaluate(str(tmp_path)), "scale100_sweep_ms")
        assert c["status"] == "pass"

    def test_custom_band_flags(self, tmp_path):
        _scale100(tmp_path, 19, sweep_ms=10.0, step_rate=40.0)
        _scale100(tmp_path, 20, sweep_ms=900.0, step_rate=34.0)
        report = perf_gate.evaluate(str(tmp_path),
                                    sweep100_tolerance_ms=100.0,
                                    scale100_tolerance=0.10)
        assert _check(report, "scale100_sweep_ms")["status"] == \
            "regression"
        assert _check(report, "scale100_step_rate")["status"] == \
            "regression"


class TestRealHistoryGreen:
    def test_repo_history_passes(self):
        """Acceptance: the gate runs green against the artifacts at the
        repo root.  The rounds 1-5 chip records are gone (their set-up no
        longer exists), so no device series has two points; what still
        gates are the host-side bands."""
        report = perf_gate.evaluate(_REPO)
        assert report["verdict"] == "PASS", json.dumps(report, indent=1)
        gated = {c["metric"] for c in report["checks"]
                 if c["status"] == "pass"}
        assert gated == {"trace_off_guard_delta_ms", "scale_pause_ms"}

    def test_cli_green(self):
        rc = perf_gate.main(["--dir", _REPO])
        assert rc == 0
