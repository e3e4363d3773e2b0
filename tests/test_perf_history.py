"""perf_history bench differ (ISSUE 6 satellite): the first slice of
the ROADMAP perf-gate item runs in tier-1 as a smoke — a
``BENCH_r*.json`` trajectory diffs clean, and the regression rules
behave as documented, all on synthetic captures (the repo commits no
capture files).

Pure JSON/regex work: no jax import in the tool path.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from perf_history import (  # noqa: E402
    DEFAULT_TOLERANCE,
    Regression,
    bench_files,
    diff_rows,
    load_rows,
    lower_is_better,
    main,
    newest_comparable_pair,
)


def _capture(tmp_path, name, rows):
    tail = "\n".join(json.dumps(r) for r in rows) + "\n"
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "rc": 0, "tail": tail}))
    return str(p)


_HEADLINE = "resnet50_train_images_per_sec_per_chip"


def _headline(value, **extra):
    return {"metric": _HEADLINE, "value": value, "step_time_ms": 44.0,
            "unit": "images/sec/chip", **extra}


def _summary(scale=1.0):
    """The compact per-config map bench.py's final row carries."""
    return {
        name: {"v": v * scale, "ms": 10.0, "u": "tokens/sec/chip"}
        for name, v in (("mnist", 1.0e7), ("resnet50_mnbn", 2700.0),
                        ("transformer_lm", 1.3e5), ("moe_lm", 8.6e4),
                        ("seq2seq_mp", 1.2e6), ("grad_wire", 2800.0))
    }


# ----------------------------------------------------------------------
# the smoke: a capture trajectory on disk, as a repo root would hold it
# ----------------------------------------------------------------------
class TestCaptureTrajectory:
    def test_repo_captures_diff_clean(self, tmp_path):
        """The two newest comparable captures under a root carry shared
        rows and no regression beyond spread — the gate a new capture
        faces."""
        _capture(tmp_path, "BENCH_r01.json", [_headline(2500.0)])
        _capture(tmp_path, "BENCH_r02.json", [_headline(2900.0)])
        _capture(tmp_path, "BENCH_r03.json", [_headline(2920.0)])
        pair = newest_comparable_pair(str(tmp_path))
        assert pair is not None
        assert [os.path.basename(p) for p in pair] == [
            "BENCH_r02.json", "BENCH_r03.json"]
        old, new = (load_rows(p) for p in pair)
        assert set(old) & set(new) == {_HEADLINE}
        assert diff_rows(old, new) == []

    def test_rich_captures_diff_many_rows_clean(self, tmp_path):
        """A full-capture pair: the final row's ``summary`` map is
        flattened to ``<config>.v`` rows, so the whole tracked config
        set is compared."""
        old = load_rows(_capture(tmp_path, "BENCH_r02.json", [
            _headline(2900.0, summary=_summary())]))
        new = load_rows(_capture(tmp_path, "BENCH_r05.json", [
            _headline(2920.0, summary=_summary(1.01))]))
        assert "transformer_lm.v" in old and "moe_lm.v" in new
        assert len(set(old) & set(new)) >= 5
        assert diff_rows(old, new) == []

    def test_failed_captures_fall_back_to_local(self, tmp_path):
        """A revision whose primary capture failed (null row) but whose
        ``_local`` capture — a bare row, not a wrapped tail — carries
        the measurement: pair selection must use the local fallback for
        that revision, not skip it (and never compare a revision
        against its own fallback)."""
        _capture(tmp_path, "BENCH_r03.json", [_headline(2700.0)])
        failed = _capture(tmp_path, "BENCH_r04.json", [
            _headline(None, error="no device")])
        local = tmp_path / "BENCH_r04_local.json"
        local.write_text(json.dumps(_headline(2750.0)))
        _capture(tmp_path, "BENCH_r05.json", [_headline(2800.0)])
        _capture(tmp_path, "BENCH_r05_local.json", [_headline(2810.0)])
        files = bench_files(str(tmp_path))
        assert [os.path.basename(f) for f in files] == [
            "BENCH_r03.json", "BENCH_r04.json", "BENCH_r04_local.json",
            "BENCH_r05.json", "BENCH_r05_local.json"]
        assert not any(
            isinstance(r.get("value"), (int, float))
            for r in load_rows(failed).values()
        )
        assert load_rows(str(local))[_HEADLINE]["value"] == 2750.0, (
            "the bare-row _local shape must parse")
        pair = newest_comparable_pair(str(tmp_path))
        assert [os.path.basename(p) for p in pair] == [
            "BENCH_r04_local.json", "BENCH_r05.json"]

    def test_console_entry_exits_zero_on_clean_history(self, tmp_path):
        old = _capture(tmp_path, "BENCH_r01.json", [_headline(2900.0)])
        new = _capture(tmp_path, "BENCH_r02.json", [_headline(2920.0)])
        proc = subprocess.run(
            [sys.executable, "benchmarks/perf_history.py", old, new],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "1 shared row(s), 0 regression(s)" in proc.stdout


# ----------------------------------------------------------------------
# rule behavior on synthetic captures
# ----------------------------------------------------------------------
class TestDiffRules:
    def test_regression_beyond_recorded_spread_flagged(self, tmp_path):
        old = _capture(tmp_path, "BENCH_r01.json", [
            {"metric": "step_time_ms", "value": 100.0,
             "n_measurements": 3, "spread_max_over_min": 1.2},
        ])
        new = _capture(tmp_path, "BENCH_r02.json", [
            {"metric": "step_time_ms", "value": 130.0,
             "n_measurements": 3, "spread_max_over_min": 1.2},
        ])
        regs = diff_rows(load_rows(old), load_rows(new))
        assert len(regs) == 1
        r = regs[0]
        assert isinstance(r, Regression)
        assert r.direction == "lower-better"
        assert r.ratio > 1.2 and r.allowed == 1.2

    def test_move_within_spread_not_flagged(self, tmp_path):
        old = _capture(tmp_path, "a.json", [
            {"metric": "step_time_ms", "value": 100.0,
             "spread_max_over_min": 1.3},
        ])
        new = _capture(tmp_path, "b.json", [
            {"metric": "step_time_ms", "value": 125.0,
             "spread_max_over_min": 1.1},
        ])
        # tolerance = max recorded spread (1.3) — 1.25x is inside it
        assert diff_rows(load_rows(old), load_rows(new)) == []

    def test_throughput_direction(self, tmp_path):
        old = _capture(tmp_path, "a.json", [
            {"metric": "images_per_sec_per_chip", "value": 2000.0},
        ])
        worse = _capture(tmp_path, "b.json", [
            {"metric": "images_per_sec_per_chip", "value": 1500.0},
        ])
        better = _capture(tmp_path, "c.json", [
            {"metric": "images_per_sec_per_chip", "value": 2500.0},
        ])
        assert len(diff_rows(load_rows(old), load_rows(worse))) == 1
        assert diff_rows(load_rows(old), load_rows(better)) == []

    def test_per_sec_per_chip_is_higher_better(self):
        # the spelling trap: "images_per_sec_per_chip" CONTAINS the
        # substring "sec_per" — throughput must win
        assert not lower_is_better("images_per_sec_per_chip", {})
        assert lower_is_better("sec_per_generate", {})
        assert lower_is_better("step_time_ms", {})
        assert not lower_is_better("mnist.v", {"unit": "samples/sec"})

    def test_throughput_collapse_to_zero_fails_the_gate(self, tmp_path):
        """Regression: a tracked throughput recording 0 (harness bug
        writing 0 instead of null) is the worst possible regression —
        it must fail, not be skipped as unratioable."""
        old = _capture(tmp_path, "a.json",
                       [{"metric": "tokens_per_sec_per_chip",
                         "value": 1000.0}])
        new = _capture(tmp_path, "b.json",
                       [{"metric": "tokens_per_sec_per_chip",
                         "value": 0.0}])
        regs = diff_rows(load_rows(old), load_rows(new))
        assert len(regs) == 1 and regs[0].ratio == float("inf")
        # ...while a lower-better metric at 0 is bogus data, not a
        # slowdown — skipped
        old_ms = _capture(tmp_path, "c.json",
                          [{"metric": "step_time_ms", "value": 10.0}])
        new_ms = _capture(tmp_path, "d.json",
                          [{"metric": "step_time_ms", "value": 0.0}])
        assert diff_rows(load_rows(old_ms), load_rows(new_ms)) == []

    def test_null_and_missing_rows_skipped(self, tmp_path):
        old = _capture(tmp_path, "a.json", [
            {"metric": "m1", "value": 10.0},
            {"metric": "gone", "value": 5.0},
        ])
        new = _capture(tmp_path, "b.json", [
            {"metric": "m1", "value": None},
            {"metric": "fresh", "value": 7.0},
        ])
        assert diff_rows(load_rows(old), load_rows(new)) == []

    def test_summary_values_flattened(self, tmp_path):
        cap = _capture(tmp_path, "a.json", [
            {"metric": "top", "value": 1.0, "summary": {
                "mnist": {"v": 100.0, "ms": 0.5, "u": "samples/sec"},
            }},
        ])
        rows = load_rows(cap)
        assert rows["mnist.v"]["value"] == 100.0
        # step-time pseudo-rows are NOT emitted: ms moves with config
        # changes even when per-chip throughput improves
        assert "mnist.ms" not in rows

    def test_default_tolerance_without_spread(self, tmp_path):
        old = _capture(tmp_path, "a.json",
                       [{"metric": "x_per_sec", "value": 100.0}])
        new = _capture(tmp_path, "b.json",
                       [{"metric": "x_per_sec", "value": 95.0}])
        # 5% inside the 10% default
        assert diff_rows(load_rows(old), load_rows(new)) == []
        assert DEFAULT_TOLERANCE == 1.10

    def test_overlap_variant_rows_synthesize_value_and_direction(
            self, tmp_path):
        """ISSUE 8 satellite: variant-shaped ``overlap_*`` rows (no
        "value", only step_time_ms) are regression-gated — value
        synthesized from step_time_ms, unit ms => lower-is-better, so
        a SLOWER overlap_on capture is flagged."""
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"variant": "overlap_on", "step_time_ms": 100.0,
             "n_measurements": 2, "spread_max_over_min": 1.02},
            {"metric": "x", "value": 1.0},
        ])
        new = _capture(tmp_path, "BENCH_r91.json", [
            {"variant": "overlap_on", "step_time_ms": 130.0,
             "n_measurements": 2, "spread_max_over_min": 1.02},
            {"metric": "x", "value": 1.0},
        ])
        ro, rn = load_rows(old), load_rows(new)
        assert ro["overlap_on"]["value"] == 100.0
        assert lower_is_better("overlap_on", rn["overlap_on"])
        regs = diff_rows(ro, rn)
        assert [r.metric for r in regs] == ["overlap_on"]
        assert regs[0].direction == "lower-better"

    def test_wire_schedule_rungs_gated_direction_aware(self, tmp_path):
        """ISSUE 11 satellite: the ``wire_flat``/``wire_hier``/
        ``wire_hier_int8`` rungs gate like every variant row —
        step_time_ms synthesized as the value, lower-is-better, the
        rung's own spread as tolerance — and the schedule/codec
        fingerprint fields ride along without confusing the loader."""
        def rows(hier_ms, int8_ms):
            return [
                {"variant": "wire_flat", "step_time_ms": 10.0,
                 "n_measurements": 2, "spread_max_over_min": 1.03,
                 "wire_schedules": {"flat": 4},
                 "wire_plan_hash": "abc", "wire_codec": "none"},
                {"variant": "wire_hier", "step_time_ms": hier_ms,
                 "n_measurements": 2, "spread_max_over_min": 1.03,
                 "wire_schedules": {"hier_rs_ag": 4},
                 "wire_plan_hash": "def", "wire_codec": "none"},
                {"variant": "wire_hier_int8", "step_time_ms": int8_ms,
                 "n_measurements": 2, "spread_max_over_min": 1.03,
                 "wire_schedules": {"hier_rs_ag": 4},
                 "wire_plan_hash": "def", "wire_codec": "int8"},
            ]

        old = _capture(tmp_path, "BENCH_r90.json", rows(8.0, 7.0))
        # hier regressed beyond spread; int8 moved within it
        new = _capture(tmp_path, "BENCH_r91.json", rows(9.5, 7.1))
        ro, rn = load_rows(old), load_rows(new)
        for name in ("wire_flat", "wire_hier", "wire_hier_int8"):
            assert lower_is_better(name, rn[name]), name
        regs = diff_rows(ro, rn)
        assert [r.metric for r in regs] == ["wire_hier"]
        assert regs[0].direction == "lower-better"

    def test_overlap_variant_rows_spread_gated(self, tmp_path):
        """A move inside the rung's own recorded spread passes."""
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"variant": "overlap_resnet_on", "step_time_ms": 100.0,
             "n_measurements": 2, "spread_max_over_min": 1.20},
        ])
        new = _capture(tmp_path, "BENCH_r91.json", [
            {"variant": "overlap_resnet_on", "step_time_ms": 115.0,
             "n_measurements": 2, "spread_max_over_min": 1.02},
        ])
        assert diff_rows(load_rows(old), load_rows(new)) == []

    def test_overlap_speedup_row_is_higher_better(self, tmp_path):
        """bench.py's vgg16_overlap_speedup ratio: dropping from 1.08x
        to 0.99x is a regression (higher-better via 'speedup')."""
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"metric": "vgg16_overlap_speedup", "value": 1.08,
             "unit": "x (bucket overlap ON / OFF)",
             "n_measurements": 4, "spread_max_over_min": 1.03},
        ])
        new = _capture(tmp_path, "BENCH_r91.json", [
            {"metric": "vgg16_overlap_speedup", "value": 0.99,
             "unit": "x (bucket overlap ON / OFF)",
             "n_measurements": 4, "spread_max_over_min": 1.03},
        ])
        ro, rn = load_rows(old), load_rows(new)
        assert not lower_is_better(
            "vgg16_overlap_speedup", rn["vgg16_overlap_speedup"]
        )
        regs = diff_rows(ro, rn)
        assert [r.metric for r in regs] == ["vgg16_overlap_speedup"]
        assert regs[0].direction == "higher-better"

    def test_metric_rows_with_step_time_keep_their_value(self,
                                                         tmp_path):
        """The synthesis only fills the gap: a metric row carrying both
        a value and a step_time_ms keeps its value (and direction)."""
        cap = _capture(tmp_path, "BENCH_r90.json", [
            {"metric": "resnet50_train_images_per_sec_per_chip",
             "value": 2900.0, "step_time_ms": 44.0,
             "unit": "images/sec/chip"},
        ])
        rows = load_rows(cap)
        row = rows["resnet50_train_images_per_sec_per_chip"]
        assert row["value"] == 2900.0
        assert not lower_is_better(
            "resnet50_train_images_per_sec_per_chip", row
        )

    def test_failed_metric_row_with_step_time_stays_skipped(
            self, tmp_path):
        """A FAILED metric capture (value: null) must stay skipped even
        when a step_time_ms sits beside it — synthesizing would compare
        a time against a throughput baseline (a 44-vs-2900 'regression'
        in one direction, a silent pass in the other)."""
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"metric": "resnet50_train_images_per_sec_per_chip",
             "value": 2900.0, "step_time_ms": 44.0,
             "unit": "images/sec/chip"},
        ])
        new = _capture(tmp_path, "BENCH_r91.json", [
            {"metric": "resnet50_train_images_per_sec_per_chip",
             "value": None, "step_time_ms": 44.0,
             "unit": "images/sec/chip", "error": "no device"},
        ])
        ro, rn = load_rows(old), load_rows(new)
        assert rn[
            "resnet50_train_images_per_sec_per_chip"
        ]["value"] is None
        assert diff_rows(ro, rn) == []
        assert diff_rows(rn, ro) == []  # reverse direction too

    def test_explicit_pair_with_unreadable_capture_fails(
        self, tmp_path, capsys
    ):
        """Regression: a typo'd/truncated explicit path must not pass
        the gate green as '0 shared rows'."""
        good = _capture(tmp_path, "BENCH_r01.json",
                        [{"metric": "x_per_sec", "value": 1.0}])
        assert main([good, str(tmp_path / "BENCH_r99.json")]) == 2
        assert "no parseable rows" in capsys.readouterr().err
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"tail": "", "parsed": None}))
        assert main([good, str(empty)]) == 2

    def test_main_on_explicit_pair(self, tmp_path, capsys):
        old = _capture(tmp_path, "BENCH_r01.json", [
            {"metric": "tokens_per_sec_per_chip", "value": 1000.0},
        ])
        new = _capture(tmp_path, "BENCH_r02.json", [
            {"metric": "tokens_per_sec_per_chip", "value": 500.0},
        ])
        assert main([old, new]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out
        assert main([old, old]) == 0


# ----------------------------------------------------------------------
# profile provenance: annotate vs gate (ISSUE 12 satellite)
# ----------------------------------------------------------------------
class TestProfileProvenance:
    """A tuned row's regression gates when its profile hash is
    UNCHANGED (that is drift) and is annotated-but-not-gated when the
    hash moved (a retune is a disclosed config change)."""

    @staticmethod
    def _rung(value, profile_hash=None):
        row = {"variant": "wire_tuned", "step_time_ms": value,
               "n_measurements": 2, "spread_max_over_min": 1.1}
        if profile_hash is not None:
            row["profile_hash"] = profile_hash
        return row

    def test_same_profile_regression_gates(self, tmp_path):
        old = _capture(tmp_path, "a.json", [self._rung(10.0, "aaaa")])
        new = _capture(tmp_path, "b.json", [self._rung(20.0, "aaaa")])
        regs = diff_rows(load_rows(old), load_rows(new))
        assert len(regs) == 1 and not regs[0].disclosed
        assert main([old, new]) == 1

    def test_retuned_regression_annotated_not_gated(self, tmp_path,
                                                    capsys):
        old = _capture(tmp_path, "a.json", [self._rung(10.0, "aaaa")])
        new = _capture(tmp_path, "b.json", [self._rung(20.0, "bbbb")])
        regs = diff_rows(load_rows(old), load_rows(new))
        # still COMPARED — the delta is reported, just not gated
        assert len(regs) == 1 and regs[0].disclosed
        assert main([old, new]) == 0
        out = capsys.readouterr().out
        assert "RETUNED" in out
        assert "RETUNE NOTE" in out
        assert "REGRESSION" not in out

    def test_profile_appearing_counts_as_retune(self, tmp_path):
        """fixed-constant -> tuned (or back) is a config change too:
        the profile hash present on only one side discloses it."""
        old = _capture(tmp_path, "a.json", [self._rung(10.0)])
        new = _capture(tmp_path, "b.json", [self._rung(20.0, "bbbb")])
        regs = diff_rows(load_rows(old), load_rows(new))
        assert len(regs) == 1 and regs[0].disclosed
        assert main([old, new]) == 0

    def test_retune_note_emitted_without_regression(self, tmp_path,
                                                    capsys):
        """Every retuned shared row is listed even when nothing
        regressed — a capture diff always shows what was re-tuned."""
        old = _capture(tmp_path, "a.json", [self._rung(10.0, "aaaa")])
        new = _capture(tmp_path, "b.json", [self._rung(10.1, "bbbb")])
        assert main([old, new]) == 0
        out = capsys.readouterr().out
        assert "RETUNE NOTE wire_tuned: profile aaaa -> bbbb" in out

    def test_unrelated_rows_unaffected_by_retune(self, tmp_path):
        """A retune on one row never launders a regression on another
        (profile provenance is per-row, not per-capture)."""
        old = _capture(tmp_path, "a.json", [
            self._rung(10.0, "aaaa"),
            {"metric": "step_time_ms", "value": 100.0},
        ])
        new = _capture(tmp_path, "b.json", [
            self._rung(10.0, "bbbb"),
            {"metric": "step_time_ms", "value": 200.0},
        ])
        regs = diff_rows(load_rows(old), load_rows(new))
        assert [r.metric for r in regs if not r.disclosed] == [
            "step_time_ms"
        ]
        assert main([old, new]) == 1


# ----------------------------------------------------------------------
# MetricsReport phase-summary rows (ISSUE 10 satellite)
# ----------------------------------------------------------------------
class TestPhaseSummaryRows:
    def test_phase_rows_load_as_ms_pseudo_metrics(self, tmp_path):
        cap = _capture(tmp_path, "BENCH_r01.json", [
            {"phase": "step", "iteration": 6, "p50_ms": 12.5,
             "p99_ms": 30.0, "mean_ms": 14.0, "max_ms": 31.0,
             "n_measurements": 6, "spread_max_over_min": 1.08},
            {"phase": "data.wait", "iteration": 6, "p50_ms": 0.4,
             "p99_ms": 1.1, "mean_ms": 0.5, "max_ms": 1.2,
             "n_measurements": 6},
        ])
        rows = load_rows(cap)
        assert rows["phase.step.p50_ms"]["value"] == 12.5
        assert rows["phase.step.p99_ms"]["value"] == 30.0
        assert rows["phase.data.wait.p50_ms"]["value"] == 0.4
        for name in ("phase.step.p50_ms", "phase.data.wait.p99_ms"):
            assert lower_is_better(name, rows[name])

    def test_phase_regression_direction_aware(self, tmp_path):
        old = _capture(tmp_path, "BENCH_r01.json", [
            {"phase": "step", "p50_ms": 10.0, "p99_ms": 12.0,
             "n_measurements": 6, "spread_max_over_min": 1.05},
        ])
        # p50 WORSENED (10 -> 15 ms): must flag beyond tolerance
        worse = _capture(tmp_path, "BENCH_r02.json", [
            {"phase": "step", "p50_ms": 15.0, "p99_ms": 12.0,
             "n_measurements": 6, "spread_max_over_min": 1.05},
        ])
        regs = diff_rows(load_rows(old), load_rows(worse))
        assert [r.metric for r in regs] == ["phase.step.p50_ms"]
        assert regs[0].direction == "lower-better"
        # p50 IMPROVED (10 -> 7 ms): lower-is-better, no flag
        better = _capture(tmp_path, "BENCH_r03.json", [
            {"phase": "step", "p50_ms": 7.0, "p99_ms": 12.0,
             "n_measurements": 6, "spread_max_over_min": 1.05},
        ])
        assert diff_rows(load_rows(old), load_rows(better)) == []

    def test_phase_rows_use_default_tolerance_not_rank_spread(
        self, tmp_path
    ):
        """Review regression: the phase row's spread_max_over_min is
        CROSS-RANK imbalance (a straggler capture records 1.5+), not
        repeat noise — inheriting it would let genuine regressions
        hide behind one slow rank.  The pseudo-metric must use the
        default tolerance instead."""
        old = _capture(tmp_path, "BENCH_r01.json", [
            {"phase": "step", "p50_ms": 10.0, "n_measurements": 6,
             "spread_max_over_min": 1.5},
        ])
        new = _capture(tmp_path, "BENCH_r02.json", [
            {"phase": "step", "p50_ms": 14.0, "n_measurements": 6,
             "spread_max_over_min": 1.5},
        ])
        rows_new = load_rows(new)
        assert "spread_max_over_min" not in rows_new[
            "phase.step.p50_ms"
        ]
        regs = diff_rows(load_rows(old), rows_new)
        assert [r.metric for r in regs] == ["phase.step.p50_ms"]
        assert regs[0].allowed == DEFAULT_TOLERANCE
        # inside the default tolerance: not a regression
        near = _capture(tmp_path, "BENCH_r03.json", [
            {"phase": "step", "p50_ms": 10.8, "n_measurements": 6,
             "spread_max_over_min": 1.5},
        ])
        assert diff_rows(load_rows(old), load_rows(near)) == []

    def test_last_report_of_a_phase_wins(self, tmp_path):
        cap = _capture(tmp_path, "BENCH_r01.json", [
            {"phase": "step", "p50_ms": 50.0, "n_measurements": 3},
            {"phase": "step", "p50_ms": 12.0, "n_measurements": 3},
        ])
        assert load_rows(cap)["phase.step.p50_ms"]["value"] == 12.0

    def test_rows_without_numbers_skipped(self, tmp_path):
        cap = _capture(tmp_path, "BENCH_r01.json", [
            {"phase": "step", "p50_ms": None, "n_measurements": 0},
            {"phase": 7, "p50_ms": 1.0},
        ])
        assert load_rows(cap) == {}

# ----------------------------------------------------------------------
# fleet recovery rungs (ISSUE 19 satellite): the peer-vs-FS A/B gates
# ----------------------------------------------------------------------
class TestRecoveryRungs:
    def test_recover_seconds_rows_are_lower_better(self):
        # the spelling trap this tier adds: "..._peer_s" ends in "_s"
        # (a latency) and must NOT match the "_per_s" throughput rule
        for name in ("fleet_recovery.recover_peer_s",
                     "fleet_recovery.recover_fs_s"):
            assert lower_is_better(name, {"unit": "s"}), name
            assert lower_is_better(name, {}), name
        assert not lower_is_better(
            "fleet_recovery.recover_speedup", {"unit": "x"}
        )

    def test_recovery_regression_direction_aware(self, tmp_path):
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"metric": "fleet_recovery.recover_peer_s", "value": 0.011,
             "unit": "s", "n_measurements": 3,
             "spread_max_over_min": 1.3},
        ])
        # peer recovery got SLOWER beyond spread: flagged lower-better
        worse = _capture(tmp_path, "BENCH_r91.json", [
            {"metric": "fleet_recovery.recover_peer_s", "value": 0.02,
             "unit": "s", "n_measurements": 3,
             "spread_max_over_min": 1.3},
        ])
        regs = diff_rows(load_rows(old), load_rows(worse))
        assert [r.metric for r in regs] == [
            "fleet_recovery.recover_peer_s"
        ]
        assert regs[0].direction == "lower-better"
        # got FASTER: lower-better, clean
        better = _capture(tmp_path, "BENCH_r92.json", [
            {"metric": "fleet_recovery.recover_peer_s", "value": 0.005,
             "unit": "s", "n_measurements": 3,
             "spread_max_over_min": 1.3},
        ])
        assert diff_rows(load_rows(old), load_rows(better)) == []

    def test_speedup_collapse_flagged_higher_better(self, tmp_path):
        """The acceptance ratio itself: dropping from 5.9x to 1.1x —
        the RAM tier losing its edge over the FS — must gate."""
        old = _capture(tmp_path, "BENCH_r90.json", [
            {"metric": "fleet_recovery.recover_speedup", "value": 5.9,
             "unit": "x", "n_measurements": 3,
             "spread_max_over_min": 1.4},
        ])
        worse = _capture(tmp_path, "BENCH_r91.json", [
            {"metric": "fleet_recovery.recover_speedup", "value": 1.1,
             "unit": "x", "n_measurements": 3,
             "spread_max_over_min": 1.4},
        ])
        regs = diff_rows(load_rows(old), load_rows(worse))
        assert [r.metric for r in regs] == [
            "fleet_recovery.recover_speedup"
        ]
        assert regs[0].direction == "higher-better"
        better = _capture(tmp_path, "BENCH_r92.json", [
            {"metric": "fleet_recovery.recover_speedup", "value": 8.0,
             "unit": "x", "n_measurements": 3,
             "spread_max_over_min": 1.4},
        ])
        assert diff_rows(load_rows(old), load_rows(better)) == []

    def test_bench_recover_rows_load_and_self_diff_clean(self):
        """The bench's _recover_rows emit the metric/value shape the
        loader requires: min-of-samples latencies (unit s), max paired
        speedup (unit x), protocol fields riding along."""
        from fleet_chaos_bench import _recover_rows

        rows = _recover_rows({
            "recover_peer_s": [0.011, 0.012],
            "recover_fs_s": [0.071, 0.066],
        })
        by = {r["metric"]: r for r in rows}
        assert by["fleet_recovery.recover_peer_s"]["value"] == 0.011
        assert by["fleet_recovery.recover_fs_s"]["unit"] == "s"
        # paired ratios, NOT min/min across repeats: max(f_i / p_i)
        want = round(max(0.071 / 0.011, 0.066 / 0.012), 2)
        assert by["fleet_recovery.recover_speedup"]["value"] == want
        assert all("n_measurements" in r for r in rows)

        import json as _json
        import tempfile as _tempfile

        with _tempfile.TemporaryDirectory() as td:
            tail = "\n".join(_json.dumps(r) for r in rows) + "\n"
            p = os.path.join(td, "BENCH_r90.json")
            with open(p, "w") as fh:
                _json.dump({"n": 1, "rc": 0, "tail": tail}, fh)
            loaded = load_rows(p)
        assert lower_is_better(
            "fleet_recovery.recover_peer_s",
            loaded["fleet_recovery.recover_peer_s"],
        )
        assert not lower_is_better(
            "fleet_recovery.recover_speedup",
            loaded["fleet_recovery.recover_speedup"],
        )
        assert diff_rows(loaded, loaded) == []
