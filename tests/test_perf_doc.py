"""A document's measured table must be a function of the bench JSON.

A hand-copied number drifts from the capture it came from.
``benchmarks/gen_perf_table.py`` renders the table between markers that
declare its source file, and its check mode fails on any drift — a stale
or hand-edited number cannot be committed silently.  The tests drive the
tool on a synthetic capture and a synthetic marked document.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "benchmarks", "gen_perf_table.py")

_DOC = """# Perf

<!-- bench-table:begin source=BENCH_r01.json -->
(never generated)
<!-- bench-table:end -->

prose after the table
"""


def _root(tmp_path):
    """A repo-shaped root: the capture at the top, the doc in docs/."""
    record = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": 2900.5, "unit": "images/sec/chip",
        "step_time_ms": 44.1, "mfu": 0.35,
        "configs": {
            "moe_lm": {"metric": "moe_lm_tokens_per_sec_per_chip",
                       "value": 86000.0, "unit": "tokens/sec/chip",
                       "step_time_ms": 95.0, "mfu": 0.54,
                       "mfu_xla_counted": 0.49},
            "seq2seq_mp": {"metric": "seq2seq_mp_tokens_per_sec_per_chip",
                           "value": 1.2e6,
                           "unit": "tokens/sec/chip (enc|dec chain)"},
            "broken": {"metric": "broken", "error": "boom"},
        },
    }
    capture = tmp_path / "BENCH_r01.json"
    capture.write_text(json.dumps({"n": 1, "rc": 0, "parsed": record}))
    doc = tmp_path / "docs" / "performance.md"
    doc.parent.mkdir()
    doc.write_text(_DOC)
    return str(capture), doc


def _tool(doc, *args):
    return subprocess.run(
        [sys.executable, TOOL, "--doc", str(doc), *args],
        capture_output=True, text=True,
    )


def test_measured_table_matches_declared_source(tmp_path):
    _, doc = _root(tmp_path)
    drift = _tool(doc)
    assert drift.returncode != 0 and "drifted" in drift.stderr
    assert _tool(doc, "--write").returncode == 0
    ok = _tool(doc)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "matches BENCH_r01.json" in ok.stdout
    text = doc.read_text()
    assert "2,900.5" in text and "prose after the table" in text
    # a hand-edited number is drift again
    doc.write_text(text.replace("2,900.5", "3,100.0"))
    assert _tool(doc).returncode != 0


def test_generator_output_shape(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        from gen_perf_table import generate
    finally:
        sys.path.pop(0)

    capture, _ = _root(tmp_path)
    lines = generate(capture).splitlines()
    assert lines[0].startswith("| config |")
    assert all(l.count("|") - l.count("\\|") == 8 for l in lines)
    # headline + every config row present
    assert any("resnet50 (headline)" in l for l in lines)
    assert any("seq2seq_mp" in l and "enc\\|dec" in l for l in lines)
    assert any("moe_lm" in l and "0.540" in l and "0.490" in l
               for l in lines)
    assert any(l.startswith("| broken |") and "error" in l for l in lines)
