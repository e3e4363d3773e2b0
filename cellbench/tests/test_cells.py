"""The harness end to end: ``--rehearse`` of every cell in
``BENCHMARK.json``, the refusal to run without a TPU, and a fourth cell
that is only files."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from cellbench import run

ROOT = run.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _child(*argv):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("workload", _cells())
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearse_every_cell(workload, trace):
    done = _child("--workload", workload, "--seed", str(2**31 + 5),
                  "--seconds", "1", "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # a CPU run never reports a time, a rate or a share
    assert line["metrics"] and all(
        m["value"] is None for m in line["metrics"].values())


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    done = _child("--workload", _cells()[0], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


def test_a_fourth_cell_is_only_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and its reader
    under ``tests/fourth_cell/``, plus entries in (a copy of)
    ``BENCHMARK.json``: no file of the harness is edited."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "fourth_cell", "entries.json")) as f:
        extra = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += extra[key]
    for metric in bench["end_to_end"]:
        metric.get("workloads", []).extend(
            extra["end_to_end_workloads"].get(metric["name"], []))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    args = argparse.Namespace(workload="tinygpt_train_s64", seed=5,
                              seconds=1.0, trace=1, rehearse=True)
    result = run.run_cell(args, check_chip=False, benchmark=str(path))
    assert result["correct"] is True
    counted = result["metrics"]["steps_counted.tiny"]
    assert counted == {"value": result["attempted"], "unit": "steps"}
