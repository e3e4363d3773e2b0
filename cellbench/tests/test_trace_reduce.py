"""``trace_reduce`` on the recorded trace (three steps of the one-chip
LM cell on a v5e) and on hand-made intervals."""

import gzip
import json
import os
import re

import pytest

from cellbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_lm_trace.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_interval_arithmetic():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert tr.subtract(merged, [[2, 6]]) == [[0, 2], [6, 8]]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_recorded_busy_steps_and_gaps(recorded):
    r = tr.reduce(recorded)
    assert r["steps"] == 3
    # three back-to-back 245 ms step programs
    assert r["window_s"] == pytest.approx(0.7351, abs=1e-3)
    assert 0.99 < r["busy_s"] / r["window_s"] <= 1.0
    assert r["launch_gap_ms"] == pytest.approx(0.0087, abs=2e-3)
    gaps = [g for _, g in r["breakdown"]["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] < 1e-4
    assert len(r["breakdown"]["device_ops"]) == 10
    assert r["collective_s"] == 0.0  # one chip: nothing to exchange


def test_recorded_kernel_sums(recorded):
    r = tr.reduce(recorded)
    rx = re.compile("^%_flash_(forward|backward)")
    calls = sum(c for n, (_, c) in r["ops"].items() if rx.search(n))
    seconds = sum(s for n, (s, _) in r["ops"].items() if rx.search(n))
    assert calls == 3 * 18 * 3  # 18 layers x (fwd, dq, dk/dv) x 3 steps
    assert seconds / r["steps"] == pytest.approx(0.0400, abs=5e-4)


def test_exposed_collective():
    ops = [("%fusion.1 = f32[8]{0} fusion()", 0, 100),
           ("%all-reduce-done.1 = f32[8]{0} all-reduce-done()", 100, 30),
           ("%fusion.2 = f32[8]{0} fusion()", 130, 70)]
    asyncs = [("%all-reduce-start.1 = f32[8]{0} all-reduce-start()", 40, 90)]
    dev = {"XLA Modules": [("jit_step(1)", 0, 200)], "XLA Ops": ops,
           "Async XLA Ops": asyncs}
    r = tr.reduce({"devices": [dev], "host": [("Execute", 90, 50)]})
    assert r["collective_s"] == pytest.approx(90e-9)      # 40..130
    assert r["collective_exposed_s"] == pytest.approx(30e-9)  # 100..130
    assert r["busy_s"] == pytest.approx(200e-9)


def test_a_cut_first_step_is_left_out():
    op = "%fusion.1 = f32[8]{0} fusion()"
    dev = {"XLA Modules": [("jit_step(1)", 60, 40), ("jit_step(1)", 100, 100),
                           ("jit_step(1)", 200, 100)],
           "XLA Ops": [(op, 60, 40), (op, 100, 100), (op, 200, 100)]}
    r = tr.reduce({"devices": [dev], "host": []})
    assert r["steps"] == 2 and r["window_s"] == pytest.approx(200e-9)
    assert r["ops"][op] == (pytest.approx(200e-9), 2)


def test_family_names():
    assert tr.family("%fusion.9 = f32[50257,1536]{1,0:T(8,128)} fusion(x)") \
        == "fusion f32[50257,1536]"
    assert tr.is_collective("%all-reduce-start.3 = f32[2] all-reduce-start()")
    assert not tr.is_collective("%fusion.3 = f32[2] fusion(%all-reduce.1)")
