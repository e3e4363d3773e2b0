"""The readers of the set-up metrics (``phase_s``, ``phase_count``) and
their helper ``_process``: on a recorded process record, in a rehearsal,
on a program that keeps no record, and on the record this process
keeps."""

import json
import os
import sys
import types

import pytest

from cellbench import run
from cellbench.readers import _process, phase_count, phase_s

METRICS = ("setup_before_program_s", "setup_import_s",
           "setup_init_params_s", "setup_step_trace_s",
           "setup_step_load_s", "setup_programs", "setup_cache_misses")


def _span(name, t, dur, sid, parent, **args):
    return {"type": "span", "name": name, "t": t, "dur": dur, "sid": sid,
            "parent": parent, "ident": 1, "args": args}


#: a set-up as the program records it (times in seconds from a start of
#: 100): two step objects' first calls, a program compiled after the
#: last of them, and a recompile inside the window
RECORDED = {
    "start": 100.0, "origin": "os", "dropped": 0, "recompiles": [],
    "counters": {}, "by_phase": {},
    "spans": [
        _span("setup", 100.0, 30.0, -1, 0),
        _span("setup.before_program", 100.0, 6.0, -2, -1),
        _span("setup.import", 106.0, 2.5, -3, -1),
        _span("setup.init_params", 110.0, 4.0, -4, -1),
        _span("jax.compile", 111.0, 1.0, -5, -4, cache="hit"),
        _span("setup.init_params", 115.0, 0.5, -6, -1),
        _span("step.first_call", 120.0, 8.0, -7, -1, ordinal=1),
        _span("jax.trace", 120.0, 3.0, -8, -7),
        _span("jax.trace", 120.5, 1.0, -9, -8),  # nested: not a child
        _span("jax.lower", 123.0, 1.5, -10, -7),
        _span("jax.compile", 124.5, 3.5, -11, -7, cache="miss"),
        _span("step.first_call", 129.0, 1.0, -12, -1, ordinal=1),
        _span("jax.trace", 129.0, 0.25, -13, -12),
        _span("jax.compile", 129.5, 0.5, -14, -12, cache="uncached"),
        _span("jax.compile", 131.0, 0.5, -15, -1, cache="miss"),
        _span("step.first_call", 140.0, 2.0, -16, -1, ordinal=9,
              recompile=True),
        _span("jax.compile", 141.0, 1.0, -17, -16, cache="miss"),
    ],
}
EXPECTED = {
    "setup_before_program_s": 6.0, "setup_import_s": 2.5,
    "setup_init_params_s": 4.5, "setup_step_trace_s": 3.0 + 1.5 + 0.25,
    "setup_step_load_s": 3.5 + 0.5, "setup_programs": 3,
    "setup_cache_misses": 1,
}


def _ctx(rehearse=False):
    return types.SimpleNamespace(
        spec=types.SimpleNamespace(rehearse=rehearse))


def _read(name, ctx):
    """The metric ``name`` as ``layers.read_metrics`` reads it."""
    with open(os.path.join(run.ROOT, "cellbench", "layer_metrics",
                           name + ".json")) as f:
        how = json.load(f)
    reader = {"phase_s": phase_s, "phase_count": phase_count}[
        how["reader"]]
    return reader.read(ctx, **how.get("args", {}))


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_on_a_recorded_process_record(monkeypatch, name):
    monkeypatch.setattr(_process, "record", lambda ctx: RECORDED)
    assert _read(name, _ctx()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", METRICS)
def test_none_in_a_rehearsal_and_without_a_record(monkeypatch, name):
    assert _read(name, _ctx(rehearse=True)) is None
    # the parent commit's program: a timeline with no process record
    monkeypatch.setitem(sys.modules,
                        "chainermn_tpu.observability.timeline",
                        types.ModuleType("timeline"))
    assert _read(name, _ctx()) is None


def test_none_where_a_phase_did_not_run_or_spans_were_dropped(monkeypatch):
    rec = dict(RECORDED, spans=RECORDED["spans"][:3])
    monkeypatch.setattr(_process, "record", lambda ctx: rec)
    assert phase_s.read(_ctx(), "setup.import") == 2.5
    assert phase_s.read(_ctx(), "setup.init_params") is None
    assert phase_s.read(_ctx(), "step.first_call", ["jax.compile"]) is None
    assert phase_count.read(_ctx(), "jax.compile", "step.first_call") is None
    full = dict(RECORDED, dropped=1)
    monkeypatch.setattr(_process, "record", lambda ctx: full)
    assert phase_count.read(_ctx(), "jax.compile", "step.first_call") is None


def test_the_metrics_are_listed_for_every_cell_and_move_setup_s():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        m = listed[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == cells
    assert [m["name"] for m in bench["per_layer"][-len(METRICS):]] \
        == list(METRICS)


def test_on_the_record_this_process_keeps():
    """A jit inside ``step.first_call``-like phases of the live record:
    the readers find what the program recorded, on its clock."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import observability as obs

    def a_program_of_this_test(x):
        return jnp.tanh(x) + 2

    with obs.phase("setup.test_readers"):
        jax.block_until_ready(jax.jit(a_program_of_this_test)(
            jnp.ones((3,))))
    ctx = _ctx()
    whole = phase_s.read(ctx, "setup.test_readers")
    parts = phase_s.read(ctx, "setup.test_readers",
                         ["jax.trace", "jax.lower", "jax.compile"])
    assert 0 < parts <= whole
    assert phase_count.read(ctx, "jax.compile", "setup.test_readers") >= 1
    assert phase_s.read(ctx, "setup.before_program") > 0
    assert phase_s.read(ctx, "setup.import") > 0
