"""The traced run: a profiler trace over the end of the window, its
reduction, and the per-layer readers found by name.

A per-layer metric ``<name>`` is ``layer_metrics/<name>.json``
(``{"reader": <module under readers/>, "args": {...}}``) beside the
``BENCHMARK.json`` entry that gives its unit, layer and ``moves``; both
directories are looked for next to the cell's ``configs/`` first, then
under ``cellbench/``.  The reader's ``read(ctx, **args)`` returns a
number, or None where it finds nothing to read, and the metric is then
left out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import tempfile

#: seconds at the end of the window that the traced run profiles
TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Context:
    """What a reader may look at."""

    spec: object        # run.Spec: config, traffic, sizes, chips
    stats: dict         # window.summarize
    samples_per_step: int
    peak_bytes: object  # peak bytes on the fullest chip, or None
    telemetry: object   # the program's own spans, or None
    trace: dict         # trace_reduce.reduce of the traced part
    device: dict
    root: str

    def untraced_rate_per_chip(self) -> float:
        """Samples a second a chip over the untraced part of the window."""
        part = self.trace["untraced"]
        return part["steps"] * self.samples_per_step / part["elapsed_s"] \
            / self.spec.chips

    def peaks(self) -> dict:
        """This device kind's published peaks; an unknown kind raises."""
        with open(os.path.join(self.root, "cellbench", "peaks.json")) as f:
            table = json.load(f)["device_kinds"]
        kind = self.device["kind"]
        if kind not in table:
            raise KeyError(f"no published peaks for device kind {kind!r}")
        return table[kind]


def traced_window(dispatch, seconds: float, spec):
    """The window, with the profiler on over its last
    ``TRACE_SECONDS``.  Returns the window's ``(t0, stamps, losses)``,
    the trace directory and the reduced trace."""
    import jax

    from . import trace_reduce, window

    span = min(TRACE_SECONDS, seconds)
    trace_dir = tempfile.mkdtemp(prefix="cellbench_trace_")
    state = {"on": False, "steps": 0, "elapsed_s": 0.0}

    # the host tracer at its lowest level: at the default a host-to-
    # device copy of one image batch writes millions of events
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 1

    def on_stamp(n, elapsed):
        if not state["on"] and elapsed >= seconds - span:
            # what came before is the untraced part: the profiler slows
            # the host, so rates are read from it
            state.update(on=True, steps=n, elapsed_s=elapsed)
            jax.profiler.start_trace(trace_dir, profiler_options=options)

    try:
        t0, stamps, losses = window.run_window(dispatch, seconds,
                                               on_stamp=on_stamp)
    finally:
        if state["on"]:
            jax.profiler.stop_trace()
    traced = trace_reduce.reduce(trace_reduce.load(trace_dir, spec.chips))
    if state["steps"] < 2:  # a window no longer than the traced part
        state.update(steps=len(stamps), elapsed_s=stamps[-1] - t0)
    traced["untraced"] = {"steps": state["steps"],
                          "elapsed_s": state["elapsed_s"]}
    return t0, stamps, losses, trace_dir, traced


def cleanup(trace_dir):
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _base_of(ctx: Context, subdir: str, filename: str) -> str:
    """The directory that holds ``<subdir>/<filename>``: the cell's own
    first, then the benchmark's."""
    for base in dict.fromkeys((ctx.spec.base, "cellbench")):
        if os.path.exists(os.path.join(ctx.root, base, subdir, filename)):
            return base
    raise FileNotFoundError(f"no {subdir}/{filename} for a metric that "
                            "BENCHMARK.json lists")


def read_metrics(wanted, ctx: Context) -> dict:
    """``{name: {"value", "unit"}}`` for every wanted per-layer metric
    whose reader found something."""
    out = {}
    for m in wanted:
        filename = m["name"] + ".json"
        with open(os.path.join(ctx.root, _base_of(ctx, "layer_metrics",
                                                  filename),
                               "layer_metrics", filename)) as f:
            how = json.load(f)
        base = _base_of(ctx, "readers", how["reader"] + ".py")
        reader = importlib.import_module(
            base.replace("/", ".") + ".readers." + how["reader"])
        value = reader.read(ctx, **how.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
