#!/usr/bin/env python3
"""One run of one cell: ``python3 -m cellbench.run --workload W --seed N
--seconds S --trace 0|1`` from the root of a checkout.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives
it; this file names none of them.  The last line of standard output is
the result object.  ``--rehearse`` runs the same path at the
configuration's tiny ``rehearse`` sizes on the CPU and reports no time.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Spec:
    """What a runner is given."""

    workload: dict
    config: dict
    traffic: dict
    sizes: dict
    chips: int
    seed: int
    rehearse: bool
    trace: bool
    base: str  # the directory that holds this cell's configs/, traffic/


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, seed: int, rehearse: bool, trace: bool,
              root: str = ROOT, benchmark: str = "") -> tuple[Spec, dict]:
    bench = _load_json(benchmark or os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    base = os.path.dirname(os.path.dirname(entry["file"]))
    traffic = _load_json(os.path.join(root, base, "traffic",
                                      cell["traffic"] + ".json"))
    sizes = {k: v for k, v in config.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if rehearse:  # tiny sizes, and the limits that hold at them
        tiny = dict(config["rehearse"])
        config = {**config, "correct": tiny.pop("correct",
                                                config["correct"])}
        sizes.update(tiny)
        traffic = {**traffic, **traffic.get("rehearse", {})}
    return Spec(cell, config, traffic, sizes, int(cell["chips"]), seed,
                rehearse, trace, base), bench


def rehearse_on_cpu(chips: int):
    """Before JAX starts: the CPU backend, with as many virtual devices
    as the cell has chips."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}")


def require_chips(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it.  Anything but ``chips`` TPU chips
    (or, rehearsing, CPU devices) ends the run with no result."""
    import jax

    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < chips:
        raise SystemExit(
            f"need {chips} {want} device(s); JAX reports {len(devs)} x "
            f"{devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def cache_every_program():
    """Let JAX's persistent cache keep every program, also the hundreds
    of small ones an example compiles outside its step (by default only
    programs that took a second to compile are kept, and a warm run
    compiles the rest again)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(chips: int):
    """Peak bytes on the fullest chip, read when the window closes, and
    the readings it is made of.  The TPU runtime counts live buffers
    (``bytes_in_use``) apart from the space it reserves for the loaded
    programs' temporaries (``bytes_reserved``); both come out of the
    same HBM, so what a step held is their sum, and the peak is that or
    the buffers' own peak, whichever is larger.  The reservation is the
    step program's ``temp_size_in_bytes`` (``PERF.md`` section 2 sets
    both cells' against an ahead-of-time compile)."""
    import jax

    keys = ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved")
    best = (None, {})
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(stats["peak_bytes_in_use"],
                       stats.get("bytes_in_use", 0)
                       + stats.get("peak_bytes_reserved", 0))
            if best[0] is None or peak > best[0]:
                best = (peak, {k: stats.get(k) for k in keys})
    return best


def metric_names(bench: dict, kind: str, workload: str):
    """The ``kind`` metrics this cell reports: those that list it under
    ``workloads``, and those without the key, which belong to every
    cell (a per-layer reader that finds nothing there returns None)."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", (workload,))]


def run_cell(args, *, check_chip: bool = True, build=None,
             benchmark: str = "") -> dict:
    """Everything a run does after the arguments are parsed; returns the
    result object.  ``build`` replaces the runner's (tests break the
    timed path through it); ``benchmark`` another ``BENCHMARK.json``
    (the tests' files-only cell)."""
    from . import compare, layers, window

    spec, bench = load_spec(args.workload, args.seed, args.rehearse,
                            bool(args.trace), benchmark=benchmark)
    if args.rehearse:
        rehearse_on_cpu(spec.chips)
    device = require_chips(spec.chips, args.rehearse) if check_chip \
        else {"platform": "unchecked", "kind": "unchecked",
              "count": spec.chips}
    cache_every_program()
    if build is None:
        build = importlib.import_module(
            f"cellbench.runners.{spec.config['runner']}").build

    e2e = metric_names(bench, "end_to_end", args.workload)
    with window.CompileCounter() as setup_compiles:
        cell = build(spec)
        program = cell.first_steps()
    trace_dir = None
    cell.start_window()
    with window.CompileCounter() as compiles:
        if spec.trace:
            t0, stamps, losses, trace_dir, traced = layers.traced_window(
                cell.dispatch, args.seconds, spec)
        else:
            t0, stamps, losses = window.run_window(cell.dispatch,
                                                   args.seconds)
    setup_s = t0 - _T_START
    stats = window.summarize(t0, stamps, losses)
    peak, peak_parts = memory_peak(spec.chips)
    telemetry = cell.telemetry()
    inputs = cell.first_inputs()
    cell.free()

    # correct: the plain reference follows the same first steps, after
    # the program's state is freed and outside set-up and window
    t_ref = time.perf_counter()
    with window.CompileCounter() as ref_compiles:
        verdict = compare.decide(program, cell.reference(inputs),
                                 spec.config["correct"])
    for line in verdict["lines"]:
        print(line)
    if compiles.count:
        print(f"correct: {compiles.count} compile(s) inside the timed "
              "window (limit 0)")
    correct = verdict["correct"] and compiles.count == 0 \
        and stats["failed"] == 0
    if not args.rehearse:  # a CPU run prints no time
        print(json.dumps({
            "window": {k: stats[k] for k in ("steps", "elapsed_s",
                                             "step_ms_median",
                                             "step_ms_max", "late_steps",
                                             "late_ms")},
            "memory": peak_parts,
            "setup_compile_s": setup_compiles.seconds,
            "setup_compiles": setup_compiles.count,
            "reference_s": time.perf_counter() - t_ref,
            "reference_compile_s": ref_compiles.seconds,
            "reference_compiles": ref_compiles.count}))

    samples = stats["steps"] * cell.samples_per_step
    values = {
        spec.traffic["rate_metric"]:
            samples / stats["elapsed_s"] / spec.chips,
        spec.traffic["tail_metric"]: stats["step_ms_p90"],
        "setup_s": setup_s,
    }
    result = {"correct": bool(correct), "attempted": stats["steps"],
              "failed": stats["failed"]}
    if spec.trace:
        listed = metric_names(bench, "per_layer", args.workload)
        # a CPU run reads counters only: never a time, a rate or a share
        wanted = [m for m in listed
                  if not args.rehearse or m["source"] == "program_counter"]
        ctx = layers.Context(spec=spec, stats=stats,
                             samples_per_step=cell.samples_per_step,
                             peak_bytes=peak, telemetry=telemetry,
                             trace=traced, device=device, root=ROOT)
        result["metrics"] = layers.read_metrics(wanted, ctx)
        layers.cleanup(trace_dir)
    else:
        listed = e2e
        result["metrics"] = {} if args.rehearse else {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in e2e}
    if args.rehearse:
        for m in listed:
            result["metrics"].setdefault(
                m["name"], {"value": None, "unit": m["unit"]})
    elif spec.trace:
        device = {**device, "busy_s": traced["busy_s"],
                  "window_s": traced["window_s"]}
        result["breakdown"] = traced["breakdown"]
    result["device"] = {**device, "memory_peak_bytes": peak}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU; correct and counts only")
    args = p.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    result = run_cell(args)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
