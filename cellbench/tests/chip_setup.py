"""Where a cell's ``setup_s`` goes, on the chip: one run of the cell
through ``run.run_cell`` with the harness's own parts of set-up marked
as phases of the program's process record beside the program's
(``harness.build`` > ``example.main`` > the program's ``setup.*`` /
``step.first_call``; ``harness.first_steps``; ``harness.window_warm``),
then the record as a table and as a file.

    python3 -m cellbench.tests.chip_setup --workload W --seed N \\
        --seconds 30 --trace 0 [--skip-reference] [--telemetry]

``--skip-reference`` hands ``correct`` the program's own readings (a
run about set-up only need not pay the reference's compile);
``--telemetry`` installs a ``Telemetry`` over the window of an untraced
run (what the program's spans cost when they are on).  Prints the
result line ``cellbench.run`` prints, with ``setup_s`` and the window's
rate and p90 also in a traced run.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def table(rec: dict, t_end: float, top: int = 4):
    """Rows ``(depth, name, start_s, seconds, note)`` of the record's
    phases up to ``t_end`` in time order, a row for every stretch
    between two phases of one parent (``(between)``: the JAX stages that
    ran there, by program), and the compile counters."""
    # harness.build is stamped around the phases it holds, not over them
    spans = [e for e in rec["spans"]
             if e["t"] < t_end and e["name"] != "harness.build"]
    kids = {}
    for e in spans:
        kids.setdefault(e["parent"], []).append(e)
    rows = []

    def stages(events):
        by = {}
        for e in events:
            if e["name"].startswith("jax."):
                key = e["args"].get("fun_name", "")
                by[key] = by.get(key, 0.0) + e["dur"]
        n = sum(1 for e in events if e["name"] == "jax.compile")
        if not by:
            return ""
        worst = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return (f"{n} programs, {sum(by.values()):.2f} s in JAX's "
                "stages: " + ", ".join(f"{k} {v:.2f}" for k, v in worst))

    def walk(span, depth):
        end = min(span["t"] + span["dur"], t_end) if span["sid"] != -1 \
            else t_end
        rows.append((depth, span["name"], span["t"] - rec["start"],
                     end - span["t"], json.dumps(span["args"])
                     if span["args"] else ""))
        own = sorted(kids.get(span["sid"], []), key=lambda e: e["t"])
        phases = [e for e in own if not e["name"].startswith("jax.")]
        if not phases:
            note = stages(own)
            if note:
                rows.append((depth + 1, "(jax stages)", 0.0, 0.0, note))
            return
        cursor = span["t"]
        for ph in phases + [None]:
            upto = end if ph is None else ph["t"]
            loose = [e for e in own if e["name"].startswith("jax.")
                     and cursor <= e["t"] < upto]
            if upto - cursor > 0.05 or loose:
                rows.append((depth + 1, "(between)", cursor - rec["start"],
                             upto - cursor, stages(loose)))
            if ph is not None:
                walk(ph, depth + 1)
                cursor = ph["t"] + ph["dur"]

    walk(spans[0], 0)
    for e in rec["spans"]:
        if e["name"] == "harness.build":
            rows.append((1, "harness.build (holds example.main)",
                         e["t"] - rec["start"], e["dur"], ""))
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--skip-reference", action="store_true")
    p.add_argument("--telemetry", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--tag", default="")
    args = p.parse_args(argv)
    t_main = time.monotonic()
    sys.path.insert(0, ROOT)
    from cellbench import run, window
    from cellbench.runners import common

    seen = {}
    # what the harness does before the program's first line (inside
    # setup.before_program): stamped here, the record does not exist yet
    stamps = [("python to this script's main", None, t_main)]

    def stamped(name, fn):
        def inner(*a, **k):
            t = time.monotonic()
            try:
                return fn(*a, **k)
            finally:
                stamps.append((name, t, time.monotonic()))

        return inner

    run.load_spec = stamped("harness.load_spec", run.load_spec)
    run.require_chips = stamped(
        "harness.require_chips (import jax, the TPU client)",
        run.require_chips)
    load_example = common.load_example

    def marked_example(rel_path):
        from chainermn_tpu.observability import timeline

        module = load_example(rel_path)
        main_ = module.main

        def main_in_phase(argv):
            with timeline.phase("example.main"):
                return main_(argv)

        module.main = main_in_phase
        return module

    def build(spec):
        runner = stamped("harness.import_runner", importlib.import_module)(
            f"cellbench.runners.{spec.config['runner']}")
        # the runners took the names at import
        runner.load_example = marked_example
        runner.load_reference = stamped("harness.load_reference",
                                        runner.load_reference)
        t_build = time.monotonic()
        cell = runner.build(spec)
        from chainermn_tpu.observability import timeline

        with timeline.PROCESS.phase("harness.build", t0=t_build):
            pass  # stamped around the phases it holds, not over them
        first_steps, start_window, free = \
            cell.first_steps, cell.start_window, cell.free

        def marked_first_steps():
            with timeline.phase("harness.first_steps"):
                seen["program"] = first_steps()
            return seen["program"]

        def marked_start_window():
            start_window()
            if args.telemetry and timeline.active() is None:
                seen["telemetry"] = timeline.Telemetry("chip_setup")
                timeline.install(seen["telemetry"])

        def marked_free():
            if "telemetry" in seen:
                timeline.install(None)
            free()

        cell.first_steps, cell.start_window, cell.free = \
            marked_first_steps, marked_start_window, marked_free
        if args.skip_reference:
            cell.reference = lambda inputs, lowp=False: seen["program"]
        return cell

    run_window, summarize = window.run_window, window.summarize

    def spy_window(*a, **k):
        out = run_window(*a, **k)
        seen["t0"] = out[0]
        return out

    def spy_summarize(*a, **k):
        seen["stats"] = summarize(*a, **k)
        return seen["stats"]

    window.run_window, window.summarize = spy_window, spy_summarize
    result = run.run_cell(args, build=build)
    print(json.dumps(result), flush=True)

    timeline = sys.modules.get("chainermn_tpu.observability.timeline")
    if not hasattr(timeline, "process_record"):
        return 0  # the parent commit's program keeps no record
    # a rehearsal's lines say so: CPU times at tiny sizes, for no record
    mark = "REHEARSAL-" if args.rehearse else ""
    rec = timeline.process_record()
    # the harness stamps perf_counter, the record monotonic
    to_mono = time.monotonic() - time.perf_counter()
    t0 = seen["t0"] + to_mono
    setup_s = seen["t0"] - run._T_START
    stats = seen["stats"]
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    line = {"workload": args.workload, "seed": args.seed,
            "trace": args.trace, "telemetry": bool(args.telemetry),
            "setup_s": setup_s,
            "setup_from_process_start_s": t0 - rec["start"],
            "steps": stats["steps"], "elapsed_s": stats["elapsed_s"],
            "steps_per_s_per_chip": stats["steps"] / stats["elapsed_s"]
            / int(cell["chips"]),
            "step_ms_p90": stats["step_ms_p90"],
            "step_ms_median": stats["step_ms_median"],
            "late_steps": stats["late_steps"],
            "counters": rec["counters"], "by_phase": rec["by_phase"],
            "recompiles": rec["recompiles"], "dropped": rec["dropped"],
            "spans_kept": len(rec["spans"])}
    if "telemetry" in seen:
        tl = seen["telemetry"].timeline
        line["spans_recorded_in_window"] = len(tl)
    print(mark + "SETUP " + json.dumps(line), flush=True)
    for name, t_a, t_b in stamps:
        t_a = rec["start"] if t_a is None else t_a
        print(f"{mark}STAMP {name:<52} {t_a - rec['start']:9.3f} "
              f"{t_b - t_a:9.3f}", flush=True)
    rows = table(rec, t0)
    for depth, name, start, seconds, note in rows:
        print(f"{mark}PHASE {'  ' * depth}{name:<{36 - 2 * depth}} "
              f"{start:9.3f} {seconds:9.3f}  {note}", flush=True)
    if args.rehearse:
        return 0
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = f"setup_{args.workload}_{args.seed}{args.tag}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump({"line": line, "result": result, "t0": t0,
                   "record": rec}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
