"""Read, on the chip and at a cell's own size, what its ``correct``
limits are set from: the program's gaps from the reference over many
seeds, and the control's (the reference in scaled float8) over a few.

    python3 -m cellbench.tests.chip_readings --workload W --seeds 12 \\
        --control 3 --first-seed 1000

One process: the cell is built once and re-seeded, the program's
readings are taken first, then its state is freed and the reference and
the control follow the same batches.  Not part of a benchmark run.
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


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=1000)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    from cellbench import compare, run

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    spec, _ = run.load_spec(args.workload, seeds[0], args.rehearse, False)
    if args.rehearse:
        run.rehearse_on_cpu(spec.chips)
    device = run.require_chips(spec.chips, args.rehearse)
    cell = importlib.import_module(
        f"cellbench.runners.{spec.config['runner']}").build(spec)
    taken = []
    for seed in seeds:
        cell.reseed(seed)
        taken.append((cell.first_steps(), cell.first_inputs()))
    cell.free()
    rows = []
    for i, (program, inputs) in enumerate(taken):
        t = time.perf_counter()
        reference = cell.reference(inputs)
        row = {"seed": inputs["seed"],
               "reference_s": time.perf_counter() - t,
               "program": compare.decide(program, reference,
                                         spec.config["correct"])["values"],
               "losses": {"program": program["losses"],
                          "reference": reference["losses"]}}
        if i < args.control:
            control = cell.reference(inputs, lowp=True)
            row["control"] = compare.decide(
                control, reference, spec.config["correct"])["values"]
            row["losses"]["control"] = control["losses"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "device": device}
    for name in rows[0]["program"]:
        sound = [r["program"][name] for r in rows]
        ctl = [r["control"][name] for r in rows if "control" in r]
        summary[name] = {"sound_max": max(sound), "sound_min": min(sound),
                         "control_min": min(ctl) if ctl else None}
    print(json.dumps(summary), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"readings_{args.workload}.json"), "w") as f:
        json.dump({"summary": summary, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
