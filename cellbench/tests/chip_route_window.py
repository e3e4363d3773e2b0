"""Read, on the chip and at the cell's own size, the two ends the
reference's ``route_tie_window`` is set from (``reference/sdar_moe.py``).

    python3 -m cellbench.tests.chip_route_window --workload W --seeds 5 \\
        --control 2 --windows 0.003 0.01 0.03 --first-seed 1000

The lower end: the program's gaps from a reference that follows its
routes inside each window (too narrow a window leaves flipped near-ties
standing, and the gaps rise), with the share of routes the reference
took from the program over its own.  The upper end: **router controls**,
the float32 reference with only its router's product in bfloat16 or in
scaled float8, routing for itself as a program would; the reference
then follows *their* routes inside each window, and a window that hides
a control's router reads it as sound.  The float8 control of every
product (``lowp=True``, following the program's routes) is read at each
window too.  One process, as ``chip_readings``; not part of a run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROUTER_CONTROLS = ("bfloat16", "float8")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=5)
    p.add_argument("--control", type=int, default=2)
    p.add_argument("--first-seed", type=int, default=1000)
    p.add_argument("--windows", type=float, nargs="+",
                   default=[0.003, 0.01, 0.03])
    p.add_argument("--wide", type=float, default=0.1,
                   help="one more window, for the router controls only")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    from cellbench import compare, run

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    spec, _ = run.load_spec(args.workload, seeds[0], args.rehearse, False)
    if args.rehearse:
        run.rehearse_on_cpu(spec.chips)
    device = run.require_chips(spec.chips, args.rehearse)
    run.cache_every_program()
    cell = importlib.import_module(
        f"cellbench.runners.{spec.config['runner']}").build(spec)
    taken = []
    for seed in seeds:
        cell.reseed(seed)
        taken.append((cell.first_steps(), cell.first_inputs()))
    cell.free()
    limits = spec.config["correct"]

    def follow(inputs, window, routes, **kw):
        return cell.ref.train_readings(
            inputs["seed"], dict(cell.cfg, route_tie_window=window),
            inputs["batches"], cell.opt_cfg, routes=routes, **kw)

    def gaps(program, reference):
        return dict(compare.decide(program, reference, limits)["values"],
                    followed=reference["routes_followed"],
                    refused=reference["routes_refused"])

    rows = []
    for i, (program, inputs) in enumerate(taken):
        row = {"seed": inputs["seed"], "sound": {}, "float8": {},
               "router": {}}
        for w in args.windows:
            reference = follow(inputs, w, inputs["routes"])
            row["sound"][w] = gaps(program, reference)
            if i < args.control:
                row["float8"][w] = gaps(
                    follow(inputs, w, inputs["routes"], lowp=True),
                    reference)
        if i < args.control:
            for to in ROUTER_CONTROLS:
                control = follow(inputs, 0.0, None, router_round=to)
                row["router"][to] = {
                    w: gaps(control, follow(inputs, w, control["routes"]))
                    for w in args.windows + [args.wide]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"route_window_{args.workload}.json"),
              "w") as f:
        json.dump({"device": device, "limits": limits, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
