"""The comparison that decides ``correct`` for a training cell.

The program's first steps against the plain reference's, number by
number, each with a limit of its own (``correct`` in the configuration's
file, with the readings it was set from in ``PERF.md``):

``loss_rel``        worst step's |loss - reference| / |reference|
``grad_norm_gap``   first gradient as the optimizer got it, worst leaf:
                    |norm - reference norm| over the larger of the
                    reference's norm of that leaf and of its median leaf
``grad_small_diff`` the first gradient's small leaves (LayerNorm and
                    BatchNorm gains and biases, biases), element by
                    element: worst leaf's |g - reference| norm over the
                    larger of the reference's norm of that leaf and of
                    its median small leaf.  Norms of leaves average
                    rounding away; this does not
``delta_norm_gap``  the parameters' change over the steps, the same way
"""

from __future__ import annotations

import math
import statistics


def norm_gap(program: dict, reference: dict):
    """Worst leaf's gap between two sets of per-leaf norms, and the leaf."""
    if set(program) != set(reference):
        raise ValueError("program and reference name different leaves: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for k, r in reference.items():
        gap = abs(program[k] - r) / max(r, floor, 1e-30)
        if not gap <= worst:  # a NaN gap is the worst
            worst, where = gap, k
    return worst, where


def small_diff(program: dict, reference: dict):
    """Worst small leaf's norm of difference, and the leaf."""
    if set(program) != set(reference):
        raise ValueError("program and reference keep different small "
                         f"leaves: {sorted(set(program) ^ set(reference))[:6]}")
    norms = {k: math.sqrt(float((r.astype("float64") ** 2).sum()))
             for k, r in reference.items()}
    floor = statistics.median(norms.values())
    worst, where = 0.0, None
    for k, r in reference.items():
        diff = program[k].astype("float64") - r
        gap = math.sqrt(float((diff ** 2).sum())) / max(norms[k], floor,
                                                        1e-30)
        if not gap <= worst:
            worst, where = gap, k
    return worst, where


def readings(program: dict, reference: dict) -> dict:
    """The numbers compared, from the two sides' first-step readings."""
    loss_rel = max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                   for p, r in zip(program["losses"], reference["losses"]))
    grad, grad_leaf = norm_gap(program["grad_norms"],
                               reference["grad_norms"])
    delta, delta_leaf = norm_gap(program["delta_norms"],
                                 reference["delta_norms"])
    small, small_leaf = small_diff(program["grad_small"],
                                   reference["grad_small"])
    return {"loss_rel": (loss_rel, "worst step"),
            "grad_norm_gap": (grad, grad_leaf),
            "grad_small_diff": (small, small_leaf),
            "delta_norm_gap": (delta, delta_leaf)}


def decide(program: dict, reference: dict, limits: dict) -> dict:
    """``correct`` and one printable line per number compared."""
    got = readings(program, reference)
    lines, ok = [], True
    for name, (value, where) in got.items():
        limit = float(limits[name])
        passed = value <= limit  # False for NaN
        ok = ok and passed
        lines.append(f"correct: {name} = {value:.6g} at {where} "
                     f"(limit {limit:g}) {'ok' if passed else 'FAILED'}")
    lines.append("correct: losses program "
                 + " ".join(f"{x:.6f}" for x in program["losses"])
                 + " | reference "
                 + " ".join(f"{x:.6f}" for x in reference["losses"]))
    return {"correct": ok, "lines": lines,
            "values": {k: v for k, (v, _) in got.items()}}
