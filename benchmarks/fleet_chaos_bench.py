#!/usr/bin/env python
"""Fleet recovery-latency bench: the detect→reform→reshard→resume path.

Subject: how long the system takes to come back from a preemption wave
— not model FLOPs.  One rung runs the 8-process smoke chain (a torn
rendezvous payload, a wave killing 2 of 8 at step 3, one reshard leg
at 6 landing on the numpy oracle) and derives its latencies from the
merged :class:`~chainermn_tpu.fleet.report.FleetReport` wall clocks:

  detect_to_reform_ms   first ``die`` fault → ``world_reformed``
                        (includes the dead world's teardown and the
                        new world's formation — the restart gap a
                        scheduler pays)
  reform_to_resume_ms   ``world_reformed`` → ``elastic_restart``
                        (checkpoint election + reshard + re-agreement)
  chain_wall_ms         whole chain, launch to last leg's exit

A second rung runs the straggler-adaptive loop (ISSUE 15: a 4-process
world with an injected straggler, conviction → rebalance → hysteresis →
demotion, then a 3-process resume leg) and derives the self-healing
latencies the same wall-anchored way:

  convict_to_action_ms  first ``straggler`` conviction → first
                        ``adapt_decision`` (how long the policy's
                        hysteresis deliberates before acting)
  action_to_recover_ms  the demote ``adapt_action`` (snapshot
                        committed, world told to shed the rank) →
                        ``elastic_restart`` of the N−1 world (includes
                        the old world's exit + relaunch — the
                        scheduler gap, as above)

A third rung runs the scale-UP loop (ISSUE 16: a 7-process world with
the capacity watcher, a concurrent 1-process probe publishing presence
for a healed host, probation → agreed promote → 8-process resume from
the decision snapshot):

  probation_to_promote_ms  first ``host_returned`` manifest observed →
                           the promote ``adapt_decision`` (the
                           probation dwell the admission gate charges
                           a healed host)
  promote_to_restart_ms    the promote ``adapt_action`` (snapshot
                           committed, admission marker posted) →
                           ``elastic_restart`` of the N+1 world (the
                           restart gap growth pays — amortized by
                           ``promote_quorum`` when several hosts heal
                           together)

A fourth rung is the ISSUE 19 A/B: two 4-process worlds run the same
single-rank-loss recovery (``peer_recover_leg``), one restoring from
the peer RAM ring, one from the shared-FS checkpointer, and the
``recover_action`` → ``recovered`` event gap prices each tier:

  recover_peer_s    RAM-ring election + payload exchange + re-place
                    (no filesystem in the loop)
  recover_fs_s      FS election + orbax read of the same step
  recover_speedup   recover_fs_s / recover_peer_s (higher-better — the
                    sub-second-recovery claim, gated as a ratio)

Unlike the other rungs these time RECOVERY only (the loss is modeled
in-process; the world stays formed), so the numbers isolate the tier
difference from the relaunch gap the other rungs already charge.
These rows are emitted ``metric``/``value``-keyed (unit ``s``):
``*_s`` is lower-is-better, ``*speedup`` higher-is-better.

Honesty: the worlds timeshare the host (CI runs this on a single
core), so these are END-TO-END wall numbers dominated by process
launch and XLA compile, useful for DIRECTION (did recovery regress
10x?) and for the event-order contract, not as interconnect truth.
The in-scenario linger (``linger_s``, disclosed per row) is harness
overhead inside detect_to_reform_ms.

Usage:
    python benchmarks/fleet_chaos_bench.py            # 1 repeat
    HUNT_FLEET_REPEATS=3 python benchmarks/fleet_chaos_bench.py
"""

import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chainermn_tpu.fleet import (  # noqa: E402
    REAPED,
    ChainLeg,
    ElasticityChain,
    FaultSchedule,
    FleetReport,
    FleetWorld,
)
from chainermn_tpu.utils.benchmarking import protocol_fields  # noqa: E402

LINGER_S = 1.5
ADAPT_PROCS, ADAPT_DELAY_S, ADAPT_DEMOTE_AFTER = 4, 0.5, 3


def run_once(scratch):
    chain = ElasticityChain(scratch, [
        ChainLeg(n_procs=8, n_steps=3, wave_at=3, wave_processes=(6, 7),
                 torn_calls=(1,)),
        ChainLeg(n_procs=6, n_steps=5),
    ], budget_s=300, linger_s=LINGER_S)
    out = chain.run()
    rep = out["report"]
    firsts = rep.assert_order("fault_injected", "retry",
                              "world_reformed", "elastic_reshard",
                              "elastic_restart")
    by_kind = {e["kind"]: e for e in firsts}
    die = min(e["wall"] for e in rep.events("fault_injected")
              if e["info"].get("fault") == "die")
    walls = [e["wall"] for e in rep.events()]
    return {
        "detect_to_reform_s": by_kind["world_reformed"]["wall"] - die,
        "reform_to_resume_s": (by_kind["elastic_restart"]["wall"]
                               - by_kind["world_reformed"]["wall"]),
        "chain_wall_s": max(walls) - min(walls),
    }


def run_adaptive_once(scratch):
    """One pass of the self-healing loop: straggler conviction →
    rebalance → demotion at ADAPT_PROCS, resume at ADAPT_PROCS-1."""
    sched = FaultSchedule().straggler(
        2, window=(1, 12), delay=ADAPT_DELAY_S
    )
    world = FleetWorld(ADAPT_PROCS, scratch, schedule=sched,
                       budget_s=300, label="adapt0")
    res = world.launch(
        "adaptive_leg",
        {"n_steps": 12, "demote_after": ADAPT_DEMOTE_AFTER,
         "linger_s": LINGER_S},
        expect_exit={p: REAPED for p in range(ADAPT_PROCS)},
    )
    payloads = res.payloads()
    demote_step = payloads[0]["iteration"]
    assert all(p["demoted"] == 2 for p in payloads.values()), payloads
    FleetWorld(ADAPT_PROCS - 1, scratch, budget_s=300,
               label="adapt1").launch(
        "chain_leg",
        {"n_steps": demote_step + 2, "wave_at": None, "lr": 0.1,
         "mom": 0.9, "dim": 4, "straggler": False, "report_every": 1},
        expect_exit={},
    )
    rep = FleetReport.from_scratch(scratch)
    rep.assert_order("fault_injected", "straggler", "adapt_decision",
                     "world_reformed", "elastic_reshard",
                     "elastic_restart")
    convict = rep.first("straggler")["wall"]
    decide = rep.first("adapt_decision")["wall"]
    demote_acts = [e["wall"] for e in rep.events("adapt_action")
                   if e["info"].get("action") == "demote"]
    recover = rep.first("elastic_restart")["wall"]
    return {
        "convict_to_action_s": decide - convict,
        "action_to_recover_s": recover - min(demote_acts),
    }


GROW_PROCS = 7


def run_grow_once(scratch):
    """One pass of the scale-UP loop: a healed host probes under
    weight-0 probation while the training world's capacity watcher
    evaluates it, the cross-rank decision promotes, and the N+1 world
    resumes from exactly the decision snapshot."""
    pace = FaultSchedule().pace(window=(1, 300), delay=0.2)
    grow = FleetWorld(GROW_PROCS, scratch, schedule=pace, budget_s=300,
                      label="grow0").start(
        "grow_leg",
        {"n_steps": 300, "probation_windows": 2, "promote_quorum": 1,
         "report_every": 1, "linger_s": LINGER_S},
    )
    probe = FleetWorld(1, scratch, budget_s=300, label="probe0").start(
        "probe_host",
        {"host": f"h{GROW_PROCS}", "world": GROW_PROCS,
         "steps_per_window": 3, "window_sleep_s": 0.25,
         "max_windows": 400},
    )
    res = grow.wait(expect_exit={p: REAPED for p in range(GROW_PROCS)})
    d = res.payloads()[0]["iteration"]
    assert probe.wait(expect_exit={}).payloads()[0]["promoted"] is True
    FleetWorld(GROW_PROCS + 1, scratch, budget_s=300,
               label="grow1").launch(
        "chain_leg",
        {"n_steps": d + 2, "wave_at": None, "lr": 0.1, "mom": 0.9,
         "dim": 4, "straggler": False, "report_every": 1},
        expect_exit={},
    )
    rep = FleetReport.from_scratch(scratch)
    rep.assert_order("host_returned", "probation_pass",
                     "adapt_decision", "adapt_action",
                     "world_reformed", "elastic_restart")
    returned = rep.first("host_returned")["wall"]
    decide = min(e["wall"] for e in rep.events("adapt_decision")
                 if e["info"].get("action") == "promote")
    act = min(e["wall"] for e in rep.events("adapt_action")
              if e["info"].get("action") == "promote")
    restart = rep.first("elastic_restart")["wall"]
    return {
        "probation_to_promote_s": decide - returned,
        "promote_to_restart_s": restart - act,
    }


PEER_PROCS, PEER_STEPS, PEER_LOSE_AT, PEER_DIM = 4, 6, 4, 4096


def run_peer_ab_once(scratch):
    """One pass of the recovery-tier A/B (ISSUE 19): the same
    single-rank loss recovered once from the peer RAM ring and once
    from the shared FS, in separate scratches (the merged report walls
    must not interleave), timed ``recover_action`` → ``recovered``."""
    out = {}
    for tier in ("peer", "fs"):
        sub = os.path.join(scratch, tier)
        os.makedirs(sub, exist_ok=True)
        FleetWorld(PEER_PROCS, sub, budget_s=300,
                   label=f"recover_{tier}").launch(
            "peer_recover_leg",
            {"n_steps": PEER_STEPS, "lose_at": PEER_LOSE_AT,
             "tier": tier, "dim": PEER_DIM},
            expect_exit={},
        )
        rep = FleetReport.from_scratch(sub)
        rep.assert_order("recover_action", "recovered")
        out[f"recover_{tier}_s"] = (rep.first("recovered")["wall"]
                                    - rep.first("recover_action")["wall"])
    return out


def _recover_rows(samples):
    """The A/B rows, ``metric``/``value``-keyed."""
    rows = []
    extra = {"n_procs": PEER_PROCS, "lose_at": PEER_LOSE_AT,
             "dim": PEER_DIM, "unit": "s"}
    for metric, vals in samples.items():
        row = {"metric": f"fleet_recovery.{metric}",
               "value": round(min(vals), 4)}
        row.update(extra)
        row.update(protocol_fields(vals))
        rows.append(row)
        print(json.dumps(row))
    speedups = [f / p for f, p in zip(samples["recover_fs_s"],
                                      samples["recover_peer_s"])]
    row = {"metric": "fleet_recovery.recover_speedup",
           "value": round(max(speedups), 2), "unit": "x",
           "n_procs": PEER_PROCS, "lose_at": PEER_LOSE_AT,
           "dim": PEER_DIM}
    row.update(protocol_fields(speedups))
    rows.append(row)
    print(json.dumps(row))
    return rows


def _rows_for(samples, extra):
    rows = []
    for metric, vals in samples.items():
        row = {
            "name": f"fleet_recovery.{metric[:-2]}",
            "unit": "ms",
            f"{metric[:-2]}_ms": round(min(vals) * 1e3, 1),
            "linger_s": LINGER_S,
        }
        row.update(extra)
        row.update(protocol_fields(vals))
        rows.append(row)
        print(json.dumps(row))
    return rows


def main():
    repeats = int(os.environ.get("HUNT_FLEET_REPEATS", "1"))
    samples = {"detect_to_reform_s": [], "reform_to_resume_s": [],
               "chain_wall_s": []}
    adaptive = {"convict_to_action_s": [], "action_to_recover_s": []}
    growth = {"probation_to_promote_s": [], "promote_to_restart_s": []}
    recover = {"recover_peer_s": [], "recover_fs_s": []}
    for _ in range(repeats):
        scratch = tempfile.mkdtemp(prefix="fleet_bench_")
        try:
            one = run_once(scratch)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        for k, v in one.items():
            samples[k].append(v)
        scratch = tempfile.mkdtemp(prefix="fleet_bench_adapt_")
        try:
            one = run_adaptive_once(scratch)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        for k, v in one.items():
            adaptive[k].append(v)
        scratch = tempfile.mkdtemp(prefix="fleet_bench_grow_")
        try:
            one = run_grow_once(scratch)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        for k, v in one.items():
            growth[k].append(v)
        scratch = tempfile.mkdtemp(prefix="fleet_bench_peer_")
        try:
            one = run_peer_ab_once(scratch)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        for k, v in one.items():
            recover[k].append(v)
    rows = _rows_for(samples, {"n_procs_wave": 8, "n_procs_resume": 6})
    rows += _rows_for(adaptive, {
        "n_procs": ADAPT_PROCS,
        "n_procs_resume": ADAPT_PROCS - 1,
        "straggler_delay_s": ADAPT_DELAY_S,
        "demote_after": ADAPT_DEMOTE_AFTER,
    })
    rows += _rows_for(growth, {
        "n_procs": GROW_PROCS,
        "n_procs_resume": GROW_PROCS + 1,
        "probation_windows": 2,
        "promote_quorum": 1,
    })
    rows += _recover_rows(recover)
    return rows


if __name__ == "__main__":
    main()
