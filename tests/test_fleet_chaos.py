"""Fleet chaos tier (ISSUE 14) — the 16-64-rank worlds.

These are the production-shape scenarios: real ``jax.distributed``
worlds of 16+ gloo-CPU processes driven through composed fault
schedules and elasticity chains.  They are ``slow`` (excluded from
tier-1 by ``-m 'not slow'`` — see tests/README.md for the tier split);
the 8-process smoke of the same machinery rides tier-1 in
test_fleet.py.

Run just these:   pytest -m slow tests/test_fleet_chaos.py
"""

import pytest

from chainermn_tpu.fleet import (
    REAPED,
    ChainLeg,
    ElasticityChain,
    FaultSchedule,
    FleetReport,
    FleetWorld,
)

pytestmark = [pytest.mark.multiprocess, pytest.mark.slow]


class TestAcceptanceChain:
    def test_wave_plus_two_leg_chain_16_12_14(self, tmp_path):
        """ISSUE 14 acceptance: a 16-process world takes a torn
        rendezvous payload (lockstep-retried) and a preemption wave
        killing 4 processes at step 4; the chain then reshards
        16→12→14 through ``Trainer.run_elastic``, every leg landing on
        the single-world numpy oracle trajectory (ZeRO momentum blocks
        re-partitioned bit-identically at each leg), with a straggler
        that MIGRATES between ranks across legs (2 → 5) convicted by
        the leave-one-out median on every rank of each world; the
        merged FleetReport asserts the
        fault→retry→reform→reshard→resume event order end to end."""
        chain = ElasticityChain(str(tmp_path), [
            ChainLeg(n_procs=16, n_steps=4, wave_at=4,
                     wave_processes=(12, 13, 14, 15), torn_calls=(1,)),
            ChainLeg(n_procs=12, n_steps=6,
                     straggler={"process": 2, "delay": 0.6}),
            ChainLeg(n_procs=14, n_steps=9,
                     straggler={"process": 5, "delay": 0.6}),
        ], budget_s=600)
        out = chain.run()
        legs = out["legs"]
        # every leg-0 process published steps_saved before the wave
        assert sorted(legs[0]) == list(range(16))
        assert all(p["steps_saved"] == 3 for p in legs[0].values())
        # leg 1: 16→12, oracle, straggler 2 convicted everywhere
        for p in legs[1].values():
            assert p["resized"] == [16, 12]
            assert p["oracle_match"] is True
            assert p["stragglers"] == [2]
        # leg 2: 12→14 (a GROWING world reshards too), migrated
        # straggler convicted
        for p in legs[2].values():
            assert p["resized"] == [12, 14]
            assert p["oracle_match"] is True
            assert p["stragglers"] == [5]
        rep = out["report"]
        firsts = rep.assert_order(
            "fault_injected", "retry", "world_reformed",
            "elastic_reshard", "elastic_restart",
        )
        assert firsts[0]["leg"] == "leg0"
        # the wave victims' die records survived os._exit (streaming
        # sink) — and a die fault precedes the re-formation
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        assert sorted(e["process"] for e in dies) == [12, 13, 14, 15]
        reform = rep.first("world_reformed")
        assert all(e["wall"] < reform["wall"] for e in dies)
        # straggler migration is visible in the merged timeline
        flagged = [(e["leg"], e["info"].get("process"))
                   for e in rep.events("straggler")]
        assert {("leg1", 2), ("leg2", 5)} <= set(flagged)
        assert ("leg1", 5) not in set(flagged)
        assert ("leg2", 2) not in set(flagged)


class TestAdaptiveDemoteFleet:
    def test_adaptive_demote_16_to_15(self, tmp_path):
        """ISSUE 15 acceptance at fleet shape (scenario
        ``adaptive_demote``): a 16-process world with a straggler that
        migrates 2→5 across report windows.  The policy rebalances
        (weighted re-scatter agreed cross-rank, iterator cursor
        remapped) on each conviction and demotes rank 5 once its streak
        outlives the hysteresis window — snapshot committed at the
        decision step, ``DemotionRequiredError`` on all 16 ranks
        together.  The 15-process resume leg reshards 16→15 through the
        bit-identical ZeRO block resharder onto the single-world numpy
        oracle, and the merged report asserts the full
        ``fault_injected→straggler→adapt_decision→world_reformed→
        elastic_reshard→elastic_restart`` order on the shared
        timeline."""
        sched = (FaultSchedule()
                 .straggler(2, window=(1, 2), delay=0.6)
                 .straggler(5, window=(3, 14), delay=0.6))
        world = FleetWorld(16, str(tmp_path), schedule=sched,
                           budget_s=600, label="leg0")
        res = world.launch(
            "adaptive_leg",
            {"n_steps": 14, "demote_after": 3, "linger_s": 2.0},
            expect_exit={p: REAPED for p in range(16)},
        )
        p1 = res.payloads()
        assert sorted(p1) == list(range(16))
        d = p1[0]["iteration"]
        for p in p1.values():
            assert p["demoted"] == 5
            assert p["iteration"] == d
            assert p["oracle_match"] is True
            assert p["rebalance_applied"] is True
            # the migration is visible in every rank's convictions
            assert 2 in p["stragglers"] and 5 in p["stragglers"]
        res2 = FleetWorld(15, str(tmp_path), budget_s=600,
                          label="leg1").launch(
            "chain_leg",
            {"n_steps": d + 3, "wave_at": None, "lr": 0.1, "mom": 0.9,
             "dim": 4, "straggler": False, "report_every": 1},
            expect_exit={},
        )
        for p in res2.payloads().values():
            assert p["resumed_step"] == d
            assert p["resized"] == [16, 15]
            assert p["oracle_match"] is True
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order(
            "fault_injected", "straggler", "adapt_decision",
            "world_reformed", "elastic_reshard", "elastic_restart",
        )
        decisions = rep.events("adapt_decision")
        reb = [e for e in decisions
               if e["info"]["action"] == "rebalance"]
        dem = [e for e in decisions if e["info"]["action"] == "demote"]
        # escalation: rebalance preceded the demotion; only the
        # persistently slow (migrated-to) rank was shed, on all ranks
        assert min(e["wall"] for e in reb) < min(
            e["wall"] for e in dem
        )
        assert {e["info"]["process"] for e in dem} == {5}
        assert sorted({e["process"] for e in dem}) == list(range(16))
        # every surviving rank resumed
        restarts = rep.events("elastic_restart")
        assert sorted(e["process"] for e in restarts) == list(range(15))


class TestCorrelatedSliceLoss:
    def test_slice_loss_16_procs_4_slices(self, tmp_path):
        """Correlated slice loss: 16 processes grouped into 4 synthetic
        slices (CHAINERMN_TPU_FAKE_SLICE_SIZE=4, exported by the
        schedule); every process of slice 3 dies at step 2 in one
        correlated wave; the survivors' snapshots carry the world
        manifest and the restart at 12 reshards onto the oracle."""
        sched = FaultSchedule().slice_loss(3, slice_size=4, at=2,
                                           exit_code=43)
        assert [d["process"] for d in sched.specs()] == [12, 13, 14, 15]
        world = FleetWorld(16, str(tmp_path), schedule=sched,
                           budget_s=600, label="leg0")
        args = {"n_steps": 2, "wave_at": 2, "lr": 0.1, "mom": 0.9,
                "dim": 4, "linger_s": 1.5, "straggler": False,
                "report_every": 1}
        res = world.launch("chain_leg", args, expect_exit={
            p: (43 if p in (12, 13, 14, 15) else REAPED)
            for p in range(16)
        })
        payloads = res.payloads()
        assert all(p["steps_saved"] == 1 for p in payloads.values())
        # the workers' topology actually factorized into the synthetic
        # slices being lost (mn_inter = 4 slices x mn_intra 4): a
        # hierarchical probe world under the same schedule env
        probe = FleetWorld(16, str(tmp_path / "probe"), schedule=sched,
                           budget_s=600, label="probe")
        pres = probe.launch("rendezvous", {"comm": "hierarchical"},
                            expect_exit={})
        for p in pres.payloads().values():
            assert p["mesh_axes"] == {"mn_inter": 4, "mn_intra": 4}
        # run B: the survivors reshard 16 -> 12 and land on the oracle
        res2 = FleetWorld(12, str(tmp_path), budget_s=600,
                          label="leg1").launch(
            "chain_leg",
            dict(args, n_steps=4, wave_at=None), expect_exit={})
        for p in res2.payloads().values():
            assert p["resized"] == [16, 12]
            assert p["oracle_match"] is True
        rep = FleetReport.from_scratch(str(tmp_path))
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        # one CORRELATED wave: all four victims at the same step site
        assert sorted(e["process"] for e in dies) == [12, 13, 14, 15]
        assert {e["info"].get("call") for e in dies} == {2}


class TestServingChurnFleet:
    def test_4_replicas_2_killed_in_one_wave(self, tmp_path):
        """Fleet-shaped serving churn (tentpole satellite): 4 decode
        replicas partition a 16-request journal by ``seq % 4``; ONE
        wave kills replicas 1 and 2 at their 3rd decode step.  The
        survivors complete exactly their own shares; the 2-survivor
        phase re-claims the dead replicas' shares by ``seq % 2`` and
        completes every request bit-identically to a fresh oracle
        engine (asserted in-scenario)."""
        sched = FaultSchedule().preemption_wave(
            (1, 2), window=(3, 3), site="serving.decode_step")
        w1 = FleetWorld(4, str(tmp_path), schedule=sched, budget_s=420,
                        label="serve0")
        # survivors may be signal-reaped after publishing their RESULT
        # (peer-death propagation) — the REAPED contract, as in the
        # chain's wave legs
        res1 = w1.launch("serving_wave", {"n_requests": 16},
                         expect_exit={0: REAPED, 1: 43, 2: 43,
                                      3: REAPED})
        p1 = res1.payloads()
        # seq-mod claiming verified: each survivor served its whole
        # share and nothing else (also asserted in-scenario)
        assert p1[0]["served"] == ["c0", "c12", "c4", "c8"]
        assert p1[3]["served"] == ["c11", "c15", "c3", "c7"]
        w2 = FleetWorld(2, str(tmp_path), budget_s=420, label="serve1")
        res2 = w2.launch("serving_resume", {"n_requests": 16},
                         expect_exit={})
        p2 = res2.payloads()
        for pid, p in p2.items():
            assert p["completed"] == 16
            assert p["pending_before"] == 8  # the dead replicas' shares
            assert p["bit_identical"] is True
        # the migrated partition re-derived over seq % 2
        assert p2[0]["served"] == ["c10", "c14", "c2", "c6"]
        assert p2[1]["served"] == ["c1", "c13", "c5", "c9"]
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order("fault_injected", "world_reformed")
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        assert sorted(e["process"] for e in dies) == [1, 2]


class TestDisaggFleet:
    def test_prefill_death_mid_handoff_decode_completes(self, tmp_path):
        """ISSUE 18 acceptance: disaggregated role pools (2 decode +
        2 prefill) under a prefill death mid-handoff.  The schedule
        kills prefill replica 0 (process 2 — never process 0, the
        coordinator) at its 4th ``serving.prefill`` call — three
        handoffs published, the rest of its share unpublished.
        Prefill replica 1 re-derives the dead share via the
        pool-scoped drain marker; the decode pool completes EVERY
        request from a handoff (zero orphan fallbacks), bit-identical
        to the unified oracle (asserted in-scenario), with no lost or
        duplicated results."""
        sched = FaultSchedule().preemption_wave(
            (2,), window=(4, 4), site="serving.prefill")
        w = FleetWorld(4, str(tmp_path), schedule=sched, budget_s=420,
                       label="disagg0")
        res = w.launch("serving_disagg", {"n_requests": 12},
                       expect_exit={0: REAPED, 1: REAPED, 2: 43,
                                    3: REAPED})
        p = res.payloads()
        # the healthy prefill replica declared the death and took over
        assert p[3]["rederived"] is True
        # its own share (6) plus the dead replica's unpublished rest
        # (3; >= allows a benign idempotent duplicate at the race)
        assert p[3]["published"] >= 9
        assert p[3]["wire_bytes"] > 0
        served = []
        for d in (0, 1):
            assert p[d]["local_prefills"] == 0
            assert p[d]["ingested"] == len(p[d]["served"])
            assert p[d]["completed"] == 12
            assert p[d]["bit_identical"] is True
            served += p[d]["served"]
        # no lost or duplicated requests across the decode pool
        assert sorted(served) == sorted(f"c{i}" for i in range(12))
        rep = FleetReport.from_scratch(str(tmp_path))
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        assert [e["process"] for e in dies] == [2]
        # both prefill replicas published (the victim got some out)
        pubs = rep.events("handoff_published")
        assert {e["process"] for e in pubs} == {2, 3}


class TestBreathingWorld:
    def test_breathes_8_6_9_7_on_oracle(self, tmp_path):
        """ISSUE 16 acceptance: the world BREATHES 8→6→9→7 under a
        composed fault schedule — a preemption wave shrinks it, three
        healed hosts re-enter through probation (one of them dirty at
        first — its early probe windows straggle, the watcher holds it,
        it heals and clears), a quorum-3 promote grows the world in ONE
        restart, a second wave shrinks it again, and every leg lands
        bit-identically on the single-world numpy sgd+momentum oracle.
        The merged report pins the promote chain host_returned →
        probation_pass → adapt_decision → world_reformed →
        elastic_reshard → elastic_restart on the shared timeline."""
        scratch = str(tmp_path)
        base = {"lr": 0.1, "mom": 0.9, "dim": 4, "straggler": False,
                "report_every": 1}

        # -- leg 0: 8 procs, torn rendezvous + wave kills 6,7 at step 4
        sched0 = (FaultSchedule()
                  .torn_payload(calls=(1,))
                  .preemption_wave((6, 7), window=(4, 4)))
        res0 = FleetWorld(8, scratch, schedule=sched0, budget_s=600,
                          label="leg0").launch(
            "chain_leg",
            dict(base, n_steps=4, wave_at=4, linger_s=1.5),
            expect_exit={p: (43 if p in (6, 7) else REAPED)
                         for p in range(8)},
        )
        assert all(p["steps_saved"] == 3
                   for p in res0.payloads().values())

        # -- leg 1: 6 survivors resume THROUGH the resharder (8→6) and
        # run under the capacity watcher; three healed hosts probe
        # concurrently — h6 straggles for its first two probe windows
        # (the heal-then-readmit path), h7/h8 are clean.  promote
        # quorum 3: ONE restart admits all three.
        pace = FaultSchedule().pace(window=(1, 200), delay=0.2)
        grow = FleetWorld(6, scratch, schedule=pace, budget_s=600,
                          label="leg1").start(
            "grow_leg",
            dict(base, n_steps=200, resume=True, probation_windows=2,
                 promote_quorum=3, linger_s=1.5),
        )
        # 5s/step dwarfs the world's 0.2s pace even under timeshared
        # contention (the 1.5x-median threshold inflates with load —
        # a 2s delay was judged clean on a single-core CI host), and
        # each ~15s dirty window spans many watcher scans
        dirty = FaultSchedule().straggler(0, window=(1, 6), delay=5.0)
        probes = {}
        for host, sched in (("h6", dirty), ("h7", None), ("h8", None)):
            probes[host] = FleetWorld(
                1, scratch, schedule=sched, budget_s=600,
                label=f"probe_{host}",
            ).start("probe_host", {
                "host": host, "world": 6, "steps_per_window": 3,
                "window_sleep_s": 0.25, "max_windows": 400,
            })
        res1 = grow.wait(expect_exit={p: REAPED for p in range(6)})
        p1 = res1.payloads()
        d1 = p1[0]["iteration"]
        for p in p1.values():
            assert p["promote"] == {"hosts": ["h6", "h7", "h8"],
                                    "new_world": 9}
            assert p["resumed_step"] == 3
            assert p["iteration"] == d1
            assert p["oracle_match"] is True
        for host, w in probes.items():
            pp = w.wait(expect_exit={}).payloads()[0]
            assert pp["promoted"] is True, host
            assert pp["admission"]["new_world"] == 9
            assert pp["admission"]["checkpoint_step"] == d1

        # -- leg 2: the world GROWS 6→9 from exactly the decision step
        res2 = FleetWorld(9, scratch, budget_s=600,
                          label="leg2").launch(
            "chain_leg",
            dict(base, n_steps=d1 + 2, wave_at=None),
            expect_exit={},
        )
        for p in res2.payloads().values():
            assert p["resumed_step"] == d1
            assert p["resized"] == [6, 9]
            assert p["oracle_match"] is True

        # -- leg 3: the grown world is preempted AGAIN (resume + wave:
        # restore through the resharder, then the wave kills 7,8 two
        # steps later — schedule windows are leg-local call counts)
        sched3 = FaultSchedule().preemption_wave((7, 8), window=(3, 3))
        res3 = FleetWorld(9, scratch, schedule=sched3, budget_s=600,
                          label="leg3").launch(
            "chain_leg",
            dict(base, n_steps=d1 + 5, wave_at=d1 + 5,
                 resume_wave=True, linger_s=1.5),
            expect_exit={p: (43 if p in (7, 8) else REAPED)
                         for p in range(9)},
        )
        for p in res3.payloads().values():
            assert p["resumed_step"] == d1 + 2
            assert p["steps_saved"] == 2  # d1+3, d1+4 saved pre-wave
        # -- leg 4: 7 survivors reshard 9→7 onto the final oracle step
        res4 = FleetWorld(7, scratch, budget_s=600,
                          label="leg4").launch(
            "chain_leg",
            dict(base, n_steps=d1 + 7, wave_at=None),
            expect_exit={},
        )
        for p in res4.payloads().values():
            assert p["resumed_step"] == d1 + 4
            assert p["resized"] == [9, 7]
            assert p["oracle_match"] is True
            assert p["iteration"] == d1 + 7

        # -- the merged post-mortem: pin the promote chain from the
        # first sighting (leg 1's own 8→6 restore reshard precedes it
        # on the full timeline, so slice from host_returned)
        rep = FleetReport.from_scratch(scratch)
        t0 = rep.first("host_returned")["wall"]
        rep.between(t0=t0).assert_order(
            "host_returned", "probation_pass", "adapt_decision",
            "adapt_action", "world_reformed", "elastic_reshard",
            "elastic_restart",
        )
        # h6's dirty probe windows were HELD (straggler rule), and its
        # pass came only after the hold
        holds = [e for e in rep.events("probation_hold")
                 if e["info"].get("host") == "h6"
                 and e["info"].get("reason") == "straggler"]
        assert holds
        h6_pass = [e for e in rep.events("probation_pass")
                   if e["info"].get("host") == "h6"]
        assert h6_pass
        assert min(e["wall"] for e in holds) < min(
            e["wall"] for e in h6_pass
        )
        # ONE promote decision per host, all in the same window
        promos = [e for e in rep.events("adapt_decision")
                  if e["info"].get("action") == "promote"]
        assert {e["info"]["host"] for e in promos} == {"h6", "h7", "h8"}
        assert {e["info"]["new_world"] for e in promos} == {9}
        # both waves' victims left die records
        dies = sorted((e["leg"], e["process"])
                      for e in rep.events("fault_injected")
                      if e["info"].get("fault") == "die")
        assert dies == [("leg0", 6), ("leg0", 7),
                        ("leg3", 7), ("leg3", 8)]


class TestServingAutoscaleFleet:
    def test_pool_breathes_2_up_down_from_load(self, tmp_path):
        """ISSUE 16 acceptance, serving half: a 5-slot replica pool
        (2 active, 3 standby drain-marked) serves an offered load whose
        opening burst outruns ``queue_per_replica`` × active — the
        autoscaler scales UP (clear_draining: the standby re-derives
        its ``seq % n`` share); the post-burst calm scales back DOWN to
        ``min_replicas``.  Zero dropped or duplicated results: every
        request completes bit-identically to a fresh single-engine
        oracle (asserted in-scenario)."""
        # a decode pace keeps the burst's backlog real on a fast CPU
        sched = FaultSchedule().fault(
            "serving.decode_step", "delay", probability=1.0, delay=0.05
        )
        res = FleetWorld(5, str(tmp_path), schedule=sched, budget_s=420,
                         label="pool").launch(
            "serving_autoscale",
            {"n_requests": 30, "burst": 18, "wave": 4,
             "min_replicas": 2, "queue_per_replica": 4,
             "scale_after": 2, "cooldown_windows": 1,
             "observe_s": 0.4},
            expect_exit={},
        )
        p = res.payloads()
        assert sorted(p) == list(range(5))
        driver = p[0]
        assert driver["totals"]["scale_up"] >= 1
        assert driver["totals"]["scale_down"] >= 1
        # the pool breathed back down to min_replicas
        assert len(driver["active_final"]) == 2
        # up before down, and the first activation was the lowest
        # standby slot
        kinds = [a["action"] for a in driver["actions"]]
        assert kinds.index("scale_up") < kinds.index("scale_down")
        first_up = next(a for a in driver["actions"]
                        if a["action"] == "scale_up")
        assert first_up["replica"] == 2
        # the activated standby actually served part of the stream
        standby_served = [rid for q in range(2, 5)
                          for rid in p[q]["served"]]
        assert standby_served
        # no request was served into a missing result: all 30 present
        # (completeness + bit-identity asserted in-scenario); shares
        # union to the whole stream
        all_served = set()
        for q in range(5):
            all_served |= set(p[q]["served"])
        assert all_served == {f"c{i}" for i in range(30)}
        rep = FleetReport.from_scratch(str(tmp_path))
        ups = [e for e in rep.events("autoscale_action")
               if e["info"].get("action") == "scale_up"]
        downs = [e for e in rep.events("autoscale_action")
                 if e["info"].get("action") == "scale_down"]
        assert ups and downs
        assert min(e["wall"] for e in ups) < min(
            e["wall"] for e in downs
        )


class TestServingDrainCycleFleet:
    def test_drain_heal_reclaim_no_dup_no_orphan(self, tmp_path):
        """ISSUE 16 satellite: ``clear_draining`` + re-claim end to
        end.  Replica 2 starts drain-marked; the 2 healthy replicas
        complete batch 1 (the drained slot's reassigned share
        included); process 0 lifts the marker at a pending-empty
        instant and submits batch 2 — the returned replica re-derives
        its pure ``seq % 3`` share.  No request is served twice, none
        is orphaned (disjoint shares, complete union, bit-identical
        results — the oracle comparison runs in-scenario)."""
        res = FleetWorld(3, str(tmp_path), budget_s=420,
                         label="drain").launch(
            "serving_drain_cycle",
            {"batch1": 12, "batch2": 12},
            expect_exit={},
        )
        p = res.payloads()
        assert sorted(p) == [0, 1, 2]
        served = {q: set(p[q]["served"]) for q in p}
        # disjoint shares, complete union — no dup, no orphan
        assert served[0] & served[1] == set()
        assert served[0] & served[2] == set()
        assert served[1] & served[2] == set()
        assert (served[0] | served[1] | served[2]
                == {f"c{i}" for i in range(24)})
        # the healed replica served EXACTLY its seq%3 share of batch 2
        # and nothing from batch 1 (it was draining then)
        assert served[2] == {f"c{i}" for i in range(12, 24)
                             if i % 3 == 2}
        rep = FleetReport.from_scratch(str(tmp_path))
        # the decision trail: the drain decision precedes every result
        drains = [e for e in rep.events("adapt_decision")
                  if e["info"].get("action") == "drain"]
        assert drains and drains[0]["info"]["process"] == 2


class TestSpeculativeBurstFleet:
    def test_replica_dies_mid_burst_survivors_reclaim(self, tmp_path):
        """ISSUE 17 fleet leg: 3 speculative replicas (half-width draft
        + target riding one allocator each) partition a shared-prefix
        stream; the schedule kills replica 1 at its 2nd
        ``serving.spec_verify`` — mid-burst, with draft proposals in
        flight and shared pages at refcount > 1.  Survivors complete
        exactly their own shares with their allocators drained clean
        (refcount invariants + every page freed, both caches, asserted
        in-scenario); phase 2 re-forms at 2 replicas, the victim's
        share re-derives over ``seq % 2`` and serves speculatively —
        and EVERY journaled result matches a fresh plain-decode oracle
        bit-for-bit (greedy-exact acceptance survives the crash)."""
        sched = FaultSchedule().preemption_wave(
            (1,), window=(2, 2), site="serving.spec_verify")
        w1 = FleetWorld(3, str(tmp_path), schedule=sched, budget_s=420,
                        label="spec0")
        res1 = w1.launch("serving_spec_burst", {"n_requests": 12,
                                                "k": 4},
                         expect_exit={0: REAPED, 1: 43, 2: REAPED})
        p1 = res1.payloads()
        # seq-mod shares, whole and nothing else; speculative + sharing
        # machinery demonstrably live on each survivor
        assert p1[0]["served"] == ["s0", "s3", "s6", "s9"]
        assert p1[2]["served"] == ["s11", "s2", "s5", "s8"]
        for q in (0, 2):
            assert p1[q]["verify_steps"] > 0
            assert p1[q]["prefix_hits"] >= 1
            assert p1[q]["tokens_proposed"] > 0
        w2 = FleetWorld(2, str(tmp_path), budget_s=420, label="spec1")
        res2 = w2.launch("serving_spec_resume", {"n_requests": 12,
                                                 "k": 4},
                         expect_exit={})
        p2 = res2.payloads()
        for pid, p in p2.items():
            assert p["completed"] == 12
            assert p["pending_before"] == 4  # the victim's share
            assert p["bit_identical"] is True
            assert p["verify_steps"] > 0
        # the migrated partition re-derived over seq % 2
        assert p2[0]["served"] == ["s10", "s4"]
        assert p2[1]["served"] == ["s1", "s7"]
        rep = FleetReport.from_scratch(str(tmp_path))
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        assert [e["process"] for e in dies] == [1]
        assert dies[0]["site"] == "serving.spec_verify"


class TestWideWorldFormation:
    @pytest.mark.parametrize("n", [32, 64])
    def test_rendezvous_with_torn_agreement(self, n, tmp_path):
        """World formation at the tier's design widths: N gloo
        processes form one world, every rank's FIRST agreement exchange
        ships a torn payload, and the lockstep retry completes the
        rendezvous on all N ranks."""
        sched = FaultSchedule().torn_payload(calls=(1,))
        w = FleetWorld(n, str(tmp_path), schedule=sched, budget_s=900,
                       label=f"w{n}")
        res = w.launch("rendezvous", expect_exit={})
        payloads = res.payloads()
        assert sorted(payloads) == list(range(n))
        assert all(p["size"] == n for p in payloads.values())
        assert all(p["faults"] >= 1 for p in payloads.values())
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order("fault_injected", "retry")
        assert len(rep.events("retry")) >= n


class TestPeerRecoveryFleet:
    def test_peer_vs_fs_recovery_ab_4_procs(self, tmp_path):
        """ISSUE 19 acceptance at chaos shape: the same 4-process
        training leg loses rank 1's state at step 4 and recovers once
        through the peer RAM ring and once through the shared-FS cold
        tier.  The peer leg pins bit-identity (0 tolerance, ZeRO
        blocked leaves included) against the FS restore of the same
        step, both legs land on the single-world numpy oracle, and the
        merged report shows recover_action → recovered per leg with
        the peer gap no slower than the FS gap (the magnitude is the
        bench's rung — asserting it here would flake on a loaded CI
        host)."""
        gaps = {}
        for tier in ("peer", "fs"):
            scratch = tmp_path / tier
            scratch.mkdir()
            w = FleetWorld(4, str(scratch), budget_s=600,
                           label=f"recover_{tier}")
            res = w.launch(
                "peer_recover_leg",
                {"n_steps": 6, "lose_at": 4, "tier": tier, "dim": 512},
                expect_exit={},
            )
            payloads = res.payloads()
            assert sorted(payloads) == list(range(4))
            for p in payloads.values():
                assert p["tier"] == tier
                assert p["restored_step"] == 3
                assert p["oracle_match"] is True
                assert p["bit_identical"] is (
                    True if tier == "peer" else None
                )
            rep = FleetReport.from_scratch(str(scratch))
            rep.assert_order("recover_action", "recovered")
            gaps[tier] = (rep.first("recovered")["wall"]
                          - rep.first("recover_action")["wall"])
            if tier == "peer":
                # every replicate moved real replica bytes on the wire
                reps = rep.events("peer_replicate")
                assert {e["process"] for e in reps} == {0, 1, 2, 3}
                assert all(e["info"]["bytes"] > 0 for e in reps)
                assert all(e["info"]["ring"] == 4 for e in reps)
        # direction only: RAM must not lose to the filesystem
        assert gaps["peer"] <= gaps["fs"], gaps

    def test_correlated_loss_breaks_ring_and_falls_back_4_procs(
        self, tmp_path
    ):
        """The correlated-loss satellite: rank 1 AND its ring replica
        holder (rank 2) forget in one wave, so no peer snapshot covers
        every owner.  The collective restore detects the broken ring,
        elects nothing, and the survivors degrade to the FS cold tier
        — still landing on the oracle."""
        w = FleetWorld(4, str(tmp_path), budget_s=600,
                       label="ring_broken")
        res = w.launch(
            "peer_ring_broken",
            {"n_steps": 6, "lose_at": 4, "dim": 64},
            expect_exit={},
        )
        payloads = res.payloads()
        assert sorted(payloads) == list(range(4))
        for p in payloads.values():
            assert p["restored_step"] == 3
            assert p["fell_back"] is True
            assert p["oracle_match"] is True
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order("recover_action", "peer_ring_broken",
                         "recovered")
        broken = rep.events("peer_ring_broken")
        # every live rank detects the same uncovered owner
        assert {e["process"] for e in broken} == {0, 1, 2, 3}
        assert all(e["info"]["missing"] == "1" for e in broken)
        rec = rep.first("recovered")
        assert rec["info"]["tier"] == "fs_cold"
        assert rec["info"]["step"] == 3
