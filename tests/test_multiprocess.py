"""True multi-process distributed tests.

Parity: the reference's execution model ``mpiexec -n 2 pytest tests/``
(SURVEY.md section 4) — no mocks, a real distributed runtime.  Here each
test spawns N fresh Python processes that rendezvous through
``jax.distributed.initialize`` on a local coordinator, with virtual CPU
devices standing in for per-host chips; scenarios live in
``tests/mp_worker.py``.

These are the only tests that execute the multi-host-only code paths:
``MultiprocessObjStore`` (KV-store send/recv, host-collective bcast/
gather), ``broadcast_one_to_all`` in ``bcast_data``, the
``make_array_from_process_local_data`` branch of ``_place_batch``,
checkpoint save/agree/resume across processes, ``barrier``, and the
global except hook's distributed shutdown.

Run just these:   pytest -m multiprocess tests/
Skip them:        pytest -m "not multiprocess" tests/
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multiprocess

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "mp_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_world(scenario, n_procs=2, local_devices=1, tmpdir="/tmp",
              timeout=240, extra_env=None):
    """Spawn ``n_procs`` workers; return list of (returncode, stdout)."""
    from conftest import subprocess_env

    port = _free_port()
    # workers build their own CPU world: subprocess_env pins
    # JAX_PLATFORMS=cpu and forces the virtual device count, so a
    # worker can never take the chip its parent might hold
    env = subprocess_env(local_devices)
    env.update(extra_env or {})
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, scenario, str(port), str(i),
             str(n_procs), str(tmpdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(n_procs)
    ]
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            results.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return results


def _assert_ok(results, scenario):
    payloads = []
    for i, (rc, out) in enumerate(results):
        assert rc == 0, (
            f"{scenario}: process {i} exited {rc}\n--- output ---\n{out[-4000:]}"
        )
        line = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert line, f"{scenario}: process {i} printed no RESULT\n{out[-2000:]}"
        payloads.append(json.loads(line[-1][len("RESULT "):]))
    return payloads


class TestObjTransport:
    def test_two_processes(self, tmp_path):
        res = run_world("obj_transport", n_procs=2, tmpdir=tmp_path)
        payloads = _assert_ok(res, "obj_transport")
        assert all(p["size"] == 2 for p in payloads)

    def test_four_processes(self, tmp_path):
        res = run_world("obj_transport", n_procs=4, tmpdir=tmp_path)
        payloads = _assert_ok(res, "obj_transport")
        assert all(p["size"] == 4 for p in payloads)


class TestBcastData:
    def test_bit_identity_across_processes(self, tmp_path):
        res = run_world("bcast_data", n_procs=2, local_devices=2,
                        tmpdir=tmp_path)
        _assert_ok(res, "bcast_data")


class TestTrainStep:
    def test_per_process_batch_placement_and_sync(self, tmp_path):
        # 2 processes x 2 local devices = 4-chip world
        res = run_world("train_step", n_procs=2, local_devices=2,
                        tmpdir=tmp_path)
        payloads = _assert_ok(res, "train_step")
        # both controllers hold the same replicated params
        assert payloads[0]["final_w"] == pytest.approx(
            payloads[1]["final_w"]
        )


class TestComposedMesh:
    def test_dp_sp_tp_ep_across_processes(self, tmp_path):
        # 2 processes x 4 local devices = (2, 2, 2) mesh spanning hosts:
        # the data axis crosses the process boundary, so the composed
        # MoE step's gradient psum and per-process batch placement ride
        # the multi-controller path for real.
        res = run_world("composed_mesh", n_procs=2, local_devices=4,
                        tmpdir=tmp_path, timeout=420)
        payloads = _assert_ok(res, "composed_mesh")
        assert payloads[0]["losses"] == pytest.approx(
            payloads[1]["losses"]
        )


class TestCheckpoint:
    def test_save_agree_resume(self, tmp_path):
        res = run_world("checkpoint", n_procs=2, local_devices=2,
                        tmpdir=tmp_path)
        payloads = _assert_ok(res, "checkpoint")
        assert all(p["resumed_step"] == 7 for p in payloads)


class TestIterators:
    def test_multi_node_and_synchronized(self, tmp_path):
        # 2 processes x 2 local devices: rank_master=3 lives on process 1,
        # so the per-batch bcast_obj must carry the *master's* stream
        # (and out-of-range roots must raise on every process).
        res = run_world("iterators", n_procs=2, local_devices=2,
                        tmpdir=tmp_path)
        payloads = _assert_ok(res, "iterators")
        assert payloads[0]["first_batch"] == payloads[1]["first_batch"]


class TestAllreducePersistent:
    def test_cross_process_mean(self, tmp_path):
        res = run_world("allreduce_persistent", n_procs=2, tmpdir=tmp_path)
        _assert_ok(res, "allreduce_persistent")


class TestBarrier:
    def test_barrier_rendezvous(self, tmp_path):
        res = run_world("barrier", n_procs=2, tmpdir=tmp_path)
        payloads = _assert_ok(res, "barrier")
        assert payloads[0]["waited"] >= 1.0


class TestKillMidCheckpoint:
    def test_agreement_survives_rank_death_after_save(self, tmp_path):
        """The agreement protocol's reason-for-existence (VERDICT r4
        #6): rank 1 writes step 3's snapshot to its local disk and dies
        before the agreement round; on restart the world must agree on
        step 2 (the newest step on ALL ranks), ignore rank 1's newer
        snapshot, restore step 2's exact params everywhere, and keep
        training on the closed-form trajectory."""
        # run A: rank 1 exits 42 by design after writing step 3
        res = run_world("kill_mid_checkpoint_phase1", n_procs=2,
                        tmpdir=tmp_path)
        rc0, out0 = res[0]
        rc1, out1 = res[1]
        assert rc0 == 0, f"rank 0 should survive run A\n{out0[-3000:]}"
        assert rc1 == 42, (
            f"rank 1 should die (42) after writing step 3\n{out1[-3000:]}"
        )
        assert "RANK1_WROTE_STEP3_AND_DIED" in out1
        # run B: fresh world over the same scratch — agree on N-1=2,
        # resume, continue
        res = run_world("kill_mid_checkpoint_phase2", n_procs=2,
                        tmpdir=tmp_path)
        payloads = _assert_ok(res, "kill_mid_checkpoint_phase2")
        assert all(p["resumed_step"] == 2 for p in payloads)
        assert payloads[0]["w4"] == pytest.approx(payloads[1]["w4"])


class TestAsyncCheckpoint:
    def test_async_save_agree_resume_two_processes(self, tmp_path):
        # use_async=True was previously only exercised single-process;
        # here the AsyncCheckpointer's background commit, the
        # save-after-save serialization, wait_until_finished, and the
        # agreement protocol all run across a real 2-process world.
        res = run_world("async_checkpoint", n_procs=2, local_devices=2,
                        tmpdir=tmp_path)
        payloads = _assert_ok(res, "async_checkpoint")
        assert all(p["resumed_step"] == 5 for p in payloads)


class TestResilience:
    def test_retry_skip_and_auto_resume_two_processes(self, tmp_path):
        """Tentpole acceptance in a real 2-process world: an injected
        transient obj-store timeout is retried and the run completes; a
        NaN gradient on one process is skipped in agreement on all
        ranks with no deadlock; an injected mid-run failure triggers
        auto-resume from newest_common_step() with max_restarts
        respected (faults reach the workers via CHAINERMN_TPU_FAULTS)."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "timeout", "at": [1]},
            {"site": "trainer.update", "kind": "timeout", "at": [4]},
        ])
        res = run_world(
            "resilience", n_procs=2, local_devices=2, tmpdir=tmp_path,
            timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "resilience")
        assert all(p["restarts"] == 1 for p in payloads)
        assert payloads[0]["final_w"] == pytest.approx(
            payloads[1]["final_w"]
        )


class TestWireInt8:
    def test_bucketed_int8_wire_under_fault_injector(self, tmp_path):
        """ISSUE 4 satellite: the bucketed+int8 gradient wire end to end
        in a real 2-process world.  The FIRST obj-store exchange (the
        bucket-plan-hash agreement) ships a truncated payload on every
        process -> PayloadCorruptionError everywhere in lockstep ->
        plan_agreement retries -> the compiled int8+error-feedback run
        completes with bit-identical params on both processes."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "truncate", "at": [1],
             "truncate_to": 4},
        ])
        res = run_world(
            "wire_int8", n_procs=2, local_devices=2, tmpdir=tmp_path,
            timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "wire_int8")
        assert all(p["faults"] >= 1 for p in payloads)
        assert all(p["final_loss"] < p["first_loss"] for p in payloads)

    def test_overlap_step_under_fault_injector(self, tmp_path):
        """ISSUE 8 satellite: a 2-proc compiled OVERLAPPED step under
        the fault injector — retried transients on the plan-agreement
        and trace-guard exchanges must not reorder or drop any bucket:
        the trace hash is stable across the faulted run (and across
        ranks), every bucket psum still issues at its dependency
        frontier, and loss/params are bit-identical to the no-fault
        synchronous run (asserted inside the scenario)."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "truncate",
             "at": [1, 3], "truncate_to": 4},
        ])
        res = run_world(
            "overlap_fault", n_procs=2, local_devices=2, tmpdir=tmp_path,
            timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "overlap_fault")
        assert all(p["faults"] >= 2 for p in payloads)
        assert all(p["buckets"] >= 3 for p in payloads)

    def test_multihop_schedule_under_fault_injector(self, tmp_path):
        """ISSUE 11 satellite: the hier_rs_ag multi-hop wire across a
        REAL 2-process hierarchical world (process grouping = slice
        grouping, so the mesh factorizes (2, 2)) with truncate faults
        injected during schedule/plan agreement — the lockstep retry
        completes, every rank lands on the same WirePlan hash (bucket
        layout AND schedule), the trace carries the rs→ar→ag triple
        per bucket and hashes identically across ranks and across the
        faulted run, and loss/params are bit-identical to the no-fault
        run (all asserted inside the scenario)."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "truncate",
             "at": [1, 3], "truncate_to": 4},
        ])
        res = run_world(
            "multihop_fault", n_procs=2, local_devices=2,
            tmpdir=tmp_path, timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "multihop_fault")
        assert all(p["faults"] >= 2 for p in payloads)
        assert all(p["buckets"] >= 3 for p in payloads)
        assert all(
            p["mesh"] == {"mn_inter": 2, "mn_intra": 2}
            for p in payloads
        )
        assert payloads[0]["final_loss"] == payloads[1]["final_loss"]

    def test_tuned_wire_under_fault_and_profile_mismatch(self, tmp_path):
        """ISSUE 12 satellite: both ranks load ONE BandwidthProfile
        from the shared scratch and tune through it — truncate faults
        on the plan-agreement exchanges are retried in lockstep and the
        agreed WirePlan hash (which now folds in the profile content
        hash) matches across ranks, with the profile-staged rs→ar→ag
        triple in the trace; then a deliberately perturbed profile on
        rank 1 makes a fresh optimizer's init raise
        WirePlanMismatchError on BOTH ranks before any collective (all
        asserted inside the scenario)."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "truncate",
             "at": [1, 3], "truncate_to": 4},
        ])
        res = run_world(
            "tuned_wire_fault", n_procs=2, local_devices=2,
            tmpdir=tmp_path, timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "tuned_wire_fault")
        assert all(p["faults"] >= 2 for p in payloads)
        assert all(p["buckets"] >= 3 for p in payloads)
        assert all(p["mismatch_raised"] for p in payloads)
        # one profile, one plan: every rank agreed on both hashes
        assert payloads[0]["profile_hash"] == payloads[1]["profile_hash"]
        assert payloads[0]["plan_hash"] == payloads[1]["plan_hash"]
        assert payloads[0]["final_loss"] == payloads[1]["final_loss"]


class TestTelemetry:
    def test_straggler_flagged_and_timeline_exported_both_ranks(
        self, tmp_path
    ):
        """ISSUE 10 satellite: a 2-proc run with an injected slow rank
        (delay fault at trainer.update TARGETED at process 1) must
        produce a cross-rank MetricsReport that flags the straggler on
        both ranks, and a fault-injected obj-store retry whose events
        appear in the exported merged timeline in order (validated
        inside the scenario: fault -> retry -> straggler, time-sorted
        JSONL + Chrome-trace JSON shape, per-bucket collective spans
        in the same stream)."""
        import json as _json

        faults = _json.dumps([
            {"site": "obj_store.exchange", "kind": "timeout", "at": [1]},
            {"site": "trainer.update", "kind": "delay", "delay": 0.25,
             "probability": 1.0, "process": 1},
        ])
        res = run_world(
            "telemetry", n_procs=2, local_devices=2, tmpdir=tmp_path,
            timeout=420,
            extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        payloads = _assert_ok(res, "telemetry")
        assert all(p["stragglers"] == [1] for p in payloads)
        assert all(p["faults"] >= 1 for p in payloads)
        assert all(p["n_bucket_psums"] >= 2 for p in payloads)
        # both ranks exported their timeline files into the shared dir
        for pid in (0, 1):
            assert (tmp_path / f"trace_p{pid}.json").exists()
            assert (tmp_path / f"trace_p{pid}.jsonl").exists()


class TestTraceDivergence:
    def test_divergent_steps_fail_fast_on_both_ranks(self, tmp_path):
        """ISSUE 5 acceptance: rank 1 builds a step with one extra psum
        (env-selected); the divergence guard exchanges trace hashes at
        the first dispatch and raises CollectiveTraceMismatchError on
        BOTH ranks before any collective runs — instead of the silent
        deadlock this world produces without the guard (this test's
        timeout is the deadlock detector)."""
        res = run_world(
            "trace_divergence", n_procs=2, local_devices=2,
            tmpdir=tmp_path, timeout=240,
            extra_env={"CHAINERMN_TPU_DIVERGE_RANK": "1"},
        )
        payloads = _assert_ok(res, "trace_divergence")
        assert all(
            p["raised"] == "CollectiveTraceMismatchError" for p in payloads
        )


class TestProtocolDivergence:
    def test_guard_raises_on_both_ranks_before_deadlock(self, tmp_path):
        """ISSUE 20 acceptance: rank 1 issues one extra obj-store
        publish and swaps its two agreement-site orderings; the
        host-protocol guard exchanges sequence hashes (through the
        lockstep retry — phase 1 tears the guard's own payload and it
        recovers) and raises ProtocolDivergenceError on BOTH ranks
        while the world is still alive (this test's timeout is the
        deadlock detector).  The per-rank recorded protocols merge
        into the FleetReport post-mortem, which pinpoints the first
        divergent exchange token."""
        res = run_world(
            "protocol_divergence", n_procs=2, local_devices=1,
            tmpdir=tmp_path, timeout=240,
            extra_env={
                "CHAINERMN_TPU_PROTOCOL_RECORD": "1",
                "CHAINERMN_TPU_DIVERGE_RANK": "1",
            },
        )
        payloads = _assert_ok(res, "protocol_divergence")
        assert all(
            p["raised"] == "ProtocolDivergenceError" for p in payloads
        )
        # the torn-then-retried phase-1 agreement converged
        assert payloads[0]["phase1"] == payloads[1]["phase1"]
        assert all(p["entries"] > 0 for p in payloads)

        from chainermn_tpu.fleet.report import FleetReport

        rep = FleetReport.from_scratch(str(tmp_path))
        div = rep.protocol_divergence("protodiv")
        assert div is not None, "merged report must expose the divergence"
        toks = div["tokens"]
        # rank 1's extra publish is the first divergent token
        assert toks[0] != toks[1]
        assert "protocol divergence" in rep.post_mortem()


class TestMismatchedSharding:
    def test_implicit_collectives_fail_both_ranks_before_dispatch(
        self, tmp_path
    ):
        """ISSUE 6 satellite: rank 1's mismatched input sharding makes
        the partitioner insert all-gathers into ITS program only; the
        cross-process ``implicit_agreement`` check raises
        ``ImplicitCollectiveError`` on BOTH ranks before dispatch, with
        the responsible dot_general cited."""
        res = run_world(
            "mismatched_sharding", n_procs=2, local_devices=2,
            tmpdir=tmp_path, timeout=240,
            extra_env={"CHAINERMN_TPU_MISMATCH_RANK": "1"},
        )
        payloads = _assert_ok(res, "mismatched_sharding")
        assert all(
            p["raised"] == "ImplicitCollectiveError" for p in payloads
        )
        assert all(p["cited_dot"] for p in payloads)


class TestSpotReclaim:
    def test_reclaim_reshard_restart_world_2_to_1(self, tmp_path):
        """ISSUE 7 acceptance: a 2-proc ZeRO run saves steps 1-3 (each
        snapshot carrying its world manifest), worker 1 is reclaimed
        mid-step by a process-targeted ``die`` at the injector's
        ``trainer.update`` site, and the restart at world size 1 routes
        the restore through the checkpoint resharder and continues on
        the single-world oracle trajectory."""
        faults = json.dumps([
            {"site": "trainer.update", "kind": "die", "at": [4],
             "process": 1, "exit_code": 43},
        ])
        res = run_world(
            "spot_reclaim_phase1", n_procs=2, tmpdir=tmp_path,
            timeout=420, extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        rc0, out0 = res[0]
        rc1, out1 = res[1]
        assert rc0 == 0 and "RESULT" in out0, (
            f"worker 0 should be reaped cleanly after the save\n"
            f"{out0[-3000:]}"
        )
        assert rc1 == 43, (
            f"worker 1 should be reclaimed (exit 43) at update 4\n"
            f"{out1[-3000:]}"
        )
        # run B: the world re-forms at size 1, reshards, and continues
        res = run_world("spot_reclaim_phase2", n_procs=1,
                        tmpdir=tmp_path, timeout=420)
        payloads = _assert_ok(res, "spot_reclaim_phase2")
        assert payloads[0]["resumed_step"] == 3
        assert payloads[0]["resized"] == [2, 1]
        assert payloads[0]["oracle_match"] is True


class TestServingChurn:
    def test_replica_killed_mid_stream_survivor_completes(self, tmp_path):
        """ISSUE 13 satellite: a 2-replica serving world decodes a
        scripted 8-request stream off one shared journal; the fault
        injector kills replica 1 mid-stream (process-targeted ``die``
        at its 3rd decode step).  The drained requests stay journaled;
        the phase-2 world (size 1, via ``serve_elastic``) re-claims and
        completes every one with outputs bit-identical to the no-fault
        run (asserted in-scenario against a fresh oracle engine).  (Over
        40 s in the driver's run: two worlds of serving processes, each
        compiling its engine; the one tier-1 hold on a replica lost
        mid-stream.)"""
        faults = json.dumps([
            {"site": "serving.decode_step", "kind": "die", "at": [3],
             "process": 1, "exit_code": 43},
        ])
        res = run_world(
            "serving_churn_phase1", n_procs=2, tmpdir=tmp_path,
            timeout=420, extra_env={"CHAINERMN_TPU_FAULTS": faults},
        )
        rc0, out0 = res[0]
        rc1, out1 = res[1]
        assert rc0 == 0 and "RESULT" in out0, (
            f"replica 0 should complete its share\n{out0[-3000:]}"
        )
        assert rc1 == 43, (
            f"replica 1 should be killed (exit 43) mid-stream\n"
            f"{out1[-3000:]}"
        )
        line = [l for l in out0.splitlines() if l.startswith("RESULT ")]
        served0 = json.loads(line[-1][len("RESULT "):])["served"]
        assert served0 == ["c0", "c2", "c4", "c6"], served0
        res = run_world("serving_churn_phase2", n_procs=1,
                        tmpdir=tmp_path, timeout=420)
        payloads = _assert_ok(res, "serving_churn_phase2")
        assert payloads[0]["pending_before"] >= 4  # replica 1's share
        assert payloads[0]["completed"] == 8
        assert payloads[0]["bit_identical"] is True


class TestExceptHook:
    def test_crash_contained_not_hung(self, tmp_path):
        # process 1 raises; its hook shuts the distributed client down;
        # process 0 (blocked in recv_obj with a 15s bound) must ALSO die
        # promptly instead of hanging for the full 10-minute default.
        res = run_world(
            "except_hook", n_procs=2, tmpdir=tmp_path, timeout=120,
            extra_env={"CHAINERMN_TPU_OBJ_TIMEOUT_MS": "15000"},
        )
        rc0, out0 = res[0]
        rc1, out1 = res[1]
        assert rc1 != 0, f"raising process exited 0\n{out1[-2000:]}"
        assert "injected failure" in out1
        assert "aborting the distributed job" in out1
        assert rc0 != 0, (
            f"peer process survived a dead-peer recv\n{out0[-2000:]}"
        )
