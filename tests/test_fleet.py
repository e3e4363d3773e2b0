"""Fleet chaos tier (ISSUE 14) — tier-1 coverage.

Three layers, cheap to expensive:

* **Harness units** (no processes): the ``FaultSchedule`` DSL's
  compilation/composition/env rendering, ``FleetWorld``'s env wiring,
  and ``FleetReport``'s merge/dedupe/ordering contracts over
  synthesized artifacts.
* **Wide-world units** (no processes): the O(world) paths pinned at
  N=16/64 against mocked obj stores — ``newest_common_step`` election
  with a corrupt snapshot and a persistently slow rank, the
  leave-one-out straggler median with TWO simultaneous stragglers and
  a migrating one, ``scatter_dataset`` shard balance, and the
  16→12→14→8 ZeRO block-reshard chain's bit-identity.
* **One 8-process smoke** (``multiprocess`` mark, hard wall-clock
  budget — see tests/README.md): a preemption wave + one reshard leg
  through the real launcher, ending in the merged report's
  fault→retry→reform→reshard→resume order assertion.  The 16-64-rank
  scenarios live in test_fleet_chaos.py behind the ``slow`` mark.
"""

import json
import os

import numpy as np
import pytest

from chainermn_tpu.fleet import (
    ChainLeg,
    ElasticityChain,
    FaultSchedule,
    FleetBudgetError,
    FleetReport,
    FleetWorld,
    momentum_oracle,
)
from chainermn_tpu.fleet.schedule import ENV_SLICE
from chainermn_tpu.resilience.fault_injection import ENV_SPEC, FaultSpec


# ----------------------------------------------------------------------
class TestFaultScheduleDSL:
    def test_preemption_wave_spreads_deterministically(self):
        s = FaultSchedule().preemption_wave((3, 5, 9, 11), window=(4, 7))
        specs = s.specs()
        assert [d["process"] for d in specs] == [3, 5, 9, 11]
        assert all(d["kind"] == "die" for d in specs)
        # evenly spread over the window, deterministic by position
        assert [d["at"] for d in specs] == [[4], [5], [6], [7]]
        # byte-identical compilation on a rebuild
        s2 = FaultSchedule().preemption_wave((3, 5, 9, 11), window=(4, 7))
        assert s2.env() == s.env()

    def test_one_call_window_is_a_simultaneous_wave(self):
        s = FaultSchedule().preemption_wave((1, 2), window=(3, 3),
                                            exit_code=44)
        assert [d["at"] for d in s.specs()] == [[3], [3]]
        assert all(d["exit_code"] == 44 for d in s.specs())

    def test_slice_loss_targets_the_whole_slice_and_exports_grouping(self):
        s = FaultSchedule().slice_loss(1, slice_size=4, at=2)
        assert [d["process"] for d in s.specs()] == [4, 5, 6, 7]
        env = s.env()
        assert env[ENV_SLICE] == "4"
        # the rendered payload round-trips through the injector's own
        # constructor (what the spawned worker's _from_env does)
        specs = [FaultSpec(**d) for d in json.loads(env[ENV_SPEC])]
        assert all(sp.kind == "die" for sp in specs)

    def test_conflicting_slice_groupings_refused(self):
        s = FaultSchedule().slice_loss(0, slice_size=4, at=1)
        with pytest.raises(ValueError, match="one slice grouping"):
            s.slice_loss(1, slice_size=8, at=2)
        other = FaultSchedule().slice_loss(0, slice_size=8, at=1)
        with pytest.raises(ValueError, match="cannot compose"):
            s.compose(other)

    def test_migrating_straggler_two_windows(self):
        s = (FaultSchedule()
             .straggler(3, window=(1, 4), delay=0.2)
             .straggler(9, window=(5, 8), delay=0.2))
        specs = s.specs()
        assert specs[0]["process"] == 3 and specs[0]["at"] == [1, 2, 3, 4]
        assert specs[1]["process"] == 9 and specs[1]["at"] == [5, 6, 7, 8]

    def test_torn_payload_and_compose(self):
        a = FaultSchedule().torn_payload(calls=(1, 3), truncate_to=4)
        b = FaultSchedule().preemption_wave((2,), window=(5, 5))
        c = a.compose(b)
        assert len(c) == 3
        assert [d["kind"] for d in c.specs()] == ["truncate", "truncate",
                                                  "die"]
        # composition copies: mutating c never reaches a or b
        c.straggler(1, window=(1, 1))
        assert len(a) == 2 and len(b) == 1

    def test_validation_is_eager(self):
        with pytest.raises(ValueError):
            FaultSchedule().fault("site", "not_a_kind")
        with pytest.raises(ValueError, match="window"):
            FaultSchedule().straggler(0, window=(3, 2))
        with pytest.raises(ValueError, match="duplicate"):
            FaultSchedule().preemption_wave((1, 1), window=(1, 1))
        with pytest.raises(ValueError, match="at least one"):
            FaultSchedule().preemption_wave((), window=(1, 1))


class TestFleetWorldEnvWiring:
    def test_env_for_wires_schedule_and_targeting(self, tmp_path):
        sched = FaultSchedule(seed=7).slice_loss(0, slice_size=2, at=1)
        w = FleetWorld(4, tmp_path, local_devices=2, schedule=sched)
        env = w.env_for(3)
        assert env["CHAINERMN_TPU_FAULT_PROCESS_INDEX"] == "3"
        assert env["CHAINERMN_TPU_FAULT_SEED"] == "7"
        # 2 processes/slice x 2 devices/process: the exported topology
        # grouping counts device positions
        assert env[ENV_SLICE] == "4"
        assert "device_count=2" in env["XLA_FLAGS"]
        assert env["JAX_PLATFORMS"] == "cpu"  # never the chip
        assert json.loads(env[ENV_SPEC]) == sched.specs()

    def test_slice_grouping_scales_with_local_devices(self, tmp_path):
        # slice_size counts PROCESSES; the topology env knob counts
        # device positions — env_for reconciles the units so both
        # groupings always name the same process sets
        sched = FaultSchedule().slice_loss(0, slice_size=2, at=1)
        w = FleetWorld(8, tmp_path, local_devices=2, schedule=sched)
        assert w.env_for(0)[ENV_SLICE] == "4"
        # one device per process: exported verbatim
        w1 = FleetWorld(8, tmp_path, schedule=sched)
        assert w1.env_for(0)[ENV_SLICE] == "2"

    def test_rejects_empty_world(self, tmp_path):
        with pytest.raises(ValueError):
            FleetWorld(0, tmp_path)


# ----------------------------------------------------------------------
# wide-world unit coverage (satellites): the O(world) paths at N=64,
# no processes
# ----------------------------------------------------------------------
class _WideObjComm:
    """A mocked 64-process obj store for the election paths: this rank's
    inventory is live, the other 63 are scripted; the first
    ``flaky_attempts`` exchanges fail the way a persistently slow (or
    torn) rank fails, exercising the lockstep retry."""

    def __init__(self, peer_inventories, process_index=0,
                 flaky_attempts=0, flaky_exc=None):
        from chainermn_tpu.resilience.errors import TransientCommError

        self.process_count = len(peer_inventories) + 1
        self.process_index = process_index
        self.size = self.process_count
        self._peers = peer_inventories
        self._flaky = flaky_attempts
        self._exc = flaky_exc or TransientCommError(
            "rank 7 persistently slow: exchange deadline exceeded",
            site="obj_store.exchange",
        )
        self.exchanges = 0

    def allgather_obj(self, local):
        self.exchanges += 1
        if self._flaky > 0:
            self._flaky -= 1
            raise self._exc
        out = list(self._peers)
        out.insert(self.process_index, local)
        return out


def _local_steps(ckpt, steps, corrupt=()):
    """Materialize npz-tier snapshots on this rank's disk; ``corrupt``
    steps get a manifest whose digest can never match (the torn-write
    case the inventory must exclude)."""
    from chainermn_tpu.resilience import elastic

    for s in steps:
        d = ckpt._step_dir(s)
        os.makedirs(d, exist_ok=True)
        if s in corrupt:
            with open(os.path.join(d, "state.npz"), "wb") as f:
                f.write(b"torn")
            elastic.write_manifest(
                {"format": 1, "world_size": 64,
                 "files": {"state.npz": {"bytes": 4, "sha256": "0" * 64}}},
                os.path.join(d, elastic.MANIFEST_NAME),
            )


class TestWideWorldElection:
    """Satellite: ``newest_common_step`` + the lockstep-retried
    inventory allgather at N=64 (scenario shape: one rank holds a
    corrupt snapshot, one rank is persistently slow)."""

    def _ckpt(self, tmp_path, comm):
        from chainermn_tpu.extensions.checkpoint import (
            _MultiNodeCheckpointer,
        )

        return _MultiNodeCheckpointer(
            "wide", comm, path=str(tmp_path), use_orbax=False
        )

    def test_corrupt_snapshot_excluded_and_election_degrades(
        self, tmp_path
    ):
        # 63 peers all hold {1, 2, 3}; THIS rank's step 3 is torn, so
        # its inventory is {1, 2} and the 64-way election must land on
        # 2 — not raise at load time on the corrupt 3
        comm = _WideObjComm([[1, 2, 3]] * 63)
        ckpt = self._ckpt(tmp_path, comm)
        _local_steps(ckpt, (1, 2, 3), corrupt=(3,))
        assert ckpt._available_steps() == [1, 2]
        assert ckpt.newest_common_step() == 2

    def test_persistently_slow_rank_retried_in_lockstep(self, tmp_path):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        comm = _WideObjComm([[1, 2]] * 63, flaky_attempts=2)
        ckpt = self._ckpt(tmp_path, comm)
        _local_steps(ckpt, (1, 2))
        slog = ResilienceLog()
        attach(slog)
        try:
            assert ckpt.newest_common_step() == 2
        finally:
            detach(slog)
        # two failed exchanges, each retried, third succeeds
        assert slog.counts.get("retry") == 2
        assert comm.exchanges == 3

    def test_torn_inventory_payload_retried(self, tmp_path):
        from chainermn_tpu.resilience.errors import PayloadCorruptionError

        comm = _WideObjComm(
            [[5]] * 63, flaky_attempts=1,
            flaky_exc=PayloadCorruptionError(
                "inventory payload failed to unpickle",
                site="obj_store.exchange",
            ),
        )
        ckpt = self._ckpt(tmp_path, comm)
        _local_steps(ckpt, (5,))
        assert ckpt.newest_common_step() == 5
        assert comm.exchanges == 2

    def test_one_empty_rank_elects_nothing(self, tmp_path):
        # a freshly joined rank with no snapshots: the 64-way common
        # set is empty and the election answers None (resume from
        # scratch), not a crash
        comm = _WideObjComm([[1, 2, 3]] * 62 + [[]])
        ckpt = self._ckpt(tmp_path, comm)
        _local_steps(ckpt, (1, 2, 3))
        assert ckpt.newest_common_step() is None


class _FakeTrainer:
    iteration = 16


def _phase_data(n, stragglers, *, straggler_host=0.3, healthy_host=0.01,
                step=1.0):
    by_proc = {}
    for p in range(n):
        host = straggler_host if p in stragglers else healthy_host
        by_proc[p] = {
            "process": p,
            "phases": {
                "step": [step] * 3,
                "update.host": [host] * 3,
            },
        }
    return by_proc


class TestWideStragglers:
    """Satellite: the leave-one-out straggler median at N=16/64 with
    TWO simultaneous stragglers, plus migration between windows."""

    def _report(self):
        from chainermn_tpu.observability import MetricsReport

        return MetricsReport(None, filename=None)

    @pytest.mark.parametrize("n", [16, 64])
    def test_two_simultaneous_stragglers_both_convicted(self, n):
        rep = self._report()
        rep._flag_stragglers(_phase_data(n, {3, 9}), _FakeTrainer())
        assert rep.straggler_processes == [3, 9]

    @pytest.mark.parametrize("n", [16, 64])
    def test_no_false_positives_on_healthy_world(self, n):
        rep = self._report()
        rep._flag_stragglers(_phase_data(n, set()), _FakeTrainer())
        assert rep.straggler_processes == []

    def test_straggler_migrates_between_windows(self):
        # window 1 convicts rank 3; window 2 (fresh samples — the
        # incremental-window contract) convicts rank 9 and NOT the
        # recovered rank 3
        rep = self._report()
        rep._flag_stragglers(_phase_data(16, {3}), _FakeTrainer())
        assert rep.straggler_processes == [3]
        rep._flag_stragglers(_phase_data(16, {9}), _FakeTrainer())
        assert rep.straggler_processes == [9]

    def test_materiality_floor_holds_at_64(self):
        # a "straggler" whose host phase is noise (way below the 5%
        # step floor) must not be convicted, even at ratio 30x
        rep = self._report()
        by_proc = _phase_data(64, {5}, straggler_host=0.03,
                              healthy_host=0.001, step=10.0)
        rep._flag_stragglers(by_proc, _FakeTrainer())
        assert rep.straggler_processes == []


class TestScatterShardBalance64:
    """Satellite: ``scatter_dataset`` shard balance at N=64 — the
    substrate a straggler-adaptive rebalance will skew."""

    def test_remainder_distribution_pattern_pinned(self):
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 1000, 64  # 1000 = 64*15 + 40
        sizes, covered = [], []
        for r in range(size):
            order, start, end = scatter_index(n, size, r, equalize=False)
            sizes.append(end - start)
            covered.extend(order[start:end])
        # the first `rem` ranks absorb the remainder, one sample each
        assert sizes == [16] * 40 + [15] * 24
        # disjoint exact cover
        assert sorted(covered) == list(range(n))

    def test_equalized_shards_wrap_and_stay_balanced(self):
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 1000, 64
        sizes, covered = [], []
        for r in range(size):
            order, start, end = scatter_index(n, size, r, equalize=True)
            sizes.append(end - start)
            covered.extend(order[start:end])
        # every rank steps the same number of times per epoch
        assert sizes == [16] * 64
        counts = np.bincount(np.asarray(covered), minlength=n)
        # the wrap-around pad re-serves exactly the first 24 samples
        assert list(counts[:24]) == [2] * 24
        assert list(counts[24:]) == [1] * (n - 24)


class TestWeightedScatter:
    """Satellite (ISSUE 15): explicit per-rank ``scatter_dataset``
    weights with deterministic remainder placement — the shard map the
    adaptive rebalance skews — pinned at N=64 alongside the existing
    ``scatter_index`` remainder tests."""

    def test_equal_weights_reproduce_equalized_remainder_pattern(self):
        from chainermn_tpu.datasets import weighted_shard_counts
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 1000, 64
        counts = weighted_shard_counts(n, [1.0] * size)
        legacy = []
        for r in range(size):
            _o, s, e = scatter_index(n, size, r, equalize=False)
            legacy.append(e - s)
        # ties in the largest-remainder placement break to the LOWER
        # rank, so equal weights reproduce the equalized split's
        # "first rem ranks absorb the remainder" exactly
        assert counts == legacy == [16] * 40 + [15] * 24

    def test_weighted_remainder_pattern_n64_pinned(self):
        from chainermn_tpu.datasets import weighted_shard_counts

        n, size = 1000, 64
        w = [1.0] * size
        w[5], w[9] = 0.5, 0.25
        counts = weighted_shard_counts(n, w)
        # deterministic largest-remainder placement: the two skewed
        # ranks take their quota floors, the last four full-weight
        # ranks lose the remainder — pinned exactly
        want = [16] * 64
        want[5], want[9] = 8, 4
        want[60:] = [15] * 4
        assert counts == want
        assert sum(counts) == n

    def test_equalized_weighted_split_uniform_width_full_cover(self):
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 1000, 64
        w = [1.0] * size
        w[5], w[9] = 0.5, 0.25
        widths, covered = set(), set()
        for r in range(size):
            order, s, e = scatter_index(n, size, r, weights=w,
                                        equalize=True)
            widths.add(e - s)
            covered.update(int(i) for i in order[s:e])
        # every rank steps the same number of times per epoch (the
        # lockstep contract a rebalance must not break): short shards
        # wrap-pad WITHIN themselves to the widest shard
        assert widths == {16}
        assert covered == set(range(n))

    def test_unequalized_weighted_split_is_contiguous_and_disjoint(self):
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 103, 8
        w = [1.0] * size
        w[3] = 0.2
        seen = []
        for r in range(size):
            order, s, e = scatter_index(n, size, r, weights=w,
                                        equalize=False)
            seen.extend(order[s:e])
        assert sorted(seen) == list(range(n))

    def test_min_count_lift_and_validation(self):
        from chainermn_tpu.datasets import weighted_shard_counts

        # a vanishing weight still gets >= 1 sample under min_count
        # (the equalized path's contract: np.resize of an empty shard
        # would fabricate indices) — stolen from the largest shard
        counts = weighted_shard_counts(10, [1.0, 1.0, 1e-9],
                                       min_count=1)
        assert counts == [4, 5, 1]
        assert sum(counts) == 10
        with pytest.raises(ValueError, match="finite and >= 0"):
            weighted_shard_counts(10, [1.0, -2.0])
        with pytest.raises(ValueError, match="at least one weight"):
            weighted_shard_counts(10, [0.0, 0.0])
        with pytest.raises(ValueError, match="cannot give"):
            weighted_shard_counts(3, [1.0] * 8, min_count=1)

    def test_explicit_zero_weight_is_a_probationary_rank(self):
        """Satellite (ISSUE 16): an EXPLICIT weight-0 rank owns no
        samples (probationary host), receives no remainder, is exempt
        from the min_count lift — and the legacy equal-weight pattern
        over the positive ranks is unchanged."""
        from chainermn_tpu.datasets import weighted_shard_counts

        assert weighted_shard_counts(10, [1.0, 0.0]) == [10, 0]
        # min_count lifts only the POSITIVE ranks
        assert weighted_shard_counts(10, [1.0, 1e-9, 0.0],
                                     min_count=1) == [9, 1, 0]
        # the remainder pattern over the data-owning ranks matches the
        # same split WITHOUT the probationary rank appended
        n = 1000
        with_probe = weighted_shard_counts(n, [1.0] * 64 + [0.0])
        assert with_probe[:64] == weighted_shard_counts(n, [1.0] * 64)
        assert with_probe[64] == 0

    def test_zero_weight_equalized_shard_pads_from_permutation_head(self):
        """The weight-0 shard's lockstep pad: under ``equalize`` it
        steps the same count per epoch as everyone (width = widest
        shard) but draws only re-served samples — the head of the
        epoch permutation — so full cover over the data-owning ranks
        is untouched."""
        from chainermn_tpu.datasets.scatter_dataset import scatter_index

        n, size = 103, 9  # 8 data ranks + 1 probe, ragged on purpose
        w = [1.0] * 8 + [0.0]
        widths, covered = set(), set()
        for r in range(size):
            order, s, e = scatter_index(n, size, r, weights=w,
                                        equalize=True)
            widths.add(e - s)
            if r < 8:
                covered.update(int(i) for i in order[s:e])
        assert len(widths) == 1  # lockstep width, probe included
        assert covered == set(range(n))  # data ranks still cover all
        # the probe shard re-serves exactly the permutation's head
        order, s, e = scatter_index(n, size, 8, weights=w,
                                    equalize=True)
        base, _s0, _e0 = scatter_index(n, size, 0, weights=w,
                                       equalize=True)
        np.testing.assert_array_equal(order[s:e], base[: e - s])

    def test_rescatter_preserves_base_permutation(self):
        from chainermn_tpu.datasets import rescatter, scatter_dataset

        class _Comm:
            process_count, process_index, rank, size = 4, 1, 1, 4

            def bcast_obj(self, x, root=0):
                return x

        data = list(range(40, 57))  # 17 samples, distinct values
        sub = scatter_dataset(data, _Comm(), shuffle=True, seed=7)
        w = [1.0, 0.5, 1.0, 1.0]
        sub2 = rescatter(sub, w)
        # same base permutation re-split: the union of unique indices
        # over all ranks is still the whole dataset, and this rank's
        # spec records the agreed weights
        assert sub2.scatter_spec["weights"] == tuple(w)
        np.testing.assert_array_equal(sub2.base_order, sub.base_order)
        # a plain SubDataset without scatter metadata is refused
        from chainermn_tpu.datasets import SubDataset

        bare = SubDataset(data, np.arange(17), 0, 5)
        with pytest.raises(ValueError, match="scatter_dataset"):
            rescatter(bare, w)


# ----------------------------------------------------------------------
class TestAdaptPolicy:
    """Tentpole (ISSUE 15): the hysteresis state machine, unit-pinned
    at fleet widths with no processes."""

    def _policy(self, **kw):
        from chainermn_tpu.resilience.adaptive import AdaptPolicy

        kw.setdefault("rebalance_after", 1)
        kw.setdefault("demote_after", 3)
        kw.setdefault("cooldown_windows", 1)
        return AdaptPolicy(**kw)

    def test_escalation_rebalance_cooldown_demote(self):
        p = self._policy()
        a1 = p.observe([3], world=16, iteration=1)
        assert a1[0]["action"] == "rebalance"
        assert a1[0]["weights"][3] == 0.5  # skewed away from the host
        # cooldown blocks the next window entirely
        assert p.observe([3], world=16, iteration=2) == []
        a3 = p.observe([3], world=16, iteration=3)
        assert a3 == [{"action": "demote", "process": 3, "streak": 3,
                       "iteration": 3}]

    def test_flap_suppression_decays_streak(self):
        # slow / recovered / slow / recovered ... never reaches the
        # demote threshold: a healthy window decays the streak
        p = self._policy(max_rebalances=0)
        for i, conv in enumerate(
            [[5], [], [5], [], [5], [], [5], []], start=1
        ):
            actions = p.observe(conv, world=16, iteration=i)
            assert actions == [], (i, actions)
            assert p.streaks.get(5, 0) <= 1

    def test_two_simultaneous_stragglers_one_weighted_map(self):
        p = self._policy()
        a = p.observe([3, 9], world=64, iteration=1)
        assert len(a) == 1 and a[0]["action"] == "rebalance"
        assert a[0]["processes"] == [3, 9]
        w = a[0]["weights"]
        assert len(w) == 64
        assert w[3] == w[9] == 0.5 and w[0] == 1.0

    def test_max_rebalances_caps_the_skew(self):
        p = self._policy(demote_after=99, cooldown_windows=0,
                         max_rebalances=2)
        kinds = [p.observe([7], world=16, iteration=i)
                 for i in range(1, 5)]
        assert [bool(k) for k in kinds] == [True, True, False, False]
        assert p.weights[7] == 0.25  # 0.5 ** 2, floored far above min

    def test_demote_picks_highest_streak_then_lowest_index(self):
        p = self._policy(rebalance_after=99, cooldown_windows=0)
        p.observe([2, 9], world=16, iteration=1)
        p.observe([2, 9], world=16, iteration=2)
        p.observe([9], world=16, iteration=3)
        a = p.observe([2, 9], world=16, iteration=4)
        # 9 has streak 4, 2 decayed to 2 (healthy window 3): 9 wins
        assert a[0] == {"action": "demote", "process": 9, "streak": 4,
                        "iteration": 4}

    def test_state_round_trips_and_resets_on_world_change(self):
        from chainermn_tpu.resilience.adaptive import AdaptPolicy

        p = self._policy()
        p.observe([3], world=16, iteration=1)
        p.observe([3], world=16, iteration=2)
        sd = p.state_dict()
        q = AdaptPolicy()
        q.load_state_dict(sd)
        assert q.streaks == {3: 2} and q.world == 16
        assert q.weights[3] == 0.5
        assert q.totals["rebalance"] == 1
        # same world: hysteresis continues where it left off
        q2 = AdaptPolicy(demote_after=3)
        q2.load_state_dict(sd)
        a = q2.observe([3], world=16, iteration=3)
        assert a[0]["action"] == "demote"
        # resized world: per-process maps reset (indices renamed),
        # run totals survive, the reset is observable
        q.observe([], world=15, iteration=9)
        assert q.streaks == {} and q.weights is None
        assert q.last_reset == (16, 15)
        assert q.totals["rebalance"] == 1

    def test_validation_is_eager(self):
        from chainermn_tpu.resilience.adaptive import AdaptPolicy

        with pytest.raises(ValueError, match="thresholds"):
            AdaptPolicy(rebalance_after=0)
        with pytest.raises(ValueError, match="rebalance_skew"):
            AdaptPolicy(rebalance_skew=1.0)
        with pytest.raises(ValueError, match="unknown actions"):
            AdaptPolicy(actions=("rebalance", "restart"))
        with pytest.raises(ValueError, match="probation_windows"):
            AdaptPolicy(probation_windows=0)
        with pytest.raises(ValueError, match="readmit_cooldown"):
            AdaptPolicy(readmit_cooldown_windows=-1)
        with pytest.raises(ValueError, match="promote_quorum"):
            AdaptPolicy(promote_quorum=0)

    def test_promote_decision_shape_and_readmit_cooldown(self):
        """Scale-up (ISSUE 16): ready hosts become one promote decision
        (world → world+k); a just-demoted host is held out until
        ``readmit_cooldown_windows`` report windows pass."""
        p = self._policy(readmit_cooldown_windows=2)
        hosts = [f"h{i}" for i in range(8)]
        a = p.observe([], world=8, iteration=4,
                      ready_hosts=["hx", "hy"], hosts=hosts)
        assert a == [{"action": "promote", "hosts": ["hx", "hy"],
                      "world": 8, "new_world": 10, "iteration": 4}]
        assert p.totals["promote"] == 1
        # demote h3 at window 2 — the NEXT two windows block its
        # re-admission, the third admits it
        p2 = self._policy(rebalance_after=99, demote_after=1,
                          cooldown_windows=0,
                          readmit_cooldown_windows=2)
        d = p2.observe([3], world=8, iteration=1, hosts=hosts)
        assert d[0]["action"] == "demote"
        assert p2.host_history["h3"] == {
            "streak": 1, "window": 1, "promoted": False,
        }
        assert p2.readmit_blocked("h3")
        assert p2.observe([], world=7, iteration=2,
                          ready_hosts=["h3"], hosts=hosts[:7]) == []
        a2 = p2.observe([], world=7, iteration=3,
                        ready_hosts=["h3"], hosts=hosts[:7])
        assert a2[0]["action"] == "promote"
        assert a2[0]["new_world"] == 8
        assert p2.host_history["h3"]["promoted"] is True

    def test_promote_quorum_holds_ready_hosts_for_one_restart(self):
        """``promote_quorum`` amortizes world re-formations: ready
        hosts are HELD (the watcher keeps them ready — nothing is
        consumed) until at least that many can join in one N→N+k
        restart; then they all promote together."""
        p = self._policy(promote_quorum=3)
        hosts = [f"h{i}" for i in range(6)]
        assert p.observe([], world=6, iteration=1,
                         ready_hosts=["hx"], hosts=hosts) == []
        assert p.observe([], world=6, iteration=2,
                         ready_hosts=["hx", "hy"], hosts=hosts) == []
        assert p.totals["promote"] == 0
        a = p.observe([], world=6, iteration=3,
                      ready_hosts=["hy", "hx", "hz"], hosts=hosts)
        assert a == [{"action": "promote",
                      "hosts": ["hx", "hy", "hz"],
                      "world": 6, "new_world": 9, "iteration": 3}]
        assert p.totals["promote"] == 1
        # a cooldown-blocked host does not count toward the quorum
        p2 = self._policy(rebalance_after=99, demote_after=1,
                          cooldown_windows=0, promote_quorum=2,
                          readmit_cooldown_windows=5)
        p2.observe([3], world=6, iteration=1, hosts=hosts)
        assert p2.observe([], world=5, iteration=2,
                          ready_hosts=["h3", "hx"],
                          hosts=hosts[:5]) == []

    def test_demote_wins_the_window_over_promote(self):
        p = self._policy(rebalance_after=99, demote_after=1,
                         cooldown_windows=0)
        a = p.observe([2], world=8, iteration=5, ready_hosts=["hx"],
                      hosts=[f"h{i}" for i in range(8)])
        assert [x["action"] for x in a] == ["demote"]
        # the ready host was NOT consumed: next (healthy) window
        # promotes it
        a2 = p.observe([], world=8, iteration=6, ready_hosts=["hx"],
                       hosts=[f"h{i}" for i in range(8)])
        assert [x["action"] for x in a2] == ["promote"]

    def test_flap_demote_probation_promote_convict_skips_to_demote(self):
        """Satellite (ISSUE 16): the full flap — demoted, re-admitted
        through probation, promoted, convicted again — skips the
        rebalance ladder: the effective streak starts from the
        pre-demotion history, so ONE fresh conviction trips
        ``demote_after`` again."""
        p = self._policy(demote_after=3, cooldown_windows=0,
                         readmit_cooldown_windows=0)
        hosts8 = [f"h{i}" for i in range(8)]
        # build h5's streak to demotion (cooldown off, rebalance fires
        # along the way — ignore the actions, watch the history)
        p.observe([5], world=8, iteration=1, hosts=hosts8)
        p.observe([5], world=8, iteration=2, hosts=hosts8)
        a = p.observe([5], world=8, iteration=3, hosts=hosts8)
        assert a[0] == {"action": "demote", "process": 5, "streak": 3,
                        "iteration": 3}
        assert p.host_history["h5"]["streak"] == 3
        # world shrank to 7 (per-process maps reset), h5 returns and
        # clears probation
        a = p.observe([], world=7, iteration=10, ready_hosts=["h5"],
                      hosts=hosts8[:7])
        assert a[0]["action"] == "promote"
        # grown world: h5 is now process 7; its FIRST re-conviction
        # goes straight to demote (3 history + 1 fresh >= 3), no
        # rebalance rung — and the fresh demotion re-records history
        hosts_new = hosts8[:7] + ["h5"]
        a = p.observe([7], world=8, iteration=20, hosts=hosts_new)
        assert a[0] == {"action": "demote", "process": 7, "streak": 4,
                        "iteration": 20}
        assert p.host_history["h5"]["promoted"] is False
        assert p.totals["demote"] == 2

    def test_readmitted_host_excluded_from_rebalance(self):
        p = self._policy(demote_after=99, cooldown_windows=0)
        hosts = [f"h{i}" for i in range(4)]
        p.host_history["h2"] = {"streak": 1, "window": 0,
                                "promoted": True}
        # h2 (process 2) convicts but is re-admitted: no rebalance for
        # it; a normal process still rebalances in the same window
        a = p.observe([1, 2], world=4, iteration=1, hosts=hosts)
        assert a[0]["action"] == "rebalance"
        assert a[0]["processes"] == [1]

    def test_host_history_round_trips_and_survives_resize(self):
        from chainermn_tpu.resilience.adaptive import AdaptPolicy

        p = self._policy(rebalance_after=99, demote_after=1,
                         cooldown_windows=0)
        p.observe([3], world=8, iteration=1,
                  hosts=[f"h{i}" for i in range(8)])
        sd = p.state_dict()
        q = AdaptPolicy()
        q.load_state_dict(sd)
        assert q.host_history == {
            "h3": {"streak": 1, "window": 1, "promoted": False},
        }
        assert q.totals["demote"] == 1
        # a resize resets per-process maps; host-keyed history survives
        q.observe([], world=7, iteration=2)
        assert q.streaks == {}
        assert q.host_history["h3"]["streak"] == 1


class TestCapacityWatcher:
    """Tentpole (ISSUE 16): the probation state machine over presence
    manifests — scan/evaluate with no processes."""

    def _watcher(self, tmp_path, **kw):
        from chainermn_tpu.resilience.adaptive import CapacityWatcher

        kw.setdefault("probation_windows", 2)
        return CapacityWatcher(str(tmp_path), **kw)

    def _publish(self, tmp_path, host, window, mean):
        from chainermn_tpu.resilience.adaptive import publish_presence

        return publish_presence(str(tmp_path), host, window=window,
                                step_mean_s=mean)

    def test_probation_clears_after_consecutive_clean_windows(
        self, tmp_path
    ):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        w = self._watcher(tmp_path)
        means = {0: 0.10, 1: 0.10, 2: 0.11}
        slog = ResilienceLog()
        attach(slog)
        try:
            self._publish(tmp_path, "c9", 1, 0.10)
            assert w.evaluate(w.scan(), means) == []
            assert slog.counts.get("host_returned") == 1
            # the SAME manifest again: no new window, no progress
            assert w.evaluate(w.scan(), means) == []
            assert w.streaks["c9"] == 1
            self._publish(tmp_path, "c9", 2, 0.12)
            assert w.evaluate(w.scan(), means) == ["c9"]
            assert slog.counts.get("probation_pass") == 1
            # cleared hosts stay ready until promoted
            assert w.evaluate(w.scan(), means) == ["c9"]
        finally:
            detach(slog)

    def test_dirty_window_resets_the_streak(self, tmp_path):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        w = self._watcher(tmp_path)
        means = {0: 0.10, 1: 0.10, 2: 0.10}
        slog = ResilienceLog()
        attach(slog)
        try:
            self._publish(tmp_path, "c9", 1, 0.10)
            w.evaluate(w.scan(), means)
            # window 2 is a straggler window (0.9 > 1.5 * 0.10)
            self._publish(tmp_path, "c9", 2, 0.9)
            assert w.evaluate(w.scan(), means) == []
            assert w.streaks["c9"] == 0
            holds = slog.events("probation_hold")
            assert holds[0].info["reason"] == "straggler"
            # two more clean windows needed from scratch
            self._publish(tmp_path, "c9", 3, 0.10)
            assert w.evaluate(w.scan(), means) == []
            self._publish(tmp_path, "c9", 4, 0.10)
            assert w.evaluate(w.scan(), means) == ["c9"]
        finally:
            detach(slog)

    def test_blocked_host_sighted_but_held(self, tmp_path):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        w = self._watcher(tmp_path)
        means = {0: 0.10, 1: 0.10}
        self._publish(tmp_path, "c9", 1, 0.10)
        slog = ResilienceLog()
        attach(slog)
        try:
            assert w.evaluate(w.scan(), means, blocked=["c9"]) == []
            hold = slog.events("probation_hold")[0]
            assert hold.info["reason"] == "readmit_cooldown"
            assert "c9" in w.returned  # sighted all the same
            assert w.streaks.get("c9", 0) == 0
        finally:
            detach(slog)

    def test_no_measurement_holds_and_torn_manifest_skipped(
        self, tmp_path
    ):
        from chainermn_tpu.resilience.adaptive import presence_path

        w = self._watcher(tmp_path)
        # no world means yet (empty report): candidate cannot clear
        self._publish(tmp_path, "c9", 1, 0.10)
        assert w.evaluate(w.scan(), {}) == []
        assert w.streaks["c9"] == 0
        # a torn manifest (killed mid-write without the atomic rename)
        # is invisible to scan — never a crash
        os.makedirs(os.path.dirname(presence_path(str(tmp_path), "t")),
                    exist_ok=True)
        with open(presence_path(str(tmp_path), "t"), "w") as f:
            f.write('{"host": "t", "win')
        assert "t" not in w.scan()

    def test_publish_is_atomic_and_clearable(self, tmp_path):
        from chainermn_tpu.resilience.adaptive import (
            clear_presence, presence_path,
        )

        p = self._publish(tmp_path, "c3", 5, 0.2)
        assert p == presence_path(str(tmp_path), "c3")
        with open(p) as f:
            doc = json.load(f)
        assert doc == {"host": "c3", "window": 5, "step_mean_s": 0.2,
                       "state": "candidate"}
        # no tmp litter next to the manifest (atomic rename contract)
        assert os.listdir(os.path.dirname(p)) == ["host_c3.json"]
        clear_presence(str(tmp_path), "c3")
        assert not os.path.exists(p)
        clear_presence(str(tmp_path), "c3")  # idempotent

    def test_validation_is_eager(self, tmp_path):
        from chainermn_tpu.resilience.adaptive import CapacityWatcher

        with pytest.raises(ValueError, match="probation_windows"):
            CapacityWatcher(str(tmp_path), probation_windows=0)
        with pytest.raises(ValueError, match="straggler_factor"):
            CapacityWatcher(str(tmp_path), straggler_factor=1.0)


class _AgreeComm:
    """Mocked obj store for the decision agreement: optionally flaky
    (torn payload) for the first ``flaky`` exchanges, then returns the
    scripted peer payloads + this rank's own."""

    def __init__(self, n, flaky=0, peers=None):
        self.process_count = self.size = n
        self.process_index = 0
        self._flaky = flaky
        self._peers = peers
        self.exchanges = 0

    def bcast_obj(self, obj, root=0):
        return obj  # rank 0's view wins — this mock IS rank 0

    def allgather_obj(self, mine):
        from chainermn_tpu.resilience.errors import (
            PayloadCorruptionError,
        )

        self.exchanges += 1
        if self._flaky > 0:
            self._flaky -= 1
            raise PayloadCorruptionError(
                "decision payload failed to unpickle",
                site="obj_store.exchange",
            )
        peers = (self._peers if self._peers is not None
                 else [mine] * (self.process_count - 1))
        return [mine] + list(peers)


class TestAdaptiveAgreement:
    """Satellite (CI/lint): every policy exchange rides the existing
    lockstep retry — a torn payload during the rebalance agreement is
    retried on all ranks together, and a genuinely divergent decision
    raises on every rank before anyone acts."""

    def _ext(self, comm):
        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
        )

        return AdaptiveExecution(AdaptPolicy(), comm=comm)

    def test_torn_rebalance_agreement_retried_in_lockstep(self):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        comm = _AgreeComm(16, flaky=1)
        ext = self._ext(comm)
        actions = [{"action": "rebalance", "processes": [3],
                    "weights": [1.0] * 16, "iteration": 4}]
        slog = ResilienceLog()
        attach(slog)
        try:
            ext._agree(4, actions)
        finally:
            detach(slog)
        assert comm.exchanges == 2  # torn once, re-exchanged
        assert slog.counts.get("retry") == 1
        assert slog.events("retry")[0].site == "adaptive.agree"

    def test_divergent_decision_raises_on_every_rank(self):
        from chainermn_tpu.resilience.errors import (
            AdaptDecisionMismatchError,
        )

        comm = _AgreeComm(4, peers=['{"other": "decision"}'] * 3)
        ext = self._ext(comm)
        with pytest.raises(AdaptDecisionMismatchError,
                           match="diverged at iteration 7"):
            ext._agree(7, [{"action": "demote", "process": 1}])

    def test_exhausted_retries_surface_the_transient_taxonomy(self):
        from chainermn_tpu.resilience.errors import TransientCommError

        comm = _AgreeComm(4, flaky=99)
        ext = self._ext(comm)
        with pytest.raises(TransientCommError):
            ext._agree(1, [{"action": "demote", "process": 1}])

    def test_torn_promote_agreement_retried_in_lockstep(self):
        """ISSUE 16 acceptance: the scale-up decision rides the SAME
        lockstep retry as rebalance/demote — a torn payload during the
        promote agreement re-exchanges on all ranks together."""
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        comm = _AgreeComm(8, flaky=1)
        ext = self._ext(comm)
        actions = [{"action": "promote", "hosts": ["c9"], "world": 8,
                    "new_world": 9, "iteration": 6}]
        slog = ResilienceLog()
        attach(slog)
        try:
            ext._agree(6, actions)
        finally:
            detach(slog)
        assert comm.exchanges == 2  # torn once, re-exchanged
        assert slog.counts.get("retry") == 1
        assert slog.events("retry")[0].site == "adaptive.agree"

    def test_divergent_promote_decision_raises_on_every_rank(self):
        """ISSUE 16 acceptance: a rank that decided a DIFFERENT grow
        (or none) raises AdaptDecisionMismatchError before anyone
        re-forms the world — mirroring the demote pin."""
        from chainermn_tpu.resilience.errors import (
            AdaptDecisionMismatchError,
        )

        other = json.dumps(
            {"iteration": 6, "actions": []}, sort_keys=True
        )
        comm = _AgreeComm(8, peers=[other] * 7)
        ext = self._ext(comm)
        with pytest.raises(AdaptDecisionMismatchError,
                           match="diverged at iteration 6"):
            ext._agree(6, [{"action": "promote", "hosts": ["c9"],
                            "world": 8, "new_world": 9,
                            "iteration": 6}])


class _StubReport:
    """Just enough MetricsReport surface for the extension."""

    def __init__(self, comm=None, means=None):
        self._comm = comm
        self.last_report = None
        self.straggler_processes = []
        self._means = dict(means or {})

    def window(self, iteration, stragglers):
        self.last_report = {"iteration": iteration, "rows": [],
                            "stragglers": list(stragglers)}
        self.straggler_processes = list(stragglers)

    def process_means(self, phase="step"):
        return dict(self._means)


class TestAdaptiveExecution:
    """The extension half of the tentpole: conviction stream in,
    applied rebalance / collective demotion out."""

    def _trainer(self, dataset):
        from chainermn_tpu.iterators import SerialIterator
        from chainermn_tpu.training.trainer import Trainer, Updater

        it = SerialIterator(dataset, 2, shuffle=False)
        return Trainer(Updater(it, lambda *a: None, None, None),
                       stop_trigger=(1, "iteration"))

    def _scattered(self, n_shards=4, rank=0, n=40):
        from chainermn_tpu.datasets import scatter_dataset

        class _Comm:
            process_count, process_index = n_shards, rank
            size = n_shards
            rank_ = rank

            def bcast_obj(self, x, root=0):
                return x

        return scatter_dataset(list(range(n)), _Comm(), shuffle=False,
                               seed=0)

    def test_rebalance_rescatters_live_iterator_and_remaps_cursor(self):
        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
        )
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        sub = self._scattered()  # 40 samples, 4 shards: width 10
        trainer = self._trainer(sub)
        saved = []

        class _Ckpt:
            def restore_trainer(self, t):
                return None

            def __call__(self, t):
                saved.append(t.iteration)

        trainer.extend(_Ckpt())
        for _ in range(3):  # advance the cursor to pos=6
            next(trainer.updater.iterator)
        rep = _StubReport(comm=_AgreeComm(4))
        ext = AdaptiveExecution(AdaptPolicy(), comm=_AgreeComm(4),
                                report=rep)
        trainer.extend(ext)
        ext.initialize(trainer)
        rep.window(iteration=5, stragglers=[2])
        slog = ResilienceLog()
        attach(slog)
        try:
            ext(trainer)
        finally:
            detach(slog)
        new_ds = trainer.updater.iterator.dataset
        assert new_ds is not sub
        assert new_ds.scatter_spec["weights"][2] == 0.5
        # width grew 10→12 (the skewed map pads every shard to the
        # widest) and the cursor remapped proportionally (6·12//10),
        # computed identically on every rank
        assert len(new_ds) == 12
        assert trainer.updater.iterator._pos == 7
        decisions = slog.events("adapt_decision")
        assert [e.info["action"] for e in decisions] == ["rebalance"]
        acts = slog.events("adapt_action")
        assert acts[0].info["applied"] is True
        assert slog.events("adaptive_iterator_remap")
        # the rebalance RE-COMMITTED the current step: the higher-
        # priority checkpointer saved before the shard map changed, so
        # without this re-save an auto-resume would restore the old
        # width's cursor against the new dataset (review regression)
        assert saved == [trainer.iteration]
        # the same window is never re-decided
        ext(trainer)
        assert len(slog.events("adapt_decision")) == 1

    def test_demotion_raises_collectively_with_peer_and_snapshot(self):
        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
        )
        from chainermn_tpu.resilience.errors import (
            DemotionRequiredError,
        )
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        trainer = self._trainer(list(range(8)))
        saved = []

        class _Ckpt:  # checkpointer double: record the forced save
            def restore_trainer(self, t):
                return None

            def __call__(self, t):
                saved.append(t.iteration)

        trainer.extend(_Ckpt())
        trainer.iteration = 9
        rep = _StubReport()
        ext = AdaptiveExecution(
            AdaptPolicy(demote_after=1, actions=("demote",)),
            comm=_AgreeComm(4), report=rep,
        )
        trainer.extend(ext)
        ext.initialize(trainer)
        rep.window(iteration=9, stragglers=[3])
        slog = ResilienceLog()
        attach(slog)
        try:
            with pytest.raises(DemotionRequiredError) as ei:
                ext(trainer)
        finally:
            detach(slog)
        assert ei.value.peer == 3
        assert ei.value.recoverable is False
        assert saved == [9]  # snapshot committed before the raise
        act = slog.events("adapt_action", "adaptive.demote")[0]
        assert act.info["checkpoint_step"] == 9

    def test_promote_commits_snapshot_and_raises_collectively(
        self, tmp_path
    ):
        """The scale-up half of the tentpole, unit shape: a candidate
        clears two probe windows, the agreed promote decision commits a
        snapshot at the decision iteration, emits the promote
        decision/action events, and raises PromotionRequiredError on
        the (mocked) world together."""
        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
            CapacityWatcher,
            publish_presence,
        )
        from chainermn_tpu.resilience.errors import (
            PromotionRequiredError,
        )
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        trainer = self._trainer(list(range(8)))
        saved = []

        class _Ckpt:
            def restore_trainer(self, t):
                return None

            def __call__(self, t):
                saved.append(t.iteration)

        trainer.extend(_Ckpt())
        rep = _StubReport(means={p: 0.1 for p in range(4)})
        ext = AdaptiveExecution(
            AdaptPolicy(), comm=_AgreeComm(4), report=rep,
            watcher=CapacityWatcher(str(tmp_path),
                                    probation_windows=2),
        )
        trainer.extend(ext)
        ext.initialize(trainer)
        assert ext._hosts == ["h0", "h1", "h2", "h3"]
        # probe window 1: sighted, streak 1, no decision yet
        publish_presence(str(tmp_path), "c9", window=1,
                         step_mean_s=0.11)
        trainer.iteration = 5
        rep.window(iteration=5, stragglers=[])
        slog = ResilienceLog()
        attach(slog)
        try:
            ext(trainer)
            assert slog.counts.get("host_returned") == 1
            assert not slog.events("adapt_decision")
            # probe window 2: clears probation -> agreed promote
            publish_presence(str(tmp_path), "c9", window=2,
                             step_mean_s=0.12)
            trainer.iteration = 6
            rep.window(iteration=6, stragglers=[])
            with pytest.raises(PromotionRequiredError) as ei:
                ext(trainer)
        finally:
            detach(slog)
        assert ei.value.hosts == ("c9",)
        assert ei.value.new_world == 5
        assert ei.value.recoverable is False
        assert saved == [6]  # snapshot committed before the raise
        dec = slog.events("adapt_decision")[0]
        assert dec.info["action"] == "promote"
        assert dec.info["host"] == "c9"
        assert dec.info["new_world"] == 5
        act = slog.events("adapt_action", "adaptive.promote")[0]
        assert act.info["checkpoint_step"] == 6
        assert act.info["hosts"] == "c9"
        assert ext.policy.totals["promote"] == 1

    def test_policy_state_rides_trainer_state_dict(self):
        import json as _json

        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
        )

        trainer = self._trainer(list(range(8)))
        rep = _StubReport()
        ext = AdaptiveExecution(AdaptPolicy(), comm=_AgreeComm(4),
                                report=rep)
        trainer.extend(ext)
        ext.initialize(trainer)
        ext.policy.observe([1], world=4, iteration=3)
        state = trainer.state_dict()
        assert _json.loads(state["adaptive"])["streaks"] == {"1": 1}
        # round-trip through a fresh trainer restores the hysteresis
        t2 = self._trainer(list(range(8)))
        ext2 = AdaptiveExecution(AdaptPolicy(), comm=_AgreeComm(4),
                                 report=_StubReport())
        t2.extend(ext2)
        t2.load_state_dict(state)
        assert ext2.policy.streaks == {1: 1}
        assert ext2.policy.weights[1] == 0.5

    def test_missing_report_fails_loudly_at_initialize(self):
        from chainermn_tpu.resilience.adaptive import AdaptiveExecution

        trainer = self._trainer(list(range(8)))
        ext = AdaptiveExecution()
        trainer.extend(ext)
        with pytest.raises(ValueError, match="MetricsReport"):
            ext.initialize(trainer)

    def test_run_adapt_attaches_the_extension_once(self):
        from chainermn_tpu.observability import MetricsReport
        from chainermn_tpu.resilience.adaptive import AdaptPolicy

        trainer = self._trainer(list(range(8)))
        trainer.stop_n, trainer.stop_unit = 0, "iteration"
        trainer.extend(MetricsReport(None, filename=None))
        with pytest.raises(TypeError, match="AdaptPolicy"):
            trainer.run(adapt=object())
        policy = AdaptPolicy(demote_after=7)
        trainer.run(adapt=policy)  # 0-iteration run: dispatch only
        ext = trainer._find_adaptive()
        assert ext is not None and ext.policy is policy
        n = len(trainer._extensions)
        trainer.run(adapt=policy)  # already attached: no duplicate
        assert len(trainer._extensions) == n

    def test_malformed_checkpointed_policy_state_degrades_gracefully(
        self,
    ):
        from chainermn_tpu.resilience.adaptive import (
            AdaptiveExecution,
            AdaptPolicy,
        )

        trainer = self._trainer(list(range(8)))
        ext = AdaptiveExecution(AdaptPolicy(), comm=_AgreeComm(4),
                                report=_StubReport())
        trainer.extend(ext)
        # a resharder-mangled leaf that is valid JSON but not an
        # object must warn and start fresh, never crash the restore
        with pytest.warns(UserWarning, match="hysteresis starts fresh"):
            trainer.load_state_dict(
                {"iteration": 3, "iterator": None, "adaptive": "[1, 2]"}
            )
        assert trainer.iteration == 3
        assert ext.policy.streaks == {}


class TestMetricsWarmupWindow:
    """Satellite (ISSUE 15): the post-resume warmup-window skip — the
    compile-dominated first window after a reshard is excluded from
    conviction BY CONTRACT (``warmup_windows=1``), not by leaning on
    the materiality floor."""

    def _trainer(self, resumed):
        from chainermn_tpu.resilience.log import ResilienceLog

        class _T:
            iteration = 4
            observation = {}
            resilience_log = ResilienceLog()

        t = _T()
        if resumed:
            t.resilience_log.record(
                "elastic_restart", "trainer.run_elastic",
                restored_step=3, world=15,
            )
        return t

    def _report(self, trainer, n=16, straggler=5, **kw):
        """A report over a scripted N-process world, with a live
        telemetry installed for its lifetime (uninstalled by its own
        finalize)."""
        from chainermn_tpu.observability import MetricsReport

        rep = MetricsReport(_ScriptedSummaryComm(n, straggler),
                            filename=None, **kw)
        rep.initialize(trainer)
        assert rep._own_telemetry is not None  # it owns the install
        return rep

    def test_first_post_resume_window_skipped_second_convicts(self):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        trainer = self._trainer(resumed=True)
        rep = self._report(trainer)
        slog = ResilienceLog()
        attach(slog)
        try:
            rep(trainer)
            # the scripted world WOULD convict (the straggler's phase
            # is far past factor and floor) — the warmup contract
            # skips it anyway
            assert rep.straggler_processes == []
            assert slog.counts.get("straggler_warmup_skip") == 1
            assert not slog.events("straggler")
            trainer.iteration = 5
            rep(trainer)
            assert rep.straggler_processes == [5]
            assert slog.events("straggler")
        finally:
            detach(slog)
            rep.finalize()

    def test_fresh_run_skips_nothing(self):
        trainer = self._trainer(resumed=False)
        rep = self._report(trainer)
        try:
            rep(trainer)
            assert rep.straggler_processes == [5]
        finally:
            rep.finalize()

    def test_midrun_auto_resume_rearms_the_skip(self):
        trainer = self._trainer(resumed=False)
        rep = self._report(trainer)
        try:
            rep(trainer)
            assert rep.straggler_processes == [5]
            # an auto-resume lands on the log mid-run: the next window
            # skips, the one after convicts again
            trainer.resilience_log.record(
                "restart", "obj_store.exchange",
                restored_step=2, restarts=1,
            )
            trainer.iteration = 5
            rep(trainer)
            assert rep.straggler_processes == []
            trainer.iteration = 6
            rep(trainer)
            assert rep.straggler_processes == [5]
        finally:
            rep.finalize()

    def test_warmup_zero_opts_out(self):
        trainer = self._trainer(resumed=True)
        rep = self._report(trainer, warmup_windows=0)
        try:
            rep(trainer)
            assert rep.straggler_processes == [5]
        finally:
            rep.finalize()


class _ScriptedSummaryComm:
    """An obj store whose allgather returns a full scripted world of
    phase summaries (this rank's live payload replaced by script):
    drives MetricsReport.__call__ through conviction without
    processes."""

    def __init__(self, n, straggler):
        self.process_count = self.size = n
        self.process_index = 0
        self._n, self._straggler = n, straggler

    def allgather_obj(self, local):
        return list(_phase_data(self._n, {self._straggler}).values())


class TestChainReshardBitIdentity:
    """Satellite/tentpole contract: the 16→12→14→8 ZeRO block-reshard
    CHAIN is bit-identical to a fresh partition of the global state at
    every leg — composition introduces no drift."""

    @staticmethod
    def _fresh(flat, world):
        k = -(-flat.size // world)  # ceil
        out = np.zeros(world * k, flat.dtype)
        out[: flat.size] = flat
        return out.reshape(world, k)

    def test_chain_16_12_14_8_bit_identical_at_every_leg(self):
        from chainermn_tpu.resilience.elastic import reshard_blocked_leaf

        rng = np.random.RandomState(0)
        flat = rng.randn(1003).astype(np.float32)  # indivisible on purpose
        state = self._fresh(flat, 16)
        for world in (12, 14, 8):
            want = self._fresh(flat, world)
            state = reshard_blocked_leaf(state, want.shape)
            np.testing.assert_array_equal(state, want)

    def test_momentum_oracle_matches_closed_form_sgd(self):
        # mom=0 collapses to plain sgd's closed form — the oracle's own
        # sanity pin
        traj = momentum_oracle(5, lr=0.1, mom=0.0, c=0.5, dim=3)
        for k, w in enumerate(traj, start=1):
            np.testing.assert_allclose(
                w, 0.5 * (1 - 0.9 ** k) * np.ones(3), rtol=1e-12
            )


# ----------------------------------------------------------------------
class TestFleetReportMerge:
    def _write_events(self, path, rows):
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    def _ev(self, kind, t, process=0, site="s", **info):
        return {"kind": kind, "site": site, "process": process,
                "time": t, "monotonic": t, "info": info}

    def test_merge_orders_across_legs_and_processes(self, tmp_path):
        self._write_events(tmp_path / "leg0_p1_events.jsonl", [
            self._ev("fault_injected", 10.0, process=1, fault="die"),
        ])
        self._write_events(tmp_path / "leg1_p0_events.jsonl", [
            self._ev("world_reformed", 20.0),
            self._ev("elastic_reshard", 21.0),
        ])
        self._write_events(tmp_path / "leg1_p0_trainer_events.jsonl", [
            self._ev("elastic_reshard", 21.0),  # duplicate: deduped
            self._ev("elastic_restart", 22.0),
        ])
        rep = FleetReport.from_scratch(tmp_path)
        assert rep.counts == {
            "fault_injected": 1, "world_reformed": 1,
            "elastic_reshard": 1, "elastic_restart": 1,
        }
        rep.assert_order("fault_injected", "world_reformed",
                         "elastic_reshard", "elastic_restart")
        assert rep.processes == {"leg0": [1], "leg1": [0]}

    def test_order_violation_raises_with_post_mortem(self, tmp_path):
        self._write_events(tmp_path / "leg0_p0_events.jsonl", [
            self._ev("world_reformed", 5.0),
            self._ev("fault_injected", 9.0),
        ])
        rep = FleetReport.from_scratch(tmp_path)
        with pytest.raises(AssertionError, match="does not precede"):
            rep.assert_order("fault_injected", "world_reformed")
        with pytest.raises(AssertionError, match="no 'retry' event"):
            rep.assert_order("retry")

    def test_trace_spans_anchor_on_wall0_and_torn_tail_skipped(
        self, tmp_path
    ):
        with open(tmp_path / "leg0_p0_trace.jsonl", "w") as f:
            f.write(json.dumps({
                "type": "meta", "name": "timeline.meta", "t": 0.0,
                "process": 0, "tid": 0, "args": {"wall0": 100.0},
            }) + "\n")
            f.write(json.dumps({
                "type": "span", "name": "step", "t": 2.5, "dur": 0.1,
                "process": 0, "tid": 0, "args": {},
            }) + "\n")
            f.write('{"type": "span", "name": "torn')  # killed mid-write
        self._write_events(tmp_path / "leg0_p0_events.jsonl", [
            self._ev("fault_injected", 101.0),
        ])
        rep = FleetReport.from_scratch(tmp_path)
        spans = rep.events("span:step")
        assert len(spans) == 1 and spans[0]["wall"] == 102.5
        # the span slots in between on the shared wall clock
        rep.assert_order("fault_injected", "span:step")

    def test_timeline_meta_row_export(self, tmp_path):
        from chainermn_tpu.observability.timeline import Timeline

        tl = Timeline(label="x")
        with tl.span("work"):
            pass
        path = tl.to_jsonl(str(tmp_path / "t.jsonl"), meta=True)
        rows = [json.loads(l) for l in open(path)]
        assert rows[0]["type"] == "meta"
        assert rows[0]["args"]["wall0"] == tl.wall0
        assert [r["name"] for r in rows[1:]] == ["work"]
        # default export unchanged: no meta row
        path2 = tl.to_jsonl(str(tmp_path / "t2.jsonl"))
        rows2 = [json.loads(l) for l in open(path2)]
        assert all(r["type"] != "meta" for r in rows2)


class TestStreamingSink:
    def test_events_flushed_per_emit(self, tmp_path):
        from chainermn_tpu.resilience.log import (
            JsonlFileSink, attach, detach, emit,
        )

        sink = JsonlFileSink(str(tmp_path / "ev.jsonl"))
        attach(sink)
        try:
            emit("fault_injected", "site.a", fault="die", call=3)
            # on disk BEFORE any close/flush call — the os._exit case
            rows = [json.loads(l) for l in open(tmp_path / "ev.jsonl")]
        finally:
            detach(sink)
            sink.close()
        assert len(rows) == 1
        assert rows[0]["kind"] == "fault_injected"
        assert rows[0]["info"] == {"fault": "die", "call": 3}
        assert "monotonic" in rows[0] and "time" in rows[0]
        # the sink is still a queryable ResilienceLog
        assert sink.counts == {"fault_injected": 1}


# ----------------------------------------------------------------------
# process-spawning tier-1 pieces: the budget teardown and the 8-proc
# smoke of the full machinery (the 16+-rank worlds are `slow`)
# ----------------------------------------------------------------------
# hard wall-clock budget for the tier-1 smoke, documented in
# tests/README.md — the budget is a deadlock detector on a timeshared
# host, not a perf assertion
SMOKE_BUDGET_S = 240


@pytest.mark.multiprocess
class TestFleetWorldBudget:
    def test_overrun_tears_down_loudly(self, tmp_path):
        # the sleep scenario wedges unconditionally, so ANY budget
        # catches it — a small one keeps this tier-1 test cheap
        w = FleetWorld(1, tmp_path, budget_s=5, label="wedge")
        with pytest.raises(FleetBudgetError) as ei:
            w.launch("sleep", {"sleep_s": 3600})
        msg = str(ei.value)
        assert "exceeded its 5s wall-clock budget" in msg
        assert "process 0" in msg  # the tail is quoted


@pytest.mark.multiprocess
class TestFleetSmoke8:
    def test_wave_plus_reshard_8_to_6_on_oracle(self, tmp_path):
        """The tier-1 smoke of the full fleet machinery (ISSUE 14
        acceptance, 8-process shape): a torn rendezvous payload
        (lockstep-retried), a preemption wave killing processes 6 and 7
        at step 3, and one elasticity-chain leg resuming at world 6
        through the checkpoint resharder onto the single-world numpy
        oracle — with the merged FleetReport asserting the
        fault→retry→reform→reshard→resume event order.

        Also the regression test for the wide-world defect this
        scenario surfaced at 16 processes (and 2-process worlds never
        lost): the coordination service's peer-death propagation
        hard-aborts the wave's SURVIVORS, racing their epilogue.  The
        fix is epilogue-before-wave (worker.scenario_chain_leg) +
        REAPED acceptance (world.assert_ok) — every survivor's RESULT
        payload and streamed artifacts must exist despite any reap,
        and the resume leg must still find all of leg0's snapshots.
        (Over 40 s in the driver's run: eight processes start,
        rendezvous and train twice over; it is the one tier-1 hold on
        this chain end to end, ``tests/README.md``.)"""
        chain = ElasticityChain(str(tmp_path), [
            ChainLeg(n_procs=8, n_steps=3, wave_at=3,
                     wave_processes=(6, 7), torn_calls=(1,)),
            ChainLeg(n_procs=6, n_steps=5),
        ], budget_s=SMOKE_BUDGET_S)
        out = chain.run()
        legs = out["legs"]
        # every leg-0 process published its payload BEFORE the wave —
        # victims included (their RESULT precedes their die)
        assert sorted(legs[0]) == list(range(8))
        assert all(p["steps_saved"] == 2 for p in legs[0].values())
        assert sorted(legs[1]) == [0, 1, 2, 3, 4, 5]
        for p in legs[1].values():
            assert p["oracle_match"] is True
            assert p["resumed_step"] == 2
            assert p["resized"] == [8, 6]
            assert p["iteration"] == 5
        rep = out["report"]
        rep.assert_order("fault_injected", "retry", "world_reformed",
                         "elastic_reshard", "elastic_restart")
        # the wave's victims left their die records via the streaming
        # sink despite os._exit
        dies = [e for e in rep.events("fault_injected")
                if e["info"].get("fault") == "die"]
        assert sorted(e["process"] for e in dies) == [6, 7]
        assert all(e["leg"] == "leg0" for e in dies)
        # every leg-1 process re-agreed and resumed
        restarts = rep.events("elastic_restart")
        assert sorted(e["process"] for e in restarts) == [0, 1, 2, 3, 4, 5]


@pytest.mark.multiprocess
class TestAdaptiveSmoke8:
    def test_migrating_straggler_rebalance_then_demote_8_to_7(
        self, tmp_path
    ):
        """The self-healing-runtime tier-1 smoke (ISSUE 15 acceptance,
        8-process shape): a straggler migrates 3→5 across report
        windows; the policy REBALANCES each conviction (weighted
        re-scatter agreed through the lockstep exchange, live iterator
        cursor remapped) and, when rank 5's streak outlives the
        hysteresis window, DEMOTES it — a snapshot committed at the
        decision iteration, ``DemotionRequiredError`` on every rank
        together.  The 7-process resume leg reshards onto the numpy
        sgd+momentum oracle from exactly that step, and the merged
        report asserts detect→decide→act→recover end to end.  (Over
        40 s in the driver's run: eight processes, then seven, start,
        rendezvous and train; the one tier-1 hold on this chain end to
        end, ``tests/README.md``.)"""
        sched = (FaultSchedule()
                 .straggler(3, window=(1, 2), delay=0.6)
                 .straggler(5, window=(3, 12), delay=0.6))
        world = FleetWorld(8, str(tmp_path), schedule=sched,
                           budget_s=SMOKE_BUDGET_S, label="leg0")
        from chainermn_tpu.fleet import REAPED

        res = world.launch(
            "adaptive_leg",
            {"n_steps": 12, "demote_after": 3, "linger_s": 1.5},
            expect_exit={p: REAPED for p in range(8)},
        )
        p1 = res.payloads()
        assert sorted(p1) == list(range(8))
        d = p1[0]["iteration"]
        for p in p1.values():
            assert p["demoted"] == 5  # the MIGRATED-to rank, never 3
            assert p["iteration"] == d
            assert p["oracle_match"] is True
            assert p["n_rebalances"] >= 1
            assert p["rebalance_applied"] is True
            # no rank that carried no delay is ever named; whether
            # rank 3's two delayed steps are sighted at all is the
            # host's clock (five other workers load it), not the code's
            assert 5 in p["stragglers"]
            assert set(p["stragglers"]) <= {3, 5}
        # resume leg: 8→7 through the checkpoint resharder, from
        # exactly the demotion's snapshot — no step lost
        res2 = FleetWorld(7, str(tmp_path), budget_s=SMOKE_BUDGET_S,
                          label="leg1").launch(
            "chain_leg",
            {"n_steps": d + 3, "wave_at": None, "lr": 0.1, "mom": 0.9,
             "dim": 4, "straggler": False, "report_every": 1},
            expect_exit={},
        )
        for p in res2.payloads().values():
            assert p["resumed_step"] == d
            assert p["resized"] == [8, 7]
            assert p["oracle_match"] is True
            assert p["iteration"] == d + 3
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order(
            "fault_injected", "straggler", "adapt_decision",
            "world_reformed", "elastic_reshard", "elastic_restart",
        )
        decisions = rep.events("adapt_decision")
        reb = [e for e in decisions
               if e["info"]["action"] == "rebalance"]
        dem = [e for e in decisions if e["info"]["action"] == "demote"]
        assert reb and dem
        # escalation order: data was rebalanced before anyone was shed
        assert min(e["wall"] for e in reb) < min(
            e["wall"] for e in dem
        )
        assert {e["info"]["process"] for e in dem} == {5}
        # the committed demote snapshot is the step the world resumed
        acts = [e for e in rep.events("adapt_action")
                if e["info"]["action"] == "demote"]
        assert {e["info"]["checkpoint_step"] for e in acts} == {d}


@pytest.mark.multiprocess
class TestGrowSmoke8:
    def test_probation_promote_7_to_8_on_oracle(self, tmp_path):
        """The scale-UP tier-1 smoke (ISSUE 16 acceptance, 8-process
        shape): a 7-process training world runs with the capacity
        watcher while a CONCURRENT 1-process probe world publishes
        presence manifests for host h7 into the shared scratch.  The
        watcher holds h7 under probation for 2 clean windows, the
        agreed decision commits a snapshot and raises
        ``PromotionRequiredError`` on every rank together, rank 0 posts
        h7's admission marker, and the 8-process resume leg reshards
        onto the numpy sgd+momentum oracle from exactly the decision
        step — the candidate's first participation in the world.  The
        merged report pins the full promote chain: host_returned →
        probation_pass → adapt_decision → adapt_action →
        world_reformed → elastic_reshard → elastic_restart.  (Over 40 s in the driver's run:
        eight processes start, rendezvous and train twice over; it is
        the one tier-1 hold on this chain end to end,
        ``tests/README.md``.)"""
        from chainermn_tpu.fleet import REAPED

        # a world-wide pace floor: probe/world step-mean RATIOS stay
        # noise-robust on a timeshared host (the probe is never slower
        # than 1.5x the world's 0.2s median)
        pace = FaultSchedule().pace(window=(1, 300), delay=0.2)
        grow = FleetWorld(7, str(tmp_path), schedule=pace,
                          budget_s=SMOKE_BUDGET_S, label="leg0").start(
            "grow_leg",
            {"n_steps": 300, "probation_windows": 2,
             "promote_quorum": 1, "report_every": 1, "linger_s": 1.5},
        )
        probe = FleetWorld(1, str(tmp_path), budget_s=SMOKE_BUDGET_S,
                           label="probe0").start(
            "probe_host",
            {"host": "h7", "world": 7, "steps_per_window": 3,
             "window_sleep_s": 0.25, "max_windows": 400},
        )
        # the promotion exits every rank together — REAPED, like the
        # demote leg
        res = grow.wait(expect_exit={p: REAPED for p in range(7)})
        pg = res.payloads()
        assert sorted(pg) == list(range(7))
        d = pg[0]["iteration"]
        for p in pg.values():
            assert p["promote"] == {"hosts": ["h7"], "new_world": 8}
            assert p["iteration"] == d
            assert p["oracle_match"] is True
            assert p["resumed_step"] is None  # fresh leg, not a resume
        pp = probe.wait(expect_exit={}).payloads()[0]
        assert pp["promoted"] is True
        assert pp["admission"]["new_world"] == 8
        assert pp["admission"]["checkpoint_step"] == d
        assert pp["windows"] >= 2  # probation took real probe windows
        # resume leg: 7→8 through the checkpoint resharder from exactly
        # the decision snapshot — no step lost across the growth
        res2 = FleetWorld(8, str(tmp_path), budget_s=SMOKE_BUDGET_S,
                          label="leg1").launch(
            "chain_leg",
            {"n_steps": d + 3, "wave_at": None, "lr": 0.1, "mom": 0.9,
             "dim": 4, "straggler": False, "report_every": 1},
            expect_exit={},
        )
        for p in res2.payloads().values():
            assert p["resumed_step"] == d
            assert p["resized"] == [7, 8]
            assert p["oracle_match"] is True
            assert p["iteration"] == d + 3
        rep = FleetReport.from_scratch(str(tmp_path))
        rep.assert_order(
            "host_returned", "probation_pass", "adapt_decision",
            "adapt_action", "world_reformed", "elastic_reshard",
            "elastic_restart",
        )
        promos = [e for e in rep.events("adapt_decision")
                  if e["info"].get("action") == "promote"]
        assert promos
        assert {e["info"]["host"] for e in promos} == {"h7"}
        assert {e["info"]["new_world"] for e in promos} == {8}
        # the committed promote snapshot is the step the world resumed
        acts = [e for e in rep.events("adapt_action")
                if e["info"].get("action") == "promote"]
        assert {e["info"]["checkpoint_step"] for e in acts} == {d}
