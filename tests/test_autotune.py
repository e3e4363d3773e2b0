"""Measured-feedback autotuner (ISSUE 12).

The tentpole pins, in order of load-bearingness:

* with ``profile=None`` every planned ``WirePlan`` — layout, schedules,
  and ``plan_hash()`` BYTES — is identical to the pre-autotuner layer
  (the hash regression test reimplements the pre-PR hash formula
  inline, so a profile-less plan can never silently grow new material);
* ``profile_hash()`` is a content hash: JSON key order and float
  formatting cannot move it, the mesh signature and every curve point
  can, and the free-text label cannot — which is what makes it safe to
  stand in for the whole tuning configuration in ``plan_agreement``;
* the interpolated bandwidth is exact at curve points, bounded between
  its endpoints inside a bin, and clamped outside the measured grid;
* tuning only ever REDUCES collective counts (candidate slot budgets
  stay under ``max_buckets``), so every ``analysis.budgets`` ceiling
  that held for the constants holds for any tuned plan;
* ``profile_from_attribution`` on the PR 9 ResNet acceptance fixture
  (eval-shape trace + eager 2-device measured wire) yields a usable
  all_reduce curve that prices every record of the trace;
* a rank that cannot load its named profile raises
  ``ProfileMissingError`` at optimizer construction — before any
  collective or exchange.
"""

import json
import math
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu import comm_wire as cw
from chainermn_tpu import observability as obs
from chainermn_tpu.analysis import CollectiveRecord, enforce
from chainermn_tpu.comm_wire.autotune import (
    BandwidthProfile,
    ProfileMissingError,
    calibrate,
    predict_collective,
    predict_cost,
    profile_from_attribution,
    resolve_profile,
)
from chainermn_tpu.communicators import _topology


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


@pytest.fixture(scope="module")
def hier_comm(devices8):
    """(2, 4) hierarchical mesh: 2 synthetic slices of 4 (the
    test_topology.py recipe)."""
    orig = _topology._node_key
    _topology._node_key = lambda d: ("slice", d.id // 4)
    try:
        comm = cmn.create_communicator("hierarchical", devices=devices8)
    finally:
        _topology._node_key = orig
    assert dict(comm.mesh.shape) == {"mn_inter": 2, "mn_intra": 4}
    return comm


MESH24 = {"mn_inter": 2, "mn_intra": 4}


def _profile(inter_bw=1e8, intra_bw=1e10, mixed_bw=2e8,
             lat=1e-5, label="test"):
    """Hand-built profile over the (2, 4) mesh: slow inter links, fast
    intra, with curves for every class the schedules issue."""
    pts = lambda bw: [(1024, bw), (1 << 22, bw)]  # noqa: E731
    return BandwidthProfile(
        mesh_axes=(("mn_inter", 2), ("mn_intra", 4)),
        curves={
            ("inter", "all_reduce"): pts(inter_bw),
            ("intra", "all_reduce"): pts(intra_bw),
            ("intra", "reduce_scatter"): pts(intra_bw),
            ("intra", "all_gather"): pts(intra_bw),
            ("mixed", "all_reduce"): pts(mixed_bw),
        },
        latency={"inter": lat, "intra": lat, "mixed": lat},
        label=label,
    )


# ----------------------------------------------------------------------
# the artifact: round-trip, hash stability, validation
# ----------------------------------------------------------------------
class TestProfileArtifact:
    def test_round_trip_preserves_hash_and_content(self, tmp_path):
        prof = _profile()
        p = str(tmp_path / "prof.json")
        prof.save(p)
        again = BandwidthProfile.load(p)
        assert again.profile_hash() == prof.profile_hash()
        assert again.curves == prof.curves
        assert again.latency == prof.latency
        assert again.mesh_axes == prof.mesh_axes

    def test_hash_invariant_to_json_key_order(self, tmp_path):
        """The hash is computed over PARSED content: shuffling the JSON
        file's key order (and re-dumping without sort_keys) cannot move
        it."""
        prof = _profile()
        p = str(tmp_path / "prof.json")
        prof.save(p)
        with open(p) as f:
            obj = json.load(f)
        shuffled = dict(reversed(list(obj.items())))
        shuffled["curves"] = dict(
            reversed(list(shuffled["curves"].items()))
        )
        p2 = str(tmp_path / "shuffled.json")
        with open(p2, "w") as f:
            json.dump(shuffled, f)  # no sort_keys, different order
        assert (
            BandwidthProfile.load(p2).profile_hash()
            == prof.profile_hash()
        )

    def test_hash_invariant_to_float_repr(self, tmp_path):
        """"2e9", "2.0e9" and "2000000000.0" parse to the same float
        and must hash the same — canonicalization happens on values,
        not text."""
        base = {
            "mesh_axes": [["mn", 8]],
            "curves": {"flat/all_reduce": [[1024, 2e9]]},
            "latency_s": {"flat": 0.0001},
        }
        hashes = set()
        for i, text in enumerate(("2e9", "2.0e9", "2000000000.0")):
            p = str(tmp_path / f"f{i}.json")
            with open(p, "w") as f:
                f.write(json.dumps(base).replace("2000000000.0", text))
            hashes.add(BandwidthProfile.load(p).profile_hash())
        assert len(hashes) == 1

    def test_hash_covers_curves_mesh_and_latency_not_label(self):
        prof = _profile()
        assert _profile(label="other").profile_hash() \
            == prof.profile_hash()
        assert _profile(inter_bw=2e8).profile_hash() \
            != prof.profile_hash()
        assert _profile(lat=2e-5).profile_hash() != prof.profile_hash()
        moved = BandwidthProfile(
            mesh_axes=(("mn_inter", 4), ("mn_intra", 2)),
            curves=prof.curves, latency=prof.latency,
        )
        assert moved.profile_hash() != prof.profile_hash()

    def test_edited_file_fails_embedded_hash_check(self, tmp_path):
        """A profile edited after capture (content no longer matching
        its embedded hash) must refuse to load — a hand-tweaked curve
        masquerading as a capture is exactly the silent config drift
        the provenance chain exists to catch."""
        p = str(tmp_path / "prof.json")
        _profile().save(p)
        with open(p) as f:
            obj = json.load(f)
        obj["curves"]["inter/all_reduce"][0][1] *= 2
        with open(p, "w") as f:
            json.dump(obj, f)
        with pytest.raises(ValueError, match="profile_hash"):
            BandwidthProfile.load(p)

    def test_non_profile_json_rejected(self, tmp_path):
        p = str(tmp_path / "not_a_profile.json")
        with open(p, "w") as f:
            json.dump({"metric": "step_time_ms", "value": 1.0}, f)
        with pytest.raises(ValueError, match="curves"):
            BandwidthProfile.load(p)

    def test_mesh_signature_is_canonical_across_constructors(self,
                                                             comm):
        """Every construction path — calibration-style mesh order,
        scrape-style sorted order, hand-built any order — lands on ONE
        canonical (sorted) signature, so equivalent profiles of the
        same mesh hash alike and the bench's pinned-profile
        ``matches_mesh`` check cannot be defeated by axis order."""
        curves = {("intra", "all_reduce"): ((1024, 1e9),)}
        a = BandwidthProfile(
            mesh_axes=(("mn_intra", 4), ("mn_inter", 2)), curves=curves
        )
        b = BandwidthProfile(
            mesh_axes=(("mn_inter", 2), ("mn_intra", 4)), curves=curves
        )
        assert a.mesh_axes == b.mesh_axes
        assert a.profile_hash() == b.profile_hash()
        assert a.matches_mesh({"mn_intra": 4, "mn_inter": 2})
        assert not a.matches_mesh({"mn_inter": 4, "mn_intra": 2})
        flat = BandwidthProfile(
            mesh_axes=BandwidthProfile.mesh_signature(comm.mesh),
            curves=curves,
        )
        assert flat.matches_mesh(comm.mesh)

    def test_malformed_curve_key_named_in_error(self, tmp_path):
        """A curves key without the '<hop>/<class>' shape fails with a
        message naming the key — not a bare unpack traceback."""
        p = str(tmp_path / "bad_key.json")
        with open(p, "w") as f:
            json.dump({"curves": {"inter": [[1024, 1e9]]}}, f)
        with pytest.raises(ValueError, match="inter"):
            BandwidthProfile.load(p)


class TestResolveProfile:
    def test_none_and_instance_pass_through(self):
        assert resolve_profile(None) is None
        prof = _profile()
        assert resolve_profile(prof) is prof

    def test_missing_file_raises(self, tmp_path):
        with pytest.raises(ProfileMissingError):
            resolve_profile(str(tmp_path / "nope.json"))

    def test_auto_without_env_raises(self, monkeypatch):
        monkeypatch.delenv(cw.PROFILE_ENV, raising=False)
        with pytest.raises(ProfileMissingError, match=cw.PROFILE_ENV):
            resolve_profile("auto")

    def test_auto_loads_env_path(self, tmp_path, monkeypatch):
        p = str(tmp_path / "prof.json")
        _profile().save(p)
        monkeypatch.setenv(cw.PROFILE_ENV, p)
        assert resolve_profile("auto").profile_hash() \
            == _profile().profile_hash()

    def test_factory_raises_before_any_collective(self, comm,
                                                  monkeypatch):
        """The production contract: a rank missing its profile file
        fails at optimizer CONSTRUCTION — no plan, no exchange, no
        collective has happened yet."""
        monkeypatch.setenv(cw.PROFILE_ENV, "/nonexistent/profile.json")
        with pytest.raises(ProfileMissingError):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, profile="auto"
            )

    def test_factory_rejects_garbage(self, comm):
        with pytest.raises(ValueError, match="profile"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, profile=42
            )

    def test_wrong_topology_profile_rejected_at_construction(self,
                                                             comm):
        """The documented guarantee, enforced in production: a profile
        captured on another mesh signature is rejected when the
        optimizer is built — every rank loading the same stale capture
        would pass plan agreement (identical hashes) while pricing
        this mesh through foreign curves."""
        with pytest.raises(ValueError, match="mesh"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, profile=_profile()  # (2,4) mesh
            )

    def test_profile_with_per_leaf_wire_rejected(self, comm):
        """The legacy per-leaf path has no plan the profile could tune
        and no plan hash to disclose it through — silently ignoring
        the profile would be untracked analytic behavior the user
        believes is measured-tuned."""
        with pytest.raises(ValueError, match="per.leaf"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, wire="per_leaf",
                profile=_profile(),
            )


# ----------------------------------------------------------------------
# interpolation
# ----------------------------------------------------------------------
class TestInterpolation:
    CURVE = ((1024, 1e8), (65536, 4e8), (1 << 22, 2e9))

    def _prof(self):
        return BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): self.CURVE},
        )

    def test_exact_at_bin_edges(self):
        prof = self._prof()
        for p, bw in self.CURVE:
            assert prof.bandwidth("flat", "all_reduce", p) \
                == pytest.approx(bw)

    def test_bounded_and_monotone_between_edges(self):
        """Between two curve points the interpolant stays within the
        endpoint bandwidths, and is monotone in payload whenever the
        endpoints are ordered (no overshoot from the log-space
        mapping)."""
        prof = self._prof()
        for (p0, b0), (p1, b1) in zip(self.CURVE, self.CURVE[1:]):
            lo, hi = min(b0, b1), max(b0, b1)
            grid = np.geomspace(p0, p1, 17)
            vals = [
                prof.bandwidth("flat", "all_reduce", int(p))
                for p in grid
            ]
            for v in vals:
                assert lo - 1e-6 <= v <= hi + 1e-6
            assert all(a <= b + 1e-6 for a, b in zip(vals, vals[1:]))

    def test_duplicate_payloads_deduped_keeping_best(self):
        """Two calibration sizes can pad to ONE payload; duplicates
        must resolve to the best bandwidth everywhere (clamp and
        interior alike) — noise only subtracts bandwidth."""
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e8), (1024, 2e8),
                                             (4096, 4e8))},
        )
        assert prof.curves[("flat", "all_reduce")] == ((1024, 2e8),
                                                       (4096, 4e8))
        assert prof.bandwidth("flat", "all_reduce", 1024) \
            == pytest.approx(2e8)
        assert prof.bandwidth("flat", "all_reduce", 512) \
            == pytest.approx(2e8)  # clamp sees the deduped point too

    def test_clamped_outside_grid(self):
        prof = self._prof()
        assert prof.bandwidth("flat", "all_reduce", 1) \
            == pytest.approx(self.CURVE[0][1])
        assert prof.bandwidth("flat", "all_reduce", 1 << 30) \
            == pytest.approx(self.CURVE[-1][1])

    def test_fallback_chain_is_deterministic(self):
        """An unmeasured (hop, cls) resolves through the documented
        chain — same hop's all_reduce first — and a fully unknown pair
        returns None rather than inventing bandwidth."""
        prof = self._prof()
        assert prof.curve_for("flat", "reduce_scatter") == self.CURVE
        empty = BandwidthProfile(mesh_axes=(), curves={})
        assert empty.bandwidth("flat", "all_reduce", 1024) is None

    def test_launch_latency_fallbacks(self):
        prof = BandwidthProfile(
            mesh_axes=(), curves={("intra", "all_reduce"): ((8, 1.0),)},
            latency={"intra": 1e-6, "inter": 1e-4},
        )
        assert prof.launch_latency("intra") == 1e-6
        # unknown hop: the WORST measured latency (never assumed cheap)
        assert prof.launch_latency("mixed") == 1e-4
        bare = BandwidthProfile(mesh_axes=(), curves={})
        assert bare.launch_latency("flat") \
            == cw.autotune.DEFAULT_LAUNCH_LATENCY_S


# ----------------------------------------------------------------------
# the measured cost model
# ----------------------------------------------------------------------
class TestPredictCost:
    def test_wire_over_bandwidth_floored_by_latency(self):
        """The curves are EFFECTIVE bandwidth (measured durations
        include the launch), so the prediction is wire/bw with the
        launch latency as a FLOOR — adding it would double-count: a
        bandwidth-bound payload prices to wire/bw exactly, a tiny one
        to the launch floor."""
        prof = _profile(inter_bw=1e8, lat=1e-4)
        payload = 1 << 20
        t = predict_collective(
            prof, "all_reduce", payload, ("mn_inter",), (2,)
        )
        wire = 2 * payload * (2 - 1) // 2
        assert t == pytest.approx(wire / 1e8)  # >> lat: bandwidth-bound
        tiny = predict_collective(
            prof, "all_reduce", 64, ("mn_inter",), (2,)
        )
        assert tiny == pytest.approx(1e-4)  # launch floor

    def test_calibrated_point_is_not_double_counted(self):
        """Re-predicting the exact point a calibration measured must
        return that measurement, not 2x it: bw = wire/dt and lat <= dt
        at the smallest size, so max(wire/bw, lat) == dt."""
        dt = 5e-4
        payload = 4096
        wire = 2 * payload * 7 // 8
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((payload, wire / dt),)},
            latency={"flat": dt},
        )
        t = predict_collective(prof, "all_reduce", payload, ("mn",), (8,))
        assert t == pytest.approx(dt)

    def test_unknown_world_unpriceable(self):
        prof = _profile()
        assert predict_collective(
            prof, "all_reduce", 1024, ("mn_inter",), (0,)
        ) is None

    def test_record_pricing_uses_its_wire_bytes(self):
        prof = _profile(mixed_bw=1e9, lat=0.0)
        rec = CollectiveRecord(
            primitive="psum", cls="all_reduce",
            axes=("mn_inter", "mn_intra"), dtypes=("float32",),
            shapes=((256,),), context=(), axis_sizes=(2, 4),
            payload_bytes=1024, bytes_on_wire=1792, hop="mixed",
        )
        t = predict_cost(rec, prof)
        assert t == pytest.approx(1792 / 1e9)
        assert predict_cost(rec, None) is None


# ----------------------------------------------------------------------
# tune_wire_for_trace: the bugfix + measured minimization
# ----------------------------------------------------------------------
def _rec(payload, axes=("mn",), sizes=(8,), cls="all_reduce",
         bytes_on_wire="ring", hop=None):
    from chainermn_tpu.analysis.trace import hop_class, wire_bytes

    world = int(np.prod(sizes)) if all(s > 0 for s in sizes) else None
    bow = (
        wire_bytes(cls, payload, world)
        if bytes_on_wire == "ring" else bytes_on_wire
    )
    return CollectiveRecord(
        primitive="psum", cls=cls, axes=tuple(axes),
        dtypes=("float32",), shapes=((payload // 4,),), context=(),
        axis_sizes=tuple(sizes), payload_bytes=payload,
        bytes_on_wire=bow, hop=hop or hop_class(axes),
    )


class TestTuneWireForTrace:
    def test_analytic_behavior_unchanged_without_profile(self):
        """profile=None keeps the PR 6 rules bit-for-bit: hop-scaled
        byte target, slot collapse when the total fits one bucket."""
        big = _rec(32 * 1024 * 1024)
        assert cw.tune_wire_for_trace([big]) == (
            2 * cw.DEFAULT_BUCKET_BYTES, cw.DEFAULT_MAX_BUCKETS
        )
        small = _rec(1024)
        assert cw.tune_wire_for_trace([small]) == (
            2 * cw.DEFAULT_BUCKET_BYTES, 1
        )

    def test_meshless_records_warn_and_fall_back_to_payload(self):
        """The satellite bugfix: a reduction record with
        bytes_on_wire=None (meshless trace) used to be silently
        dropped from the total — a partially-seeded trace could then
        'fit one bucket' and tune toward a fraction of its real
        traffic.  Now it warns ONCE and counts payload bytes."""
        priced_small = _rec(1024)
        unpriced_huge = _rec(
            64 * 1024 * 1024, sizes=(0,), bytes_on_wire=None
        )
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = cw.tune_wire_for_trace([priced_small, unpriced_huge])
        hits = [x for x in w if "bytes_on_wire" in str(x.message)]
        assert len(hits) == 1, [str(x.message) for x in w]
        # the huge unpriced payload keeps the slot budget open — the
        # old code collapsed to (bytes, 1) on the 1 KiB priced total
        assert got == (
            2 * cw.DEFAULT_BUCKET_BYTES, cw.DEFAULT_MAX_BUCKETS
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # fully-priced: no warning
            cw.tune_wire_for_trace([priced_small])
        # a SUCCESSFUL measured tune prices payload_bytes directly and
        # never takes the analytic fallback — the fallback warning
        # would be a false diagnostic there, so it must not fire
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 27, 1e9))},
            latency={"flat": 1e-4},
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            cw.tune_wire_for_trace(
                [priced_small, _rec(1 << 20, bytes_on_wire=None)],
                profile=prof,
            )
        # bytes_on_wire == 0 is PRICED (a world-1 axis ships nothing),
        # not missing: no warning, and the payload is not re-counted
        # as unpriced traffic (pre-PR behavior preserved)
        zero_wire = _rec(2_000_000, sizes=(1,), bytes_on_wire=0)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got0 = cw.tune_wire_for_trace([zero_wire])
        assert got0 == (2 * cw.DEFAULT_BUCKET_BYTES,
                        cw.DEFAULT_MAX_BUCKETS)

    def test_profile_minimizes_predicted_sync_time(self):
        """With flat bandwidth and positive launch latency ONE bucket
        is provably cheapest (ring bytes are B-invariant, launches are
        not) — and with bandwidth that degrades sharply for large
        payloads, splitting wins.  Both verdicts must come from the
        measured model, not the constants."""
        flat_bw = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 26, 1e9))},
            latency={"flat": 1e-3},
        )
        total = 24 * 1024 * 1024
        rec = _rec(total)
        bb, slots = cw.tune_wire_for_trace([rec], profile=flat_bw)
        assert slots == 1
        assert bb == total
        # bandwidth cliff at large payloads: > 4 MiB buckets run at
        # 1/100th speed, so the minimum splits to the slot cap
        cliff = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): (
                (1024, 1e9), (4 << 20, 1e9), (5 << 20, 1e7),
                (1 << 26, 1e7),
            )},
            latency={"flat": 1e-6},
        )
        bb2, slots2 = cw.tune_wire_for_trace([rec], profile=cliff)
        assert slots2 == cw.DEFAULT_MAX_BUCKETS
        assert bb2 == -(-total // slots2)

    def test_tuned_slots_never_exceed_max_buckets(self):
        """Pins-are-ceilings: tuning may only REDUCE counts.  Whatever
        the curves say, candidates stop at max_buckets — so every
        budgets.py all_reduce ceiling derived from the default 6-slot
        plan holds for any tune."""
        for bw in (1.0, 1e6, 1e12):
            prof = BandwidthProfile(
                mesh_axes=(("mn", 8),),
                curves={("flat", "all_reduce"): ((1024, bw),
                                                 (1 << 26, bw / 7))},
                latency={"flat": 0.0},
            )
            _, slots = cw.tune_wire_for_trace(
                [_rec(48 * 1024 * 1024)], profile=prof
            )
            assert 1 <= slots <= cw.DEFAULT_MAX_BUCKETS

    def test_no_cap_sentinel_preserved_under_profile(self):
        """max_buckets=0 means UNBOUNDED (one bucket per leaf in the
        planner); profile tuning must not silently substitute the
        default cap — the same arguments plan the same slot budget
        with and without a profile."""
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 26, 1e9))},
            latency={"flat": 1e-3},
        )
        rec = _rec(24 * 1024 * 1024)
        assert cw.tune_wire_for_trace(
            [rec], max_buckets=0, profile=prof
        ) == cw.tune_wire_for_trace([rec], max_buckets=0)

    def test_predict_sync_time_totals_the_sync_classes(self):
        """The trace-level prediction (emitted as predicted_sync_ms on
        tuned bench rows) sums per-record predictions over ALL sync
        classes — incl. the all_gather leg of hier/ZeRO syncs, whose
        omission would under-predict exactly the staged rows the field
        exists to check — and is None as soon as one is unpriceable.
        Permutes are not sync and are skipped."""
        from chainermn_tpu.comm_wire.autotune import predict_sync_time

        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 24, 1e9))},
            latency={"flat": 1e-5},
        )
        sync = [_rec(1 << 20), _rec(1 << 16, cls="reduce_scatter"),
                _rec(1 << 16, cls="all_gather")]
        skipped = _rec(1 << 12, cls="collective_permute")
        total = predict_sync_time(sync + [skipped], prof)
        assert total == pytest.approx(sum(
            predict_cost(r, prof) for r in sync
        ))
        unpriced = sync + [_rec(64, sizes=(0,), bytes_on_wire=None)]
        assert predict_sync_time(unpriced, prof) is None

    def test_staged_trace_not_double_counted_and_priced_on_inter(self):
        """Review regression: a trace of an ALREADY-hier-staged step
        carries each bucket twice (full-payload intra reduce_scatter +
        shard-payload inter all_reduce).  The tuner must (a) take the
        largest per-class total as the gradient payload — not the sum
        of both legs — and (b) price candidates through the staged
        triple, with the slow inter hop on its own curve (the old
        largest-record subject was the intra-only reduce_scatter,
        silently dropping the inter bottleneck)."""
        p = 1 << 20
        staged = []
        for _ in range(3):  # 3 buckets: rs + ar + ag triple each
            staged.append(_rec(p, axes=("mn_intra",), sizes=(4,),
                               cls="reduce_scatter"))
            staged.append(_rec(p // 4, axes=("mn_inter",), sizes=(2,)))
            staged.append(_rec(p // 4, axes=("mn_intra",), sizes=(4,),
                               cls="all_gather"))
        staged.append(_rec(4, axes=("mn_inter", "mn_intra"),
                           sizes=(2, 4)))  # loss pmean
        # slow-inter profile with a large inter launch floor: every
        # staged bucket pays it, so B=1 must win — and the payload
        # must be the rs-class total (3 MiB), not rs+ar (3.75 MiB)
        prof = _profile(inter_bw=1e6, intra_bw=1e12, mixed_bw=1e6,
                        lat=0.0)
        prof.latency["inter"] = 0.5
        bb, slots = cw.tune_wire_for_trace(staged, profile=prof)
        assert slots == 1
        assert bb == 3 * p  # per-class max, not the double-counted sum

    def test_pinned_schedule_prices_candidates_as_pinned(self):
        """Review regression: a wire whose schedule is PINNED must have
        its tune candidates priced as that schedule — not as what
        'auto' would pick.  Cheap flat links with a bandwidth cliff
        make the auto decision go flat and SPLIT; the same trace with
        schedule='hier_rs_ag' pinned pays the huge inter launch floor
        per staged bucket and must collapse to ONE."""
        prof = BandwidthProfile(
            mesh_axes=(("mn_inter", 2), ("mn_intra", 4)),
            curves={
                ("mixed", "all_reduce"): ((1024, 1e9), (4 << 20, 1e9),
                                          (5 << 20, 1e7),
                                          (1 << 26, 1e7)),
                ("inter", "all_reduce"): ((1024, 1e9), (1 << 26, 1e9)),
                ("intra", "all_reduce"): ((1024, 1e12),
                                          (1 << 26, 1e12)),
                ("intra", "reduce_scatter"): ((1024, 1e12),
                                              (1 << 26, 1e12)),
                ("intra", "all_gather"): ((1024, 1e12),
                                          (1 << 26, 1e12)),
            },
            latency={"mixed": 1e-6, "intra": 1e-6, "inter": 0.5},
        )
        rec = _rec(24 * 1024 * 1024, axes=("mn_inter", "mn_intra"),
                   sizes=(2, 4))
        _, auto_slots = cw.tune_wire_for_trace([rec], profile=prof)
        assert auto_slots > 1  # flat-priced cliff: splitting wins
        _, pinned_slots = cw.tune_wire_for_trace(
            [rec], profile=prof, schedule="hier_rs_ag"
        )
        assert pinned_slots == 1  # every staged bucket pays the floor

    def test_activation_psums_do_not_pollute_the_tune(self):
        """Review regression: a hybrid DP×TP trace carries forward
        activation all_reduces (>=2-D operands over the TP axis) that
        the gradient wire never ships — the measured tune must size
        buckets from the flat wire records only, and must not union
        the TP axis into the sync world."""
        from chainermn_tpu.analysis.trace import wire_bytes

        grad = _rec(1 << 20)  # the wire's flat bucket over ("mn",)
        activation = CollectiveRecord(
            primitive="psum", cls="all_reduce", axes=("mn_tp",),
            dtypes=("float32",), shapes=((64, 512, 128),), context=(),
            axis_sizes=(4,), payload_bytes=64 * 512 * 128 * 4,
            bytes_on_wire=wire_bytes(
                "all_reduce", 64 * 512 * 128 * 4, 4
            ),
            hop="flat",
        )
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 26, 1e9))},
            latency={"flat": 1e-3},
        )
        bb, slots = cw.tune_wire_for_trace(
            [activation, grad], profile=prof
        )
        # sized from the 1 MiB wire bucket, not the 16 MiB activation
        assert (bb, slots) == (1 << 20, 1)
        # the forecast uses the SAME predicate as the tuner's
        # objective: predicted_sync_ms covers only the wire records
        from chainermn_tpu.comm_wire.autotune import predict_sync_time

        assert not cw.is_wire_record(activation)
        assert cw.is_wire_record(grad)
        assert predict_sync_time([activation, grad], prof) \
            == pytest.approx(predict_cost(grad, prof))

    def test_statistics_psums_excluded_by_provenance(self):
        """Review regression, one rank below the >=2-D filter: sync-BN's
        per-channel ``(C,)`` moment psums ride the
        ``functions.collectives`` wrappers — 1-D like the wire's flat
        buckets, but statistics traffic the wire never ships.  A 1-D
        all_reduce sourced OUTSIDE the comm layer is excluded from the
        tune and the forecast; the wire's own call sites
        (comm_wire/communicators) and provenance-less records stay
        counted, and the 0-D loss pmean is wire no matter where it was
        issued."""
        import dataclasses

        from chainermn_tpu.comm_wire.autotune import predict_sync_time

        bucket = dataclasses.replace(
            _rec(1 << 20),
            source="/repo/chainermn_tpu/comm_wire/codecs.py:194",
        )
        bn_stats = dataclasses.replace(
            _rec(8 << 20),
            source="/repo/chainermn_tpu/functions/collectives.py:50",
        )
        sourceless = _rec(1 << 18)
        loss = dataclasses.replace(
            _rec(4), shapes=((),),
            source="/repo/chainermn_tpu/optimizers.py:1457",
        )
        assert cw.is_wire_record(bucket)
        assert not cw.is_wire_record(bn_stats)
        assert cw.is_wire_record(sourceless)
        assert cw.is_wire_record(loss)
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 26, 1e9))},
            latency={"flat": 1e-3},
        )
        # sized from the 1 MiB bucket, not the 8 MiB BN statistics
        bb, slots = cw.tune_wire_for_trace([bn_stats, bucket],
                                           profile=prof)
        assert (bb, slots) == (1 << 20, 1)
        assert predict_sync_time([bn_stats, bucket, loss], prof) \
            == pytest.approx(predict_cost(bucket, prof)
                             + predict_cost(loss, prof))

    def test_activation_all_gathers_excluded_by_provenance(self):
        """Review regression, the rs/ag twin of the psum filters:
        forward TP/MoE activation all_gathers are in SYNC_CLASSES and
        cannot be told apart from ZeRO's blocked legs by shape (those
        are legitimately 2-D), so provenance is the discriminator — a
        reduce_scatter/all_gather sourced outside
        comm_wire/communicators/optimizers neither sizes buckets nor
        unions its tensor-parallel axis into the priced world."""
        import dataclasses

        from chainermn_tpu.comm_wire.autotune import predict_sync_time

        bucket = dataclasses.replace(
            _rec(1 << 20),
            source="/repo/chainermn_tpu/comm_wire/codecs.py:194",
        )
        tp_act = dataclasses.replace(
            CollectiveRecord(
                primitive="all_gather", cls="all_gather",
                axes=("mn_tp",), dtypes=("float32",),
                shapes=((64, 512, 32),), context=(),
                axis_sizes=(4,), payload_bytes=64 * 512 * 32 * 4,
                bytes_on_wire=64 * 512 * 32 * 4 * 3, hop="flat",
            ),
            source="/repo/chainermn_tpu/parallel/tensor_parallel.py:68",
        )
        zero_rs = dataclasses.replace(
            _rec(1 << 18, cls="reduce_scatter"),
            shapes=((8, (1 << 18) // 32),),
            source="/repo/chainermn_tpu/optimizers.py:776",
        )
        eager_ag = dataclasses.replace(
            _rec(1 << 16, cls="all_gather"),
            source="/repo/chainermn_tpu/communicators/"
                   "xla_communicator_base.py:431",
        )
        assert cw.is_wire_record(bucket)
        assert not cw.is_wire_record(tp_act)
        assert cw.is_wire_record(zero_rs)
        assert cw.is_wire_record(eager_ag)
        assert cw.is_wire_record(_rec(1 << 16, cls="all_gather"))
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 26, 1e9))},
            latency={"flat": 1e-3},
        )
        # sized from the 1 MiB bucket over ("mn",) — NOT the 4 MiB
        # activation gather, and mn_tp never enters the axis union
        bb, slots = cw.tune_wire_for_trace([tp_act, bucket],
                                           profile=prof)
        assert (bb, slots) == (1 << 20, 1)
        assert predict_sync_time([tp_act, bucket], prof) \
            == pytest.approx(predict_cost(bucket, prof))

    def test_zero_shape_tunes_against_its_own_programs(self, comm):
        """Review regression: ZeRO's bucket sizing must be minimized
        against the rs+ag programs it issues, not the gradient wire's
        psum.  Curves where all_reduce is uniformly fast but rs/ag
        fall off a cliff above 4 MiB: the plain wrapper tunes to ONE
        bucket, ZeRO splits to the cap — and the factory threads the
        shape automatically."""
        cliff = ((1024, 1e9), (4 << 20, 1e9), (5 << 20, 1e3),
                 (1 << 26, 1e3))
        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={
                ("flat", "all_reduce"): ((1024, 1e9), (1 << 26, 1e9)),
                ("flat", "reduce_scatter"): cliff,
                ("flat", "all_gather"): cliff,
            },
            latency={"flat": 1e-6},
        )
        recs = [_rec(24 * 1024 * 1024)]
        _, plain_slots = cw.tune_wire_for_trace(recs, profile=prof)
        assert plain_slots == 1  # flat ar is cheap at any size
        _, zero_slots = cw.tune_wire_for_trace(
            recs, profile=prof, shape="zero"
        )
        assert zero_slots == cw.DEFAULT_MAX_BUCKETS  # rs/ag cliff
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, zero_redundancy=True,
            profile=prof, tune_trace=recs,
        )
        assert opt.wire.max_buckets == zero_slots
        plain = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, profile=prof, tune_trace=recs,
        )
        assert plain.wire.max_buckets == plain_slots

    def test_unpriceable_trace_falls_back_to_analytic(self):
        """A profile with no usable curve for the trace's hop must not
        guess: the analytic rules apply exactly as with
        profile=None."""
        empty = BandwidthProfile(mesh_axes=(("mn", 8),), curves={
            ("inter", "all_gather"): ((1024, 1.0),),
        })
        # curve_for falls back cross-hop, so build a record whose world
        # is unknown instead — the unpriceable case with a profile
        rec = _rec(1024, sizes=(0,), bytes_on_wire=None)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            got = cw.tune_wire_for_trace([rec], profile=empty)
            want = cw.tune_wire_for_trace([rec])
        assert got == want


# ----------------------------------------------------------------------
# schedule decision: measured flat-vs-hier
# ----------------------------------------------------------------------
class TestScheduleDecisionWithProfile:
    def test_profile_none_is_bit_identical_to_analytic_rule(self):
        """The fallback contract: across a payload sweep spanning the
        analytic threshold, profile=None decides exactly as the
        documented byte rule."""
        split = cw.axis_split(("mn_inter", "mn_intra"), (2, 4))
        for payload in (64, 4096, 64 * 1024, 1 << 20, 1 << 24):
            want = (
                "hier_rs_ag"
                if cw.hier_inter_savings(payload, split)
                >= cw.MIN_HIER_INTER_SAVINGS else "flat"
            )
            assert cw.schedule_for_bucket(
                payload, MESH24, profile=None
            ) == want

    def test_slow_inter_profile_stages(self):
        """Slow DCN + fast ICI: predicted hier (compressed shard over
        the slow hop) beats the flat psum — staged even for payloads
        the analytic byte rule would leave flat."""
        prof = _profile(inter_bw=1e7, intra_bw=1e11, mixed_bw=1e7,
                        lat=1e-7)
        payload = 16 * 1024  # analytic rule says flat (savings < 64 KiB)
        assert cw.schedule_for_bucket(payload, MESH24) == "flat"
        assert cw.schedule_for_bucket(
            payload, MESH24, profile=prof
        ) == "hier_rs_ag"

    def test_fast_inter_profile_stays_flat(self):
        """Uniformly fast links: the two extra intra launches never pay
        — flat even for payloads the analytic byte rule WOULD stage.
        The measured decision genuinely overrides the heuristic in both
        directions."""
        prof = _profile(inter_bw=1e11, intra_bw=1e11, mixed_bw=1e11,
                        lat=1e-4)
        payload = 8 << 20  # analytic rule stages this
        assert cw.schedule_for_bucket(payload, MESH24) == "hier_rs_ag"
        assert cw.schedule_for_bucket(
            payload, MESH24, profile=prof
        ) == "flat"

    def test_zero_shape_priced_as_scatter_gather(self):
        """Review regression: ZeRO's blocked path issues rs-down +
        ag-up (flat) vs 2rs+2ag (staged), not the gradient wire's
        psum-vs-triple — the measured decision must price THOSE legs.
        A profile with a slow mixed all_reduce but fast mixed rs/ag
        and awful inter rs/ag stages the gradient wire (its flat psum
        is the slow leg) while keeping ZeRO flat (its staged path pays
        the awful inter rs+ag; its flat path never touches the slow
        all_reduce curve)."""
        fast, slow = 1e12, 1e6
        pts = lambda bw: ((1024, bw), (1 << 24, bw))  # noqa: E731
        prof = BandwidthProfile(
            mesh_axes=(("mn_inter", 2), ("mn_intra", 4)),
            curves={
                ("mixed", "all_reduce"): pts(slow),
                ("mixed", "reduce_scatter"): pts(fast),
                ("mixed", "all_gather"): pts(fast),
                ("intra", "all_reduce"): pts(fast),
                ("intra", "reduce_scatter"): pts(fast),
                ("intra", "all_gather"): pts(fast),
                ("inter", "all_reduce"): pts(fast),
                ("inter", "reduce_scatter"): pts(1.0),
                ("inter", "all_gather"): pts(1.0),
            },
            latency={"mixed": 1e-9, "intra": 1e-9, "inter": 1e-9},
        )
        payload = 1 << 20
        assert cw.schedule_for_bucket(
            payload, MESH24, profile=prof
        ) == "hier_rs_ag"
        assert cw.schedule_for_bucket(
            payload, MESH24, profile=prof, shape="zero"
        ) == "flat"

    def test_explicit_schedule_overrides_profile(self):
        prof = _profile(inter_bw=1e11, lat=1.0)
        assert cw.schedule_for_bucket(
            8 << 20, MESH24, requested="hier_rs_ag", profile=prof
        ) == "hier_rs_ag"
        assert cw.schedule_for_bucket(
            8 << 20, MESH24, requested="flat",
            profile=_profile(inter_bw=1.0)
        ) == "flat"

    def test_unpriceable_leg_falls_back_to_analytic(self):
        """A profile that cannot price one hier leg (no curve resolves)
        must fall back to the byte rule, not guess."""
        empty = BandwidthProfile(mesh_axes=(), curves={})
        for payload in (16 * 1024, 8 << 20):
            assert cw.schedule_for_bucket(
                payload, MESH24, profile=empty
            ) == cw.schedule_for_bucket(payload, MESH24)


# ----------------------------------------------------------------------
# plan identity: the profile=None regression pin + hash folding
# ----------------------------------------------------------------------
class TestPlanIdentity:
    TREE = {"w": jnp.zeros((1 << 20,)), "b": jnp.zeros((7,))}

    def test_profile_none_plan_hash_is_pre_autotuner_bytes(self):
        """Acceptance pin: with profile=None the WirePlan hash is
        byte-identical to the pre-PR formula (reimplemented inline
        here) — layout + schedules + axes and NOTHING else."""
        import hashlib

        wp = cw.plan_wire(self.TREE, cw.WireConfig(), MESH24)
        assert wp.profile_hash is None
        h = hashlib.sha256()
        h.update(wp.plan.plan_hash().encode())
        h.update(("|sched=" + ",".join(wp.schedules)).encode())
        h.update(("|axes=" + ",".join(
            f"{a}:{s}" for a, s in zip(wp.axes, wp.axis_sizes)
        )).encode())
        assert wp.plan_hash() == h.hexdigest()

    def test_profile_hash_folds_into_plan_hash(self):
        base = cw.plan_wire(self.TREE, cw.WireConfig(), MESH24)
        prof = _profile()
        tuned = cw.plan_wire(
            self.TREE, cw.WireConfig(), MESH24, profile=prof
        )
        assert tuned.profile_hash == prof.profile_hash()
        assert tuned.plan_hash() != base.plan_hash()
        # same curves, different label: same decisions, same hash
        relabeled = cw.plan_wire(
            self.TREE, cw.WireConfig(), MESH24,
            profile=_profile(label="recaptured"),
        )
        assert relabeled.plan_hash() == tuned.plan_hash()
        # different curves: different hash EVEN IF the schedule
        # decisions happen to coincide — the next model would diverge
        perturbed = cw.plan_wire(
            self.TREE, cw.WireConfig(), MESH24,
            profile=_profile(inter_bw=1.01e8),
        )
        assert perturbed.schedules == tuned.schedules or True
        assert perturbed.plan_hash() != tuned.plan_hash()

    def test_meshless_agreement_token_folds_profile(self):
        """Review regression: a mesh-LESS communicator's plan-agreement
        token must also cover the profile hash — two ranks whose
        analytic layouts coincide but whose profiles differ have to
        mismatch at init, not diverge on the next profile-sensitive
        decision (the mesh path gets this via WirePlan.plan_hash; the
        plan_of_tree fallback was profile-blind)."""
        class MeshlessComm:
            process_count = 2
            allreduce_grad_dtype = None

            def __init__(self):
                self.exchanged = []

            def allgather_obj(self, x):
                self.exchanged.append(x)
                return [x]  # echo: agreement passes, token recorded

        params = {"w": jnp.zeros((256,))}
        tokens = {}
        for name, prof in (("a", _profile()),
                           ("b", _profile(inter_bw=9e7)),
                           ("none", None)):
            comm = MeshlessComm()
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, profile=prof
            )
            opt._check_plan_agreement(params)
            tokens[name] = comm.exchanged[-1]
        assert tokens["a"] != tokens["b"]       # profiles differ
        assert tokens["a"] != tokens["none"]    # tuned != untuned
        # and the untuned token is the bare plan hash (pre-PR bytes)
        assert tokens["none"] == cw.plan_of_tree(params).plan_hash()

    def test_meshless_wire_plan_raises_clearly(self):
        """Review regression: ``opt.wire_plan`` on a mesh-less comm
        used to die deep in schedules.py (``dict(None)``) — the method
        must refuse with the same clarity as its per-leaf branch and
        point at the mesh-less layout path."""
        class MeshlessComm:
            process_count = 1
            allreduce_grad_dtype = None

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), MeshlessComm()
        )
        with pytest.raises(ValueError, match="plan_of_tree"):
            opt.wire_plan({"w": jnp.zeros((256,))})

    def test_optimizer_plans_identically_without_profile(self, comm):
        """End to end through the factory: a profile-less optimizer's
        plan (the one plan_agreement would exchange) is unchanged."""
        params = {"w": jnp.zeros((4096, 16)), "b": jnp.zeros((16,))}
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        wp = opt.wire_plan(params)
        legacy = cw.plan_wire(params, opt.wire, comm.mesh)
        assert wp.plan_hash() == legacy.plan_hash()
        assert wp.profile_hash is None


# ----------------------------------------------------------------------
# tuned plans still satisfy the pinned budgets
# ----------------------------------------------------------------------
class TestTunedBudgets:
    def test_tuned_mlp_step_within_pinned_budget(self, comm, tmp_path):
        """The analysis touchpoint: budgets.py ceilings are CONTRACTS
        — a profile+trace-tuned compiled step must stay under the same
        mlp_train_step pin as the constant-planned one (tuning may
        only reduce counts)."""
        from chainermn_tpu.models import MLP

        model = MLP(n_units=32)
        x = jnp.zeros((16, 28, 28), jnp.float32)
        y = jnp.zeros((16,), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), x[:1])

        def loss_fn(p, b):
            logits = model.apply(p, b[0])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, b[1]
            ).mean()

        def build(**kw):
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, **kw
            )
            step = cmn.build_train_step(comm, loss_fn, opt,
                                        donate=False)
            return opt, step

        opt0, step0 = build()
        p0, o0 = step0.place(params, opt0.init(params))
        batch = (
            jax.device_put(x, step0.batch_sharding),
            jax.device_put(y, step0.batch_sharding),
        )
        tr0 = step0.collective_trace(p0, o0, batch)
        enforce("mlp_train_step", tr0)

        prof = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e8),
                                             (1 << 24, 1e9))},
            latency={"flat": 1e-5},
        )
        opt1, step1 = build(profile=prof, tune_trace=tr0)
        assert opt1.wire.max_buckets <= cw.DEFAULT_MAX_BUCKETS
        p1, o1 = step1.place(params, opt1.init(params))
        tr1 = step1.collective_trace(p1, o1, batch)
        enforce("mlp_train_step", tr1)  # the pin holds for the tune
        assert tr1.count("all_reduce") <= tr0.count("all_reduce")

    def test_tuned_hier_plan_within_schedule_budget(self, hier_comm):
        """A profile-staged plan obeys the hier collective arithmetic
        the budget pins encode: rs/ar/ag counts equal the staged bucket
        count (+1 loss all-reduce comes from the step, not the wire)."""
        prof = _profile(inter_bw=1e6, intra_bw=1e12, mixed_bw=1e6,
                        lat=1e-9)
        tree = {"w": jnp.zeros((1 << 18,)), "v": jnp.zeros((1 << 18,))}
        wp = cw.plan_wire(
            tree, cw.WireConfig(bucket_bytes=1 << 19, max_buckets=0),
            hier_comm.mesh, profile=prof,
        )
        staged = [s for s in wp.schedules if s == "hier_rs_ag"]
        assert staged, wp.schedules
        assert len(wp.schedules) <= max(cw.DEFAULT_MAX_BUCKETS,
                                        len(wp.buckets))


# ----------------------------------------------------------------------
# profile construction: attribution scrape + calibration sweep
# ----------------------------------------------------------------------
class TestProfileFromAttribution:
    def test_resnet_acceptance_fixture_yields_usable_curve(self, comm):
        """The satellite acceptance: the PR 9 attribution fixture —
        ResNet-50 compiled-step trace over eval_shape params, measured
        via the eager bucketed wire on a 2-device sub-communicator —
        scrapes into a profile whose all_reduce curve prices every
        record of the trace."""
        from chainermn_tpu.comm_wire import plan_of_tree
        from chainermn_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, train=False)
        pshapes = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jnp.zeros((1, 32, 32, 3)),
        )
        plan = plan_of_tree(pshapes)

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
        ostate = jax.eval_shape(opt.init, pshapes)
        batch = (
            jax.device_put(jnp.zeros((8, 32, 32, 3)),
                           step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32),
                           step.batch_sharding),
        )
        trace = step.collective_trace(pshapes, ostate, batch)

        comm2 = cmn.create_communicator(
            "tpu", devices=jax.devices()[:2]
        )
        leaves, treedef = jax.tree_util.tree_flatten(pshapes)
        grads = jax.tree_util.tree_unflatten(treedef, [
            np.zeros((2,) + tuple(l.shape), l.dtype) for l in leaves
        ])
        with obs.observe() as tel:
            comm2.allreduce_grad(grads)
            comm2.allreduce(np.zeros((2,), np.float32), op="mean")
        report = obs.attribute(tel, trace)
        assert report.n_matched >= plan.n_buckets + 1

        prof = profile_from_attribution(report, label="resnet_fixture")
        assert ("flat", "all_reduce") in prof.curves
        assert len(prof.curves[("flat", "all_reduce")]) >= 2, (
            "bucket payloads span several log2 bins — the curve must "
            "carry more than one point"
        )
        assert prof.launch_latency("flat") > 0
        # usable: every record of the trace prices to a positive time
        for rec in trace:
            t = predict_cost(rec, prof)
            assert t is not None and t > 0, rec
        # and the timeline+trace spelling produces the same profile
        prof2 = profile_from_attribution(tel, trace,
                                         label="resnet_fixture")
        assert prof2.profile_hash() == prof.profile_hash()
        # bandwidth_points is the raw export the binning consumes
        pts = report.bandwidth_points()
        assert len(pts) >= plan.n_buckets
        assert all(bw > 0 for _, _, _, bw, _ in pts)

    def test_empty_report_raises(self):
        from chainermn_tpu.analysis import CollectiveTrace

        with obs.observe() as tel:
            pass
        with pytest.raises(ValueError, match="no byte-priced"):
            profile_from_attribution(tel, CollectiveTrace(records=()))


class TestStagedAttribution:
    """Review regression (ISSUE 12): the eager hier wire times a whole
    rs→ar→ag triple under ONE span — attribution must pair it with the
    triple, and the curve export must exclude the composite."""

    P = 256 * 1024  # bucket payload, bytes
    SHARD = 64 * 1024  # P / intra_size(4)

    def _triple_trace(self):
        from chainermn_tpu.analysis import CollectiveTrace

        return CollectiveTrace(records=(
            _rec(self.P, axes=("mn_intra",), sizes=(4,),
                 cls="reduce_scatter"),
            _rec(self.SHARD, axes=("mn_inter",), sizes=(2,)),
            _rec(self.SHARD, axes=("mn_intra",), sizes=(4,),
                 cls="all_gather"),
            _rec(4, axes=("mn_inter", "mn_intra"), sizes=(2, 4)),
        ))

    def test_staged_span_consumes_its_triple(self):
        trace = self._triple_trace()
        with obs.observe() as tel:
            with obs.span("collective.psum", bucket=0, bytes=self.P,
                          schedule="hier_rs_ag", rs_bytes=self.P,
                          ar_bytes=self.SHARD, ag_bytes=self.SHARD):
                pass
            with obs.span("collective.allreduce", bytes=4):
                pass
        report = obs.attribute(tel, trace)
        assert not report.unmatched_records, report.unmatched_records
        assert not report.unmatched_spans
        staged = [a for a in report.matched
                  if a.span_args.get("schedule") == "hier_rs_ag"]
        assert len(staged) == 1
        a = staged[0]
        assert a.byte_exact
        assert a.record.cls == "reduce_scatter"
        triple_bow = sum(
            r.bytes_on_wire for r in trace.records[:3]
        )
        assert a.bytes_on_wire == triple_bow
        # the loss pmean still pairs byte-exactly with ITS span — the
        # staged span can no longer steal it through the order fallback
        loss = [x for x in report.matched if x is not a][0]
        assert loss.record.payload_bytes == 4 and loss.byte_exact
        # curve export: the composite (two hop classes, three
        # collectives) belongs to no single curve and is excluded
        pts = report.bandwidth_points()
        assert all(p[2] == 4 for p in pts), pts

    def test_flat_trace_degrades_to_generic_matching(self):
        """A schedule-marked span against a trace with NO staged
        records (e.g. the flat program of another config) falls back
        to the generic passes instead of erroring."""
        from chainermn_tpu.analysis import CollectiveTrace

        trace = CollectiveTrace(records=(_rec(self.P),))
        with obs.observe() as tel:
            with obs.span("collective.psum", bucket=0, bytes=self.P,
                          schedule="hier_rs_ag", rs_bytes=self.P,
                          ar_bytes=self.SHARD, ag_bytes=self.SHARD):
                pass
        report = obs.attribute(tel, trace)
        assert report.n_matched == 1
        assert report.matched[0].record.cls == "all_reduce"

    def test_tiny_shard_leg_cannot_steal_the_loss_pmean(self):
        """Review regression: a tiny staged bucket's 4-byte ar leg must
        not consume the 4-byte loss pmean record (bytes collide, hops
        don't) — triple legs are hop-pinned (rs/ag intra, ar inter)."""
        from chainermn_tpu.analysis import CollectiveTrace

        trace = CollectiveTrace(records=(
            _rec(4, axes=("mn_inter", "mn_intra"),
                 sizes=(2, 4)),  # loss pmean FIRST in program order
            _rec(16, axes=("mn_intra",), sizes=(4,),
                 cls="reduce_scatter"),
            _rec(4, axes=("mn_inter",), sizes=(2,)),
            _rec(4, axes=("mn_intra",), sizes=(4,),
                 cls="all_gather"),
        ))
        with obs.observe() as tel:
            with obs.span("collective.psum", bucket=0, bytes=16,
                          schedule="hier_rs_ag", rs_bytes=16,
                          ar_bytes=4, ag_bytes=4):
                pass
            with obs.span("collective.allreduce", bytes=4):
                pass
        report = obs.attribute(tel, trace)
        assert not report.unmatched_records
        assert not report.unmatched_spans
        by_name = {a.span_name: a for a in report.matched}
        # the triple's ar leg is the INTER record; the loss span keeps
        # its mixed-hop pmean
        assert by_name["collective.allreduce"].record.hop == "mixed"
        staged = by_name["collective.psum"]
        assert staged.record.cls == "reduce_scatter"
        assert staged.byte_exact

    def test_composite_span_excluded_from_latency_bound(self):
        """Review regression: the scraped per-hop launch floor must not
        min over composite triple durations — a slow staged span would
        otherwise inflate the intra floor with inter-bound time and
        bias every staged-schedule prediction toward flat."""
        trace = self._triple_trace()
        with obs.observe() as tel:
            with obs.span("collective.psum", bucket=0, bytes=self.P,
                          schedule="hier_rs_ag", rs_bytes=self.P,
                          ar_bytes=self.SHARD, ag_bytes=self.SHARD):
                import time as _t
                _t.sleep(0.01)  # the composite is SLOW
            with obs.span("collective.allreduce", bytes=4):
                pass
        prof = profile_from_attribution(tel, trace)
        # the only latency source is the flat loss-pmean span, not the
        # 10 ms composite (the head rs record's hop is intra)
        assert "intra" not in prof.latency
        assert prof.latency.get("mixed", 1.0) < 0.01

    def test_scrape_from_staged_run_discloses_excluded_composites(self):
        """Review regression: a telemetry export whose wire buckets the
        planner STAGED joins as composite triples — excluded from
        ``bandwidth_points()`` by design — so the scraped profile is
        missing exactly the buckets' inter/intra curves.  That must be
        a RuntimeWarning at scrape time (the same disclosure contract
        as ``calibrate()``'s untimeable classes), not a silent
        'measured' profile whose every staged prediction resolves
        through the wrong-class fallback chain.  The latency-bound test
        above feeds the same shape; this pins the disclosure."""
        trace = self._triple_trace()
        with obs.observe() as tel:
            with obs.span("collective.psum", bucket=0, bytes=self.P,
                          schedule="hier_rs_ag", rs_bytes=self.P,
                          ar_bytes=self.SHARD, ag_bytes=self.SHARD):
                pass
            with obs.span("collective.allreduce", bytes=4):
                pass
        with pytest.warns(RuntimeWarning, match="staged-triple"):
            prof = profile_from_attribution(tel, trace)
        # the surviving curve is the loss pmean's point only — the
        # disclosure is what tells the operator the capture is partial
        assert ("intra", "reduce_scatter") not in prof.curves
        assert ("inter", "all_reduce") not in prof.curves

    def test_eager_staged_span_carries_per_leg_bytes(self, hier_comm):
        """End to end: the eager wire on a hierarchical mesh marks a
        staged bucket's span with schedule + each leg's exact operand
        bytes (rs: padded native, ar: wire-dtype shard, ag: native
        shard) — the raw material the triple-aware join reads."""
        big = np.zeros((hier_comm.size, 128 * 1024), np.float32)
        with obs.observe() as tel:
            hier_comm.allreduce_grad({"w": big})
        spans = tel.timeline.spans("collective.psum")
        assert spans, "the eager wire must emit bucket spans"
        staged = [s for s in spans
                  if s["args"].get("schedule") == "hier_rs_ag"]
        assert staged, [s["args"] for s in spans]
        a = staged[0]["args"]
        # 128Ki f32 elems divide the intra width 4 evenly: rs = the
        # full native bucket, ar/ag = the quarter shard (no cast:
        # allreduce_grad_dtype is None on this comm)
        assert a["rs_bytes"] == a["bytes"]
        assert a["ar_bytes"] == a["bytes"] // 4
        assert a["ag_bytes"] == a["bytes"] // 4


class TestCalibrate:
    def test_flat_mesh_sweep(self, comm, tmp_path):
        prof = calibrate(comm, sizes=(4096, 65536), repeats=1)
        for cls in cw.autotune.CALIBRATED_CLASSES:
            assert ("flat", cls) in prof.curves, sorted(prof.curves)
            for p, bw in prof.curves[("flat", cls)]:
                assert p > 0 and bw > 0
        assert prof.launch_latency("flat") > 0
        assert prof.mesh_axes == (("mn", 8),)
        p = str(tmp_path / "cal.json")
        prof.save(p)
        assert BandwidthProfile.load(p).profile_hash() \
            == prof.profile_hash()

    def test_hier_mesh_sweep_measures_every_hop(self, hier_comm):
        prof = calibrate(hier_comm, sizes=(4096,), repeats=1)
        hops = {h for h, _ in prof.curves}
        assert hops == {"inter", "intra", "mixed"}, sorted(prof.curves)
        assert prof.mesh_axes == (("mn_inter", 2), ("mn_intra", 4))

    def test_rejects_degenerate_sizes(self, comm):
        with pytest.raises(ValueError, match="sizes"):
            calibrate(comm, sizes=(2,))

    def test_warns_when_a_class_cannot_be_timed(self, comm,
                                                monkeypatch):
        """Review regression: a backend where one collective class
        fails to trace must not hand back a silently-degraded profile —
        the missing curve would later price that class through
        ``curve_for``'s wrong-class fallback chain (the exact
        degradation the SYNC_CLASSES contract names).  The sweep still
        returns the classes it could time, but says what it dropped."""

        def boom(*a, **k):
            raise RuntimeError("psum_scatter unsupported here")

        monkeypatch.setattr(jax.lax, "psum_scatter", boom)
        with pytest.warns(RuntimeWarning,
                          match=r"DROPPED.*flat/reduce_scatter"):
            prof = calibrate(comm, sizes=(4096,), repeats=1)
        assert ("flat", "all_reduce") in prof.curves
        assert ("flat", "all_gather") in prof.curves
        assert ("flat", "reduce_scatter") not in prof.curves


# ----------------------------------------------------------------------
# the CLI
# ----------------------------------------------------------------------
class TestCLI:
    def test_calibrate_cli_writes_loadable_profile(self, tmp_path):
        from conftest import subprocess_env

        out = str(tmp_path / "prof.json")
        env = subprocess_env(8)
        # the CLI initializes jax itself — keep it on the CPU mesh
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "chainermn_tpu.comm_wire.autotune",
             "--calibrate", out, "--sizes", "4096,65536",
             "--repeats", "1"],
            env=env, capture_output=True, text=True,
            timeout=240,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            )),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        summary = json.loads(
            [l for l in proc.stdout.splitlines()
             if l.startswith("{")][-1]
        )
        prof = BandwidthProfile.load(out)
        assert summary["profile_hash"] == prof.profile_hash()
        assert summary["n_curves"] == len(prof.curves) >= 3


# ----------------------------------------------------------------------
# end to end: a tuned compiled step trains
# ----------------------------------------------------------------------
class TestTunedStepEndToEnd:
    def test_profile_tuned_step_trains_and_plans_agree(self, hier_comm,
                                                       tmp_path):
        """A hier-mesh step planned through a saved profile file: the
        optimizer loads it by path, the plan folds the hash, the staged
        program runs, and the loss decreases — the single-process twin
        of the tuned_wire_fault mp scenario."""
        prof = _profile(inter_bw=1e6, intra_bw=1e12, mixed_bw=1e6,
                        lat=1e-9)
        path = str(tmp_path / "prof.json")
        prof.save(path)
        rng = np.random.RandomState(0)
        params = {
            "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
            "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
        }
        w_true = rng.randn(8, 4).astype(np.float32)
        x = rng.randn(32, 8).astype(np.float32)
        y = x @ w_true

        def loss_fn(p, b):
            bx, by = b
            return jnp.mean(((jnp.tanh(bx @ p["w1"]) @ p["w2"])
                             - by) ** 2)

        wire = cw.WireConfig(bucket_bytes=64, max_buckets=0)
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), hier_comm, wire=wire, profile=path
        )
        wp = opt.wire_plan(params)
        assert set(wp.schedules) == {"hier_rs_ag"}
        assert wp.profile_hash == prof.profile_hash()
        step = cmn.build_train_step(hier_comm, loss_fn, opt,
                                    donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(x, step.batch_sharding),
            jax.device_put(y, step.batch_sharding),
        )
        losses = []
        for _ in range(8):
            p, o, m = step(p, o, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        tr = step.collective_trace(p, o, batch)
        census = tr.census()
        assert census.get("reduce_scatter", 0) == wp.n_buckets
        assert census.get("all_gather", 0) == wp.n_buckets
