"""Bucket-granularity comm/compute overlap engine (ISSUE 8).

Tentpole pins, in order of load-bearingness:

* the overlapped step is BIT-IDENTICAL to the synchronous bucketed
  step — params, opt state, error-feedback residuals, losses — for
  every codec (the pass only reorders equations: same buckets, same
  codec, same summands, same per-collective reduction order);
* the collective census is UNCHANGED (the existing analysis budget
  pins pass on the overlapped program without edits) — only the trace
  ordering moves;
* ordering: in the scheduled program every bucket psum is issued at
  its dependency frontier (``delay == 0`` — dispatched before the
  remaining backward segments complete), checked by the new
  ordering-aware ``analysis.check_overlap``; the synchronous program
  FAILS that check for any multi-bucket plan;
* segment/bucket alignment: the program carries exactly one fused
  psum per plan bucket, issue order follows backward readiness
  (reverse-planner order on a sequential model), and consecutive
  bucket issues are separated by real backward compute (the segments
  the scheduler threads the collectives through);
* ``plan_hash()`` is untouched by the overlap mode (the plan is a pure
  function of shapes; overlap is a schedule, not a wire).

Satellites: the host-staged eager tier's pipelined bucket exchanges
equal the serial schedule bit-for-bit; overlap composes with ZeRO
(reduce-scatter/all-gather census unchanged) and is rejected on the
GSPMD path and under double buffering.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu import comm_wire as cw
from chainermn_tpu.analysis import check_overlap, enforce
from chainermn_tpu.comm_wire import (
    WireConfig,
    assert_overlap_order,
    bucket_issue_report,
    issue_report,
    plan_of_tree,
    resolve_overlap,
    schedule_jaxpr,
)
from chainermn_tpu.comm_wire.overlap import OverlappedStep
from chainermn_tpu.optimizers import build_train_step


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


def _assert_tree_bit_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert jnp.dtype(x.dtype) == jnp.dtype(y.dtype)
        np.testing.assert_array_equal(
            np.asarray(x, np.float64) if x.dtype == jnp.bfloat16
            else np.asarray(x),
            np.asarray(y, np.float64) if y.dtype == jnp.bfloat16
            else np.asarray(y),
        )


def _mlp3_setup(comm, wire, overlap, tx=None, n_steps=5):
    """3-layer MLP regression fixture shared by the bit-identity and
    ordering tests; returns (params, opt_state, step, batch, losses)."""
    rng = np.random.RandomState(0)
    params = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 8) * 0.3, jnp.float32),
        "w3": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32),
    }
    w_true = rng.randn(8, 4).astype(np.float32)
    x = rng.randn(32, 8).astype(np.float32)
    y = x @ w_true

    def loss_fn(p, b):
        bx, by = b
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean((jnp.tanh(h @ p["w2"]) @ p["w3"] - by) ** 2)

    opt = cmn.create_multi_node_optimizer(
        tx or optax.adam(1e-2), comm, wire=wire, overlap=overlap
    )
    step = build_train_step(comm, loss_fn, opt, donate=False)
    p, o = step.place(params, opt.init(params))
    batch = (
        jax.device_put(x, step.batch_sharding),
        jax.device_put(y, step.batch_sharding),
    )
    losses = []
    for _ in range(n_steps):
        p, o, m = step(p, o, batch)
        losses.append(float(m["loss"]))
    return p, o, step, batch, losses


# tiny buckets => one bucket per leaf: genuinely multi-bucket programs
_TINY = dict(bucket_bytes=64, max_buckets=0)


# ----------------------------------------------------------------------
# bit identity: overlapped == synchronous, all codecs
# ----------------------------------------------------------------------
class TestBitIdentity:
    @pytest.mark.parametrize("wire", [
        "auto",
        "per_leaf",
        WireConfig(codec="bf16", **_TINY),
        WireConfig(codec="f16", **_TINY),
        WireConfig(codec="int8", **_TINY),
    ])
    def test_overlapped_equals_synchronous_exactly(self, comm, wire):
        """Acceptance: 0 tolerance across params, opt state, and the
        per-step losses — the pass reorders, never recomputes."""
        ps, os_, _, _, ls = _mlp3_setup(comm, wire, "none")
        pb, ob, _, _, lb = _mlp3_setup(comm, wire, "bucket")
        _assert_tree_bit_equal(ps, pb)
        _assert_tree_bit_equal(os_, ob)
        assert ls == lb

    def test_int8_error_feedback_residual_carry_identical(self, comm):
        """The EF residual (flat wire buckets in the optimizer state)
        rides the same reordered program: bit-identical carry."""
        wire = WireConfig(codec="int8", error_feedback=True, **_TINY)
        ps, os_, _, _, ls = _mlp3_setup(comm, wire, "none")
        pb, ob, _, _, lb = _mlp3_setup(comm, wire, "bucket")
        assert isinstance(ob.wire_residual, tuple) and ob.wire_residual
        _assert_tree_bit_equal(os_.wire_residual, ob.wire_residual)
        _assert_tree_bit_equal(ps, pb)
        assert ls == lb

    def test_zero_redundancy_overlap_identical(self, comm):
        ps, os_, _, _, ls = _mlp3_setup(
            comm, "bf16", "none",
            tx=optax.adam(1e-2),
        )
        # same fixture through the ZeRO wrapper, overlap on/off
        outs = {}
        for mode in ("none", "bucket"):
            rng = np.random.RandomState(0)
            params = {
                "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
                "w2": jnp.asarray(rng.randn(16, 8) * 0.3, jnp.float32),
                "w3": jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32),
            }
            w_true = rng.randn(8, 4).astype(np.float32)
            x = rng.randn(32, 8).astype(np.float32)
            y = x @ w_true

            def loss_fn(p, b):
                bx, by = b
                h = jnp.tanh(bx @ p["w1"])
                return jnp.mean(
                    (jnp.tanh(h @ p["w2"]) @ p["w3"] - by) ** 2
                )

            opt = cmn.create_multi_node_optimizer(
                optax.adam(1e-2), comm, zero_redundancy=True,
                wire="bf16", overlap=mode,
            )
            step = build_train_step(comm, loss_fn, opt, donate=False)
            p, o = step.place(params, opt.init(params))
            batch = (
                jax.device_put(x, step.batch_sharding),
                jax.device_put(y, step.batch_sharding),
            )
            for _ in range(5):
                p, o, m = step(p, o, batch)
            tr = step.collective_trace(p, o, batch)
            outs[mode] = (p, o, tr)
        pn, on, tn = outs["none"]
        pb, ob, tb = outs["bucket"]
        _assert_tree_bit_equal(pn, pb)
        _assert_tree_bit_equal(on, ob)
        # ZeRO census unchanged: reduce_scatter down + all_gather up
        assert tn.census() == tb.census()
        assert tb.count("reduce_scatter") >= 1
        assert tb.count("all_gather") >= 1


# ----------------------------------------------------------------------
# census unchanged, ordering moved
# ----------------------------------------------------------------------
class TestCensusAndOrdering:
    def _mnist_step(self, comm, overlap):
        from chainermn_tpu.models import MLP

        model = MLP(n_units=1000)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, overlap=overlap
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((64, 28, 28)), step.batch_sharding),
            jax.device_put(jnp.zeros((64,), jnp.int32),
                           step.batch_sharding),
        )
        return step, p, o, batch, params

    def test_census_unchanged_budget_pin_passes_as_is(self, comm):
        """Acceptance: the lowered census is unchanged — the EXISTING
        mlp budget pin enforces the overlapped trace without edits."""
        step_s, p, o, batch, params = self._mnist_step(comm, "none")
        step_b, pb, ob, batch_b, _ = self._mnist_step(comm, "bucket")
        tr_s = step_s.collective_trace(p, o, batch)
        tr_b = step_b.collective_trace(pb, ob, batch_b)
        assert tr_s.census() == tr_b.census()
        plan = plan_of_tree(params)
        assert tr_b.count("all_reduce") == plan.n_buckets + 1
        enforce("mlp_train_step", tr_b)  # the pre-existing pin, as-is

    def test_only_ordering_moves(self, comm):
        """Same multiset of record signatures, different sequence."""
        step_s, p, o, batch, _ = self._mnist_step(comm, "none")
        step_b, pb, ob, batch_b, _ = self._mnist_step(comm, "bucket")
        tr_s = step_s.collective_trace(p, o, batch)
        tr_b = step_b.collective_trace(pb, ob, batch_b)
        sig_s = [r.signature() for r in tr_s.records]
        sig_b = [r.signature() for r in tr_b.records]
        assert sorted(sig_s) == sorted(sig_b)
        assert sig_s != sig_b
        assert tr_s.trace_hash() != tr_b.trace_hash()

    def test_census_agrees_with_lowered_hlo(self, comm):
        """The walker counts the same overlapped program XLA lowers
        (the analyzer stays a first-class citizen of the new shape)."""
        from chainermn_tpu.analysis import assert_census_agreement

        step, p, o, batch, _ = self._mnist_step(comm, "bucket")
        tr = step.collective_trace(p, o, batch)
        txt = step.get_jitted(p, o).lower(p, o, batch).as_text()
        assert_census_agreement(tr, txt)

    def test_overlap_check_passes_on_scheduled_program(self, comm):
        step, p, o, batch, params = self._mnist_step(comm, "bucket")
        plan = plan_of_tree(params)
        assert plan.n_buckets >= 2
        jb = step.get_jitted(p, o).scheduled_jaxpr(p, o, batch)
        assert check_overlap(jb, plan) == []
        assert_overlap_order(jb, plan)  # assert-style spelling

    def test_overlap_check_fails_on_synchronous_program(self, comm):
        """The ordering-aware check is not vacuous: the synchronous
        multi-bucket program queues psums at the tail and FAILS."""
        step, p, o, batch, params = self._mnist_step(comm, "none")
        plan = plan_of_tree(params)
        closed = jax.make_jaxpr(step.get_jitted(p, o))(p, o, batch)
        findings = check_overlap(closed, plan)
        assert findings and all(f.severity == "error" for f in findings)
        with pytest.raises(AssertionError, match="issued late"):
            assert_overlap_order(closed, plan)

    def test_overlap_check_flags_missing_buckets(self, comm):
        """A program that does not carry the plan's fused reductions is
        an error, not a silent pass."""
        plan = plan_of_tree({"w": jnp.zeros((128,))})
        closed = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((4,)))
        findings = check_overlap(closed, plan)
        assert any("does not carry" in f.message for f in findings)

    def test_trace_guard_hash_agrees_per_mode(self, comm):
        """verify_collective_trace works on the overlapped step (the
        divergence guard is keyed per compiled program variant, and the
        overlapped variant hashes consistently)."""
        step, p, o, batch, _ = self._mnist_step(comm, "bucket")
        h1 = step.verify_collective_trace(p, o, batch)
        h2 = step.collective_trace(p, o, batch).trace_hash()
        assert h1 == h2


# ----------------------------------------------------------------------
# segment / bucket alignment
# ----------------------------------------------------------------------
class TestSegmentAlignment:
    def _aligned(self, step, p, o, batch, plan):
        """Common alignment pins: one fused psum per bucket, all at
        their dependency frontier, separated by real backward compute
        (the per-bucket segments)."""
        jb = step.get_jitted(p, o).scheduled_jaxpr(p, o, batch)
        recs = bucket_issue_report(jb, plan)
        assert len(recs) == plan.n_buckets
        assert all(r.delay == 0 for r in recs)
        # consecutive bucket issues are separated by >= 1 equation (the
        # pack of the next bucket at minimum, its backward segment in
        # general): the psums did NOT collapse into one tail cluster
        idx = sorted(r.index for r in recs)
        if len(idx) > 1:
            assert all(b - a > 1 for a, b in zip(idx, idx[1:]))
        return recs

    def test_mlp_per_layer_buckets_reverse_planner_order(self, comm):
        """On a sequential model with one bucket per layer, issue order
        is REVERSE planner order: backward finalizes the last layer's
        leaves first, so its bucket's psum dispatches first."""
        from chainermn_tpu.models import MLP

        model = MLP(n_units=64)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
        wire = WireConfig(codec="none", bucket_bytes=8, max_buckets=0)
        plan = plan_of_tree(params, wire.bucket_bytes, wire.max_buckets)
        assert plan.n_buckets == plan.n_leaves  # one bucket per leaf

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, wire=wire, overlap="bucket"
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((16, 28, 28)), step.batch_sharding),
            jax.device_put(jnp.zeros((16,), jnp.int32),
                           step.batch_sharding),
        )
        recs = self._aligned(step, p, o, batch, plan)
        # map issue order back to plan order via the (distinct) kernel
        # bucket sizes: Dense_0 784*64, Dense_1 64*64, Dense_2 64*10
        sizes_by_issue = [
            r.operand_shapes[0][0]
            for r in sorted(recs, key=lambda r: r.index)
        ]
        k0, k1, k2 = 784 * 64, 64 * 64, 64 * 10
        assert sizes_by_issue.index(k2) < sizes_by_issue.index(k1)
        assert sizes_by_issue.index(k1) < sizes_by_issue.index(k0)

    def test_resnet50_alignment_and_pinned_budget(self, comm):
        """ResNet-50: the default plan's buckets all issue at their
        frontier and the EXISTING resnet50 budget pin (<= 8 all-reduce)
        enforces the overlapped trace unchanged — 5 psums (4 buckets +
        loss pmean), only reordered."""
        from chainermn_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, train=False)
        params = jax.jit(model.init)(  # one program, not op by op
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
        )
        plan = plan_of_tree(params)
        assert plan.n_buckets >= 2

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, overlap="bucket"
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((8, 32, 32, 3)),
                           step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32),
                           step.batch_sharding),
        )
        self._aligned(step, p, o, batch, plan)
        tr = step.collective_trace(p, o, batch)
        assert tr.count("all_reduce") == plan.n_buckets + 1
        enforce("resnet50_train_step", tr)  # the pre-existing pin

    def test_transformer_alignment_and_pinned_budget(self, comm):
        from chainermn_tpu.models.transformer import TransformerLM, lm_loss

        model = TransformerLM(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2,
            max_len=64, dtype=jnp.float32,
        )
        toks = jnp.zeros((8, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks[:1])
        # force a multi-bucket plan on the tiny fixture while staying
        # inside the wire's promised <= 6-bucket ceiling (the budget
        # pin enforces buckets + loss pmean <= 8)
        wire = WireConfig(codec="none", bucket_bytes=16 * 1024)
        plan = plan_of_tree(params, wire.bucket_bytes, wire.max_buckets)
        assert plan.n_buckets >= 2

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, wire=wire, overlap="bucket"
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(toks, step.batch_sharding)
        self._aligned(step, p, o, batch, plan)
        enforce("transformer_train_step",
                step.collective_trace(p, o, batch))

    def test_int8_scale_pmax_stays_single_and_first(self, comm):
        """int8's batched absmax pmax remains ONE collective (census
        contract) and — depending on every bucket — necessarily issues
        before any int8 payload psum."""
        wire = WireConfig(codec="int8", **_TINY)
        p, o, step, batch, _ = _mlp3_setup(comm, wire, "bucket",
                                           n_steps=1)
        tr = step.collective_trace(p, o, batch)
        pmaxes = [r for r in tr.records if r.primitive == "pmax"]
        assert len(pmaxes) == 1
        order = [r.primitive for r in tr.records]
        int8_psums = [
            i for i, r in enumerate(tr.records)
            if r.primitive == "psum" and r.dtypes
            and r.dtypes[0] == "int32"
        ]
        assert order.index("pmax") < min(int8_psums)


# ----------------------------------------------------------------------
# plan hash / agreement untouched by the overlap mode
# ----------------------------------------------------------------------
class TestPlanHashUnaffected:
    def test_plan_is_mode_independent(self, comm):
        params = {"a": jnp.zeros((300,)), "b": jnp.zeros((40, 5))}
        opts = {
            mode: cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, overlap=mode
            )
            for mode in ("none", "bucket")
        }
        plans = {
            mode: plan_of_tree(
                params, o.wire.bucket_bytes, o.wire.max_buckets
            )
            for mode, o in opts.items()
        }
        assert plans["none"].plan_hash() == plans["bucket"].plan_hash()

    def test_plan_agreement_guard_runs_identically(self, monkeypatch,
                                                   comm):
        """optimizer.init's plan_agreement sees the same hash either
        way — overlap is a schedule, not a wire layout."""
        seen = {}

        def fake_agreement(c, plan, **kw):
            seen.setdefault("hashes", []).append(plan.plan_hash())
            return plan.plan_hash()

        monkeypatch.setattr(cw, "plan_agreement", fake_agreement)
        monkeypatch.setattr(comm.__class__, "process_count", 2,
                            raising=False)
        params = {"w": jnp.zeros((64,))}
        for mode in ("none", "bucket"):
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, overlap=mode
            )
            opt.init(params)
        monkeypatch.undo()
        assert len(seen["hashes"]) == 2
        assert seen["hashes"][0] == seen["hashes"][1]


# ----------------------------------------------------------------------
# engine plumbing
# ----------------------------------------------------------------------
class TestEngine:
    def test_resolve_overlap_forms(self):
        assert resolve_overlap(None) == "none"
        assert resolve_overlap("none") == "none"
        assert resolve_overlap("bucket") == "bucket"
        with pytest.raises(ValueError, match="overlap"):
            resolve_overlap("layer")

    def test_double_buffering_rejected(self, comm):
        with pytest.raises(ValueError, match="double_buffering"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, double_buffering=True,
                overlap="bucket",
            )

    def test_gspmd_path_rejected(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, overlap="bucket"
        )
        with pytest.raises(ValueError, match="use_shard_map"):
            build_train_step(
                comm, lambda p, b: jnp.sum(p["w"] * b), opt,
                use_shard_map=False, donate=False,
            )

    def test_schedule_jaxpr_is_pure_reorder(self):
        """Unit: same equation multiset, topological validity, value
        identity on a hand-built program with a fake 'collective'-free
        body (no collectives => unchanged at that level)."""
        def f(x):
            a = x * 2
            b = a + 1
            return b * a

        closed = jax.make_jaxpr(f)(jnp.zeros((4,)))
        out = schedule_jaxpr(closed)
        assert [e.primitive.name for e in out.jaxpr.eqns] == [
            e.primitive.name for e in closed.jaxpr.eqns
        ]

    def test_overlapped_step_caches_per_signature(self, comm):
        p, o, step, batch, _ = _mlp3_setup(comm, "auto", "bucket",
                                           n_steps=1)
        inner = step.get_jitted(p, o)
        assert isinstance(inner, OverlappedStep)
        n0 = len(inner._cache)
        inner(p, o, batch)
        inner(p, o, batch)
        assert len(inner._cache) == n0  # no retrace on same signature

    def test_overlapped_step_donation(self, comm):
        """donate=True consumes params/opt_state buffers on the second
        call (the first call's outputs feed the next), proving the flat
        donation mapping is live."""
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32)}

        def loss_fn(p, b):
            return jnp.mean((b @ p["w"]) ** 2)

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, overlap="bucket"
        )
        step = build_train_step(comm, loss_fn, opt)  # donate=True
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(
            jnp.asarray(rng.randn(16, 8), jnp.float32),
            step.batch_sharding,
        )
        p1, o1, _ = step(p, o, batch)
        p2, o2, _ = step(p1, o1, batch)
        assert jax.tree_util.tree_leaves(p1)[0].is_deleted()
        assert not jax.tree_util.tree_leaves(p2)[0].is_deleted()

    def test_issue_report_walks_nested_jaxprs(self, comm):
        p, o, step, batch, _ = _mlp3_setup(comm, "auto", "bucket",
                                           n_steps=1)
        # from the OUTER (jit-wrapped) program: the walker descends
        # pjit -> shard_map and still finds every collective
        closed = jax.make_jaxpr(step.get_jitted(p, o))(p, o, batch)
        recs = issue_report(closed)
        assert any(r.primitive == "psum" for r in recs)
        assert all(r.context for r in recs)  # all nested, none top-level

    def test_accum_steps_compose(self, comm):
        """Gradient accumulation (scan) composes: the scan body is left
        untouched, the post-scan bucket psums still overlap-schedule,
        numerics bit-identical."""
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32)}
        x = rng.randn(32, 8).astype(np.float32)
        y = (x @ rng.randn(8, 4)).astype(np.float32)

        def loss_fn(p, b):
            bx, by = b
            return jnp.mean((bx @ p["w"] - by) ** 2)

        outs = {}
        for mode in ("none", "bucket"):
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.05), comm, overlap=mode
            )
            step = build_train_step(
                comm, loss_fn, opt, accum_steps=2, donate=False
            )
            p, o = step.place(params, opt.init(params))
            batch = (
                jax.device_put(x, step.batch_sharding),
                jax.device_put(y, step.batch_sharding),
            )
            for _ in range(3):
                p, o, m = step(p, o, batch)
            outs[mode] = p
        _assert_tree_bit_equal(outs["none"], outs["bucket"])


# ----------------------------------------------------------------------
# bench rungs CI smoke
# ----------------------------------------------------------------------
class TestOverlapBenchRungsCI:
    def test_overlap_rungs_emit_protocol_json_on_cpu_mesh(self,
                                                          tmp_path):
        """Acceptance: the ``overlap_off/on`` A/B runs on the
        8-virtual-device CPU mesh and prints per-rung JSON carrying the
        min-of-N protocol fields plus the overlap/wire provenance —
        measurement-ready for the next TPU capture.  Tiny shapes via
        the HUNT_* knobs: a smoke of the harness, not a measurement."""
        import json as _json
        import os
        import subprocess
        import sys

        from conftest import subprocess_env

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        env = subprocess_env(8)
        env.update({"HUNT_MLP_UNITS": "32", "HUNT_MLP_BATCH": "8",
                    "HUNT_K": "4", "HUNT_REPEATS": "2"})
        rungs = ["overlap_off", "overlap_on"]
        proc = subprocess.run(
            [sys.executable,
             os.path.join(repo, "benchmarks", "comm_overlap_bench.py"),
             "--cpu-mesh", *rungs],
            env=env, capture_output=True, text=True, timeout=420,
            cwd=tmp_path,
        )
        assert proc.returncode == 0, (
            f"comm_overlap_bench exited {proc.returncode}\n"
            f"--- stdout ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr ---\n{proc.stderr[-3000:]}"
        )
        recs = {}
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                r = _json.loads(line)
                if "variant" in r:
                    recs[r["variant"]] = r
        assert set(rungs) <= set(recs), (rungs, sorted(recs))
        for name in rungs:
            r = recs[name]
            assert r["n_measurements"] >= 2, r
            if len([s for s in r["samples_ms"] if s > 0]) >= 2:
                assert "spread_max_over_min" in r, r
        assert recs["overlap_off"]["overlap"] == "none"
        assert recs["overlap_on"]["overlap"] == "bucket"
        # identical wire either side: the A/B isolates pure scheduling
        assert (recs["overlap_on"]["wire_buckets"]
                == recs["overlap_off"]["wire_buckets"])
        # the retired rung stayed retired (decision rule, ISSUE 8):
        # db's bench presence ended when the overlap engine landed
        sys.path.insert(0, os.path.join(repo, "benchmarks"))
        try:
            import comm_overlap_bench as _cob

            names = set(_cob._variants())
        finally:
            sys.path.pop(0)
        assert "wire_db_on" not in names
        assert {"overlap_off", "overlap_on", "overlap_resnet_off",
                "overlap_resnet_on"} <= names


# ----------------------------------------------------------------------
# satellite: pipelined eager tiers == serial, bit for bit
# ----------------------------------------------------------------------
class TestEagerPipelining:
    def _grads(self, size, seed=0):
        rng = np.random.RandomState(seed)
        return {
            "a": jnp.asarray(rng.randn(size, 6, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(size, 31), jnp.float32),
            "c": jnp.asarray(rng.randn(size, 5), jnp.bfloat16),
        }

    @staticmethod
    def _serial_reference(comm, grads, mean=True):
        """The pre-pipelining serial schedule, verbatim: pack, reduce
        bucket k fully, ship it, only then touch bucket k+1 — the
        arithmetic the pipelined path must reproduce bit for bit."""
        dt = comm.allreduce_grad_dtype
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        hosts = [np.asarray(jax.device_get(g)) for g in leaves]
        size = comm.size
        plan = cw.make_plan([h[0] for h in hosts])
        placed = []
        for cat in cw.pack_stacked(plan, hosts, size, xp=np):
            if dt is None:
                red = cat.mean(axis=0) if mean else cat.sum(axis=0)
            else:
                red = np.sum(cat.astype(dt), axis=0, dtype=dt)
                red = red.astype(cat.dtype)
                if mean:
                    red = red / size
            placed.append(jnp.asarray(
                np.broadcast_to(red, cat.shape).copy()
            ))
        out = cw.unpack_stacked(
            plan, placed, [h.shape for h in hosts]
        )
        return jax.tree_util.tree_unflatten(treedef, out)

    @pytest.mark.parametrize("dtype", [None, "bfloat16"])
    @pytest.mark.parametrize("mean", [True, False])
    def test_host_staged_pipelined_equals_serial(self, devices8, dtype,
                                                 mean):
        """Satellite acceptance: the ThreadPool-pipelined host-staged
        bucket exchange (bucket k+1's reduce overlapping bucket k's
        device_put) returns EXACTLY the serial schedule's result — per
        bucket the arithmetic and order are unchanged."""
        comm = cmn.create_communicator(
            "non_cuda_aware", devices=devices8,
            allreduce_grad_dtype=dtype,
        )
        grads = self._grads(comm.size)
        out = comm.allreduce_grad(grads, mean=mean)
        ref = self._serial_reference(comm, grads, mean=mean)
        _assert_tree_bit_equal(
            jax.tree_util.tree_map(lambda x: np.asarray(x), out), ref
        )

    def test_xla_eager_staged_dispatch_matches_oracle(self, devices8):
        """All-buckets-staged-then-reduced dispatch (the pipelined
        order) returns the same means as the numpy oracle."""
        comm = cmn.create_communicator("tpu", devices=devices8)
        grads = self._grads(comm.size, seed=7)
        out = comm.allreduce_grad(grads, mean=True)
        for k, g in grads.items():
            np.testing.assert_allclose(
                np.asarray(out[k][0], np.float32),
                np.asarray(jax.device_get(g), np.float32).mean(axis=0),
                rtol=2e-2, atol=1e-2,
            )
