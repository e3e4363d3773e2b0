"""Gradient wire tests: bucketed fused allreduce + compressed codecs.

ISSUE 4 tentpole pins, in order of load-bearingness:

* the compiled ResNet-50 train step lowers to <= 8 ``all-reduce`` HLO
  ops under the default bucket plan (vs one per gradient leaf — 267 —
  before the wire layer), counted in the lowered StableHLO text the
  same way PR 2's ``block_census`` pinned the kernel taxonomy;
* the uncompressed bucketed sync is BIT-IDENTICAL to the per-leaf path
  (flatten order is tree-flatten order, reduction is elementwise, so
  grouping changes neither the summands nor their rank order) —
  asserted at 0 tolerance;
* int8 wire + error feedback converges to within 1% of fp32 sync on
  the MLP tier over 200 steps;
* the bucket plan is a pure function of shapes (deterministic across
  processes — same shapes, same hash);
* the reduced-precision mean divides AFTER casting off the wire: the
  old ``psum(g.astype(bf16)) / n`` order rounded the mean to bf16 for
  no wire-byte saving; the ULP test below constructs a mean that the
  old order misses by a full bf16 ULP and the new order hits exactly.
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu import comm_wire as cw
from chainermn_tpu.comm_wire import (
    WireConfig,
    WirePlanMismatchError,
    codec_of_dtype,
    flatten_to_buckets,
    make_plan,
    plan_agreement,
    plan_of_tree,
    resolve_wire,
    storage_dtype,
    unflatten_from_buckets,
    zero_residuals,
)
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


# ----------------------------------------------------------------------
# planner: plan shape, determinism, round trip
# ----------------------------------------------------------------------
def _mixed_tree():
    rng = np.random.RandomState(7)
    return {
        "a": {
            "w": jnp.asarray(rng.randn(3, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(7), jnp.bfloat16),
        },
        "scalar": jnp.asarray(1.25, jnp.float32),
        "ints": jnp.asarray(rng.randint(0, 100, (2, 2)), jnp.int32),
        "more": [
            jnp.asarray(rng.randn(5, 5), jnp.float32),
            jnp.asarray(rng.randn(6), jnp.bfloat16),
        ],
    }


class TestPlanner:
    def test_round_trip_mixed_dtypes_bit_exact(self):
        tree = _mixed_tree()
        plan = plan_of_tree(tree)
        buckets = flatten_to_buckets(plan, tree)
        out = unflatten_from_buckets(plan, buckets, tree)
        _assert_tree_bit_equal(out, tree)

    def test_round_trip_scalar_leaf_only(self):
        tree = {"s": jnp.asarray(3.5, jnp.float32)}
        plan = plan_of_tree(tree)
        assert plan.n_leaves == 1 and plan.n_buckets == 1
        out = unflatten_from_buckets(
            plan, flatten_to_buckets(plan, tree), tree
        )
        _assert_tree_bit_equal(out, tree)

    def test_round_trip_empty_tree(self):
        plan = plan_of_tree({})
        assert plan.n_leaves == 0 and plan.n_buckets == 0
        assert flatten_to_buckets(plan, {}) == []
        assert unflatten_from_buckets(plan, [], {}) == {}

    def test_round_trip_tiny_buckets(self):
        # bucket_bytes=1: every leaf gets its own bucket, still exact
        tree = _mixed_tree()
        plan = plan_of_tree(tree, bucket_bytes=1, max_buckets=0)
        assert plan.n_buckets == plan.n_leaves
        out = unflatten_from_buckets(
            plan, flatten_to_buckets(plan, tree), tree
        )
        _assert_tree_bit_equal(out, tree)

    def test_buckets_are_dtype_homogeneous(self):
        plan = plan_of_tree(_mixed_tree(), bucket_bytes=64)
        leaves = jax.tree_util.tree_leaves(_mixed_tree())
        for b in plan.buckets:
            for s in b.slots:
                assert leaves[s.index].dtype == jnp.dtype(b.dtype)

    def test_slots_contiguous_in_flatten_order(self):
        plan = plan_of_tree(_mixed_tree(), bucket_bytes=1 << 30)
        for b in plan.buckets:
            off = 0
            last_index = -1
            for s in b.slots:
                assert s.offset == off
                assert s.index > last_index  # tree-flatten order
                off += s.size
                last_index = s.index
            assert off == b.size

    def test_every_leaf_covered_exactly_once(self):
        plan = plan_of_tree(_mixed_tree(), bucket_bytes=64)
        seen = sorted(
            s.index for b in plan.buckets for s in b.slots
        )
        assert seen == list(range(plan.n_leaves))

    def test_max_buckets_coalesces_upward(self):
        # 40 x 1KiB f32 leaves with a 1KiB target would be 40 buckets;
        # max_buckets=6 must coalesce to <= 6
        leaves = [jnp.zeros((256,), jnp.float32) for _ in range(40)]
        plan = make_plan(leaves, bucket_bytes=1024, max_buckets=6)
        assert plan.n_buckets <= 6
        unbounded = make_plan(leaves, bucket_bytes=1024, max_buckets=0)
        assert unbounded.n_buckets == 40

    def test_dtype_floor_beats_max_buckets(self):
        # 3 dtypes cannot fit in 2 buckets: the floor is one per dtype
        leaves = [
            jnp.zeros((4,), jnp.float32),
            jnp.zeros((4,), jnp.bfloat16),
            jnp.zeros((4,), jnp.int32),
        ]
        plan = make_plan(leaves, bucket_bytes=1, max_buckets=2)
        assert plan.n_buckets == 3

    def test_oversized_leaf_gets_own_bucket(self):
        leaves = [
            jnp.zeros((4,), jnp.float32),
            jnp.zeros((10_000,), jnp.float32),  # >> bucket_bytes
            jnp.zeros((4,), jnp.float32),
        ]
        plan = make_plan(leaves, bucket_bytes=64, max_buckets=0)
        sizes = sorted(len(b.slots) for b in plan.buckets)
        assert 10_000 in [b.size for b in plan.buckets]
        assert sizes.count(1) >= 1

    def test_plan_is_pure_function_of_shapes(self):
        # arrays vs ShapeDtypeStructs vs different VALUES: same plan hash
        tree = _mixed_tree()
        structs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree
        )
        other_values = jax.tree_util.tree_map(
            lambda l: (l * 0 + 1).astype(l.dtype), tree
        )
        h = plan_of_tree(tree).plan_hash()
        assert plan_of_tree(structs).plan_hash() == h
        assert plan_of_tree(other_values).plan_hash() == h

    def test_plan_hash_changes_with_shapes_and_knobs(self):
        tree = _mixed_tree()
        h = plan_of_tree(tree).plan_hash()
        grown = dict(tree, extra=jnp.zeros((9,), jnp.float32))
        assert plan_of_tree(grown).plan_hash() != h
        assert plan_of_tree(tree, bucket_bytes=64).plan_hash() != h

    def test_leaf_count_mismatch_raises(self):
        tree = _mixed_tree()
        plan = plan_of_tree(tree)
        with pytest.raises(ValueError, match="leaves"):
            flatten_to_buckets(plan, {"just_one": jnp.zeros((3,))})
        with pytest.raises(ValueError, match="leaves"):
            unflatten_from_buckets(plan, [], {"just_one": jnp.zeros((3,))})

    def test_bad_bucket_bytes_rejected(self):
        with pytest.raises(ValueError, match="bucket_bytes"):
            make_plan([jnp.zeros((3,))], bucket_bytes=0)


# ----------------------------------------------------------------------
# codecs: config resolution + storage dtype
# ----------------------------------------------------------------------
class TestWireConfig:
    def test_unknown_codec_rejected(self):
        with pytest.raises(ValueError, match="codec"):
            WireConfig(codec="int4").validate()

    @pytest.mark.parametrize("codec", ["none", "f32"])
    def test_error_feedback_needs_lossy_codec(self, codec):
        with pytest.raises(ValueError, match="error_feedback"):
            WireConfig(codec=codec, error_feedback=True).validate()

    def test_codec_of_dtype_reference_parity(self):
        # the reference's PureNcclCommunicator(allreduce_grad_dtype=...)
        # knob maps onto codec names
        assert codec_of_dtype(None) == "none"
        assert codec_of_dtype(jnp.float16) == "f16"
        assert codec_of_dtype(jnp.bfloat16) == "bf16"
        assert codec_of_dtype(jnp.float32) == "f32"
        with pytest.raises(ValueError, match="int8"):
            codec_of_dtype(jnp.int8)

    def test_resolve_wire_forms(self, comm):
        assert resolve_wire("per_leaf", comm) is None
        assert resolve_wire(None, comm).codec == "none"
        assert resolve_wire("auto", comm).codec == "none"
        assert resolve_wire("int8", comm).codec == "int8"
        explicit = WireConfig(codec="bf16", bucket_bytes=123)
        assert resolve_wire(explicit, comm) == explicit
        with pytest.raises(ValueError, match="wire"):
            resolve_wire(42, comm)

    def test_resolve_wire_auto_follows_comm_dtype(self, devices8):
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        assert resolve_wire("auto", c).codec == "bf16"

    def test_auto_falls_back_per_leaf_on_uncodeced_dtype(self, devices8):
        """An allreduce_grad_dtype with no wire codec (float64) worked
        as a bare per-leaf cast before the wire layer; the "auto"
        default must keep that working (legacy path) instead of raising
        at optimizer construction.  Only an explicit codec raises."""
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype="float64"
        )
        assert resolve_wire("auto", c) is None
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), c)
        assert opt.wire is None  # legacy per-leaf cast path
        with pytest.raises(ValueError, match="float64"):
            resolve_wire("float64", c)

    def test_storage_dtype_never_widens(self):
        # cast codecs store in the wire dtype (half the db state bytes)
        assert storage_dtype(
            WireConfig(codec="bf16"), jnp.float32
        ) == jnp.dtype(jnp.bfloat16)
        # ... unless that would WIDEN the gradient
        assert storage_dtype(
            WireConfig(codec="f32"), jnp.bfloat16
        ) == jnp.dtype(jnp.bfloat16)
        # none/int8 store natively (int8's scale is sync-time state)
        assert storage_dtype(
            WireConfig(codec="none"), jnp.float32
        ) == jnp.dtype(jnp.float32)
        assert storage_dtype(
            WireConfig(codec="int8"), jnp.float32
        ) == jnp.dtype(jnp.float32)

    def test_zero_residuals_match_plan_layout(self):
        tree = _mixed_tree()
        plan = plan_of_tree(tree)
        res = zero_residuals(plan, tree)
        assert len(res) == plan.n_buckets
        for r, b in zip(res, plan.buckets):
            assert r.shape == (b.size,)
            assert r.dtype == jnp.dtype(b.dtype)
            assert not np.any(np.asarray(r, np.float32))


# ----------------------------------------------------------------------
# compiled tier: bit identity + HLO collective census
# ----------------------------------------------------------------------
def _two_leaf_loss(params, batch):
    m = batch.mean(axis=0)
    return 0.5 * jnp.sum((params["a"] - m[:4]) ** 2) + 0.5 * jnp.sum(
        (params["b"] - m[4:].reshape(1, 3)) ** 2
    )


def _run_steps(comm, wire, n_steps=3, lr=0.7, dtype=None, db=False):
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(lr), comm, wire=wire, double_buffering=db
    )
    params = {"a": jnp.zeros((4,)), "b": jnp.zeros((1, 3))}
    step = build_train_step(comm, _two_leaf_loss, opt, donate=False)
    p, o = step.place(params, opt.init(params))
    x = jnp.asarray(
        np.random.RandomState(3).randn(8, 7), jnp.float32
    )
    bx = jax.device_put(x, step.batch_sharding)
    for _ in range(n_steps):
        p, o, _ = step(p, o, bx)
    return p


class TestBitIdentity:
    def test_uncompressed_bucketed_equals_per_leaf_exactly(self, comm):
        """Acceptance: f32 wire, 0 tolerance.  Within a bucket leaf data
        is concatenated in tree-flatten order; psum is elementwise, so
        grouping changes neither summands nor their rank order."""
        p_leaf = _run_steps(comm, "per_leaf")
        p_wire = _run_steps(comm, "auto")
        _assert_tree_bit_equal(p_leaf, p_wire)

    def test_bf16_wire_bucketed_equals_per_leaf_exactly(self, devices8):
        # cast codecs too: cast -> psum -> cast back -> /n runs the same
        # elementwise program either way
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        p_leaf = _run_steps(c, "per_leaf")
        p_wire = _run_steps(c, "auto")
        _assert_tree_bit_equal(p_leaf, p_wire)

    def test_update_applies_mean_gradient_on_wire(self, comm):
        # the canonical TestGradientSync numbers, through the wire
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(1.0), comm, wire="auto"
        )
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        x = jnp.stack([jnp.full((4,), float(r)) for r in range(8)])
        p, _, _ = step(p, o, jax.device_put(x, step.batch_sharding))
        np.testing.assert_allclose(np.asarray(p["w"]), 3.5, rtol=1e-6)


class TestReducedPrecisionMeanULP:
    def test_divide_runs_off_the_wire(self, devices8):
        """Satellite: the mean divide happens AFTER casting back to the
        param dtype.  5 ranks contribute bf16-exact grads summing to 16;
        16/5 = 3.2 is NOT bf16-representable.  The fixed order returns
        float32(16)/5 (exact in f32); the old ``psum/n``-in-bf16 order
        returned bf16(3.2) = 3.203125 — one full bf16 ULP worse.  Both
        the per-leaf path and the bucketed wire must hit the f32 value
        bit-exactly."""
        c5 = cmn.create_communicator(
            "tpu", devices=devices8[:5], allreduce_grad_dtype=jnp.bfloat16
        )
        vals = np.asarray([1.0, 2.0, 3.0, 4.0, 6.0], np.float32)

        def loss(p, b):
            # one row per rank: local grad = w - row = vals[r] at w=0
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        exact = np.float32(16.0) / np.float32(5.0)
        old_order = np.float32(
            jnp.asarray(16.0, jnp.bfloat16) / jnp.asarray(5, jnp.bfloat16)
        )
        assert old_order != exact  # the ULP gap this test pins

        for wire in ("per_leaf", "auto"):
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(1.0), c5, wire=wire
            )
            params = {"w": jnp.zeros((2,))}
            step = build_train_step(comm=c5, loss_fn=loss, optimizer=opt,
                                    donate=False)
            p, o = step.place(params, opt.init(params))
            x = jnp.stack([jnp.full((2,), -v) for v in vals])
            p, _, _ = step(p, o, jax.device_put(x, step.batch_sharding))
            # sgd(1.0) from 0: w = -mean(grad) = +3.2 exactly, in f32
            np.testing.assert_array_equal(
                np.asarray(p["w"]), np.full((2,), -exact)
            )


def _count_all_reduce(step, p, o, batch):
    """Collective count via the STATIC analyzer (jaxpr walk — nothing
    lowers or compiles), which ISSUE 5 makes the source of truth for
    these pins; the HLO-text cross-check below keeps the walker honest
    against what XLA actually sees."""
    return step.collective_trace(p, o, batch).count("all_reduce")


class TestHLOCollectiveCensus:
    """Structural verification: the train step's all-reduce count equals
    bucket count + 1 (the loss pmean), not leaf count + 1.  Rewritten on
    the ISSUE 5 analyzer — the count pin reads the jaxpr walker's
    census, so the pin and the walk cannot drift apart — with ONE
    HLO-text cross-check retained (test_census_agrees_with_hlo_text)
    proving the walker counts the same program XLA lowers."""

    def _mnist_setup(self, comm, wire):
        from chainermn_tpu.models import MLP

        model = MLP(n_units=1000)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, wire=wire
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((64, 28, 28)), step.batch_sharding),
            jax.device_put(jnp.zeros((64,), jnp.int32),
                           step.batch_sharding),
        )
        return step, p, o, batch, params

    def test_mnist_bucketed_vs_per_leaf(self, comm):
        step, p, o, batch, params = self._mnist_setup(comm, "per_leaf")
        n_leaves = len(jax.tree_util.tree_leaves(params))
        assert _count_all_reduce(step, p, o, batch) == n_leaves + 1

        step, p, o, batch, params = self._mnist_setup(comm, "auto")
        plan = plan_of_tree(params)
        assert plan.n_buckets < n_leaves
        tr = step.collective_trace(p, o, batch)
        assert tr.count("all_reduce") == plan.n_buckets + 1
        # the MLP-tier budget pin: small trees still bucket (a bucketing
        # regression back to the leaf storm trips this, not just resnet)
        from chainermn_tpu.analysis import enforce

        enforce("mlp_train_step", tr)

    def test_census_agrees_with_hlo_text(self, comm):
        """The retained HLO-text cross-check: the jaxpr walker and a
        grep of the lowered StableHLO count the same all-reduces on the
        bucketed MNIST step — the two censuses verify each other, so a
        walker regression (missed sub-jaxpr) or a lowering surprise
        (GSPMD inserting a reduce) fails here."""
        from chainermn_tpu.analysis import assert_census_agreement

        step, p, o, batch, params = self._mnist_setup(comm, "auto")
        tr = step.collective_trace(p, o, batch)
        txt = step.get_jitted(p, o).lower(p, o, batch).as_text()
        n_text = len(re.findall(r"stablehlo\.all_reduce", txt))
        agreed = assert_census_agreement(tr, txt)
        assert agreed["all_reduce"] == n_text == tr.count("all_reduce")

    def test_mnist_int8_adds_exactly_one_scale_collective(self, comm):
        # the per-bucket absmax agreement is ONE batched pmax, not one
        # per bucket: buckets + pmax + loss pmean
        step, p, o, batch, params = self._mnist_setup(
            comm, WireConfig(codec="int8")
        )
        plan = plan_of_tree(params)
        assert _count_all_reduce(step, p, o, batch) == plan.n_buckets + 2

    def test_resnet50_lowers_to_at_most_8_all_reduces(self, comm):
        """Acceptance criterion: 267 gradient leaves -> default plan's
        4 buckets -> 5 all-reduce ops (4 grad buckets + loss pmean),
        enforced via the analyzer's pinned budget AND cross-checked
        against the lowered HLO text (ISSUE 5 acceptance: the walker
        agrees with the HLO census on the ResNet-50 step)."""
        from chainermn_tpu.analysis import assert_census_agreement, enforce
        from chainermn_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, train=False)
        params = jax.jit(model.init)(  # one program, not op by op
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
        )
        n_leaves = len(jax.tree_util.tree_leaves(params))
        assert n_leaves > 200  # the leaf storm the wire replaces

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((8, 32, 32, 3)), step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32), step.batch_sharding),
        )
        tr = step.collective_trace(p, o, batch)
        n = tr.count("all_reduce")
        plan = plan_of_tree(params)
        assert n == plan.n_buckets + 1
        # the pinned budget (analysis.budgets): <= 8 all-reduce
        enforce("resnet50_train_step", tr)
        # the walker counts the same program XLA lowers
        txt = step.get_jitted(p, o).lower(p, o, batch).as_text()
        assert_census_agreement(tr, txt)


# ----------------------------------------------------------------------
# int8 + error feedback
# ----------------------------------------------------------------------
class TestInt8ErrorFeedback:
    def _mlp_run(self, comm, wire, n_steps, lr=0.05):
        rng = np.random.RandomState(0)
        w_true = rng.randn(8, 4).astype(np.float32)
        x = rng.randn(64, 8).astype(np.float32)
        y = x @ w_true
        params = {
            "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
            "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
        }

        def loss_fn(p, b):
            bx, by = b
            h = jnp.tanh(bx @ p["w1"])
            return jnp.mean((h @ p["w2"] - by) ** 2)

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(lr), comm, wire=wire
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.asarray(x), step.batch_sharding),
            jax.device_put(jnp.asarray(y), step.batch_sharding),
        )
        loss = None
        for _ in range(n_steps):
            p, o, m = step(p, o, batch)
            loss = float(m["loss"])
        return loss, p, o

    def test_int8_ef_converges_with_fp32_equivalent_loss(self, comm):
        """Acceptance: int8 wire + error feedback matches fp32 sync
        within 1% training loss on the MLP tier over 200 steps."""
        l_fp32, _, _ = self._mlp_run(comm, "auto", 200)
        l_int8, _, _ = self._mlp_run(
            comm, WireConfig(codec="int8", error_feedback=True), 200
        )
        assert l_int8 <= l_fp32 * 1.01 + 1e-7, (
            f"int8+EF loss {l_int8} vs fp32 {l_fp32} exceeds 1%"
        )

    def test_error_feedback_residual_carried_in_state(self, comm):
        wire = WireConfig(codec="int8", error_feedback=True)
        _, _, o = self._mlp_run(comm, wire, 2)
        # state carries one flat residual per bucket, and quantization
        # of off-grid gradients leaves a nonzero residual behind
        res = o.wire_residual
        assert isinstance(res, tuple) and len(res) >= 1
        assert any(np.any(np.asarray(r) != 0) for r in res)

    def test_no_error_feedback_no_residual_state(self, comm):
        _, _, o = self._mlp_run(comm, WireConfig(codec="int8"), 2)
        assert o.wire_residual == ()

    def test_int8_mean_is_scale_correct(self, comm):
        # values exactly on the int8 grid reduce exactly: grads all
        # equal -> mean == the value (absmax scale maps it to +/-127)
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(1.0), comm, wire=WireConfig(codec="int8")
        )
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        x = jnp.full((8, 4), 2.0)  # same grad everywhere: w - 2
        p, _, _ = step(p, o, jax.device_put(x, step.batch_sharding))
        np.testing.assert_allclose(np.asarray(p["w"]), 2.0, rtol=1e-6)


# ----------------------------------------------------------------------
# composition: double buffering, ZeRO, config rejections
# ----------------------------------------------------------------------
class TestDoubleBufferingWire:
    def test_stale_grad_state_is_flat_buckets(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, double_buffering=True,
            wire=WireConfig(codec="bf16"),
        )
        params = {"a": jnp.zeros((4,)), "b": jnp.zeros((1, 3))}
        state = opt.init(params)
        plan = plan_of_tree(params)
        assert isinstance(state.prev_grads, tuple)
        assert len(state.prev_grads) == plan.n_buckets
        # cast codec stores the stale buffer in the WIRE dtype — half
        # the state bytes, the same buffer the reference's swap held
        assert all(
            b.dtype == jnp.bfloat16 for b in state.prev_grads
        )

    def test_bucketed_db_matches_per_leaf_db_exactly(self, comm):
        p_leaf = _run_steps(comm, "per_leaf", db=True)
        p_wire = _run_steps(comm, "auto", db=True)
        _assert_tree_bit_equal(p_leaf, p_wire)

    def test_db_staleness_semantics_on_wire(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(1.0), comm, double_buffering=True, wire="auto"
        )
        params = {"w": jnp.zeros((2,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        x = jnp.stack([jnp.full((2,), float(r)) for r in range(8)])
        bx = jax.device_put(x, step.batch_sharding)
        p1, o, _ = step(p, o, bx)
        np.testing.assert_allclose(np.asarray(p1["w"]), 0.0, atol=1e-7)
        p2, o, _ = step(p1, o, bx)
        np.testing.assert_allclose(np.asarray(p2["w"]), 3.5, rtol=1e-6)


class TestZeroRedundancyWire:
    def test_bucketed_zero_matches_plain_adam(self, comm):
        params = {"w": jnp.ones((8,)) * 0.3, "v": jnp.ones((16,)) * -0.2}

        def loss(p, b):
            m = b.mean(axis=0)
            return 0.5 * jnp.sum((p["w"] - m[:8]) ** 2) + 0.5 * jnp.sum(
                (p["v"] - m[8:]) ** 2
            )

        def run(opt):
            step = build_train_step(comm, loss, opt, donate=False)
            p, o = step.place(params, opt.init(params))
            x = jnp.asarray(
                np.random.RandomState(5).randn(8, 24), jnp.float32
            )
            bx = jax.device_put(x, step.batch_sharding)
            for _ in range(3):
                p, o, _ = step(p, o, bx)
            return p

        p_plain = run(cmn.create_multi_node_optimizer(optax.adam(0.1), comm))
        p_zero = run(cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        ))
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_plain[k]), np.asarray(p_zero[k]), rtol=1e-5
            )

    def test_int8_zero_rejected(self, comm):
        with pytest.raises(ValueError, match="int8"):
            cmn.create_multi_node_optimizer(
                optax.adam(0.1), comm, zero_redundancy=True, wire="int8"
            )

    def test_error_feedback_zero_rejected(self, comm):
        with pytest.raises(ValueError, match="error_feedback"):
            cmn.create_multi_node_optimizer(
                optax.adam(0.1), comm, zero_redundancy=True,
                wire=WireConfig(codec="bf16", error_feedback=True),
            )

    def test_error_feedback_double_buffering_rejected(self, comm):
        with pytest.raises(ValueError, match="error_feedback"):
            cmn.create_multi_node_optimizer(
                optax.adam(0.1), comm, double_buffering=True,
                wire=WireConfig(codec="bf16", error_feedback=True),
            )


# ----------------------------------------------------------------------
# eager tier: bucketed allreduce_grad on the stacked-array communicators
# ----------------------------------------------------------------------
class TestEagerBucketedAllreduce:
    def _stacked_tree(self, comm, seed=11):
        rng = np.random.RandomState(seed)
        return {
            "w": jnp.asarray(rng.randn(comm.size, 3, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(comm.size, 5), jnp.float32),
        }

    def test_xla_bucketed_mean_matches_oracle(self, comm):
        grads = self._stacked_tree(comm)
        out = comm.allreduce_grad(grads)
        for k in grads:
            expect = np.asarray(grads[k]).mean(0)
            for r in range(comm.size):
                np.testing.assert_allclose(
                    np.asarray(out[k])[r], expect, rtol=1e-5
                )

    def test_noncudaaware_bucketed_mean_matches_oracle(self, devices8):
        # "non_cuda_aware", not "naive": NaiveCommunicator inherits the
        # per-leaf base allreduce_grad — only this name exercises the
        # host-staged bucketed path in variants.py
        c = cmn.create_communicator("non_cuda_aware", devices=devices8)
        grads = self._stacked_tree(c)
        out = c.allreduce_grad(grads)
        for k in grads:
            expect = np.asarray(grads[k]).mean(0)
            for r in range(c.size):
                np.testing.assert_allclose(
                    np.asarray(out[k])[r], expect, rtol=1e-5
                )

    def test_empty_tree_passthrough(self, comm):
        assert comm.allreduce_grad({}) == {}

    def test_sum_without_wire_dtype_is_bucketed(self, comm):
        """mean=False with no wire dtype rides the bucketed path too
        (it used to fall back to the per-leaf collective storm)."""
        grads = self._stacked_tree(comm)
        out = comm.allreduce_grad(grads, mean=False)
        for k in grads:
            expect = np.asarray(grads[k]).sum(0)
            for r in range(comm.size):
                np.testing.assert_allclose(
                    np.asarray(out[k])[r], expect, rtol=1e-5
                )

    def test_cast_dtype_sum_not_mean(self, devices8):
        """``mean=False`` with a wire dtype must return the SUM: the
        cast fn pair carries a true sum variant (the old single cast fn
        always divided, handing a mean to callers asking for a sum)."""
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        rng = np.random.RandomState(3)
        # small integers: exactly representable in bf16, sums ≤ 32 are
        # exact too, so the oracle holds bit-for-bit despite the wire
        grads = {"w": jnp.asarray(
            rng.randint(0, 5, size=(c.size, 3, 4)), jnp.float32
        )}
        out = c.allreduce_grad(grads, mean=False)
        expect = np.asarray(grads["w"]).sum(0)
        for r in range(c.size):
            np.testing.assert_array_equal(np.asarray(out["w"])[r], expect)


# ----------------------------------------------------------------------
# the bench's pinned-profile resolution
# ----------------------------------------------------------------------
class TestPinnedProfileResolution:
    """``_pinned_profile``: the tuned rungs' pin-vs-calibrate decision.
    Review regression: a pinned path that stopped resolving silently
    demoted every capture to in-process calibration — fresh hash each
    run, every regression disclosed as RETUNED, the gate permanently
    off — so the MISSING-file case must say so on stderr.  A
    mesh-signature mismatch stays silent by design (one pinned file can
    only match one rung's mesh)."""

    @pytest.fixture()
    def bench(self):
        import os
        import sys

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        sys.path.insert(0, os.path.join(repo, "benchmarks"))
        try:
            import comm_overlap_bench as cob
        finally:
            sys.path.pop(0)
        return cob

    def _profile(self, mesh_axes):
        from chainermn_tpu.comm_wire import BandwidthProfile

        return BandwidthProfile(
            mesh_axes=mesh_axes,
            curves={("flat", "all_reduce"): ((1024, 1e9),
                                             (1 << 22, 1e9))},
            latency={"flat": 1e-4},
        )

    def test_unset_env_is_silent_none(self, bench, comm, monkeypatch,
                                      capsys):
        from chainermn_tpu.comm_wire import PROFILE_ENV

        monkeypatch.delenv(PROFILE_ENV, raising=False)
        assert bench._pinned_profile(comm.mesh) is None
        assert capsys.readouterr().err == ""

    def test_missing_pinned_path_discloses_on_stderr(self, bench, comm,
                                                     monkeypatch,
                                                     capsys):
        from chainermn_tpu.comm_wire import PROFILE_ENV

        monkeypatch.setenv(PROFILE_ENV, "/nonexistent/profile.json")
        assert bench._pinned_profile(comm.mesh) is None
        err = capsys.readouterr().err
        assert "does not exist" in err
        assert "retuned" in err

    def test_matching_pin_loads_and_mismatch_is_silent_none(
            self, bench, comm, monkeypatch, capsys, tmp_path):
        from chainermn_tpu.comm_wire import PROFILE_ENV

        good = self._profile((("mn", 8),))
        path = str(tmp_path / "pin.json")
        good.save(path)
        monkeypatch.setenv(PROFILE_ENV, path)
        got = bench._pinned_profile(comm.mesh)
        assert got is not None
        assert got.profile_hash() == good.profile_hash()
        # a pin for some OTHER mesh: fresh-calibration fallback, silent
        other = self._profile((("mn_inter", 2), ("mn_intra", 4)))
        other.save(path)
        assert bench._pinned_profile(comm.mesh) is None
        assert capsys.readouterr().err == ""


# ----------------------------------------------------------------------
# wire_* bench rungs: CI smoke on the CPU mesh
# ----------------------------------------------------------------------
class TestWireBenchRungsCI:
    def test_wire_rungs_emit_protocol_json_on_cpu_mesh(self, tmp_path):
        """Acceptance: the ``wire_*`` rungs of comm_overlap_bench.py run
        on the 8-virtual-device CPU mesh and print per-rung JSON carrying
        the min-of-N protocol fields (``n_measurements``/
        ``spread_max_over_min``) plus the wire provenance
        (``wire_codec``/``wire_buckets``) — measurement-ready for the
        next TPU capture.  Tiny shapes via the HUNT_* knobs so this is
        a smoke of the harness, not a measurement."""
        import json as _json
        import os
        import subprocess
        import sys

        from conftest import subprocess_env

        from chainermn_tpu.comm_wire import BandwidthProfile, PROFILE_ENV

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # a PINNED profile for the flat (mn, 8) mesh: the wire_tuned
        # rung must prefer it (stable hash: captures stay comparable),
        # while the hier rung's mesh signature mismatches and
        # falls back to in-process calibration (fresh hash -> disclosed
        # retune)
        pinned = BandwidthProfile(
            mesh_axes=(("mn", 8),),
            curves={("flat", "all_reduce"): ((1024, 1e8), (1 << 22, 1e9)),
                    ("flat", "reduce_scatter"): ((1024, 1e8),
                                                 (1 << 22, 1e9)),
                    ("flat", "all_gather"): ((1024, 1e8), (1 << 22, 1e9))},
            latency={"flat": 1e-4}, label="ci_pinned",
        )
        pinned_path = str(tmp_path / "pinned_profile.json")
        pinned.save(pinned_path)
        env = subprocess_env(8)
        env.update({"HUNT_MLP_UNITS": "32", "HUNT_MLP_BATCH": "8",
                    "HUNT_K": "4", "HUNT_REPEATS": "2",
                    "HUNT_CAL_SIZES": "4096,65536",
                    PROFILE_ENV: pinned_path})
        # one subprocess covers the PR 3 wire ladder, the ISSUE 11
        # multi-hop schedule rungs (wire_flat/wire_hier/wire_hier_int8
        # run on a hierarchical mesh of 2 synthetic slices — the bench
        # sets CHAINERMN_TPU_FAKE_SLICE_SIZE itself under --cpu-mesh)
        # AND the ISSUE 12 measured-autotune rungs (wire_tuned runs an
        # in-process calibration sweep, sizes kept tiny via
        # HUNT_CAL_SIZES)
        rungs = ["wire_perleaf_sync", "wire_bucketed_sync",
                 "wire_int8_sync",
                 "wire_flat", "wire_hier", "wire_hier_int8",
                 "wire_tuned_base", "wire_tuned", "wire_tuned_hier"]
        proc = subprocess.run(
            [sys.executable,
             os.path.join(repo, "benchmarks", "comm_overlap_bench.py"),
             "--cpu-mesh", *rungs],
            env=env, capture_output=True, text=True, timeout=560,
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
            # spread needs >= 2 POSITIVE paired samples; on the noisy
            # CPU mesh a sample can land non-positive — the protocol
            # then omits the field honestly rather than fabricating it
            if len([s for s in r["samples_ms"] if s > 0]) >= 2:
                assert "spread_max_over_min" in r, r
        assert recs["wire_perleaf_sync"]["wire_codec"] == "per_leaf"
        assert "wire_buckets" not in recs["wire_perleaf_sync"]
        assert recs["wire_bucketed_sync"]["wire_codec"] == "none"
        assert recs["wire_bucketed_sync"]["wire_buckets"] >= 1
        assert recs["wire_int8_sync"]["wire_codec"] == "int8"
        # the leaf storm the bucket plan replaces, in numbers
        assert (recs["wire_bucketed_sync"]["wire_buckets"]
                < recs["wire_perleaf_sync"]["wire_n_leaves"])
        # ISSUE 11 rungs: schedule/codec fingerprints on a genuinely
        # factorized (2, 4) hierarchical mesh — wire_flat pins the
        # single-psum baseline, wire_hier/_int8 the staged program
        for name in ("wire_flat", "wire_hier", "wire_hier_int8"):
            assert recs[name]["mesh_shape"] == {
                "mn_inter": 2, "mn_intra": 4,
            }, recs[name]
            assert "wire_plan_hash" in recs[name]
        assert recs["wire_flat"]["wire_schedules"] == {
            "flat": recs["wire_flat"]["wire_buckets"]
        }
        assert recs["wire_hier"]["wire_schedules"] == {
            "hier_rs_ag": recs["wire_hier"]["wire_buckets"]
        }
        assert recs["wire_hier_int8"]["wire_codec"] == "int8"
        assert recs["wire_hier_int8"]["wire_schedules"] == {
            "hier_rs_ag": recs["wire_hier_int8"]["wire_buckets"]
        }
        # same layout, different schedule => different agreed plan hash
        assert (recs["wire_flat"]["wire_plan_hash"]
                != recs["wire_hier"]["wire_plan_hash"])
        # ISSUE 12 rungs: the tuned legs carry full provenance — the
        # profile content hash, the tuner's chosen knobs, and a plan
        # hash that differs from the untuned leg's (the profile hash
        # is folded in); the fixed-constant base leg carries none
        assert "profile_hash" not in recs["wire_tuned_base"]
        for name in ("wire_tuned", "wire_tuned_hier"):
            r = recs[name]
            assert r["profile_hash"], r
            assert r["tuned_max_buckets"] >= 1, r
            assert r["tuned_bucket_bytes"] >= 1, r
            assert r["wire_schedules"], r
            assert r["predicted_sync_ms"] > 0, r
        assert (recs["wire_tuned"]["wire_plan_hash"]
                != recs["wire_tuned_base"]["wire_plan_hash"])
        assert recs["wire_tuned_hier"]["mesh_shape"] == {
            "mn_inter": 2, "mn_intra": 4,
        }
        # pinned-vs-fresh provenance: the flat rung used the env
        # profile (hash stable -> gateable), the hier rung's mesh
        # mismatched it and calibrated fresh (hash differs -> retune
        # disclosure path)
        assert recs["wire_tuned"]["profile_hash"] \
            == pinned.profile_hash()[:12]
        assert recs["wire_tuned_hier"]["profile_hash"] \
            != pinned.profile_hash()[:12]


# ----------------------------------------------------------------------
# cross-process plan agreement
# ----------------------------------------------------------------------
class TestPlanAgreement:
    def test_agreement_on_real_communicator(self, comm):
        plan = plan_of_tree(_mixed_tree())
        assert plan_agreement(comm, plan) == plan.plan_hash()

    def test_truncated_payload_is_retried_in_lockstep(self, comm):
        """The mp satellite's single-controller half: a truncated
        exchange payload surfaces as PayloadCorruptionError on EVERY
        rank, plan_agreement retries the whole exchange, and the run
        completes (the 2-process version lives in mp_worker.py's
        wire_int8 scenario)."""
        from chainermn_tpu.resilience.fault_injection import (
            FaultSpec, inject_faults,
        )

        plan = plan_of_tree(_mixed_tree())
        with inject_faults(
            [FaultSpec("obj_store.exchange", "truncate", at=[1],
                       truncate_to=4)]
        ) as inj:
            assert plan_agreement(comm, plan) == plan.plan_hash()
        assert inj.log.counts.get("fault_injected", 0) >= 1

    def test_mismatch_raises(self):
        class FakeComm:
            def allgather_obj(self, h):
                return [h, "a-divergent-plan-hash"]

        plan = plan_of_tree(_mixed_tree())
        with pytest.raises(WirePlanMismatchError, match="mismatch"):
            plan_agreement(FakeComm(), plan)

    class _DivergentComm:
        """Multi-process comm whose world disagrees on the plan."""

        process_count = 2
        allreduce_grad_dtype = None
        axis_names = ("mn",)

        def allgather_obj(self, h):
            return [h, "a-divergent-plan-hash"]

    def test_optimizer_init_guards_plan_in_multi_process_world(self):
        """The guard is production-wired, not opt-in: ``init`` on a
        multi-process world exchanges the plan hash and fails loudly on
        divergence — BEFORE the first bucketed collective can deadlock
        or silently mix wire layouts."""
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), self._DivergentComm()
        )
        with pytest.raises(WirePlanMismatchError, match="mismatch"):
            opt.init(_mixed_tree())

    def test_init_guard_skips_under_tracing(self):
        """Traced init (eval_shape/jit) cannot run an eager obj
        exchange — the guard steps aside instead of crashing."""
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), self._DivergentComm()
        )
        state = jax.eval_shape(opt.init, _mixed_tree())
        assert state is not None
