"""Sharding-flow, implicit-collective attribution, HBM estimation, and
the per-collective cost model (ISSUE 6 tentpole).

Pins, in order of load-bearingness:

* the seeded mismatched-sharding fixture: a partitioner-inserted
  all-gather the author never wrote fails the ``implicit_collectives``
  check with an equation-level citation (XLA op metadata + the
  sharding-flow pass's reshard site, both naming the dot_general);
* the four pinned train steps — ResNet-50, transformer, ZeRO, MoE —
  pass attribution with ZERO unattributed collectives against their
  COMPILED text (the partitioner runs at compile time; the StableHLO
  lowering cannot contain its insertions);
* the live-range HBM estimator: per-rank breakdown read off the
  shard_map body (ZeRO state at 1/n), ceilings enforced via
  ``enforce_memory`` like the collective budgets, and the estimate
  cross-checked against XLA's own ``memory_analysis()`` within a
  documented tolerance;
* every CollectiveRecord carries ``bytes_on_wire`` + ``hop``, and the
  comm_wire planner's ``tune_wire_for_trace`` consumes them (the
  cost-model decision path).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu import comm_wire as cw
from chainermn_tpu.analysis import (
    HBM_BUDGETS,
    ImplicitCollectiveError,
    MemoryBudgetError,
    assert_attributed,
    attribute_collectives,
    check_implicit_collectives,
    enforce,
    enforce_memory,
    estimate_hbm,
    hlo_collective_ops,
    hop_class,
    memory_budget_for,
    shardflow,
    trace_collectives,
    trace_jaxpr,
    train_step_memory,
    wire_bytes,
)
from chainermn_tpu.optimizers import build_train_step

MiB = 1024 * 1024


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


def _smap(fn, mesh, n_in=1, out_spec=None):
    spec = P("mn")
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple([spec] * n_in),
        out_specs=spec if out_spec is None else out_spec,
        check_vma=False,
    )


# ----------------------------------------------------------------------
# the seeded mismatched-sharding fixture
# ----------------------------------------------------------------------
class TestImplicitCollectiveFixture:
    def _fixture(self, mesh8):
        def f(x):
            return x @ x.T

        jitted = jax.jit(
            f,
            in_shardings=NamedSharding(mesh8, P("mn", None)),
            out_shardings=NamedSharding(mesh8, P()),
        )
        sds = jax.ShapeDtypeStruct((8, 4), jnp.float32)
        txt = jitted.lower(sds).compile().as_text()
        tr = trace_collectives(f, sds)
        flow = shardflow(f, sds, in_specs=(P("mn", None),),
                         out_specs=(P(),))
        return tr, txt, flow

    def test_partitioner_inserted_all_gather_is_flagged(self, mesh8):
        """Acceptance: the seeded fixture produces a partitioner-
        inserted all-gather that the check flags as an error, while the
        authored trace is empty."""
        tr, txt, flow = self._fixture(mesh8)
        assert len(tr) == 0  # the author wrote no collective
        from chainermn_tpu.analysis import hlo_census

        assert hlo_census(txt).get("all_gather", 0) >= 1
        findings = check_implicit_collectives(tr, txt, flow)
        errors = [f for f in findings if f.severity == "error"]
        assert errors, findings
        assert all(f.check == "implicit_collectives" for f in errors)

    def test_citation_names_the_responsible_equation(self, mesh8):
        """The flagged insert carries BOTH citation layers: the XLA op
        metadata (op_name ending in dot_general + source line) and the
        sharding-flow reshard site (eqn index + why)."""
        tr, txt, flow = self._fixture(mesh8)
        assert any(
            s.primitive == "dot_general" for s in flow.reshard_sites
        )
        with pytest.raises(ImplicitCollectiveError) as ei:
            assert_attributed(tr, txt, flow=flow, name="mismatched")
        msg = str(ei.value)
        assert "dot_general" in msg
        assert "eqn" in msg

    def test_hlo_op_extraction_carries_metadata(self, mesh8):
        _tr, txt, _flow = self._fixture(mesh8)
        ops = hlo_collective_ops(txt)
        gathers = [o for o in ops if o.cls == "all_gather"]
        assert gathers
        # compiled classic HLO stamps op provenance on inserted ops
        assert any(
            o.op_name and "dot_general" in o.op_name for o in gathers
        )

    def test_surplus_citation_skips_the_authored_op(self, mesh8):
        """Regression: when the inserted collective appears textually
        BEFORE the authored one, the citation must name the inserted
        op, not the author's own call site (tail-slicing would)."""
        fn = _smap(
            lambda x: lax.all_gather(x, "mn", axis=0, tiled=True),
            mesh8, out_spec=P(),
        )
        tr = trace_collectives(fn, jnp.zeros((8, 4)))
        authored_src = tr.records[0].source
        assert authored_src
        f, ln = authored_src.rsplit(":", 1)
        txt = (
            # the partitioner's insert, FIRST in text order
            '%ag0 = f32[8,4] all-gather(%p0), metadata={'
            'op_name="jit(f)/dot_general" '
            'source_file="inserted_by_partitioner.py" source_line=7}\n'
            # the authored op, carrying the author's real call site
            f'%ag1 = f32[8,4] all-gather(%p1), metadata={{'
            f'op_name="jit(f)/all_gather" source_file="{f}" '
            f'source_line={ln}}}\n'
        )
        rep = attribute_collectives(tr, txt)
        implicit = rep["all_gather/all_to_all"]["implicit"]
        assert len(implicit) == 1
        assert "inserted_by_partitioner.py" in implicit[0]
        assert authored_src not in implicit[0]

    def test_clean_shard_map_program_attributes_exactly(self, mesh8):
        fn = _smap(lambda x: lax.psum(x, "mn"), mesh8)
        txt = jax.jit(fn).lower(jnp.zeros((8, 4))).compile().as_text()
        tr = trace_collectives(fn, jnp.zeros((8, 4)))
        rep = assert_attributed(tr, txt, name="clean")
        assert rep["all_reduce"] == {
            "authored": 1, "lowered": 1, "implicit": [],
        }


# ----------------------------------------------------------------------
# sharding-flow pass semantics
# ----------------------------------------------------------------------
class TestShardFlow:
    def test_elementwise_propagation_and_clean_flow(self):
        def f(x):
            return jnp.tanh(x) * 2.0 + x

        flow = shardflow(
            f, jnp.zeros((8, 4)), in_specs=(P("mn", None),)
        )
        assert flow.reshard_sites == ()
        assert flow.out_specs[0] == (("mn",), ())

    def test_transpose_moves_the_sharded_dim(self):
        flow = shardflow(
            lambda x: x.T, jnp.zeros((8, 4)), in_specs=(P("mn", None),)
        )
        assert flow.out_specs[0] == ((), ("mn",))

    def test_sharded_contraction_is_a_site(self):
        def f(x, w):
            return x @ w

        # x: (B, D) with D sharded; w: (D, K) replicated -> the
        # partitioner must gather the contracted operand
        flow = shardflow(
            f, jnp.zeros((8, 16)), jnp.zeros((16, 4)),
            in_specs=(P(None, "mn"), P()),
        )
        sites = flow.reshard_sites
        assert any(s.primitive == "dot_general" for s in sites)
        assert any("contracting" in s.note for s in sites)

    def test_reduction_over_sharded_dim_is_a_site(self):
        flow = shardflow(
            lambda x: x.sum(axis=0), jnp.zeros((8, 4)),
            in_specs=(P("mn", None),),
        )
        assert any(s.cls == "all_reduce" for s in flow.reshard_sites)

    def test_declared_output_mismatch_is_a_site(self):
        flow = shardflow(
            lambda x: x + 1.0, jnp.zeros((8, 4)),
            in_specs=(P("mn", None),), out_specs=(P(),),
        )
        assert any(
            s.primitive == "<output>" for s in flow.reshard_sites
        )

    def test_scan_body_reshard_is_cited(self):
        """Regression: the pass descends into scan bodies (carry/const
        specs pass through, stacked xs lose their leading dim) — a
        resharding dot inside the loop is cited at its own equation."""
        def f(c, xs):
            def body(carry, x):
                return carry @ carry.T + x.sum(), None

            out, _ = lax.scan(body, c, xs)
            return out

        flow = shardflow(
            f, jnp.zeros((8, 8)), jnp.zeros((4, 8)),
            in_specs=(P("mn", None), P()),
        )
        assert any(
            s.primitive == "dot_general" and "mn" in s.note
            for s in flow.reshard_sites
        ), flow.reshard_sites

    def test_scan_stacked_input_spec_sliced(self):
        """xs arrive stacked (T, ...) — the body sees the per-step
        slice, so a leading-dim sharding on xs does not leak onto the
        body's view."""
        def f(c, xs):
            def body(carry, x):
                return carry + x, carry * 1.0

            out, ys = lax.scan(body, c, xs)
            return out, ys

        flow = shardflow(
            f, jnp.zeros((4,)), jnp.zeros((8, 4)),
            in_specs=(P(), P("mn", None)),
        )
        assert flow.reshard_sites == ()
        # carry stays replicated; stacked ys gain an unsharded lead dim
        assert flow.out_specs[0] == ((),)
        assert flow.out_specs[1] == ((), ())

    def test_same_shape_unknown_primitive_stays_unknown(self):
        """Regression: a same-shape non-elementwise op (sort) must NOT
        get the elementwise passthrough — fabricated specs let later
        equations be accused of reshards they don't cause."""
        flow = shardflow(
            lambda x: jnp.sort(x, axis=0), jnp.zeros((8, 4)),
            in_specs=(P("mn", None),), out_specs=(P(),),
        )
        # sort's output layout is unknown -> even the declared-output
        # check stays silent (unknown accuses nobody)
        assert flow.reshard_sites == ()
        assert flow.out_specs[0] is None

    def test_unknown_primitives_accuse_nobody(self):
        # sort's output layout is unknown to the pass: no spec, no site
        flow = shardflow(
            lambda x: jnp.sort(x, axis=1) * 1.0, jnp.zeros((8, 4)),
            in_specs=(P("mn", None),),
        )
        assert flow.reshard_sites == ()

    def test_parallel_layer_declarations_feed_the_pass(self, mesh8):
        """The parallel modules' flow-spec declarations seed the pass:
        the EP MoE layout declares tokens/experts sharded over the
        expert axis — and the flow over a matching toy program is
        site-free."""
        from chainermn_tpu.parallel import (
            ep_flow_specs,
            pipeline_flow_specs,
            tp_flow_specs,
        )

        ep = ep_flow_specs("mn")
        assert ep["x"] == P("mn") and ep["router_w"] == P()
        pp = pipeline_flow_specs("mn")
        assert pp["stage_params"] == P("mn") and pp["out"] == P()
        params = {"ColumnParallelDense_0": {"kernel": jnp.zeros((4, 8))}}
        tp = tp_flow_specs(params, "mn")
        assert tp["params"]["ColumnParallelDense_0"]["kernel"] == P(
            None, "mn"
        )

        def routerless_moe(x, w):
            return jnp.einsum("td,dk->tk", x, w)

        flow = shardflow(
            routerless_moe, jnp.zeros((16, 8)), jnp.zeros((8, 4)),
            in_specs=(ep["x"], ep["router_w"]),
        )
        assert flow.reshard_sites == ()


# ----------------------------------------------------------------------
# attribution on the pinned train steps (acceptance)
# ----------------------------------------------------------------------
def _attribution_and_memory(step, p, o, batch, name):
    tr = step.collective_trace(p, o, batch)
    comp = step.get_jitted(p, o).lower(p, o, batch).compile()
    rep = assert_attributed(tr, comp.as_text(), name=name)
    assert not any(g["implicit"] for g in rep.values())
    est = step.memory_estimate(p, o, batch)
    enforce_memory(name, est)
    return tr, est, comp


class TestPinnedAttribution:
    def test_transformer_step_attributes_and_fits_memory(self, comm):
        from chainermn_tpu.models.transformer import TransformerLM, lm_loss

        model = TransformerLM(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2,
            max_len=64, dtype=jnp.float32,
        )
        toks = jnp.zeros((8, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks[:1])
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = build_train_step(
            comm, lambda p, b: lm_loss(model.apply(p, b), b), opt,
            donate=False,
        )
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(toks, step.batch_sharding)
        tr, est, comp = _attribution_and_memory(
            step, p, o, batch, "transformer_train_step"
        )
        # cost model on a real step: every record priced and hop-classed
        assert all(r.bytes_on_wire is not None for r in tr)
        assert all(r.hop == "flat" for r in tr)
        # estimator vs XLA's own accounting, documented tolerance:
        # within [0.5x, 4x] of args+temp (no-fusion upper bound; see
        # docs/static_analysis.md "Estimator assumptions")
        ma = comp.memory_analysis()
        measured = ma.argument_size_in_bytes + ma.temp_size_in_bytes
        assert 0.5 * measured <= est.peak_bytes <= 4.0 * measured, (
            est.peak_bytes, measured,
        )

    def test_zero_step_attributes_and_shards_state(self, comm):
        params = {
            "w": jnp.ones((2048,)) * 0.3, "v": jnp.ones((4096,)) * -0.2,
        }

        def loss(p, b):
            m = b.mean(axis=0)
            return 0.5 * jnp.sum((p["w"] - m[:2048]) ** 2) + 0.5 * (
                jnp.sum((p["v"] - m[2048:]) ** 2)
            )

        opt = cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        )
        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 6144)), step.batch_sharding)
        _tr, est, _comp = _attribution_and_memory(
            step, p, o, batch, "zero_train_step"
        )
        # the ZeRO sharding annotation is visible to the estimator: the
        # per-rank opt state the shard_map body receives matches the
        # optimizer's own closed-form declaration (1/8 of replicated)
        want = opt.hbm_bytes_per_rank(params, o)
        assert est.opt_state_bytes == want["opt_state"]
        assert est.params_bytes == want["params"]
        replicated = 2 * (2048 + 4096) * 4  # adam mu+nu, full width
        assert want["opt_state"] < replicated / 4

    def test_moe_step_attributes_and_fits_memory(self, devices8):
        from chainermn_tpu.models.moe_transformer import (
            MoeTransformerLM,
            moe_lm_loss,
            moe_param_specs,
        )
        from chainermn_tpu.parallel import sharded_init

        mcomm = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        B, S, V = 4, 16, 61
        model = MoeTransformerLM(
            vocab_size=V, d_model=32, n_heads=4, n_layers=2,
            n_experts=4, d_ff=64, moe_every=2, k=2, capacity=B * S * 2,
            max_len=S, dtype=jnp.float32, seq_axis="mn_seq",
            tp_axis="mn_model", expert_axis="mn_model",
            aux_stat_axes=("mn_data", "mn_seq", "mn_model"),
        )
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, V, (B, S)), jnp.int32
        )
        params, specs = sharded_init(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            mcomm.mesh, (P("mn_data", "mn_seq"),), moe_param_specs, toks,
        )
        opt = cmn.create_multi_node_optimizer(optax.sgd(5e-2), mcomm)

        def loss_fn(p, b):
            return moe_lm_loss(
                model.apply(p, b), b, seq_axis="mn_seq",
                model_axis="mn_model", aux_coef=1e-2,
            )

        step = build_train_step(
            mcomm, loss_fn, opt, data_axes=mcomm.data_axis_names,
            param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
            donate=False,
        )
        p, o = step.place(params, opt.init(params))
        batch = step.place_batch(toks)
        tr, _est, _comp = _attribution_and_memory(
            step, p, o, batch, "moe_train_step"
        )
        assert tr.count("all_to_all") >= 2  # dispatch + return, traced

    def test_resnet50_step_attributes_and_fits_memory(self, comm):
        """Acceptance (the one real ResNet-50 CPU compile in this
        file): the full ResNet-50 train step passes attribution with
        zero unattributed collectives and stays under its pinned
        per-rank HBM ceiling."""
        from chainermn_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, train=False)
        x = jnp.zeros((8, 64, 64, 3), jnp.float32)
        # one program, not op by op
        params = jax.jit(model.init)(jax.random.PRNGKey(0), x[:1])

        def loss_fn(p, b):
            imgs, labels = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, imgs), labels
            ).mean()

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(x, step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32),
                           step.batch_sharding),
        )
        _tr, est, _comp = _attribution_and_memory(
            step, p, o, batch, "resnet50_train_step"
        )
        # params resident ~98 MiB on the 64x64 fixture; the peak adds
        # the gradient tree, fresh output params, and the conv
        # activation chain
        assert est.params_bytes > 90 * MiB
        assert est.peak_bytes > 128 * MiB


# ----------------------------------------------------------------------
# HBM estimator semantics
# ----------------------------------------------------------------------
class TestMemoryEstimator:
    def test_remat_and_accum_lower_the_estimated_peak(self, comm):
        """Remat-awareness for free: ``jax.checkpoint`` changes the
        JAXPR (residuals recomputed, not saved), so the live-range walk
        sees per-layer remat's smaller footprint — and microbatching
        (``accum_steps``, a scan) shrinks the activation term the same
        way — with no special-casing in the estimator."""
        D, L = 64, 6
        w = {f"l{i}": jnp.zeros((D, D)) for i in range(L)}

        def make_loss(per_layer_remat):
            def loss(p, b):
                h = b
                for i in range(L):
                    f = lambda ww, hh: jnp.tanh(hh @ ww)  # noqa: E731
                    if per_layer_remat:
                        f = jax.checkpoint(f)
                    h = f(p[f"l{i}"], h)
                return jnp.sum(h ** 2)

            return loss

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)

        def est_of(loss, **kw):
            step = build_train_step(comm, loss, opt, donate=False, **kw)
            p, o = step.place(w, opt.init(w))
            batch = jax.device_put(
                jnp.zeros((2048, D)), step.batch_sharding
            )
            return step.memory_estimate(p, o, batch)

        plain = est_of(make_loss(False))
        remat = est_of(make_loss(True))
        accum = est_of(make_loss(False), accum_steps=4)
        assert remat.peak_bytes < plain.peak_bytes
        assert accum.peak_bytes < plain.peak_bytes

    def test_violation_raises_with_breakdown(self, comm):
        w = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(w, opt.init(w))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        est = step.memory_estimate(p, o, batch)
        assert est.peak_bytes > 0
        import chainermn_tpu.analysis.budgets as budgets

        with pytest.raises(MemoryBudgetError, match="HBM budget"):
            # a 1-byte ceiling: any real program exceeds it
            orig = budgets.HBM_BUDGETS.get("transformer_train_step")
            try:
                budgets.HBM_BUDGETS["transformer_train_step"] = 1
                enforce_memory("transformer_train_step", est)
            finally:
                budgets.HBM_BUDGETS["transformer_train_step"] = orig

    def test_budget_registry(self):
        assert set(HBM_BUDGETS) == {
            "resnet50_train_step", "transformer_train_step",
            "zero_train_step", "moe_train_step",
        }
        assert memory_budget_for("zero_train_step") > 0
        with pytest.raises(KeyError, match="no pinned HBM budget"):
            memory_budget_for("nonexistent")

    def test_estimate_hbm_on_plain_function(self, mesh8):
        est = estimate_hbm(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        )
        # per-shard view: one (1, 4) f32 input resident
        assert est.inputs_bytes == 16
        assert est.peak_bytes >= est.inputs_bytes

    def test_batch_breakdown_is_per_rank(self, comm):
        w = {"w": jnp.zeros((1024,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(w, opt.init(w))
        batch = jax.device_put(jnp.zeros((8, 1024)), step.batch_sharding)
        est = train_step_memory(step, p, o, batch)
        assert est.params_bytes == 1024 * 4  # replicated: full copy
        assert est.batch_bytes == 1024 * 4   # 1/8 of the (8, 1024) batch


# ----------------------------------------------------------------------
# per-collective cost model + the planner decision path
# ----------------------------------------------------------------------
class TestCostModel:
    def test_ring_formulas(self):
        p = 1000
        assert wire_bytes("all_reduce", p, 8) == int(2 * p * 7 / 8)
        assert wire_bytes("reduce_scatter", p, 8) == int(p * 7 / 8)
        assert wire_bytes("all_gather", p, 8) == 7 * p
        assert wire_bytes("collective_permute", p, 8) == p
        assert wire_bytes("all_reduce", p, None) is None

    def test_hop_classes(self):
        assert hop_class(("mn_inter",)) == "inter"
        assert hop_class(("mn_intra",)) == "intra"
        assert hop_class(("mn",)) == "flat"
        assert hop_class(("mn_inter", "mn_intra")) == "mixed"
        assert hop_class(()) == "local"

    def test_records_priced_from_shard_map_mesh(self, mesh8):
        tr = trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8),
            jnp.zeros((8, 4), jnp.float32),
        )
        r = tr.records[0]
        assert r.axis_sizes == (8,)
        assert r.world == 8
        assert r.payload_bytes == 16  # per-shard (1, 4) f32
        assert r.bytes_on_wire == wire_bytes("all_reduce", 16, 8)
        assert tr.wire_census() == {"flat": r.bytes_on_wire}

    def test_hierarchical_step_has_intra_and_inter_hops(self, devices8):
        c = cmn.create_communicator("hierarchical", devices=devices8)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), c)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(c, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        tr = step.collective_trace(p, o, batch)
        hops = {r.hop for r in tr}
        # the hierarchical wire reduces over BOTH axes of the
        # ('mn_inter', 'mn_intra') pair — the cost model sees the pair
        assert hops & {"inter", "intra", "mixed"}, hops
        assert all(r.bytes_on_wire is not None for r in tr)

    def test_axis_sizes_seed_for_meshless_traces(self):
        """A jaxpr with no shard_map mesh (an axis bound by ``axis_env``;
        jax 0.9's pmap is a shard_map and carries one) prices records
        only from the caller's seed."""
        jaxpr = jax.make_jaxpr(
            lambda x: lax.psum(x, "i"), axis_env=[("i", 8)]
        )(jnp.zeros((4,)))
        unpriced = trace_jaxpr(jaxpr)
        assert unpriced.records[0].bytes_on_wire is None
        priced = trace_jaxpr(jaxpr, axis_sizes={"i": 8})
        assert priced.records[0].world == 8
        assert priced.records[0].bytes_on_wire is not None

    def test_planner_consumes_bytes_and_hop(self, mesh8):
        """The decision path: an inter-hop trace gets a 4x byte target
        (fewer, larger buckets); a tiny flat trace collapses to one
        bucket."""
        big = _smap(lambda x: lax.psum(x, "mn"), mesh8)
        tr_flat = trace_collectives(big, jnp.zeros((8, 4)))
        bb, mb = cw.tune_wire_for_trace(tr_flat.records)
        assert bb == cw.DEFAULT_BUCKET_BYTES * 2  # flat: one notch up
        assert mb == 1  # 28 wire bytes fit any bucket: don't split

        inter = tr_flat.records[0].__class__(
            **{**tr_flat.records[0].__dict__,
               "axes": ("mn_inter",), "hop": "inter",
               "bytes_on_wire": 64 * MiB, "payload_bytes": 36 * MiB},
        )
        bb2, mb2 = cw.tune_wire_for_trace([inter])
        assert bb2 == cw.DEFAULT_BUCKET_BYTES * 4
        assert mb2 == cw.DEFAULT_MAX_BUCKETS  # 64 MiB does not collapse

        # plan_for_trace end to end: the tiny trace's plan is 1 bucket
        leaves = [jnp.zeros((128,)), jnp.zeros((256,)),
                  jnp.zeros((64,))]
        plan = cw.plan_for_trace(tr_flat, leaves)
        assert plan.n_buckets == 1

    def test_eager_tier_records_are_priced(self, comm):
        """The eager allreduce_grad dispatch is shard_map-backed — its
        records carry the mesh's sizes with no seed needed."""
        grads = {"w": jnp.zeros((comm.size, 3, 4), jnp.float32)}
        tr = trace_collectives(lambda t: comm.allreduce_grad(t), grads)
        assert tr.records, "bucketed path must trace"
        assert all(r.bytes_on_wire is not None for r in tr)
