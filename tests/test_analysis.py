"""Static collective analysis tests (ISSUE 5 tentpole).

Pins, in order of load-bearingness:

* the jaxpr walker extracts a correct ORDERED CollectiveTrace (axis
  names, dtypes, shapes, control-flow context) through
  ``pjit``/``scan``/``cond``/``while``/``shard_map`` nesting — including
  the eager communicator tier
  (``XlaCommunicatorBase.allreduce_grad``'s bucketed path);
* the walker census AGREES with the HLO-text census on real compiled
  train steps (the transformer step here; ResNet-50 in
  test_comm_wire.py) — two independent counters verifying each other;
* the check catalog: deadlock lint on divergent ``cond`` arms, mesh
  axis audit, narrowing-cast wire audit (flags the legacy per-leaf
  cast, exempts the comm_wire codecs);
* budget pins enforced from the analyzer for the ZeRO, expert-parallel
  MoE, and pipeline paths (ResNet-50's pin lives in test_comm_wire.py);
* the divergence guard: ``trace_agreement`` raises the non-recoverable
  ``CollectiveTraceMismatchError`` on hash mismatch, and
  ``build_train_step`` wires it into the first multi-process dispatch
  (the real 2-process version is mp_worker.py's ``trace_divergence``).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu.analysis import (
    BUDGETS,
    CollectiveBudgetError,
    CollectiveTraceMismatchError,
    assert_census_agreement,
    assert_within_budget,
    budget_for,
    check_axes,
    check_deadlocks,
    check_wire,
    enforce,
    hlo_census,
    trace_agreement,
    trace_collectives,
)
from chainermn_tpu.optimizers import build_train_step


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
# walker: ordering, metadata, nesting
# ----------------------------------------------------------------------
class TestWalker:
    def test_ordered_records_with_axes_dtypes_shapes(self, mesh8):
        def f(x):
            a = lax.psum(x, "mn")
            b = lax.pmax(x.astype(jnp.float32), "mn")
            g = lax.all_gather(x, "mn", axis=0, tiled=True)
            s = lax.psum_scatter(g, "mn", scatter_dimension=0, tiled=True)
            p = lax.ppermute(
                x, "mn", [(i, (i + 1) % 8) for i in range(8)]
            )
            return a + b.astype(x.dtype) + s[:1] * 0 + p

        tr = trace_collectives(
            _smap(f, mesh8), jnp.zeros((8, 4), jnp.bfloat16)
        )
        prims = [r.primitive for r in tr]
        # lax.psum_scatter binds the reduce_scatter primitive
        assert prims == [
            "psum", "pmax", "all_gather", "reduce_scatter", "ppermute"
        ]
        assert [r.cls for r in tr] == [
            "all_reduce", "all_reduce", "all_gather", "reduce_scatter",
            "collective_permute",
        ]
        assert all(r.axes == ("mn",) for r in tr)
        assert tr.records[0].dtypes == ("bfloat16",)
        assert tr.records[1].dtypes == ("float32",)
        # per-shard operand shapes: (1, 4) into the psum, (8, 4) into
        # the reduce_scatter (it consumes the gathered block)
        assert tr.records[0].shapes == ((1, 4),)
        assert tr.records[3].shapes == ((8, 4),)
        # ppermute's permutation is part of the program identity
        assert "perm=" in tr.records[4].detail
        assert tr.axis_names() == ("mn",)

    def test_pmean_is_one_psum(self, mesh8):
        tr = trace_collectives(
            _smap(lambda x: lax.pmean(x, "mn"), mesh8), jnp.zeros((8, 4))
        )
        assert [r.primitive for r in tr] == ["psum"]
        assert tr.census() == {"all_reduce": 1}

    @pytest.mark.parametrize("check_vma,primitive", [
        (False, "psum"), (True, "psum_invariant"),
    ])
    def test_tuple_psum_is_one_record_a_leaf(self, mesh8, check_vma,
                                             primitive):
        def f(x):
            a, b = lax.psum((x, x * 2), "mn")
            return a + b

        fn = jax.shard_map(f, mesh=mesh8, in_specs=P("mn"), out_specs=P(),
                           check_vma=check_vma)
        tr = trace_collectives(fn, jnp.zeros((8, 4)))
        # jax 0.9 binds a tuple psum leaf by leaf (no variadic eqn any
        # more): one record an equation, each an all_reduce, under the
        # vma check as the primitive it emits there.  pvary (the 2.0
        # retyped for the check) moves nothing and is no record.
        assert [r.primitive for r in tr] == [primitive] * 2
        assert [r.dtypes for r in tr] == [("float32",)] * 2
        assert tr.census() == {"all_reduce": 2}

    def test_nested_scan_cond_pjit_contexts(self, mesh8):
        def inner(c):
            return lax.psum(c, "mn")

        def f(x):
            def body(c, _):
                c = jax.jit(inner)(c)
                c = lax.cond(
                    c.sum() > 0,
                    lambda y: lax.pmax(y, "mn"),
                    lambda y: y * 2.0,
                    c,
                )
                return c, None

            out, _ = lax.scan(body, x, None, length=3)
            return out

        tr = trace_collectives(_smap(f, mesh8), jnp.zeros((8, 4)))
        assert [r.primitive for r in tr] == ["psum", "pmax"]
        psum_rec, pmax_rec = tr.records
        assert psum_rec.context == ("shard_map", "scan", "pjit")
        assert pmax_rec.context[:2] == ("shard_map", "scan")
        assert pmax_rec.context[2].startswith("cond#1[")
        assert pmax_rec.in_cond() and not psum_rec.in_cond()

    def test_while_loop_context(self, mesh8):
        def f(x):
            def wcond(c):
                return c[1] < 3

            def wbody(c):
                return (lax.psum(c[0], "mn"), c[1] + 1)

            out, _ = lax.while_loop(wcond, wbody, (x, 0))
            return out

        tr = trace_collectives(_smap(f, mesh8), jnp.zeros((8, 4)))
        assert len(tr) == 1
        assert tr.records[0].context == ("shard_map", "while/body")

    def test_bare_shard_map(self, mesh8):
        """The walker descends a bare ``jax.shard_map`` eqn."""
        sm = jax.shard_map(
            lambda x: lax.pmean(x, "mn"), mesh=mesh8,
            in_specs=(P("mn"),), out_specs=P("mn"), check_vma=False,
        )
        tr = trace_collectives(sm, jnp.zeros((8, 4)))
        assert tr.census() == {"all_reduce": 1}
        assert tr.records[0].context[0] == "shard_map"

    def test_trace_hash_is_value_independent(self, mesh8):
        fn = _smap(lambda x: lax.psum(x, "mn"), mesh8)
        h1 = trace_collectives(fn, jnp.zeros((8, 4))).trace_hash()
        h2 = trace_collectives(fn, jnp.ones((8, 4)) * 7).trace_hash()
        h3 = trace_collectives(
            fn, jax.ShapeDtypeStruct((8, 4), jnp.float32)
        ).trace_hash()
        assert h1 == h2 == h3

    def test_trace_hash_changes_with_program(self, mesh8):
        h1 = trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        ).trace_hash()
        h2 = trace_collectives(
            _smap(lambda x: lax.psum(lax.psum(x, "mn"), "mn"), mesh8),
            jnp.zeros((8, 4)),
        ).trace_hash()
        h3 = trace_collectives(
            _smap(lambda x: lax.pmax(x, "mn"), mesh8), jnp.zeros((8, 4))
        ).trace_hash()
        assert len({h1, h2, h3}) == 3

    def test_canonical_excludes_source_locations(self, mesh8):
        # two textually-distinct call sites, same program -> same hash
        def f1(x):
            return lax.psum(x, "mn")

        def f2(x):
            return lax.psum(x, "mn")  # different line on purpose

        t1 = trace_collectives(_smap(f1, mesh8), jnp.zeros((8, 4)))
        t2 = trace_collectives(_smap(f2, mesh8), jnp.zeros((8, 4)))
        assert t1.trace_hash() == t2.trace_hash()
        # ... while the records still carry sources for diagnostics
        assert t1.records[0].source and "test_analysis" in t1.records[0].source


# ----------------------------------------------------------------------
# deadlock lint
# ----------------------------------------------------------------------
class TestDeadlockLint:
    def _trace_cond(self, mesh8, true_fn, false_fn):
        def f(x):
            return lax.cond(x.sum() > 0, true_fn, false_fn, x)

        return trace_collectives(_smap(f, mesh8), jnp.zeros((8, 4)))

    def test_divergent_branches_are_an_error(self, mesh8):
        tr = self._trace_cond(
            mesh8,
            lambda y: lax.psum(y, "mn"),
            lambda y: y * 2.0,
        )
        findings = check_deadlocks(tr)
        assert len(findings) == 1
        assert findings[0].severity == "error"
        assert "different collective sequences" in findings[0].message
        assert tr.cond_reports[0].diverges

    def test_lockstep_branches_warn_only(self, mesh8):
        tr = self._trace_cond(
            mesh8,
            lambda y: lax.psum(y, "mn") * 2.0,
            lambda y: lax.psum(y, "mn") + 1.0,
        )
        findings = check_deadlocks(tr)
        assert [f.severity for f in findings] == ["warning"]
        assert not tr.cond_reports[0].diverges

    def test_identical_nested_cond_arms_are_lockstep(self, mesh8):
        """Regression: the walk-global cond counter gives arm 0's inner
        cond a different id (cond#2) than arm 1's identical inner cond
        (cond#3); the branch comparison must strip the ids, or every
        lockstep program with nested conds false-positives as a
        deadlock."""
        def nested(y):
            return lax.cond(
                y.sum() > 1.0,
                lambda z: lax.psum(z, "mn"),
                lambda z: lax.psum(z, "mn") * 2.0,
                y,
            )

        tr = self._trace_cond(mesh8, nested, nested)
        outer = [r for r in tr.cond_reports if r.cond_id == "cond#1"]
        assert outer and not outer[0].diverges
        assert all(f.severity == "warning" for f in check_deadlocks(tr))

    def test_divergent_nested_cond_arms_still_error(self, mesh8):
        def n_psum(y):
            return lax.cond(
                y.sum() > 1.0,
                lambda z: lax.psum(z, "mn"),
                lambda z: lax.psum(z, "mn") * 2.0,
                y,
            )

        def n_pmax(y):
            return lax.cond(
                y.sum() > 1.0,
                lambda z: lax.pmax(z, "mn"),
                lambda z: lax.pmax(z, "mn") * 2.0,
                y,
            )

        tr = self._trace_cond(mesh8, n_psum, n_pmax)
        outer = [r for r in tr.cond_reports if r.cond_id == "cond#1"]
        assert outer[0].diverges
        assert any(f.severity == "error" for f in check_deadlocks(tr))

    def test_collective_free_cond_is_clean(self, mesh8):
        tr = self._trace_cond(
            mesh8, lambda y: y * 2.0, lambda y: y + 1.0
        )
        assert check_deadlocks(tr) == []
        # the report still exists (branch structure was analyzed), it
        # just has nothing to flag
        assert not tr.cond_reports[0].has_collectives


# ----------------------------------------------------------------------
# deadlock lint: while bodies (ISSUE 6 satellite — PR 4 only compared
# cond arms)
# ----------------------------------------------------------------------
class TestWhileDeadlockLint:
    def _findings(self, mesh8, fn):
        tr = trace_collectives(_smap(fn, mesh8), jnp.zeros((8, 4)))
        return tr, check_deadlocks(tr)

    def test_counter_while_with_collective_warns(self, mesh8):
        """The fori shape: predicate reads a carry slot the body
        advances by a constant — trip count rank-uniform, so the
        collective inside gets the lockstep-cond treatment (warning)."""
        def f(x):
            def wbody(c):
                return (lax.psum(c[0], "mn"), c[1] + 1)

            out, _ = lax.while_loop(lambda c: c[1] < 3, wbody, (x, 0))
            return out

        tr, findings = self._findings(mesh8, f)
        assert tr.while_reports[0].counter_only_predicate
        assert tr.while_reports[0].trip_count_agreed
        assert [f.severity for f in findings] == ["warning"]
        assert "counter-only" in findings[0].message

    def test_data_dependent_while_with_collective_errors(self, mesh8):
        """Predicate reads a data-carrying slot: rank-divergent trip
        counts issue divergent collective sequences — error."""
        def f(x):
            def wbody(c):
                return (lax.psum(c[0], "mn") * 0.5, c[1] + 1)

            out, _ = lax.while_loop(
                lambda c: c[0].sum() < 3.0, wbody, (x, 0)
            )
            return out

        tr, findings = self._findings(mesh8, f)
        assert not tr.while_reports[0].trip_count_agreed
        assert [f.severity for f in findings] == ["error"]
        assert "data-dependent while" in findings[0].message

    def test_reduction_agreed_predicate_warns(self, mesh8):
        """The convergence-loop shape: the predicate itself is computed
        through a psum, so every rank agrees to continue or exit —
        aligned today, warning not error."""
        def f(x):
            def wbody(c):
                return (c[0] * 0.5, c[1] + 1)

            out, _ = lax.while_loop(
                lambda c: lax.psum(c[0].sum(), "mn") > 1.0, wbody,
                (x, 0),
            )
            return out

        tr, findings = self._findings(mesh8, f)
        rep = tr.while_reports[0]
        assert rep.cond_has_reduction and rep.trip_count_agreed
        assert [f.severity for f in findings] == ["warning"]
        assert "cross-rank reduction" in findings[0].message

    def test_collective_free_while_is_clean(self, mesh8):
        def f(x):
            def wbody(c):
                return (c[0] * 0.5, c[1] + 1)

            out, _ = lax.while_loop(
                lambda c: c[0].sum() < 3.0, wbody, (x, 0)
            )
            return out

        tr, findings = self._findings(mesh8, f)
        assert not tr.while_reports[0].has_collectives
        assert findings == []


# ----------------------------------------------------------------------
# axis audit
# ----------------------------------------------------------------------
class TestAxisAudit:
    def test_unknown_axis_flagged(self, comm, mesh8):
        tr = trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        )
        assert check_axes(tr, comm.axis_names) == []
        findings = check_axes(tr, ("mn_inter", "mn_intra"))
        assert len(findings) == 1
        assert "unknown axis mn" in findings[0].message

    def test_bare_string_axis_name_not_split_into_chars(self, mesh8):
        # axis_name attributes are often plain strings; "mn" must mean
        # the axis, not the set {'m', 'n'}
        tr = trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        )
        assert check_axes(tr, "mn") == []
        assert check_axes(tr, "mn_other") != []

    def test_hierarchical_step_passes_its_own_mesh(self, devices8):
        c = cmn.create_communicator("hierarchical", devices=devices8)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), c)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(c, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        tr = step.collective_trace(p, o, batch)
        assert len(tr) >= 2  # grad bucket(s) + loss pmean
        assert check_axes(tr, c.axis_names) == []
        # and the flat communicator's axis set would (correctly) fail
        assert check_axes(tr, ("mn",)) != []


# ----------------------------------------------------------------------
# wire audit
# ----------------------------------------------------------------------
class TestWireAudit:
    def _step_trace(self, devices8, wire):
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), c, wire=wire)
        params = {"w": jnp.zeros((8,)), "v": jnp.zeros((3,))}

        def loss(p, b):
            m = b.mean(axis=0)
            return 0.5 * jnp.sum((p["w"] - m[:8]) ** 2) + 0.5 * jnp.sum(
                (p["v"] - m[8:]) ** 2
            )

        step = build_train_step(c, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 11)), step.batch_sharding)
        return step.collective_trace(p, o, batch)

    def test_legacy_per_leaf_cast_is_flagged(self, devices8):
        tr = self._step_trace(devices8, "per_leaf")
        findings = check_wire(tr)
        assert findings, "per-leaf bf16 cast must be flagged"
        assert all("optimizers.py" in (f.source or "") for f in findings)
        assert all("bfloat16" in f.message for f in findings)

    def test_comm_wire_codec_is_exempt(self, devices8):
        tr = self._step_trace(devices8, "auto")  # bf16 codec, bucketed
        # the narrowing cast EXISTS (it's the wire codec)...
        assert tr.narrowing_casts, "bf16 codec must narrow on the wire"
        # ...but it lives in comm_wire, the sanctioned place
        assert check_wire(tr) == []

    def test_uncompressed_wire_has_no_narrowing(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        tr = step.collective_trace(p, o, batch)
        assert tr.narrowing_casts == ()
        assert check_wire(tr) == []


# ----------------------------------------------------------------------
# census agreement + budget pins (transformer / ZeRO / MoE / pipeline)
# ----------------------------------------------------------------------
class TestTransformerCensus:
    def test_transformer_step_analyzer_agrees_with_hlo(self, comm):
        """Acceptance: the walker and the HLO text count the same
        all-reduces on the transformer train step, and the step stays
        within the pinned wire budget."""
        from chainermn_tpu.models.transformer import TransformerLM, lm_loss

        model = TransformerLM(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2,
            max_len=64, dtype=jnp.float32,
        )
        toks = jnp.zeros((8, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks[:1])

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(toks, step.batch_sharding)
        tr = step.collective_trace(p, o, batch)
        txt = step.get_jitted(p, o).lower(p, o, batch).as_text()
        agreed = assert_census_agreement(tr, txt)
        assert agreed["all_reduce"] >= 2  # bucket(s) + loss pmean
        enforce("transformer_train_step", tr)


class TestBudgets:
    def test_zero_step_within_reduce_scatter_budget(self, comm):
        params = {"w": jnp.ones((8,)) * 0.3, "v": jnp.ones((16,)) * -0.2}

        def loss(p, b):
            m = b.mean(axis=0)
            return 0.5 * jnp.sum((p["w"] - m[:8]) ** 2) + 0.5 * jnp.sum(
                (p["v"] - m[8:]) ** 2
            )

        opt = cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        )
        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 24)), step.batch_sharding)
        tr = step.collective_trace(p, o, batch)
        census = enforce("zero_train_step", tr)
        # the ZeRO shape: gradients go DOWN via reduce_scatter, updates
        # come BACK via all_gather, and only the loss pmean all-reduces
        assert census["reduce_scatter"] >= 1
        assert census["all_gather"] >= 1
        assert census["all_reduce"] == 1

    def test_ep_moe_layer_exactly_two_all_to_all(self, comm, mesh8):
        from chainermn_tpu.parallel.expert_parallel import (
            expert_parallel_moe,
            mlp_experts,
        )

        d, dff, E = 8, 16, 8
        router = jnp.zeros((d, E))
        w1 = jnp.zeros((E // 8, d, dff))
        w2 = jnp.zeros((E // 8, dff, d))

        def moe(x):
            return expert_parallel_moe(
                x, router, mlp_experts(w1, w2), "mn", E, k=2
            )[0]

        tr = trace_collectives(
            _smap(moe, mesh8, out_spec=P()), jnp.zeros((16, d))
        )
        census = enforce("ep_moe_layer", tr)
        assert census["all_to_all"] == 2  # dispatch + return, no more

    def test_pipeline_forward_one_permute_one_psum(self, comm, mesh8):
        from chainermn_tpu.parallel.pipeline import gpipe

        def stage_fn(sp, h):
            return jnp.tanh(h @ sp)

        def fwd(sp, xm):
            y = gpipe(stage_fn, sp[0], xm, "mn")
            is_last = lax.axis_index("mn") == lax.axis_size("mn") - 1
            return lax.psum(
                jnp.where(is_last, y.sum(), 0.0), "mn"
            )

        tr = trace_collectives(
            jax.shard_map(
                fwd, mesh=mesh8, in_specs=(P("mn"), P()),
                out_specs=P(), check_vma=False,
            ),
            jnp.zeros((8, 4, 4)),  # per-stage params, stacked
            jnp.zeros((4, 2, 4)),  # (n_micro, micro_batch, d)
        )
        census = enforce("pipeline_forward", tr)
        # the ring edge appears ONCE (inside the scan body), exactly as
        # it appears once in the lowered while-loop body
        assert census["collective_permute"] == 1
        assert tr.records[0].context[-1] == "scan"

    def test_pipeline_train_step_backward_permute_pinned(
        self, comm, mesh8
    ):
        """ISSUE 6 satellite: only the FORWARD ppermute was pinned —
        the transposed reverse-ring permute that autodiff generates was
        unguarded.  The full train step traces to exactly 2
        collective_permute (forward edge + transposed edge, each once
        inside its scan body) and 2 all_reduce (loss psum + its
        transpose), pinned by ``pipeline_train_step``."""
        from chainermn_tpu.parallel.pipeline import gpipe

        def stage_fn(sp, h):
            return jnp.tanh(h @ sp)

        def fwd(sp, xm):
            y = gpipe(stage_fn, sp[0], xm, "mn")
            is_last = lax.axis_index("mn") == lax.axis_size("mn") - 1
            return lax.psum(jnp.where(is_last, y.sum(), 0.0), "mn")

        def train(sp, xm):
            return jax.grad(fwd)(sp, xm)

        tr = trace_collectives(
            jax.shard_map(
                train, mesh=mesh8, in_specs=(P("mn"), P()),
                out_specs=P("mn"), check_vma=False,
            ),
            jnp.zeros((8, 4, 4)),
            jnp.zeros((4, 2, 4)),
        )
        census = enforce("pipeline_train_step", tr)
        assert census["collective_permute"] == 2
        # both ring edges live inside their scan bodies (fwd + bwd)
        permutes = [r for r in tr if r.cls == "collective_permute"]
        assert all("scan" in r.context for r in permutes)
        # the reverse permute is the transpose of the forward one
        assert permutes[0].detail != permutes[1].detail

    def test_budget_violation_raises_with_census(self, comm):
        from chainermn_tpu.models import MLP

        model = MLP(n_units=50)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
        n_leaves = len(jax.tree_util.tree_leaves(params))
        assert n_leaves > 4

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, wire="per_leaf"
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = (
            jax.device_put(jnp.zeros((8, 28, 28)), step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32),
                           step.batch_sharding),
        )
        tr = step.collective_trace(p, o, batch)
        assert tr.count("all_reduce") == n_leaves + 1  # the leaf storm
        with pytest.raises(CollectiveBudgetError, match="all_reduce"):
            assert_within_budget(tr, {"all_reduce": n_leaves // 2},
                                 name="per_leaf_storm")

    def test_budget_registry(self):
        assert budget_for("resnet50_train_step") == {"all_reduce": 8}
        assert "zero_train_step" in BUDGETS
        with pytest.raises(KeyError, match="no pinned budget"):
            budget_for("nonexistent_path")


# ----------------------------------------------------------------------
# eager communicator tier
# ----------------------------------------------------------------------
class TestEagerTier:
    def test_allreduce_grad_bucketed_path_traces(self, comm):
        """Satellite: the eager ``XlaCommunicatorBase.allreduce_grad``
        traces end to end — the walker descends the cached-jit (pjit)
        dispatch and finds ONE psum per wire bucket, which is the
        bucketed-launch contract of PR 3."""
        from chainermn_tpu import comm_wire as cw

        rng = np.random.RandomState(0)
        grads = {
            "w": jnp.asarray(rng.randn(comm.size, 3, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(comm.size, 5), jnp.float32),
        }
        per_rank = [l[0] for l in jax.tree_util.tree_leaves(grads)]
        plan = cw.make_plan(per_rank)

        tr = trace_collectives(
            lambda t: comm.allreduce_grad(t), grads, label="allreduce_grad"
        )
        assert tr.count("all_reduce") == plan.n_buckets
        assert all(r.context and r.context[0] == "pjit" for r in tr)

    def test_eager_cast_tier_is_wire_audit_visible(self, devices8):
        # the bf16 eager tier narrows OUTSIDE comm_wire codecs — the
        # audit must see it (it is the eager analogue of the per-leaf
        # legacy path, kept for reference parity)
        c = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        grads = {"w": jnp.zeros((8, 3))}
        tr = trace_collectives(lambda t: c.allreduce_grad(t), grads)
        assert check_wire(tr), "eager cast tier should be flagged"


# ----------------------------------------------------------------------
# divergence guard
# ----------------------------------------------------------------------
class _FakeComm:
    """Host-control-plane stub: only what trace_agreement touches."""

    def __init__(self, peers):
        self._peers = peers

    def allgather_obj(self, h):
        return [h] + list(self._peers(h))


class TestTraceAgreement:
    def _trace(self, mesh8):
        return trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        )

    def test_agreement_returns_hash(self, mesh8, comm):
        tr = self._trace(mesh8)
        # real communicator (single process: world of one agrees)
        assert trace_agreement(comm, tr) == tr.trace_hash()
        # fake 2-process world that agrees
        fake = _FakeComm(lambda h: [h])
        assert trace_agreement(fake, tr) == tr.trace_hash()

    def test_mismatch_raises_nonrecoverable(self, mesh8):
        tr = self._trace(mesh8)
        fake = _FakeComm(lambda h: ["a-divergent-trace-hash"])
        with pytest.raises(CollectiveTraceMismatchError,
                           match="trace hash mismatch") as ei:
            trace_agreement(fake, tr)
        assert ei.value.recoverable is False
        assert "trace_agreement" in ei.value.site

    def test_truncated_exchange_retries_in_lockstep(self, mesh8, comm):
        from chainermn_tpu.resilience.fault_injection import (
            FaultSpec,
            inject_faults,
        )

        tr = self._trace(mesh8)
        with inject_faults(
            [FaultSpec("obj_store.exchange", "truncate", at=[1],
                       truncate_to=4)]
        ) as inj:
            assert trace_agreement(comm, tr) == tr.trace_hash()
        assert inj.log.counts.get("fault_injected", 0) >= 1


class _MultiProcProxy:
    """Wrap a real single-process communicator so build_train_step sees
    a 2-process world whose trace exchange we script — the
    single-controller half of the mp ``trace_divergence`` scenario."""

    def __init__(self, real, exchange):
        object.__setattr__(self, "_real", real)
        object.__setattr__(self, "_exchange", exchange)

    def __getattr__(self, name):
        if name == "process_count":
            return 2
        if name == "allgather_obj":
            return self._exchange
        return getattr(object.__getattribute__(self, "_real"), name)


class TestGuardWiring:
    def _pieces(self, comm, proxy):
        # the optimizer keeps the REAL comm (its init-time plan guard
        # would otherwise also exchange through the scripted proxy)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(proxy, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        return step, p, o, batch

    def test_first_dispatch_guards_in_multiprocess_world(self, comm):
        proxy = _MultiProcProxy(comm, lambda h: [h, "divergent-peer"])
        step, p, o, batch = self._pieces(comm, proxy)
        with pytest.raises(CollectiveTraceMismatchError):
            step(p, o, batch)
        # the guard fired ONCE, before dispatch; after the (fatal)
        # mismatch a retry would re-raise from the exchange only if
        # re-armed — it is not, matching plan_agreement's fail-fast
        out = step(p, o, batch)  # agreement not retried; step runs
        assert np.isfinite(float(out[2]["loss"]))

    def test_agreeing_world_proceeds(self, comm):
        proxy = _MultiProcProxy(comm, lambda h: [h, h])
        step, p, o, batch = self._pieces(comm, proxy)
        p2, _, m = step(p, o, batch)
        assert np.isfinite(float(m["loss"]))

    def test_new_program_variant_reguards(self, comm):
        """Regression: the guard is per compiled-program variant, not
        once per step object — a new batch shape (or params/opt_state
        structure) retraces into a potentially different collective
        sequence and must be re-verified before it dispatches."""
        exchanges = []

        def agreeing(h):
            exchanges.append(h)
            return [h, h]

        proxy = _MultiProcProxy(comm, agreeing)
        step, p, o, batch = self._pieces(comm, proxy)
        step(p, o, batch)
        step(p, o, batch)  # same variant: verified once
        assert len(exchanges) == 1
        batch2 = jax.device_put(jnp.zeros((16, 4)), step.batch_sharding)
        step(p, o, batch2)  # new batch shape: a NEW program — re-guard
        assert len(exchanges) == 2
        step(p, o, batch2)
        assert len(exchanges) == 2
        # same pytree STRUCTURE, different leaf avals (resized param —
        # (2, 4) still broadcasts against the (B, 4) batch): jit
        # retraces — the bucket plan is a function of shapes, so the
        # collective sequence can change — and must be re-guarded
        params2 = {"w": jnp.zeros((2, 4))}
        opt2 = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        p2, o2 = step.place(params2, opt2.init(params2))
        step(p2, o2, batch2)
        assert len(exchanges) == 3

    def test_transient_exchange_failure_rearms_guard(self, comm):
        """Regression: a transiently-failed hash exchange must NOT
        disarm the guard — an auto-resumed run re-verifies instead of
        skipping straight into the potential deadlock.  Only success
        and a fatal mismatch disarm."""
        from chainermn_tpu.resilience.errors import TransientCommError

        attempts = []

        def flaky(h):
            attempts.append(h)
            if len(attempts) <= 4:  # exhaust the whole retry budget
                raise TransientCommError("injected", site="test")
            return [h, h]

        proxy = _MultiProcProxy(comm, flaky)
        step, p, o, batch = self._pieces(comm, proxy)
        with pytest.raises(TransientCommError):
            step(p, o, batch)
        assert len(attempts) == 4  # the internal retry budget, spent
        # still armed: the next call re-exchanges, agrees, and runs
        _, _, m = step(p, o, batch)
        assert np.isfinite(float(m["loss"]))
        assert len(attempts) == 5
        # disarmed after success: no further exchanges
        step(p, o, batch)
        assert len(attempts) == 5

    def test_env_opt_out(self, comm, monkeypatch):
        monkeypatch.setenv("CHAINERMN_TPU_TRACE_GUARD", "0")
        proxy = _MultiProcProxy(comm, lambda h: [h, "divergent-peer"])
        step, p, o, batch = self._pieces(comm, proxy)
        _, _, m = step(p, o, batch)  # guard disabled: no raise
        assert np.isfinite(float(m["loss"]))

    def test_single_process_never_exchanges(self, comm):
        calls = []

        class _Counting(_MultiProcProxy):
            def __getattr__(self, name):
                if name == "process_count":
                    return 1  # single-controller world
                if name == "allgather_obj":
                    def ag(h):
                        calls.append(h)
                        return [h]

                    return ag
                return getattr(
                    object.__getattribute__(self, "_real"), name
                )

        proxy = _Counting(comm, None)
        step, p, o, batch = self._pieces(comm, proxy)
        step(p, o, batch)
        assert calls == []  # nothing to disagree with, no exchange

    def test_explicit_verify_returns_hash(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        batch = jax.device_put(jnp.zeros((8, 4)), step.batch_sharding)
        h = step.verify_collective_trace(p, o, batch)
        assert h == step.collective_trace(p, o, batch).trace_hash()


# ----------------------------------------------------------------------
# hlo census unit behavior
# ----------------------------------------------------------------------
class TestHloCensus:
    def test_stablehlo_spellings(self):
        txt = (
            '%0 = "stablehlo.all_reduce"(%a)\n'
            '%1 = "stablehlo.all_reduce"(%b)\n'
            '%2 = "stablehlo.all_gather"(%c) {all_gather_dim = 0}\n'
            '%3 = "stablehlo.reduce_scatter"(%d)\n'
            '%4 = "stablehlo.collective_permute"(%e)\n'
        )
        assert hlo_census(txt) == {
            "all_reduce": 2,
            "all_gather": 1,
            "reduce_scatter": 1,
            "collective_permute": 1,
        }

    def test_classic_hlo_spellings(self):
        txt = (
            "ROOT %r = f32[4] all-reduce(%a), replica_groups={}\n"
            "%g = f32[32] all-gather(%b)\n"
        )
        assert hlo_census(txt) == {"all_reduce": 1, "all_gather": 1}

    def test_disagreement_raises(self, mesh8):
        tr = trace_collectives(
            _smap(lambda x: lax.psum(x, "mn"), mesh8), jnp.zeros((8, 4))
        )
        with pytest.raises(AssertionError, match="census disagreement"):
            assert_census_agreement(
                tr, '"stablehlo.all_reduce" "stablehlo.all_reduce"'
            )


# ----------------------------------------------------------------------
# scheduled-program census: asynchronous against synchronous collectives
# ----------------------------------------------------------------------
class TestCollectiveSchedule:
    """``collective_schedule`` on a recorded piece of the LM step as the
    TPU compiler scheduled it for a v5e 2x2 (``tests/data/``; lines cut
    to the instructions the reader looks at): a glued synchronous tuple,
    an asynchronous collective fusion with a kernel and a matmul-fused
    step inside, one with only a plain elementwise fusion inside, one
    whose step rides an optimizer fusion, and an ``-start`` / ``-done``
    pair around a matmul fusion."""

    @pytest.fixture(scope="class")
    def schedule(self):
        import os

        from chainermn_tpu.analysis.hlo import collective_schedule

        path = os.path.join(os.path.dirname(__file__), "data",
                            "scheduled_hlo_v5e.txt")
        with open(path) as f:
            return collective_schedule(f.read())

    def test_condensed_schedule(self, schedule):
        assert schedule.condensed == "RmSkmDS.DSeDSmD"

    @pytest.mark.parametrize("index,cls,nbytes,asynchronous,inside", [
        # three bf16 weight gradients glued into one blocking all-reduce
        (0, "all_reduce", 2 * (2 * 1536 * 6144 + 1536 * 4608), False, 0),
        # start ... kernel, step fused onto a matmul ... done
        (1, "all_reduce", 2 * 6144 * 1536, True, 2),
        # asynchronous in form, no step between start and done
        (2, "all_reduce", 2 * 6144 * 1536, True, 0),
        # its step fused onto an AdamW update of other leaves
        (3, "all_reduce", 2 * 6144 * 1536, True, 1),
        # XLA's own start / done pair (a ring step), a matmul inside
        (4, "collective_permute", 4 * 256 * 128, True, 1),
    ], ids=["sync_tuple", "async_overlapped", "async_bare",
            "async_on_optimizer", "start_done"])
    def test_each_collective(self, schedule, index, cls, nbytes,
                             asynchronous, inside):
        op = schedule.ops[index]
        assert (op.cls, op.nbytes, op.asynchronous, op.compute_inside) == (
            cls, nbytes, asynchronous, inside)

    def test_op_name_is_the_issuing_equation(self, schedule):
        assert schedule.ops[1].op_name.endswith(
            "TransformerBlock_2/MlpBlock_0/Dense_1/psum_invariant")

    def test_census_shares(self, schedule):
        big = schedule.census(min_bytes=1 << 20)  # leaves the ring step out
        assert (big["n_sync"], big["n_async"], big["n_overlapped"]) == (
            1, 3, 2)
        total = big["sync_bytes"] + big["async_bytes"]
        assert big["async_bytes_share"] == pytest.approx(
            3 * 18874368 / total)
        assert big["overlapped_bytes_share"] == pytest.approx(
            2 * 18874368 / total)
        assert schedule.census()["n_async"] == 4

    def test_unscheduled_or_empty_text(self):
        from chainermn_tpu.analysis.hlo import collective_schedule

        empty = collective_schedule("")
        assert empty.ops == () and empty.condensed == ""
        assert empty.census()["overlapped_bytes_share"] == 0.0

    def test_cpu_step_exposes_its_schedule(self, comm):
        """``step.collective_schedule`` compiles and reads the step it
        would dispatch; on the CPU mesh the gradient sum is blocking."""
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((4,))}

        def loss(p, b):
            return 0.5 * jnp.sum((p["w"] - b.mean(axis=0)) ** 2)

        step = build_train_step(comm, loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        census = step.collective_schedule(p, o, jnp.zeros((8, 4))).census()
        assert census["n_sync"] + census["n_async"] >= 1
        assert census["overlapped_bytes_share"] == 0.0
