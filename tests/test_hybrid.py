"""Hybrid DP x TP end-to-end tests.

Parity: the reference's dual-parallelism pattern — a 2-D process grid of
DP groups x MP groups built with ``CommunicatorBase.split`` (SURVEY.md
section 2, "Hybrid DP x MP") — realized TPU-first as ONE
``('mn_data', 'mn_model')`` mesh: the batch shards over the data axis,
Megatron column/row layers shard over the model axis, and
``build_train_step(param_specs=...)`` compiles the whole thing into one
program whose gradient collectives are generated by vma-checked autodiff.

The oracle is pure data parallelism over the same 8 chips with a dense
(unsharded) model holding identical weights: after k steps the hybrid
run's global parameters must match the dense run's bit-for-close.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.sharding import PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu.optimizers import build_train_step
from chainermn_tpu.parallel import (
    ColumnParallelDense,
    RowParallelDense,
    megatron_param_specs,
)

IN, EMB, HID, OUT, BATCH = 6, 8, 16, 4, 8


class TpMLP(nn.Module):
    """Replicated embed -> column-parallel -> row-parallel: exercises
    sharded params AND replicated params below/above the TP block."""

    model_axis: str = "mn_model"

    @nn.compact
    def __call__(self, x):
        x = jnp.tanh(nn.Dense(EMB, name="embed")(x))
        x = ColumnParallelDense(HID, axis_name=self.model_axis)(x)
        x = jax.nn.relu(x)
        x = RowParallelDense(OUT, axis_name=self.model_axis)(x)
        return x


class DenseMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = jnp.tanh(nn.Dense(EMB, name="embed")(x))
        x = nn.Dense(HID, name="col")(x)
        x = jax.nn.relu(x)
        x = nn.Dense(OUT, name="row")(x)
        return x


def _mse_loss(model):
    def loss(params, batch):
        x, y = batch
        pred = model.apply(params, x)
        return jnp.mean((pred - y) ** 2)

    return loss


def _dense_params():
    x0 = jnp.zeros((1, IN))
    return DenseMLP().init(jax.random.PRNGKey(0), x0)


def _tp_params_from_dense(dense_params):
    """Same global weights, laid out in the TP module's param structure."""
    p = dense_params["params"]
    return {
        "params": {
            "embed": p["embed"],
            "ColumnParallelDense_0": dict(p["col"]),
            "RowParallelDense_0": dict(p["row"]),
        }
    }


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(BATCH, IN), jnp.float32)
    y = jnp.asarray(rng.randn(BATCH, OUT), jnp.float32)
    return x, y


@pytest.fixture(scope="module")
def hybrid_comm(devices8):
    return cmn.create_communicator("hybrid", devices=devices8, tp_size=2)


class TestHybridMatchesPureDP:
    def _run_hybrid(self, comm, n_steps=3, lr=0.05):
        model = TpMLP()
        tp_params = _tp_params_from_dense(_dense_params())
        specs = megatron_param_specs(tp_params, model_axis="mn_model")
        opt = cmn.create_multi_node_optimizer(optax.adam(lr), comm)
        step = build_train_step(
            comm, _mse_loss(model), opt,
            data_axes=comm.data_axis_names, param_specs=specs,
            donate=False,
        )
        params, opt_state = step.place(tp_params, opt.init(tp_params))
        batch = step.place_batch(_batch())
        metrics = None
        for _ in range(n_steps):
            params, opt_state, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics

    def _run_dense_dp(self, devices8, n_steps=3, lr=0.05):
        comm = cmn.create_communicator("tpu", devices=devices8)
        model = DenseMLP()
        dense = _dense_params()
        opt = cmn.create_multi_node_optimizer(optax.adam(lr), comm)
        step = build_train_step(comm, _mse_loss(model), opt, donate=False)
        params, opt_state = step.place(dense, opt.init(dense))
        batch = step.place_batch(_batch())
        metrics = None
        for _ in range(n_steps):
            params, opt_state, metrics = step(params, opt_state, batch)
        return params, metrics

    def test_params_match_after_training(self, hybrid_comm, devices8):
        hp, _, hmetrics = self._run_hybrid(hybrid_comm)
        dp, dmetrics = self._run_dense_dp(devices8)
        np.testing.assert_allclose(
            float(hmetrics["loss"]), float(dmetrics["loss"]), rtol=1e-5
        )
        pairs = [
            ("embed", hp["params"]["embed"], dp["params"]["embed"]),
            ("col", hp["params"]["ColumnParallelDense_0"],
             dp["params"]["col"]),
            ("row", hp["params"]["RowParallelDense_0"], dp["params"]["row"]),
        ]
        for name, got, want in pairs:
            np.testing.assert_allclose(
                np.asarray(got["kernel"]), np.asarray(want["kernel"]),
                rtol=1e-4, atol=1e-6, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(got["bias"]), np.asarray(want["bias"]),
                rtol=1e-4, atol=1e-6, err_msg=name,
            )

    def test_tp_kernels_and_their_adam_moments_are_sharded(
            self, hybrid_comm):
        params, opt_state, _ = self._run_hybrid(hybrid_comm, n_steps=1)
        col_kernel = params["params"]["ColumnParallelDense_0"]["kernel"]
        assert col_kernel.shape == (EMB, HID)  # global shape
        # each chip materializes only its (EMB, HID/2) column block
        assert {s.data.shape for s in col_kernel.addressable_shards} == {
            (EMB, HID // 2)
        }
        mu = opt_state.inner_state[0].mu["params"][
            "ColumnParallelDense_0"]["kernel"]
        assert {s.data.shape for s in mu.addressable_shards} == {
            (EMB, HID // 2)
        }
        # replicated params stay replicated
        emb = params["params"]["embed"]["kernel"]
        assert {s.data.shape for s in emb.addressable_shards} == {(IN, EMB)}

    def test_wire_dtype_rejected_with_param_specs(self, devices8):
        comm = cmn.create_communicator(
            "hybrid", devices=devices8, tp_size=2,
            allreduce_grad_dtype=jnp.bfloat16,
        )
        model = TpMLP()
        tp_params = _tp_params_from_dense(_dense_params())
        specs = megatron_param_specs(tp_params, model_axis="mn_model")
        opt = cmn.create_multi_node_optimizer(optax.adam(0.1), comm)
        with pytest.raises(ValueError, match="allreduce_grad_dtype"):
            build_train_step(
                comm, _mse_loss(model), opt,
                data_axes=comm.data_axis_names, param_specs=specs,
            )

    def test_zero_redundancy_rejected_with_param_specs(self, hybrid_comm):
        model = TpMLP()
        tp_params = _tp_params_from_dense(_dense_params())
        specs = megatron_param_specs(tp_params, model_axis="mn_model")
        zopt = cmn.create_multi_node_optimizer(
            optax.adam(0.1), hybrid_comm, zero_redundancy=True
        )
        with pytest.raises(ValueError, match="zero_redundancy"):
            build_train_step(
                hybrid_comm, _mse_loss(model), zopt,
                data_axes=hybrid_comm.data_axis_names, param_specs=specs,
            )


class TestHybridCommunicator:
    def test_mesh_axes_and_sizes(self, hybrid_comm):
        assert hybrid_comm.axis_names == ("mn_data", "mn_model")
        assert hybrid_comm.dp_size == 4 and hybrid_comm.tp_size == 2
        assert dict(hybrid_comm.mesh.shape) == {"mn_data": 4, "mn_model": 2}

    def test_tp_size_must_divide(self, devices8):
        with pytest.raises(ValueError, match="divide"):
            cmn.create_communicator("hybrid", devices=devices8, tp_size=3)

    def test_split_based_groups_align_with_mesh(self, hybrid_comm):
        """The reference's split() composition: DP groups are the mesh
        columns (fixed model coordinate), TP groups the mesh rows."""
        dp = hybrid_comm.dp_groups()
        assert set(dp) == {0, 1}
        grid = hybrid_comm.mesh.devices
        for m, sub in dp.items():
            assert sub.size == 4
            assert set(sub.devices) == set(grid[:, m])
        tp = hybrid_comm.tp_groups()
        assert set(tp) == {0, 1, 2, 3}
        for d, sub in tp.items():
            assert sub.size == 2
            assert set(sub.devices) == set(grid[d, :])

    def test_split_group_allreduce_equals_group_mean(self, hybrid_comm):
        """Eager split-based DP: allreduce inside one DP group averages
        only that group's slices — the building block of hand-composed
        dual parallelism."""
        sub = hybrid_comm.dp_groups()[0]
        x = jnp.arange(float(sub.size * 2)).reshape(sub.size, 2)
        out = np.asarray(sub.allreduce(x, op="mean"))
        want = np.asarray(x).mean(axis=0)
        for r in range(sub.size):
            np.testing.assert_allclose(out[r], want, rtol=1e-6)


class TestGradReduceCompilerOptions:
    """The step builder's compile options are for TPU meshes whose data
    axes span chips: every CPU program compiles with none (XLA:CPU
    rejects ``xla_tpu_*``), whichever body ``_finish_build`` jits."""

    @pytest.mark.parametrize("body", ["param_specs", "wire", "bucket"])
    def test_cpu_mesh_jit_receives_no_options(
        self, hybrid_comm, devices8, monkeypatch, body
    ):
        seen = []
        real_jit = jax.jit

        def spy(*args, **kwargs):
            seen.append(kwargs.get("compiler_options"))
            return real_jit(*args, **kwargs)

        monkeypatch.setattr(jax, "jit", spy)
        if body == "param_specs":
            comm, model = hybrid_comm, TpMLP()
            params = _tp_params_from_dense(_dense_params())
            opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
            step = build_train_step(
                comm, _mse_loss(model), opt,
                data_axes=comm.data_axis_names,
                param_specs=megatron_param_specs(
                    params, model_axis="mn_model"),
                donate=False,
            )
        else:
            comm = cmn.create_communicator("tpu", devices=devices8)
            model, params = DenseMLP(), _dense_params()
            opt = cmn.create_multi_node_optimizer(
                optax.adam(0.05), comm,
                overlap="bucket" if body == "bucket" else "none")
            step = build_train_step(comm, _mse_loss(model), opt,
                                    donate=False)
        params, opt_state = step.place(params, opt.init(params))
        seen.clear()  # place() and init may jit helpers of their own
        _, _, metrics = step(params, opt_state, step.place_batch(_batch()))
        assert np.isfinite(float(metrics["loss"]))
        assert seen and all(options is None for options in seen)

    #: the ``param_specs`` body sums over the data axes; the plain body
    #: (the optimizer's own exchange) over all of the communicator's
    DATA, ALL = ("mn_data", "mn_seq"), ("mn_data", "mn_seq", "mn_model")

    @pytest.mark.parametrize("platform,dp,tp,axes,expect_options", [
        ("cpu", 4, 1, DATA, False),  # a CPU mesh never gets options
        ("tpu", 1, 1, DATA, False),  # one chip: the parent's program
        ("tpu", 4, 1, DATA, True),   # gradients summed across every chip
        ("tpu", 2, 2, DATA, False),  # dp x tp: the model axis' all-reduces
        ("tpu", 1, 4, DATA, False),  #   sit on the critical path; never
        #                                read or timed
        # the plain body: every axis is a data axis to it
        ("cpu", 4, 1, ALL, False),
        ("tpu", 1, 1, ALL, False),   # ResNet's one-chip cell
        ("tpu", 4, 1, ALL, True),    # cgpt590m_dpwire4_s2048
        ("tpu", 2, 2, ALL, True),    # the same sum, the mesh shaped 2 x 2
    ])
    def test_rule_reads_platform_and_reduced_extent(
        self, platform, dp, tp, axes, expect_options
    ):
        from types import SimpleNamespace

        from chainermn_tpu.optimizers import (
            _ASYNC_GRAD_REDUCE_OPTIONS,
            _grad_reduce_compiler_options,
        )

        device = SimpleNamespace(platform=platform)
        mesh = SimpleNamespace(
            devices=np.array([device] * (dp * tp), dtype=object).reshape(
                dp, 1, tp),
            shape={"mn_data": dp, "mn_seq": 1, "mn_model": tp},
        )
        got = _grad_reduce_compiler_options(mesh, axes)
        assert got == (_ASYNC_GRAD_REDUCE_OPTIONS if expect_options
                       else None)
        if expect_options:
            assert got is not _ASYNC_GRAD_REDUCE_OPTIONS  # a copy


def _param_specs_step(devices, dp, tp, loss_fn=None):
    """A ``param_specs`` step of ``TpMLP`` over a dp x tp CPU mesh and
    its placed ``(params, opt_state, batch)``."""
    comm = cmn.create_communicator(
        "hybrid", devices=devices[:dp * tp], tp_size=tp)
    params = _tp_params_from_dense(_dense_params())
    opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
    step = build_train_step(
        comm, loss_fn or _mse_loss(TpMLP()), opt,
        data_axes=comm.data_axis_names,
        param_specs=megatron_param_specs(params, model_axis="mn_model"),
        donate=False,
    )
    params, opt_state = step.place(params, opt.init(params))
    return comm, step, (params, opt_state, step.place_batch(_batch()))


class TestParamSpecsCollectiveTrace:
    """On the ``param_specs`` body autodiff sums the gradients, and under
    jax 0.9's vma-checked shard_map each of those sums is a
    ``psum_invariant``: the static trace, and with it the cross-rank
    divergence guard, must see them."""

    @pytest.mark.parametrize("dp,tp", [(2, 1), (4, 1), (2, 2)],
                             ids=["dp2", "dp4", "dp2_tp2"])
    def test_one_all_reduce_a_gradient_leaf_over_the_data_axes(
        self, devices8, dp, tp
    ):
        from chainermn_tpu.analysis import assert_census_agreement

        comm, step, args = _param_specs_step(devices8, dp, tp)
        trace = step.collective_trace(*args)
        assert {r.cls for r in trace} == {"all_reduce"}
        over_data = [r for r in trace
                     if r.axes == tuple(comm.data_axis_names)]
        # every leaf's gradient, at its per-chip size (a bias' is summed
        # as a row), and the loss
        want = [leaf.addressable_shards[0].data.size
                for leaf in jax.tree_util.tree_leaves(args[0])] + [1]
        assert sorted(int(np.prod(r.shapes[0])) for r in over_data) \
            == sorted(want)
        # the rest are the Megatron layers' own sums over the model axis
        assert all(r.axes == ("mn_model",)
                   for r in trace if r not in over_data)
        # the lowered program holds one all-reduce an equation ...
        lowered = step.get_jitted(*args[:2]).lower(*args)
        assert_census_agreement(trace, lowered.as_text())
        # ... which XLA:CPU may glue into tuples when it compiles: the
        # compiled step's all-reduces carry the same bytes in no more ops
        scheduled = step.collective_schedule(*args).ops
        assert {op.cls for op in scheduled} == {"all_reduce"}
        assert len(scheduled) <= len(trace)
        assert sum(op.nbytes for op in scheduled) == sum(
            r.payload_bytes for r in trace)

    def test_guard_tells_steps_apart_that_differ_by_one_pmean(
        self, devices8
    ):
        from chainermn_tpu.analysis import trace_agreement
        from chainermn_tpu.functions import collectives as cc
        from chainermn_tpu.resilience.errors import (
            CollectiveTraceMismatchError,
        )

        def one_more(params, batch):
            return cc.pmean(_mse_loss(TpMLP())(params, batch), "mn_model")

        _, step, args = _param_specs_step(devices8, 2, 2)
        _, other, other_args = _param_specs_step(devices8, 2, 2, one_more)
        mine = step.verify_collective_trace(*args)
        theirs = other.verify_collective_trace(*other_args)
        # the authored pmean and, from autodiff, its transpose
        assert len(other.collective_trace(*other_args)) == len(
            step.collective_trace(*args)) + 2
        assert mine != theirs

        class Peer:  # a two-process world whose other rank built `other`
            def allgather_obj(self, h):
                return [h, theirs]

        with pytest.raises(CollectiveTraceMismatchError):
            trace_agreement(Peer(), step.collective_trace(*args))


class _StepCompiles:
    """Counts backend compiles of ``jit(_step)`` while entered (the event
    ``cellbench/window.py::CompileCounter`` listens to; ``_cache_size()``
    counts argument signatures, not programs)."""

    def __enter__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, fun_name=None, **_):
        if event.endswith("backend_compile_duration") \
                and fun_name == "jit(_step)":
            self.count += 1

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)


class TestAStepIsOneExecutable:
    """However params and optimizer state arrive, the step they run is
    the one program: ``_finish_build`` pins ``in_shardings`` (state that
    is not committed to the mesh is laid out, not compiled for), and the
    step lays host values out before ``jit`` sees them (jit keys its
    trace on an argument's type, which carries the array's mesh)."""

    @pytest.mark.parametrize("n_devices", [1, 4])
    @pytest.mark.parametrize("body", ["param_specs", "wire"])
    def test_one_compile_and_equal_results_however_state_arrives(
        self, devices8, body, n_devices
    ):
        devices = devices8[:n_devices]
        if body == "param_specs":
            comm = cmn.create_communicator(
                "hybrid", devices=devices, tp_size=1)
            host_params = _tp_params_from_dense(_dense_params())
            opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
            step = build_train_step(
                comm, _mse_loss(TpMLP()), opt,
                data_axes=comm.data_axis_names,
                param_specs=megatron_param_specs(
                    host_params, model_axis="mn_model"),
                donate=False,
            )
        else:
            comm = cmn.create_communicator("tpu", devices=devices)
            host_params = _dense_params()
            opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
            step = build_train_step(
                comm, _mse_loss(DenseMLP()), opt, donate=False)
        tree_map = jax.tree_util.tree_map
        host_state = opt.init(host_params)  # Adam's: all zeros
        params, state = step.place(host_params, host_state)
        batch = step.place_batch(_batch())
        arrivals = {
            "placed": (params, state),
            # what the cells' runner hands the step at every reseed: the
            # outputs of a jit whose inputs are all dead are uncommitted
            "uncommitted": (
                params, jax.jit(lambda s: tree_map(jnp.zeros_like, s))(state)),
            "default_device": (host_params, host_state),
            "numpy_state": (params, tree_map(np.asarray, state)),
            "numpy": (tree_map(np.asarray, params),
                      tree_map(np.asarray, state)),
        }
        assert not any(leaf.committed for leaf in jax.tree_util.tree_leaves(
            arrivals["uncommitted"][1]))
        with _StepCompiles() as compiles:
            got = {name: step(p, s, batch)
                   for name, (p, s) in arrivals.items()}
            # and the step's own outputs, the steady state of a loop
            step(*got["placed"][:2], batch)
        assert compiles.count == 1
        want = jax.tree_util.tree_leaves(got["placed"])
        for name, out in got.items():
            for a, b in zip(want, jax.tree_util.tree_leaves(out)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=name)
        assert np.isfinite(float(got["placed"][2]["loss"]))

    @pytest.mark.parametrize("body", ["param_specs", "wire"])
    def test_placed_call_compiles_to_the_unpinned_text(
        self, devices8, pinned_and_unpinned_texts, body
    ):
        def build():
            if body == "param_specs":
                _, step, args = _param_specs_step(devices8, 4, 1)
                return step, args
            comm = cmn.create_communicator("tpu", devices=devices8[:4])
            opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
            step = build_train_step(
                comm, _mse_loss(DenseMLP()), opt, donate=False)
            params = _dense_params()
            return step, (*step.place(params, opt.init(params)),
                          step.place_batch(_batch()))

        pinned, unpinned = pinned_and_unpinned_texts(build)
        assert pinned == unpinned
