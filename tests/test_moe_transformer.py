"""Composed-parallelism tests: MoE transformer on a (data, seq, model) mesh.

The oracle is **mesh-factorization invariance**: the SAME
``MoeTransformerLM`` runs on a ``(1,1,1)`` mesh (every axis width 1 — all
collectives degenerate) and on a ``(2,2,2)`` mesh (DP x SP ring attention
x TP Megatron x EP all_to_all all live), with identical global parameter
values and ample expert capacity (no token drops).  Losses and updated
parameters must agree — which exercises every collective the composition
inserts: ring ppermute, sp_lm_loss boundary exchange, column/row TP
psums, EP all_to_all dispatch/return, and the vma-generated gradient
reductions over all three axes.

Reference anchor: the reference composed at most DP x hand-built MP via
``CommunicatorBase.split`` (SURVEY.md section 2 strategy table); SP and EP
are the new capabilities its ``alltoall``/p2p primitives point at
(SURVEY.md section 5.7).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu.models.moe_transformer import (
    MoeMlp,
    MoeTransformerLM,
    moe_lm_loss,
    moe_param_specs,
)
from chainermn_tpu.optimizers import build_train_step
from chainermn_tpu.parallel import sharded_init

VOCAB, D, HEADS, LAYERS, EXPERTS, FF = 61, 32, 4, 2, 4, 64
B, S = 4, 16
CAP = B * S * 2  # >= total routed claims: nothing is ever dropped


def _model(comm=None, capacity=CAP):
    kw = {}
    if comm is not None:
        # aux_stat_axes over every token-splitting axis: the
        # load-balancing loss becomes the exact global-batch value, so
        # the factorization oracle can run with the aux term ON.
        kw = dict(seq_axis="mn_seq", tp_axis="mn_model",
                  expert_axis="mn_model",
                  aux_stat_axes=("mn_data", "mn_seq", "mn_model"))
    return MoeTransformerLM(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        n_experts=EXPERTS, d_ff=FF, moe_every=2, k=2, capacity=capacity,
        max_len=S, dtype=jnp.float32, **kw,
    )


def _tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, S)), jnp.int32
    )


def _init_on(comm):
    model = _model(comm)
    toks = _tokens()
    params, specs = sharded_init(
        lambda t: model.init(jax.random.PRNGKey(0), t),
        comm.mesh, (P("mn_data", "mn_seq"),),
        moe_param_specs, toks,
    )
    return model, params, specs


def _host_tree(params):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), params)


def _run_steps(comm, params_host, n_steps=2, lr=5e-2, aux_coef=1e-2):
    model = _model(comm)
    specs = moe_param_specs(params_host)
    opt = cmn.create_multi_node_optimizer(optax.sgd(lr), comm)

    def loss_fn(p, b):
        return moe_lm_loss(
            model.apply(p, b), b, seq_axis="mn_seq",
            model_axis="mn_model", aux_coef=aux_coef,
        )

    step = build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
        donate=False,
    )
    params, opt_state = step.place(params_host, opt.init(params_host))
    batch = step.place_batch(_tokens())
    losses = []
    for _ in range(n_steps):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    return params, losses


class TestMeshCommunicator:
    def test_axes_and_sizes(self, devices8):
        comm = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        assert comm.axis_names == ("mn_data", "mn_seq", "mn_model")
        assert (comm.dp_size, comm.sp_size, comm.tp_size) == (2, 2, 2)
        assert dict(comm.mesh.shape) == {
            "mn_data": 2, "mn_seq": 2, "mn_model": 2
        }

    def test_sizes_must_divide(self, devices8):
        with pytest.raises(ValueError, match="divide"):
            cmn.create_communicator(
                "mesh", devices=devices8, sp_size=3, tp_size=2
            )

    def test_width_one_axes_are_plain_dp(self, devices8):
        comm = cmn.create_communicator("mesh", devices=devices8)
        assert (comm.dp_size, comm.sp_size, comm.tp_size) == (8, 1, 1)


class TestFactorizationOracle:
    """(1,1,1) vs (2,2,2): same global params, same numerics."""

    @pytest.fixture(scope="class")
    def runs(self, devices8):
        comm222 = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        comm111 = cmn.create_communicator(
            "mesh", devices=devices8[:1], sp_size=1, tp_size=1
        )
        _, params, _ = _init_on(comm222)
        host = _host_tree(params)
        p222, l222 = _run_steps(comm222, host)
        p111, l111 = _run_steps(comm111, host)
        return (
            _host_tree(p222), l222, _host_tree(p111), l111
        )

    def test_losses_match(self, runs):
        _, l222, _, l111 = runs
        np.testing.assert_allclose(l222, l111, rtol=2e-4, atol=1e-5)

    def test_updated_params_match(self, runs):
        p222, _, p111, _ = runs
        flat222 = jax.tree_util.tree_leaves_with_path(p222)
        flat111 = dict(jax.tree_util.tree_leaves_with_path(p111))
        assert flat222
        for path, leaf in flat222:
            want = flat111[path]
            np.testing.assert_allclose(
                leaf, want, rtol=5e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(path),
            )

    def test_expert_and_tp_leaves_are_sharded(self, devices8):
        comm = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        _, params, specs = _init_on(comm)
        flat = jax.tree_util.tree_leaves_with_path(params)
        by_name = {jax.tree_util.keystr(p): v for p, v in flat}
        w1 = next(v for k, v in by_name.items()
                  if k.endswith("['expert_w1']"))
        assert w1.shape == (EXPERTS, D, FF)  # global expert dim
        assert {s.data.shape for s in w1.addressable_shards} == {
            (EXPERTS // 2, D, FF)
        }
        up = next(v for k, v in by_name.items()
                  if "TpMlpBlock" in k and "ColumnParallel" in k
                  and k.endswith("['kernel']"))
        assert up.shape == (D, FF)
        assert {s.data.shape for s in up.addressable_shards} == {
            (D, FF // 2)
        }


class TestComposedVocabParallel:
    """The fully-loaded flagship: DP x SP(ring) x TP x EP PLUS the
    vocab-parallel embedding/head — factorization oracle on a
    64-vocab model (divisible by the model-axis width)."""

    def _run(self, comm, params_host, n_steps=2):
        model = MoeTransformerLM(
            vocab_size=64, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            n_experts=EXPERTS, d_ff=FF, moe_every=2, k=2, capacity=CAP,
            max_len=S, dtype=jnp.float32, seq_axis="mn_seq",
            tp_axis="mn_model", expert_axis="mn_model",
            vocab_parallel=True,
            aux_stat_axes=("mn_data", "mn_seq", "mn_model"),
        )
        specs = moe_param_specs(params_host)
        opt = cmn.create_multi_node_optimizer(optax.sgd(5e-2), comm)

        def loss_fn(p, b):
            return moe_lm_loss(
                model.apply(p, b), b, seq_axis="mn_seq",
                model_axis="mn_model", aux_coef=1e-2,
                vocab_parallel=True,
            )

        step = build_train_step(
            comm, loss_fn, opt, data_axes=comm.data_axis_names,
            param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
            donate=False,
        )
        params, opt_state = step.place(params_host, opt.init(params_host))
        toks = jnp.asarray(
            np.random.RandomState(2).randint(0, 64, (B, S)), jnp.int32
        )
        batch = step.place_batch(toks)
        losses = []
        for _ in range(n_steps):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return _host_tree(params), losses

    def test_factorizations_agree(self, devices8):
        comm222 = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        comm111 = cmn.create_communicator(
            "mesh", devices=devices8[:1], sp_size=1, tp_size=1
        )
        model = MoeTransformerLM(
            vocab_size=64, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            n_experts=EXPERTS, d_ff=FF, moe_every=2, k=2, capacity=CAP,
            max_len=S, dtype=jnp.float32, seq_axis="mn_seq",
            tp_axis="mn_model", expert_axis="mn_model",
            vocab_parallel=True,
            aux_stat_axes=("mn_data", "mn_seq", "mn_model"),
        )
        toks = jnp.asarray(
            np.random.RandomState(2).randint(0, 64, (B, S)), jnp.int32
        )
        params, _ = sharded_init(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            comm222.mesh, (P("mn_data", "mn_seq"),), moe_param_specs,
            toks,
        )
        emb = params["params"]["VocabParallelEmbed_0"]["embedding"]
        assert emb.shape == (64, D)  # global vocab dim
        assert {sh.data.shape for sh in emb.addressable_shards} == {
            (32, D)
        }
        host = _host_tree(params)
        p222, l222 = self._run(comm222, host)
        p111, l111 = self._run(comm111, host)
        assert all(np.isfinite(l222))
        np.testing.assert_allclose(l222, l111, rtol=2e-4, atol=1e-5)
        flat111 = dict(jax.tree_util.tree_leaves_with_path(p111))
        for path, leaf in jax.tree_util.tree_leaves_with_path(p222):
            np.testing.assert_allclose(
                leaf, flat111[path], rtol=5e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(path),
            )


class TestComposedTraining:
    def test_loss_decreases_with_aux(self, devices8):
        comm = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        _, params, _ = _init_on(comm)
        _, losses = _run_steps(
            comm, _host_tree(params), n_steps=6, lr=0.1, aux_coef=1e-2
        )
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestShardedCheckpoint:
    """Checkpoint/resume round-trip with mesh-sharded parameters: TP
    kernels and expert blocks live sharded over mn_model; a snapshot
    taken mid-run must restore into an identical continued training
    trajectory (SURVEY.md section 2 #29, arrays now global/sharded)."""

    def test_resume_matches_uninterrupted(self, devices8, tmp_path):
        comm = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=2, tp_size=2
        )
        _, params0, _ = _init_on(comm)
        host = _host_tree(params0)

        # uninterrupted: 2 steps
        p_full, _ = _run_steps(comm, host, n_steps=2)

        # interrupted: 1 step, checkpoint, restore, 1 more step
        model = _model(comm)
        specs = moe_param_specs(host)
        opt = cmn.create_multi_node_optimizer(optax.sgd(5e-2), comm)

        def loss_fn(p, b):
            return moe_lm_loss(
                model.apply(p, b), b, seq_axis="mn_seq",
                model_axis="mn_model", aux_coef=1e-2,
            )

        step = build_train_step(
            comm, loss_fn, opt, data_axes=comm.data_axis_names,
            param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
            donate=False,
        )
        params, opt_state = step.place(host, opt.init(host))
        batch = step.place_batch(_tokens())
        params, opt_state, _ = step(params, opt_state, batch)

        ckpt = cmn.create_multi_node_checkpointer(
            "moe", comm, path=str(tmp_path)
        )
        ckpt.save(1, {"params": params, "opt_state": opt_state})

        restored_step, state = ckpt.resume(
            like={"params": params, "opt_state": opt_state}
        )
        assert restored_step == 1
        # re-place per the sharding specs (restore may yield host arrays)
        rparams, ropt = step.place(state["params"], state["opt_state"])
        rparams, ropt, _ = step(rparams, ropt, batch)

        flat_full = dict(jax.tree_util.tree_leaves_with_path(
            _host_tree(p_full)
        ))
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            _host_tree(rparams)
        ):
            np.testing.assert_allclose(
                leaf, flat_full[path], rtol=1e-6, atol=1e-7,
                err_msg=jax.tree_util.keystr(path),
            )


class TestMoeMlpDenseVsParallel:
    """The expert_axis=None tier is the numerics oracle for the EP path."""

    def test_dense_matches_expert_parallel(self, devices8):
        mesh = cmn.create_communicator(
            "mesh", devices=devices8[:2], sp_size=1, tp_size=2
        ).mesh
        cap = 64
        par = MoeMlp(n_experts=4, d_ff=32, k=2, capacity=cap,
                     expert_axis="mn_model", dtype=jnp.float32)
        dense = MoeMlp(n_experts=4, d_ff=32, k=2, capacity=cap,
                       expert_axis=None, dtype=jnp.float32)
        x = jnp.asarray(
            np.random.RandomState(3).randn(2, 8, 16), jnp.float32
        )

        def init_fn(xx):
            return par.init(jax.random.PRNGKey(1), xx)

        params, _ = sharded_init(
            init_fn, mesh, (P(),),
            lambda p: moe_param_specs(p, model_axis="mn_model"), x,
        )
        y_par = jax.jit(
            jax.shard_map(
                lambda p, xx: par.apply(p, xx)[0],
                mesh=mesh,
                in_specs=(moe_param_specs(params), P()),
                out_specs=P(), check_vma=False,
            )
        )(params, x)
        y_dense, aux_dense = dense.apply(_host_tree(params), x)
        np.testing.assert_allclose(
            np.asarray(y_par), np.asarray(y_dense), rtol=1e-5, atol=1e-6
        )
        assert np.isfinite(float(aux_dense))

    def test_capacity_drop_zeroes_tokens(self):
        """With capacity 1 and concentrated routing, overflow tokens
        contribute zeros (standard MoE drop semantics)."""
        m = MoeMlp(n_experts=2, d_ff=8, k=1, capacity=1,
                   expert_axis=None, dtype=jnp.float32)
        x = jnp.ones((1, 4, 6), jnp.float32)  # identical tokens
        params = m.init(jax.random.PRNGKey(0), x)
        y, _ = m.apply(params, x)
        # identical tokens route identically: 1 kept per expert per
        # claim-route, the rest dropped -> some rows exactly zero
        rows = np.asarray(y)[0]
        assert (np.abs(rows).sum(axis=-1) == 0).any()


class TestVocabParallel:
    """Megatron vocab-parallel embedding + cross entropy: the (.., V)
    logits row never materializes; numerics must match the dense path."""

    def test_cross_entropy_matches_optax(self, devices8):
        import optax
        from jax.sharding import Mesh, NamedSharding

        from chainermn_tpu.parallel import vocab_parallel_cross_entropy

        mesh2 = Mesh(np.array(devices8[:2]), ("tp",))
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(4, 10, 32), jnp.float32)
        targets = jnp.asarray(rng.randint(0, 32, (4, 10)), jnp.int32)
        want = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        )
        f = jax.jit(
            jax.shard_map(
                lambda lg, t: vocab_parallel_cross_entropy(lg, t, "tp"),
                mesh=mesh2,
                in_specs=(P(None, None, "tp"), P()),
                out_specs=P(), check_vma=False,
            )
        )
        got = f(
            jax.device_put(
                logits, NamedSharding(mesh2, P(None, None, "tp"))
            ),
            targets,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_embed_matches_dense_lookup(self, devices8):
        from jax.sharding import Mesh
        from chainermn_tpu.parallel import VocabParallelEmbed
        from chainermn_tpu.parallel.tensor_parallel import _tp_leaf_spec

        mesh2 = cmn.create_communicator(
            "mesh", devices=devices8[:2], sp_size=1, tp_size=2
        ).mesh
        vp = VocabParallelEmbed(32, 8, axis_name="mn_model")
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 32, (3, 5)), jnp.int32
        )
        params, _ = sharded_init(
            lambda t: vp.init(jax.random.PRNGKey(0), t),
            mesh2, (P(),),
            lambda p: jax.tree_util.tree_map(
                lambda _: P("mn_model", None), p
            ),
            toks,
        )
        table = np.asarray(params["params"]["embedding"])  # global (32, 8)
        assert table.shape == (32, 8)
        out = jax.jit(
            jax.shard_map(
                lambda p, t: vp.apply(p, t),
                mesh=mesh2,
                in_specs=(
                    jax.tree_util.tree_map(
                        lambda _: P("mn_model", None), params
                    ),
                    P(),
                ),
                out_specs=P(), check_vma=False,
            )
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out), table[np.asarray(toks)], rtol=1e-6
        )

    def _run_vp(self, comm, params_host, n_steps=2):
        from chainermn_tpu.models.transformer import (
            TransformerLM,
            vp_lm_loss,
        )
        from chainermn_tpu.parallel import megatron_param_specs

        model = TransformerLM(
            vocab_size=64, d_model=D, n_heads=HEADS, n_layers=2,
            max_len=S, dtype=jnp.float32, tp_axis="mn_model",
            vocab_parallel=True,
        )
        specs = megatron_param_specs(params_host, model_axis="mn_model")
        opt = cmn.create_multi_node_optimizer(optax.sgd(5e-2), comm)

        def loss_fn(p, b):
            return vp_lm_loss(model.apply(p, b), b, "mn_model")

        step = build_train_step(
            comm, loss_fn, opt, data_axes=comm.data_axis_names,
            param_specs=specs, batch_specs=P("mn_data"), donate=False,
        )
        params, opt_state = step.place(params_host, opt.init(params_host))
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (8, S)), jnp.int32
        )
        batch = step.place_batch(toks)
        losses = []
        for _ in range(n_steps):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return _host_tree(params), losses

    def test_vp_lm_factorization_oracle(self, devices8):
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.parallel import megatron_param_specs

        comm_tp = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=1, tp_size=2
        )
        comm_dp = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=1, tp_size=1
        )
        model = TransformerLM(
            vocab_size=64, d_model=D, n_heads=HEADS, n_layers=2,
            max_len=S, dtype=jnp.float32, tp_axis="mn_model",
            vocab_parallel=True,
        )
        params, _ = sharded_init(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            comm_tp.mesh, (P("mn_data"),),
            lambda p: megatron_param_specs(p, model_axis="mn_model"),
            jnp.zeros((4, S), jnp.int32),
        )
        # embedding is genuinely vocab-sharded on the TP mesh
        emb = params["params"]["VocabParallelEmbed_0"]["embedding"]
        assert emb.shape == (64, D)
        assert {sh.data.shape for sh in emb.addressable_shards} == {
            (32, D)
        }
        host = _host_tree(params)
        p_tp, l_tp = self._run_vp(comm_tp, host)
        p_dp, l_dp = self._run_vp(comm_dp, host)
        np.testing.assert_allclose(l_tp, l_dp, rtol=2e-4, atol=1e-5)
        flat_dp = dict(jax.tree_util.tree_leaves_with_path(p_dp))
        for path, leaf in jax.tree_util.tree_leaves_with_path(p_tp):
            np.testing.assert_allclose(
                leaf, flat_dp[path], rtol=5e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(path),
            )

    def test_vocab_parallel_without_tp_axis_rejected(self):
        from chainermn_tpu.models.transformer import TransformerLM

        model = TransformerLM(
            vocab_size=64, d_model=D, n_heads=HEADS, n_layers=1,
            max_len=S, dtype=jnp.float32, vocab_parallel=True,
        )
        with pytest.raises(ValueError, match="vocab_parallel"):
            model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32)
            )


class TestTpOnlyTransformer:
    """TransformerLM(tp_axis=...) factorization oracle: (8,1,1) vs
    (4,1,2) — Megatron attention + MLP sharding changes nothing."""

    def _run(self, comm, params_host, n_steps=2):
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.parallel import megatron_param_specs

        model = TransformerLM(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=2,
            max_len=S, dtype=jnp.float32, tp_axis="mn_model",
        )
        specs = megatron_param_specs(params_host, model_axis="mn_model")
        opt = cmn.create_multi_node_optimizer(optax.sgd(5e-2), comm)

        def loss_fn(p, b):
            from chainermn_tpu.models.transformer import lm_loss

            return lm_loss(model.apply(p, b), b)

        step = build_train_step(
            comm, loss_fn, opt, data_axes=comm.data_axis_names,
            param_specs=specs, batch_specs=P("mn_data"), donate=False,
        )
        params, opt_state = step.place(params_host, opt.init(params_host))
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, VOCAB, (8, S)), jnp.int32
        )
        batch = step.place_batch(toks)
        losses = []
        for _ in range(n_steps):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return _host_tree(params), losses

    def test_tp_matches_width_one(self, devices8):
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.parallel import megatron_param_specs

        comm_tp = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=1, tp_size=2
        )
        comm_dp = cmn.create_communicator(
            "mesh", devices=devices8, sp_size=1, tp_size=1
        )
        model = TransformerLM(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=2,
            max_len=S, dtype=jnp.float32, tp_axis="mn_model",
        )
        params, _ = sharded_init(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            comm_tp.mesh, (P("mn_data"),),
            lambda p: megatron_param_specs(p, model_axis="mn_model"),
            _tokens(1),
        )
        host = _host_tree(params)
        p_tp, l_tp = self._run(comm_tp, host)
        p_dp, l_dp = self._run(comm_dp, host)
        np.testing.assert_allclose(l_tp, l_dp, rtol=2e-4, atol=1e-5)
        flat_dp = dict(jax.tree_util.tree_leaves_with_path(p_dp))
        for path, leaf in jax.tree_util.tree_leaves_with_path(p_tp):
            np.testing.assert_allclose(
                leaf, flat_dp[path], rtol=5e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(path),
            )
