"""Multi-node optimizer tests.

Parity: ``optimizers_tests/test_multi_node_optimizer.py`` — grads applied
equal the mean of per-rank grads; double-buffering staleness semantics.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu.optimizers import build_train_step


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


def _quadratic_loss(params, batch):
    # loss = 0.5 * ||w - x_mean||^2 per shard; grad = w - mean(local batch)
    x = batch
    return 0.5 * jnp.sum((params["w"] - x.mean(axis=0)) ** 2)


class TestGradientSync:
    def test_update_applies_mean_gradient(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(1.0), comm)
        params = {"w": jnp.zeros((4,))}
        step = build_train_step(comm, _quadratic_loss, opt, donate=False)
        params, opt_state = step.place(params, opt.init(params))
        # batch: shard r has all-r rows -> local grad = w - r
        x = jnp.stack([jnp.full((4,), float(r)) for r in range(8)])
        bx = jax.device_put(x, step.batch_sharding)
        new_params, _, metrics = step(params, opt_state, bx)
        # mean over ranks of (w - r) = -3.5 ; sgd(1.0): w <- w + 3.5
        np.testing.assert_allclose(np.asarray(new_params["w"]), 3.5, rtol=1e-6)

    def test_loss_is_global_mean(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.0), comm)
        params = {"w": jnp.zeros((4,))}
        step = build_train_step(comm, _quadratic_loss, opt, donate=False)
        params, opt_state = step.place(params, opt.init(params))
        x = jnp.stack([jnp.full((4,), float(r)) for r in range(8)])
        _, _, metrics = step(params, opt_state, jax.device_put(x, step.batch_sharding))
        expect = np.mean([0.5 * 4 * r * r for r in range(8)])
        np.testing.assert_allclose(float(metrics["loss"]), expect, rtol=1e-5)

    def test_gspmd_path_matches_shard_map_path(self, comm):
        opt1 = cmn.create_multi_node_optimizer(optax.sgd(0.5), comm)
        opt2 = optax.sgd(0.5)
        params = {"w": jnp.ones((4,))}
        x = jnp.stack([jnp.full((4,), float(r)) for r in range(8)])

        s1 = build_train_step(comm, _quadratic_loss, opt1, donate=False)
        p1, o1 = s1.place(params, opt1.init(params))
        p1, _, _ = s1(p1, o1, jax.device_put(x, s1.batch_sharding))

        def global_loss(params, batch):
            return 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)

        s2 = build_train_step(comm, global_loss, opt2, donate=False,
                              use_shard_map=False)
        p2, o2 = s2.place(params, opt2.init(params))
        p2, _, _ = s2(p2, o2, jax.device_put(x, s2.batch_sharding))
        # Note: shard-map path averages per-shard losses of per-shard means;
        # GSPMD path differentiates global-batch mean. For this loss both
        # give w - mean(r) gradients.
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5
        )


class TestGradAccumulation:
    """accum_steps=k: microbatched gradients inside one compiled step.
    For a mean-style loss over equal microbatches the numerics match the
    unaccumulated step exactly."""

    def _mean_loss(self, params, batch):
        x = batch
        return jnp.mean((x @ params["w"] - 1.0) ** 2)

    def _run(self, comm, accum, n_steps=3):
        opt = cmn.create_multi_node_optimizer(optax.adam(0.1), comm)
        params = {"w": jnp.ones((4,)) * 0.3}
        step = build_train_step(
            comm, self._mean_loss, opt, donate=False, accum_steps=accum
        )
        params, opt_state = step.place(params, opt.init(params))
        x = jnp.asarray(
            np.random.RandomState(0).randn(32, 4), jnp.float32
        )
        bx = jax.device_put(x, step.batch_sharding)
        losses = []
        for _ in range(n_steps):
            params, opt_state, m = step(params, opt_state, bx)
            losses.append(float(m["loss"]))
        return np.asarray(params["w"]), losses

    def test_matches_unaccumulated(self, comm):
        w1, l1 = self._run(comm, accum=1)
        w2, l2 = self._run(comm, accum=2)
        w4, l4 = self._run(comm, accum=4)
        np.testing.assert_allclose(l2, l1, rtol=1e-5)
        np.testing.assert_allclose(l4, l1, rtol=1e-5)
        np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(w4, w1, rtol=1e-5, atol=1e-7)

    def test_indivisible_microbatch_rejected(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.ones((4,))}
        step = build_train_step(
            comm, self._mean_loss, opt, donate=False, accum_steps=3
        )
        params, opt_state = step.place(params, opt.init(params))
        x = jnp.zeros((32, 4))  # 4 rows/chip, not divisible by 3
        with pytest.raises(ValueError, match="accum_steps"):
            step(params, opt_state, jax.device_put(x, step.batch_sharding))

    def test_bad_accum_steps_rejected(self, comm):
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
        with pytest.raises(ValueError, match="accum_steps"):
            build_train_step(comm, self._mean_loss, opt, accum_steps=0)

    def test_with_aux_state(self, comm):
        """has_aux + accumulation: numeric aux leaves are averaged over
        microbatches (and across the mesh)."""

        def loss_fn(params, batch):
            x = batch
            loss = jnp.mean((x @ params["w"]) ** 2)
            return loss, {"batch_mean": jnp.mean(x)}

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.01), comm)
        params = {"w": jnp.ones((4,))}
        step = build_train_step(
            comm, loss_fn, opt, donate=False, accum_steps=2,
            has_aux=True,
            merge_aux=lambda p, a: {"w": p["w"], "seen": a["batch_mean"]},
        )
        full = {"w": params["w"], "seen": jnp.zeros(())}
        params, opt_state = step.place(full, opt.init(full))
        x = jnp.asarray(
            np.random.RandomState(1).randn(32, 4), jnp.float32
        )
        params, opt_state, m = step(
            params, opt_state, jax.device_put(x, step.batch_sharding)
        )
        assert np.isfinite(float(m["loss"]))
        # numeric aux averaged over microbatches AND the mesh = the
        # global batch mean
        np.testing.assert_allclose(
            float(params["seen"]), float(jnp.mean(x)), rtol=1e-5
        )


class TestRemat:
    """remat=True rematerializes the forward in the backward — values
    and updates must be bit-comparable to the plain step."""

    def _mlp_loss(self, params, batch):
        x = batch
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"]) ** 2)

    def _run(self, comm, remat):
        opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
        rng = np.random.RandomState(0)
        params = {
            "w1": jnp.asarray(rng.randn(4, 8), jnp.float32) * 0.4,
            "w2": jnp.asarray(rng.randn(8, 2), jnp.float32) * 0.4,
        }
        step = build_train_step(
            comm, self._mlp_loss, opt, donate=False, remat=remat,
            accum_steps=2,
        )
        params, opt_state = step.place(params, opt.init(params))
        x = jnp.asarray(rng.randn(32, 4), jnp.float32)
        bx = jax.device_put(x, step.batch_sharding)
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, bx)
        return np.asarray(params["w1"]), float(m["loss"])

    def test_remat_matches_plain(self, comm):
        w_plain, l_plain = self._run(comm, remat=False)
        w_remat, l_remat = self._run(comm, remat=True)
        np.testing.assert_allclose(l_remat, l_plain, rtol=1e-6)
        np.testing.assert_allclose(w_remat, w_plain, rtol=1e-6, atol=1e-8)

    def test_policy_object_accepted(self, comm):
        policy = jax.checkpoint_policies.nothing_saveable
        w_pol, l_pol = self._run(comm, remat=policy)
        w_plain, l_plain = self._run(comm, remat=False)
        np.testing.assert_allclose(l_pol, l_plain, rtol=1e-6)
        np.testing.assert_allclose(w_pol, w_plain, rtol=1e-6, atol=1e-8)


class TestDoubleBuffering:
    def test_first_update_is_zero_then_stale(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(1.0), comm, double_buffering=True
        )
        params = {"w": jnp.zeros((2,))}
        step = build_train_step(comm, _quadratic_loss, opt, donate=False)
        params, opt_state = step.place(params, opt.init(params))
        x = jnp.stack([jnp.full((2,), float(r)) for r in range(8)])
        bx = jax.device_put(x, step.batch_sharding)

        p1, opt_state, _ = step(params, opt_state, bx)
        # step 1 applied zeros (no synced grads yet)
        np.testing.assert_allclose(np.asarray(p1["w"]), 0.0, atol=1e-7)
        p2, opt_state, _ = step(p1, opt_state, bx)
        # step 2 applies step-1's grads: mean(w0 - r) = -3.5 -> w = 3.5
        np.testing.assert_allclose(np.asarray(p2["w"]), 3.5, rtol=1e-6)

    def test_state_carries_step_count(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, double_buffering=True
        )
        params = {"w": jnp.zeros((2,))}
        state = opt.init(params)
        assert int(state.step) == 0
        assert "prev_grads" in state._fields


class TestReducedPrecisionGrads:
    def test_bf16_grad_sync_close_to_fp32(self, devices8):
        comm_bf16 = cmn.create_communicator(
            "tpu", devices=devices8, allreduce_grad_dtype=jnp.bfloat16
        )
        comm_fp32 = cmn.create_communicator("tpu", devices=devices8)
        params = {"w": jnp.zeros((4,))}
        x = jnp.stack([jnp.full((4,), float(r)) for r in range(8)])
        outs = []
        for comm in (comm_bf16, comm_fp32):
            opt = cmn.create_multi_node_optimizer(optax.sgd(1.0), comm)
            step = build_train_step(comm, _quadratic_loss, opt, donate=False)
            p, o = step.place(params, opt.init(params))
            p, _, _ = step(p, o, jax.device_put(x, step.batch_sharding))
            outs.append(np.asarray(p["w"]))
        np.testing.assert_allclose(outs[0], outs[1], rtol=2e-2)


class TestDelegation:
    def test_wrapper_exposes_inner(self, comm):
        inner = optax.adam(1e-3)
        opt = cmn.create_multi_node_optimizer(inner, comm)
        assert opt.actual_optimizer is inner
        assert opt.communicator is comm


class TestZeroRedundancy:
    """ZeRO-1 optimizer-state sharding (zero_redundancy=True)."""

    def _run(self, comm, opt, params, n_steps=3):
        step = build_train_step(comm, _quadratic_loss, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        x = jnp.stack([jnp.full(params["w"].shape, float(r)) for r in range(8)])
        bx = jax.device_put(x, step.batch_sharding)
        for _ in range(n_steps):
            p, o, _ = step(p, o, bx)
        return p, o

    def test_matches_plain_adam(self, comm):
        params = {"w": jnp.ones((8,)) * 0.3}
        plain = cmn.create_multi_node_optimizer(optax.adam(0.1), comm)
        zero = cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        )
        p_plain, _ = self._run(comm, plain, params)
        p_zero, _ = self._run(comm, zero, params)
        np.testing.assert_allclose(
            np.asarray(p_plain["w"]), np.asarray(p_zero["w"]), rtol=1e-5
        )

    def test_matches_with_padding(self, comm):
        # 5 elements over 8 shards: blocks are zero-padded
        params = {"w": jnp.asarray([0.1, -0.2, 0.3, 0.5, -0.4])}
        plain = cmn.create_multi_node_optimizer(optax.adam(0.05), comm)
        zero = cmn.create_multi_node_optimizer(
            optax.adam(0.05), comm, zero_redundancy=True
        )
        p_plain, _ = self._run(comm, plain, params)
        p_zero, _ = self._run(comm, zero, params)
        np.testing.assert_allclose(
            np.asarray(p_plain["w"]), np.asarray(p_zero["w"]), rtol=1e-5
        )

    def test_state_is_sharded_one_block_per_chip(self, comm):
        params = {"w": jnp.ones((16,))}
        zero = cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        )
        _, opt_state = self._run(comm, zero, params, n_steps=1)
        # Adam mu leaf: global shape (8, 2), each chip holds one (1, 2) block
        mu = opt_state.inner_state[0].mu["w"]
        assert mu.shape == (8, 2)
        shard_shapes = {s.data.shape for s in mu.addressable_shards}
        assert shard_shapes == {(1, 2)}

    def test_per_chip_state_memory_is_one_nth(self, comm):
        """The ZeRO-1 memory claim, measured: per-device optimizer-state
        bytes for a real TransformerLM under adam must drop to ~1/8 on
        the 8-device mesh (exact shard accounting via
        addressable_shards — the same layout a real TPU mesh gets)."""
        import jax.tree_util as jtu

        from chainermn_tpu.models.transformer import TransformerLM

        model = TransformerLM(
            vocab_size=8192, d_model=512, n_heads=8, n_layers=4,
            max_len=128, dtype=jnp.float32,
        )
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)
        )
        n_params = sum(
            x.size for x in jtu.tree_leaves(params)
        )

        def per_device_state_bytes(opt):
            step = build_train_step(
                comm, lambda p, b: 0.0 * jnp.sum(b),
                opt, donate=False,
            )
            p, o = step.place(params, opt.init(params))
            dev = comm.devices[0]
            total = 0
            for leaf in jtu.tree_leaves(o):
                if not hasattr(leaf, "addressable_shards"):
                    continue
                for s in leaf.addressable_shards:
                    if s.device == dev:
                        total += s.data.nbytes
            return total

        plain = cmn.create_multi_node_optimizer(optax.adam(0.1), comm)
        zero = cmn.create_multi_node_optimizer(
            optax.adam(0.1), comm, zero_redundancy=True
        )
        b_plain = per_device_state_bytes(plain)
        b_zero = per_device_state_bytes(zero)
        # plain adam replicates mu+nu: ~2 x params x 4B per device
        assert b_plain >= 2 * n_params * 4
        # ZeRO-1 shards them: ~1/8 per device (+ block padding)
        ratio = b_zero / b_plain
        assert ratio < 1 / 6, (
            f"per-device state {b_zero / 1e6:.1f} MB vs plain "
            f"{b_plain / 1e6:.1f} MB (ratio {ratio:.3f})"
        )
        print(
            f"\nZERO1_MEMORY params={n_params} "
            f"plain_MB={b_plain / 1e6:.1f} zero_MB={b_zero / 1e6:.1f} "
            f"ratio={ratio:.4f}"
        )

    def test_zero_with_double_buffering_rejected(self, comm):
        with pytest.raises(ValueError):
            cmn.create_multi_node_optimizer(
                optax.adam(0.1), comm, double_buffering=True,
                zero_redundancy=True,
            )

    def test_eager_unbound_path_matches(self, comm):
        # Outside shard_map the blocks update full-width — numerics equal
        # the inner optimizer applied directly.
        params = {"w": jnp.ones((8,))}
        grads = {"w": jnp.arange(8.0) / 10.0}
        inner = optax.adam(0.1)
        zero = cmn.create_multi_node_optimizer(
            inner, comm, zero_redundancy=True
        )
        zstate = zero.init(params)
        zupd, _ = zero.update(grads, zstate, params)
        istate = inner.init(params)
        iupd, _ = inner.update(grads, istate, params)
        np.testing.assert_allclose(
            np.asarray(zupd["w"]), np.asarray(iupd["w"]), rtol=1e-6
        )
