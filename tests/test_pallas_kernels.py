"""Pallas kernel tests (interpret mode on CPU).

Pins: flash attention matches the reference attention core (values and
gradients), composes with ring/Ulysses sequence parallelism through the
``attention_fn`` hook, and fused_cast_scale matches cast+multiply.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.ops import multi_head_attention
from chainermn_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_fn,
    fused_cast_scale,
)


def _qkv(b=2, s=32, h=4, d=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.3
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        want = multi_head_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal, None, 16, 16, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_ragged_lengths_padded_correctly(self):
        # seq length not a multiple of the block: padding keys must not
        # leak into the softmax.
        q, k, v = _qkv(s=23)
        want = multi_head_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, True, None, 16, 16, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_cross_attention_lengths(self):
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32)
        want = multi_head_attention(q, k, v)
        got = flash_attention(q, k, v, False, None, 16, 16, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_gradients_match_reference(self):
        q, k, v = _qkv(s=16)

        def f_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=True) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 8, 8, True) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_flash):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-4
            )

    def test_split_fwd_bwd_blocks_gradients_exact(self):
        """Separate backward block geometry (round 5: the scoped-VMEM
        limit binds only the backward, so the forward can stream wider
        K/V blocks): value AND gradients with asymmetric fwd/bwd blocks
        must match the shared-block configuration exactly — the block
        decomposition is numerically invisible."""
        q, k, v = _qkv(s=32)

        def f(bq, bk, bwd_bq, bwd_bk):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, True, None, bq, bk, True,
                                    bwd_bq, bwd_bk) ** 2
                )

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        g_shared = f(8, 8, None, None)
        g_split = f(8, 32, 8, 8)       # wide fwd K blocks, narrow bwd
        g_split2 = f(16, 16, 8, 32)    # and the reverse asymmetry
        for a, b, c in zip(g_shared, g_split, g_split2):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6
            )
            np.testing.assert_allclose(
                np.asarray(c), np.asarray(a), rtol=1e-5, atol=1e-6
            )

    @pytest.mark.parametrize("bq,bk,s_q,s_k", [
        (16, 24, 20, 20),   # blocks don't divide each other, ragged q
        (24, 16, 24, 17),   # ragged k against larger q block
        (8, 32, 40, 40),
    ])
    def test_mismatched_block_sizes(self, bq, bk, s_q, s_k):
        # Regression: q and k/v must be padded by their OWN block sizes;
        # shared padding produced NaN rows or out-of-bounds reads.
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(2, s_q, 2, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, s_k, 2, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, s_k, 2, 8), jnp.float32)
        want = multi_head_attention(q, k, v, causal=(s_q == s_k))
        got = flash_attention(q, k, v, s_q == s_k, None, bq, bk, True)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_ragged_lengths(self, causal):
        # seq not a multiple of the block: padded rows/cols must not
        # contribute to dq/dk/dv (the bwd kernels mask by q AND k index)
        q, k, v = _qkv(s=23)

        def f_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=causal) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal, None, 16, 16, True) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_flash):
            assert np.isfinite(np.asarray(b)).all()
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-4
            )

    def test_gradients_cross_attention(self):
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32) * 0.3
        k = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32) * 0.3
        v = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32) * 0.3

        def f_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, False, None, 16, 16, True) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_flash):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-4
            )

    def test_gradients_bf16(self):
        q, k, v = _qkv(s=32)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 16, 16, True)
                .astype(jnp.float32) ** 2
            )

        g = jax.grad(f_flash, argnums=(0, 1, 2))(qb, kb, vb)

        def f_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=True) ** 2)

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g):
            assert b.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(b, np.float32), np.asarray(a),
                rtol=1e-1, atol=5e-2,
            )

    def test_bf16_inputs(self):
        q, k, v = _qkv()
        got = flash_attention(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), False, None, 16, 16, True,
        )
        want = multi_head_attention(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want),
            rtol=2e-2, atol=2e-2,
        )


def _brute_census(s_q, s_k, bq, bk, causal, kind):
    """Oracle block classification from the literal padded mask matrix
    (the kernels classify from corner predicates; this classifies every
    element and must agree)."""
    def up(x, m):
        return (x + m - 1) // m * m

    s_qp, s_kp = up(s_q, bq), up(s_k, bk)
    qi = np.arange(s_qp)[:, None]
    kj = np.arange(s_kp)[None, :]
    valid = np.broadcast_to(kj < s_k, (s_qp, s_kp))  # fwd masks only k
    if kind == "bwd":
        valid = valid & (qi < s_q)
    census = {"dead": 0, "interior": 0, "masked": 0,
              "n_q_blocks": s_qp // bq, "n_k_blocks": s_kp // bk}
    for j in range(s_qp // bq):
        for kb in range(s_kp // bk):
            sl = (slice(j * bq, (j + 1) * bq),
                  slice(kb * bk, (kb + 1) * bk))
            c_ok = (kj <= qi)[sl] if causal else np.ones(
                (bq, bk), dtype=bool
            )
            if causal and not c_ok.any():
                census["dead"] += 1
            elif c_ok.all() and valid[sl].all():
                census["interior"] += 1
            else:
                census["masked"] += 1
    return census


class TestDiagonalSplit:
    """The diagonal-split kernel taxonomy: classification correctness,
    bit-exactness vs the pre-split (legacy) kernels, and oracle checks
    at the geometries where the classes meet."""

    @pytest.mark.parametrize("kind", ["fwd", "bwd"])
    @pytest.mark.parametrize("s_q,s_k,bq,bk,causal", [
        (32, 32, 16, 16, True),    # aligned square: all classes present
        (32, 32, 16, 16, False),
        (23, 23, 16, 16, True),    # ragged q AND k tails
        (23, 23, 16, 16, False),
        (48, 48, 8, 16, True),     # bk > bq: coarse diagonal band
        (48, 48, 16, 8, True),     # bq > bk: fully-masked rows exist
        (24, 17, 24, 16, False),   # cross-attention, ragged k
        (40, 40, 8, 32, True),
        (2048, 2048, 1024, 2048, True),   # the shipping fwd geometry
        (8192, 8192, 1024, 1024, True),   # the seq-8192 tier
    ])
    def test_block_census_matches_brute_force(self, kind, s_q, s_k, bq,
                                              bk, causal):
        from chainermn_tpu.ops.pallas_attention import block_census

        assert block_census(s_q, s_k, bq, bk, causal, kind=kind) == \
            _brute_census(s_q, s_k, bq, bk, causal, kind)

    def test_census_shipping_geometries(self):
        """The numbers the perf doc's anatomy section quotes: block
        counts per (batch*head) program at the shipped configs."""
        from chainermn_tpu.ops.pallas_attention import block_census

        # seq 2048, bwd 1024x1024: 1 of 3 live blocks interior
        c = block_census(2048, 2048, 1024, 1024, True, kind="bwd")
        assert c == {"dead": 1, "interior": 1, "masked": 2,
                     "n_q_blocks": 2, "n_k_blocks": 2}
        # seq 2048, fwd 1024x2048 (the r5 split geometry): every live
        # block straddles the diagonal — the split buys the forward
        # nothing at this geometry (the anatomy rungs A/B it against
        # 1024x1024, where 1 of 3 live blocks goes fast-path)
        c = block_census(2048, 2048, 1024, 2048, True)
        assert c["interior"] == 0 and c["masked"] == 2
        # seq 8192, 1024^2: 28 of 36 live blocks interior (78%)
        c = block_census(8192, 8192, 1024, 1024, True)
        assert (c["dead"], c["interior"], c["masked"]) == (28, 28, 8)
        # seq 16384: 120 of 136 live blocks interior (88%)
        c = block_census(16384, 16384, 1024, 1024, True)
        assert (c["dead"], c["interior"], c["masked"]) == (120, 120, 16)
        # non-causal aligned: no mask work anywhere
        c = block_census(64, 64, 16, 16, False)
        assert c["masked"] == 0 and c["interior"] == 16

    def test_census_conservation_and_kind(self):
        from chainermn_tpu.ops.pallas_attention import block_census

        c = block_census(40, 40, 16, 16, True)
        assert c["dead"] + c["interior"] + c["masked"] == \
            c["n_q_blocks"] * c["n_k_blocks"]
        # a ragged q tail reclassifies blocks only for the backward
        fwd = block_census(40, 48, 16, 16, False, kind="fwd")
        bwd = block_census(40, 48, 16, 16, False, kind="bwd")
        assert fwd["masked"] == 0 and bwd["masked"] == 3
        with pytest.raises(ValueError, match="fwd/bwd"):
            block_census(8, 8, 8, 8, False, kind="nope")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s,bq,bk", [
        (32, 16, 16),   # block-boundary aligned
        (23, 16, 16),   # ragged tails
        (48, 16, 8),    # fully-masked rows inside live blocks
        (40, 8, 32),    # wide k blocks
    ])
    def test_split_matches_legacy_exactly(self, causal, s, bq, bk):
        """The split kernels must be BIT-IDENTICAL to the pre-split
        kernels in interpret mode, values and all three gradients: the
        interior fast branch skips a mask that is provably all-true,
        and the first-k-block direct write skips a rescale whose factor
        is provably exp(-inf) = 0 — neither may change a single bit."""
        q, k, v = _qkv(s=s, seed=7)

        def run(tax):
            def f(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal, None, bq, bk, True,
                                    None, None, tax) ** 2
                )

            out = flash_attention(q, k, v, causal, None, bq, bk, True,
                                  None, None, tax)
            grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
            return out, grads

        out_s, g_s = run("split")
        out_l, g_l = run("legacy")
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_l))
        for a, b in zip(g_s, g_l):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_split_matches_legacy_with_lse(self):
        """Same exactness through the (out, lse)-differentiable entry
        point (the ring-attention building block): both outputs and the
        folded g_lse backward."""
        from chainermn_tpu.ops.pallas_attention import (
            flash_attention_with_lse,
        )

        q, k, v = _qkv(s=32, seed=11)

        def run(tax):
            def f(q, k, v):
                out, lse = flash_attention_with_lse(
                    q, k, v, True, None, 16, 16, True, None, None, tax
                )
                return jnp.sum(out ** 2) + jnp.sum(lse * 0.3)

            out, lse = flash_attention_with_lse(
                q, k, v, True, None, 16, 16, True, None, None, tax
            )
            return out, lse, jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        out_s, lse_s, g_s = run("split")
        out_l, lse_l, g_l = run("legacy")
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_l))
        np.testing.assert_array_equal(np.asarray(lse_s), np.asarray(lse_l))
        for a, b in zip(g_s, g_l):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("s", [32, 23])
    def test_split_gradients_match_dense_oracle(self, s):
        """Gradients of the split path vs the dense oracle exactly at
        the geometries where the taxonomy matters: block boundaries
        (s = 2 blocks: the diagonal class) and ragged tails (the tail
        class), with the census proving BOTH live branches executed."""
        from chainermn_tpu.ops.pallas_attention import block_census

        c = block_census(s, s, 16, 16, True, kind="bwd")
        if s == 32:
            assert c["interior"] >= 1 and c["masked"] >= 1
        q, k, v = _qkv(s=s, seed=3)

        def f_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=True) ** 2)

        def f_split(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 16, 16, True, None,
                                None, "split") ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_split = jax.grad(f_split, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_split):
            assert np.isfinite(np.asarray(b)).all()
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-4
            )

    def test_launch_census_applies_clamps(self):
        """launch_census (the bench anatomy rungs' census source) must
        describe the geometry that RUNS: None blocks resolve to the
        defaults, the head-dim clamp and the sequence clamp both
        apply — a clamped launch cannot print the requested census."""
        from chainermn_tpu.ops.pallas_attention import (
            block_census,
            launch_census,
        )

        c = launch_census(2048, 2048, 128)  # defaults at dh=128
        assert c["fwd"] == block_census(2048, 2048, 1024, 1024, True)
        assert c["bwd"] == block_census(2048, 2048, 1024, 1024, True,
                                        kind="bwd")
        # head dim past the measured d<=256 boundary: blocks halve and
        # the census follows the clamp
        c = launch_census(2048, 2048, 512)
        assert c["fwd"] == block_census(2048, 2048, 512, 512, True)
        # split fwd/bwd geometry resolves independently
        c = launch_census(2048, 2048, 128, 1024, 2048, 1024, 1024)
        assert c["fwd"]["n_k_blocks"] == 1 and c["bwd"]["n_k_blocks"] == 2
        # sequence clamp: blocks never exceed the (rounded) sequence
        c = launch_census(64, 64, 128)
        assert c["fwd"]["n_q_blocks"] == 1 and c["fwd"]["n_k_blocks"] == 1
        # compiled TPU floors the q block at the 128 lane tile
        # (_effective_q_block): a sub-128 request must census at 128
        c = launch_census(8192, 8192, 128, 64, 1024)
        assert c["fwd"]["n_q_blocks"] == 8192 // 128
        c = launch_census(8192, 8192, 128, 64, 1024, interpret=True)
        assert c["fwd"]["n_q_blocks"] == 8192 // 64

    def test_interior_taxonomy_timing_only(self):
        """``taxonomy="interior"`` (the anatomy bench's floor) must
        equal split exactly when no mask exists (non-causal aligned),
        and must DIFFER under causal masking — pinning that it is a
        timing knob, not a numerics mode."""
        q, k, v = _qkv(s=32, seed=5)
        args = (None, 16, 16, True, None, None)
        same = flash_attention(q, k, v, False, *args, "interior")
        want = flash_attention(q, k, v, False, *args, "split")
        np.testing.assert_array_equal(np.asarray(same), np.asarray(want))
        wrong = flash_attention(q, k, v, True, *args, "interior")
        right = flash_attention(q, k, v, True, *args, "split")
        assert not np.allclose(np.asarray(wrong), np.asarray(right))

    def test_invalid_taxonomy_raises(self):
        q, k, v = _qkv(s=16)
        with pytest.raises(ValueError, match="taxonomy"):
            flash_attention(q, k, v, True, None, 8, 8, True, None, None,
                            "diagonalize")


class TestFlashWithSequenceParallel:
    def test_ulysses_with_flash_core(self, mesh8):
        from chainermn_tpu.parallel import ulysses_attention

        q, k, v = _qkv(b=2, s=64, h=8, d=8)
        want = multi_head_attention(q, k, v, causal=True)
        core = flash_attention_fn(block_q=8, block_k=8, interpret=True)

        f = jax.jit(
            jax.shard_map(
                lambda q, k, v: ulysses_attention(
                    q, k, v, "mn", causal=True, attention_fn=core
                ),
                mesh=mesh8,
                in_specs=(P(None, "mn"),) * 3,
                out_specs=P(None, "mn"),
                check_vma=False,
            )
        )
        sh = NamedSharding(mesh8, P(None, "mn"))
        got = f(*(jax.device_put(t, sh) for t in (q, k, v)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )


class TestFlashUnderVmaCheckedShardMap:
    """``build_train_step`` runs the loss under a vma-checked
    ``shard_map``; there a ``pallas_call`` must declare how its outputs
    vary over the mesh (``_out_struct``), or tracing fails."""

    def test_value_and_grad_batch_sharded(self, mesh8):
        q, k, v = _qkv(b=8, s=32, h=2, d=8)

        def loss(attend):
            def per_shard(q, k, v):
                out = attend(q, k, v)
                return lax.pmean((out ** 2).mean(), "mn")

            sharded = jax.shard_map(
                per_shard, mesh=mesh8, in_specs=(P("mn"),) * 3,
                out_specs=P(),
            )  # check_vma left ON
            return jax.jit(jax.value_and_grad(sharded, argnums=(0, 1, 2)))

        flash = loss(lambda q, k, v: flash_attention(
            q, k, v, True, None, 16, 16, True))
        dense = loss(lambda q, k, v: multi_head_attention(
            q, k, v, causal=True))
        (l_f, g_f), (l_d, g_d) = flash(q, k, v), dense(q, k, v)
        np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
        for a, b in zip(g_f, g_d):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6
            )


class TestFusedCastScale:
    @pytest.mark.parametrize("shape", [(7,), (128,), (3, 5, 11), (256, 128)])
    def test_matches_cast_multiply(self, shape):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(*shape), jnp.float32)
        got = fused_cast_scale(x, 0.125, jnp.bfloat16, interpret=True)
        want = (x * 0.125).astype(jnp.bfloat16)
        assert got.shape == x.shape and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2,
        )

    def test_empty_input(self):
        x = jnp.zeros((0,), jnp.float32)
        got = fused_cast_scale(x, 0.5, jnp.bfloat16, interpret=True)
        assert got.shape == (0,) and got.dtype == jnp.bfloat16


class TestBlockClamp:
    def test_dim_clamp_table(self):
        """VMEM block clamp (pallas_attention._clamp_blocks_for_dim):
        d <= 256 untouched — the round-5 probe compiled and ran the
        full 1024x1024 geometry at d=192/256 on the real chip, so the
        old d>128 clamp was over-conservative; beyond the measured
        boundary d shrinks by ceil(d/256), floored to lane multiples.
        ``None`` = the 1024 default (the sentinel is what lets the clamp
        distinguish "caller passed nothing" from "caller asked for
        exactly 1024")."""
        import warnings as _w

        from chainermn_tpu.ops.pallas_attention import (
            _clamp_blocks_for_dim,
        )

        with _w.catch_warnings():
            _w.simplefilter("error")  # defaults must clamp SILENTLY
            assert _clamp_blocks_for_dim(None, None, 64) == (1024, 1024)
            assert _clamp_blocks_for_dim(None, None, 128) == (1024, 1024)
            # measured feasible on-chip (round 5): no clamp
            assert _clamp_blocks_for_dim(None, None, 192) == (1024, 1024)
            assert _clamp_blocks_for_dim(None, None, 256) == (1024, 1024)
            # beyond the measured boundary: extrapolated shrink
            assert _clamp_blocks_for_dim(None, None, 512) == (512, 512)
            # floor: never below 256, and always a lane multiple
            bq, bk = _clamp_blocks_for_dim(None, None, 384)
            assert bq >= 256 and bq % 128 == 0
            bq, bk = _clamp_blocks_for_dim(None, None, 1024)
            assert bq >= 256 and bq % 128 == 0

    def test_explicit_blocks_warn_when_clamped(self):
        """Explicitly requested blocks that get shrunk must WARN
        (advisor r4: a tuning sweep at large d would otherwise silently
        measure the clamp, not its requested geometry) — including an
        explicit 1024x1024, which value-equality default detection
        would have missed.  warn=False (the backward's path) and
        unclamped explicit blocks stay silent."""
        import warnings as _w

        from chainermn_tpu.ops import pallas_attention as pa

        pa._warned_geometries.clear()
        with pytest.warns(UserWarning, match="clamped"):
            assert pa._clamp_blocks_for_dim(512, 512, 512) == (256, 256)
        with pytest.warns(UserWarning, match="clamped"):
            assert pa._clamp_blocks_for_dim(1024, 1024, 512) == (512, 512)
        with _w.catch_warnings():
            _w.simplefilter("error")
            # once per geometry: a repeat stays silent
            pa._clamp_blocks_for_dim(512, 512, 512)
            # the backward pass never warns (fwd already did)
            pa._clamp_blocks_for_dim(1024, 512, 512, warn=False)
            # explicit blocks that FIT are silent (incl. the measured
            # d=256 boundary, which rounds 1-4 would have clamped)
            pa._clamp_blocks_for_dim(256, 256, 64)
            pa._clamp_blocks_for_dim(1024, 1024, 256)
        pa._warned_geometries.clear()

    def test_flash_matches_oracle_at_d192(self):
        """The clamp path (d=192: previously unshrunk) must stay
        numerically exact vs the dense oracle."""
        import jax

        from chainermn_tpu.ops import multi_head_attention
        from chainermn_tpu.ops.pallas_attention import flash_attention

        rng = np.random.RandomState(0)
        q, k, v = (
            jnp.asarray(rng.randn(1, 256, 2, 192), jnp.float32)
            for _ in range(3)
        )
        out = flash_attention(q, k, v, causal=True)
        want = multi_head_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )


class TestVmemRetry:
    """ADVICE r5: the d<=256 clamp boundary was measured on v5e only; on
    other TPU generations the default backward geometry may exceed
    scoped VMEM at COMPILE time.  The backward now catches that failure
    and retries with ceil-shrunk blocks (the resilience layer's
    retry-on-failure shape applied to kernel compilation)."""

    def test_retries_with_shrunk_geometry(self, monkeypatch):
        from chainermn_tpu.ops import pallas_attention as pa

        calls = []

        def fake_backward(q, k, v, out, lse, g, causal, scale, bq, bk,
                          interp, taxonomy="split", g_lse=None):
            eff = pa._clamp_blocks_for_dim(bq, bk, q.shape[-1],
                                           warn=False)
            calls.append(eff)
            if eff[0] > 256:
                raise RuntimeError(
                    "Mosaic failed: scoped vmem limit exceeded "
                    f"({eff[0]}x{eff[1]})"
                )
            return "dq", "dk", "dv"

        monkeypatch.setattr(pa, "_flash_backward", fake_backward)
        q = jnp.zeros((1, 8, 1, 64), jnp.float32)
        with pytest.warns(UserWarning, match="scoped VMEM"):
            out = pa._backward_with_vmem_retry(
                q, q, q, q, None, q, False, 1.0, 1024, 1024, False
            )
        assert out == ("dq", "dk", "dv")
        # deterministic halving ladder, floored at the lane tile
        assert calls == [(1024, 1024), (512, 512), (256, 256)]

    def test_non_vmem_failure_propagates(self, monkeypatch):
        from chainermn_tpu.ops import pallas_attention as pa

        def fake_backward(*a, **kw):
            raise RuntimeError("INVALID_ARGUMENT: something else")

        monkeypatch.setattr(pa, "_flash_backward", fake_backward)
        q = jnp.zeros((1, 8, 1, 64), jnp.float32)
        with pytest.raises(RuntimeError, match="something else"):
            pa._backward_with_vmem_retry(
                q, q, q, q, None, q, False, 1.0, 512, 512, False
            )

    def test_exhausted_shrink_reraises(self, monkeypatch):
        from chainermn_tpu.ops import pallas_attention as pa

        def fake_backward(q, k, v, out, lse, g, causal, scale, bq, bk,
                          interp, taxonomy="split", g_lse=None):
            raise RuntimeError("scoped vmem limit exceeded")

        monkeypatch.setattr(pa, "_flash_backward", fake_backward)
        q = jnp.zeros((1, 8, 1, 64), jnp.float32)
        with pytest.warns(UserWarning, match="scoped VMEM"):
            with pytest.raises(RuntimeError, match="vmem"):
                pa._backward_with_vmem_retry(
                    q, q, q, q, None, q, False, 1.0, 256, 256, False
                )

    def test_compile_probe_is_safe_everywhere(self):
        """The AOT compile probe (how VMEM failures are caught on the
        jitted TPU path) must never crash — eagerly or under an outer
        jit trace — and must report not-blocked when the probe itself
        cannot run (CPU backend: non-interpret pallas compile is an
        infrastructure error, not a VMEM verdict)."""
        from chainermn_tpu.ops import pallas_attention as pa

        q = jnp.zeros((1, 128, 1, 64), jnp.float32)
        lse = jnp.zeros((1, 128), jnp.float32)

        assert pa._bwd_compile_blocked(
            (q, q, q, q, lse, q), False, 1.0, 128, 128
        ) is False

        def body(x):
            # probing with tracer-derived shapes during an outer trace
            assert pa._bwd_compile_blocked(
                (x, x, x, x, lse, x), True, 0.5, 128, 128
            ) is False
            return x * 2

        np.testing.assert_allclose(np.asarray(jax.jit(body)(q)), 0.0)

    def test_grad_routes_through_retry(self, monkeypatch):
        """The custom-vjp backward rule must reach the retry wrapper (a
        VMEM failure during jax.grad is recovered, not fatal)."""
        from chainermn_tpu.ops import pallas_attention as pa

        seen = []
        real = pa._flash_backward

        def spying(q, k, v, out, lse, g, causal, scale, bq, bk, interp,
                   taxonomy="split", g_lse=None):
            seen.append((bq, bk))
            if len(seen) == 1:
                raise RuntimeError("scoped vmem limit exceeded")
            return real(q, k, v, out, lse, g, causal, scale, bq, bk,
                        interp, taxonomy=taxonomy, g_lse=g_lse)

        monkeypatch.setattr(pa, "_flash_backward", spying)
        q, k, v = _qkv(s=32)
        with pytest.warns(UserWarning, match="scoped VMEM"):
            g = jax.grad(
                lambda q: jnp.sum(
                    pa.flash_attention(q, k, v, False, None, 256, 256,
                                       True)
                )
            )(q)
        assert len(seen) == 2  # failed once, retried shrunk
        assert seen[1][0] < seen[0][0]
        assert np.isfinite(np.asarray(g)).all()


class TestAnalyticAttnFlops:
    def test_formula(self):
        """bench.py's analytic flash-attention FLOP term (the part XLA
        cannot see): fwd = 4*b*h*s^2*dh, training = 3.5x fwd, causal
        halves — stated in the docstring, pinned here."""
        import bench

        b, h, s, dh, L = 2, 8, 1024, 128, 4
        full = bench._flash_attn_tflops(b, h, s, dh, L, causal=False)
        assert full == pytest.approx(14.0 * b * h * s * s * dh * L / 1e12)
        causal = bench._flash_attn_tflops(b, h, s, dh, L, causal=True)
        assert causal == pytest.approx(full / 2)


class TestTimeKloop:
    def test_measures_and_fallback(self):
        """time_kloop returns a positive per-step time from paired k/2k
        calls, and falls back to the long run's average (never a
        negative paired difference) when timings are noise-dominated."""
        import time as _time

        from chainermn_tpu.utils.benchmarking import time_kloop

        calls = []

        def run_k(n):
            calls.append(n)
            _time.sleep(0.001 * n)
            return np.zeros(1)

        dt, samples = time_kloop(run_k, k=10, repeats=2)
        assert calls[0] == 2  # warm call
        assert dt > 0
        assert len(samples) == 2

        # degenerate timings (instant run_k): fallback stays positive
        dt2, _ = time_kloop(lambda n: np.zeros(1), k=4, repeats=1)
        assert dt2 >= 0
