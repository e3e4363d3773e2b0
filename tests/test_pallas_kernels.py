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


def _brute_census(s_q, s_k, bq, bk, causal, kind, tile=None):
    """Oracle block classification from the literal padded mask matrix
    (the kernels classify from corner predicates; this classifies every
    element and must agree) -- and, where the compute tile engages
    (restated here, not imported: causal, square blocks, no padding on
    an axis the kernel masks, at least two tiles a block), every
    ``tile`` x ``tile`` tile of the masked blocks the same way."""
    def up(x, m):
        return (x + m - 1) // m * m

    if tile is None:  # the shipped defaults (measured: PERF.md, PR 28)
        tile = {"fwd": 512, "bwd": 256}[kind]
    s_qp, s_kp = up(s_q, bq), up(s_k, bk)
    qi = np.arange(s_qp)[:, None]
    kj = np.arange(s_kp)[None, :]
    valid = np.broadcast_to(kj < s_k, (s_qp, s_kp))  # fwd masks only k
    if kind == "bwd":
        valid = valid & (qi < s_q)
    live = valid & (kj <= qi) if causal else valid
    engaged = (causal and bq == bk and s_k == s_kp
               and (kind == "fwd" or s_q == s_qp)
               and bq % tile == 0 and bq >= 2 * tile)
    census = {"dead": 0, "interior": 0, "masked": 0,
              "n_q_blocks": s_qp // bq, "n_k_blocks": s_kp // bk,
              "tile": tile if engaged else None, "tiles_executed": 0,
              "tiles_masked": 0, "tiles_skipped": 0,
              "executed_units": 0.0, "masked_units": 0.0}
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
                census["executed_units"] += 1.0
            else:
                census["masked"] += 1
                if not engaged:
                    census["executed_units"] += 1.0
                    census["masked_units"] += 1.0
                    continue
                blk, n = live[sl], bq // tile
                for r in range(n):
                    for c in range(n):
                        t = blk[r * tile:(r + 1) * tile,
                                c * tile:(c + 1) * tile]
                        if not t.any():
                            census["tiles_skipped"] += 1
                            continue
                        census["tiles_executed"] += 1
                        census["executed_units"] += 1.0 / n ** 2
                        if not t.all():
                            census["tiles_masked"] += 1
                            census["masked_units"] += 1.0 / n ** 2
    return census


class TestDiagonalSplit:
    """The diagonal-split kernel taxonomy: classification correctness
    and oracle checks at the geometries where the classes meet."""

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

    @pytest.mark.parametrize("kind", ["fwd", "bwd"])
    @pytest.mark.parametrize("s_q,s_k,bq,bk,causal,tile", [
        (32, 32, 16, 16, True, 8),      # 2 tiles a block
        (64, 64, 32, 32, True, 8),      # 4
        (192, 192, 64, 64, True, 8),    # 8, three diagonal blocks
        (2048, 2048, 1024, 1024, True, 256),   # the cells' geometry
        (2048, 2048, 1024, 1024, True, 128),
        (8192, 8192, 1024, 1024, True, 256),
        (32, 32, 16, 16, False, 8),     # the rule leaves these alone:
        (23, 23, 16, 16, True, 8),      # ragged tails
        (40, 32, 16, 16, True, 8),      # ragged q only: fwd engages
        (48, 48, 8, 16, True, 4),       # bq != bk
        (32, 32, 16, 16, True, 16),     # one tile a block
        (48, 48, 24, 24, True, 16),     # tile does not divide the block
    ])
    def test_tile_census_matches_brute_force(self, kind, s_q, s_k, bq, bk,
                                             causal, tile):
        """The compute tile's counters (tiles executed / masked /
        skipped inside the masked blocks, block-units of work and of
        masked work) against an element-by-element count."""
        from chainermn_tpu.ops.pallas_attention import block_census

        got = block_census(s_q, s_k, bq, bk, causal, kind=kind, tile=tile)
        want = _brute_census(s_q, s_k, bq, bk, causal, kind, tile)
        units = {"executed_units", "masked_units"}
        assert {k: v for k, v in got.items() if k not in units} == \
            {k: v for k, v in want.items() if k not in units}
        for k in units:
            assert got[k] == pytest.approx(want[k])
        n = (bq // tile) ** 2
        if got["tile"] is not None:
            assert got["tiles_executed"] + got["tiles_skipped"] == \
                got["masked"] * n

    def test_census_shipping_geometries(self):
        """The numbers the perf doc's anatomy section quotes: block
        counts per (batch*head) program at the shipped configs."""
        from chainermn_tpu.ops.pallas_attention import block_census

        # seq 2048, bwd 1024x1024 (the LM cells): 1 of 3 live blocks
        # interior; the two diagonal blocks run 10 of their 16 tiles of
        # 256 and mask 4: 2.25 of 3 block-units executed, 0.5 masked
        c = block_census(2048, 2048, 1024, 1024, True, kind="bwd")
        assert c == {"dead": 1, "interior": 1, "masked": 2,
                     "n_q_blocks": 2, "n_k_blocks": 2, "tile": 256,
                     "tiles_executed": 20, "tiles_masked": 8,
                     "tiles_skipped": 12, "executed_units": 2.25,
                     "masked_units": 0.5}
        # ... and the cells' forward, whose tile is 512: 3 of 4 tiles a
        # diagonal block, 2 of them masked: 2.5 of 3 units, 1 masked
        c = block_census(2048, 2048, 1024, 1024, True)
        assert c == {"dead": 1, "interior": 1, "masked": 2,
                     "n_q_blocks": 2, "n_k_blocks": 2, "tile": 512,
                     "tiles_executed": 6, "tiles_masked": 4,
                     "tiles_skipped": 2, "executed_units": 2.5,
                     "masked_units": 1.0}
        c = block_census(2048, 2048, 1024, 1024, True, tile=128)
        assert (c["executed_units"], c["masked_units"]) == (2.125, 0.25)
        # seq 2048, fwd 1024x2048 (the r5 split geometry): every live
        # block straddles the diagonal — the split buys the forward
        # nothing at this geometry (the anatomy rungs A/B it against
        # 1024x1024, where 1 of 3 live blocks goes fast-path)
        c = block_census(2048, 2048, 1024, 2048, True)
        assert c["interior"] == 0 and c["masked"] == 2
        assert c["tile"] is None and c["executed_units"] == 2.0
        # seq 8192, 1024^2: 28 of 36 live blocks interior (78%); the
        # compute tile executes 33 units of the 36
        c = block_census(8192, 8192, 1024, 1024, True)
        assert (c["dead"], c["interior"], c["masked"]) == (28, 28, 8)
        assert (c["executed_units"], c["masked_units"]) == (34.0, 4.0)
        c = block_census(8192, 8192, 1024, 1024, True, kind="bwd")
        assert (c["executed_units"], c["masked_units"]) == (33.0, 2.0)
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

    @pytest.mark.parametrize("entry", ["flash_attention",
                                       "flash_attention_with_lse"])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s,bq,bk", [
        (32, 16, 16),   # block-boundary aligned
        (23, 16, 16),   # ragged tails
        (48, 16, 8),    # fully-masked rows inside live blocks
        (40, 8, 32),    # wide k blocks
    ])
    def test_split_matches_dense_oracle(self, entry, causal, s, bq, bk):
        """Both public entry points against the dense float32 oracle at
        the geometries where the taxonomy matters: values, ``lse`` (the
        ring-attention building block returns it, and folds its
        cotangent into the same backward kernels) and all three
        gradients.  The interior fast branch skips a mask that is
        all-true there and the first k block's direct write skips a
        rescale whose factor is exp(-inf) = 0: neither may show."""
        from chainermn_tpu.ops.pallas_attention import (
            _dense_attention_with_lse,
            block_census,
            flash_attention_with_lse,
        )

        if causal and (s, bq, bk) == (32, 16, 16):  # BOTH live branches
            c = block_census(s, s, bq, bk, True, kind="bwd")
            assert c["interior"] >= 1 and c["masked"] >= 1
        q, k, v = _qkv(s=s, seed=7)
        with_lse = entry == "flash_attention_with_lse"

        def kernels(q, k, v):
            if with_lse:
                return flash_attention_with_lse(q, k, v, causal, None, bq,
                                                bk, True)
            return (flash_attention(q, k, v, causal, None, bq, bk, True),)

        def dense(q, k, v):
            return _dense_attention_with_lse(
                q, k, v, causal, q.shape[-1] ** -0.5)[:1 + with_lse]

        def run(fn):
            def loss(q, k, v):
                out, *lse = fn(q, k, v)
                return jnp.sum(out ** 2) + sum(
                    jnp.sum(x * 0.3) for x in lse)

            return fn(q, k, v) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        got, want = run(kernels), run(dense)
        assert len(got) == len(want) == 4 + with_lse
        for a, b in zip(got, want):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
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


class TestComputeTile:
    """The compute tile inside the diagonal blocks (PR 28): reached at
    tiny interpret-mode shapes through the private entry points' static
    ``tile`` argument (the public API resolves it from shapes alone)."""

    TILE = 8

    @staticmethod
    def _inputs(s_q, s_k, dtype, seed=13):
        rng = np.random.RandomState(seed)

        def mk(s):
            return (jnp.asarray(rng.randn(2, s, 2, 8), jnp.float32)
                    * 0.3).astype(dtype)

        q, k, v, g = mk(s_q), mk(s_k), mk(s_k), mk(s_q)
        g_lse = jnp.asarray(rng.randn(2 * 2, s_q), jnp.float32) * 0.3
        return q, k, v, g, g_lse

    @staticmethod
    def _run(q, k, v, g, g_lse, causal, bq, bk, tile):
        """(out, lse, dq, dk, dv) through the two private entry points,
        the lse cotangent folded in as ``flash_attention_with_lse``
        does."""
        from chainermn_tpu.ops import pallas_attention as pa

        scale = q.shape[-1] ** -0.5
        out, lse = pa._flash_forward(q, k, v, causal, scale, bq, bk, True,
                                     tile=tile)
        grads = pa._flash_backward(q, k, v, out, lse, g, causal, scale,
                                   bq, bk, True, g_lse=g_lse, tile=tile)
        return (out, lse) + tuple(grads)

    @staticmethod
    def _dense(q, k, v, g, g_lse, causal):
        from chainermn_tpu.ops.pallas_attention import (
            _dense_attention_with_lse,
        )

        q, k, v, g = (t.astype(jnp.float32) for t in (q, k, v, g))
        b, s_q, h, d = q.shape
        (out, lse), vjp = jax.vjp(
            lambda q, k, v: _dense_attention_with_lse(
                q, k, v, causal, d ** -0.5),
            q, k, v,
        )
        g_l = jnp.moveaxis(g_lse.reshape(b, h, s_q), 1, 2)  # (b, s_q, h)
        lse_bh = jnp.moveaxis(lse, 2, 1).reshape(b * h, s_q)
        return (out, lse_bh) + tuple(vjp((g, g_l)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n_tiles", [2, 4, 8])
    def test_tiled_matches_oracle(self, n_tiles, dtype):
        """Forward, lse, dq, dk, dv with the diagonal blocks computed in
        2, 4 and 8 tiles a side, against the dense oracle: tiles above
        the diagonal contributed exact zeros, so nothing of the
        mathematics is left out."""
        from chainermn_tpu.ops.pallas_attention import block_census

        blk = self.TILE * n_tiles
        s = 3 * blk  # three diagonal, three interior, three dead blocks
        c = block_census(s, s, blk, blk, True, kind="bwd", tile=self.TILE)
        assert c["tile"] == self.TILE and c["interior"] == 3
        assert c["tiles_masked"] == 3 * n_tiles
        assert c["tiles_skipped"] == 3 * n_tiles * (n_tiles - 1) // 2
        args = self._inputs(s, s, dtype)
        tiled = self._run(*args, True, blk, blk, self.TILE)
        dense = self._dense(*args, True)
        names = ("out", "lse", "dq", "dk", "dv")
        exact = dtype == jnp.float32
        for name, t, w in zip(names, tiled, dense):
            assert t.dtype == (jnp.float32 if name == "lse" else dtype)
            t, w = (np.asarray(x, np.float32) for x in (t, w))
            assert np.isfinite(t).all(), name
            np.testing.assert_allclose(
                t, w, err_msg=name,
                **(dict(rtol=2e-3, atol=2e-4) if exact
                   else dict(rtol=1e-1, atol=5e-2)))

    def test_tile_as_wide_as_the_block_is_no_tile_bit_for_bit(self):
        """A tile as wide as the block is the launch with no tile asked
        for, bit for bit: a diagonal block is computed in strips only
        where it holds at least two tiles."""
        args = self._inputs(32, 32, jnp.float32)
        whole = self._run(*args, True, 16, 16, 16)
        unasked = self._run(*args, True, 16, 16, None)
        for a, b in zip(whole, unasked):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("s_q,s_k,bq,bk,causal", [
        (32, 32, 16, 16, False),   # non-causal
        (48, 48, 16, 8, True),     # bq != bk
        (40, 40, 8, 32, True),
        (23, 23, 16, 16, True),    # ragged q and k tails
        (16, 40, 16, 16, False),   # cross-attention lengths
        (24, 17, 24, 16, False),   # ... ragged k
        (32, 32, 8, 8, True),      # a block is one tile wide
    ])
    def test_rule_leaves_other_launches_bit_identical(self, s_q, s_k, bq,
                                                      bk, causal):
        """Launches the rule does not cover run the code they run with
        no tile asked for, even when one is: bit-identical values, lse
        and all three gradients."""
        from chainermn_tpu.ops.pallas_attention import block_census

        for kind in ("fwd", "bwd"):
            assert block_census(s_q, s_k, bq, bk, causal, kind=kind,
                                tile=self.TILE)["tile"] is None
        args = self._inputs(s_q, s_k, jnp.float32)
        asked = self._run(*args, causal, bq, bk, self.TILE)
        unasked = self._run(*args, causal, bq, bk, None)
        for a, b in zip(asked, unasked):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rule_table(self):
        from chainermn_tpu.ops.pallas_attention import _compute_tile

        on = dict(causal=True, aligned=True)
        assert _compute_tile(1024, 1024, "bwd", **on) == 256  # defaults
        assert _compute_tile(1024, 1024, "fwd", **on) == 512
        assert _compute_tile(512, 512, "bwd", **on) == 256
        assert _compute_tile(512, 512, "fwd", **on) is None   # one tile
        assert _compute_tile(256, 256, "bwd", **on) is None
        assert _compute_tile(128, 128, "bwd", **on) is None
        assert _compute_tile(1024, 2048, "bwd", **on) is None  # not square
        assert _compute_tile(1024, 1024, "bwd", causal=False,
                             aligned=True) is None
        assert _compute_tile(1024, 1024, "bwd", causal=True,
                             aligned=False) is None
        assert _compute_tile(1024, 1024, "fwd", tile=128, **on) == 128
        assert _compute_tile(640, 640, "bwd", **on) is None  # 256 divides not

    def test_public_api_resolves_the_tile_from_shapes(self):
        """No public argument: ``flash_attention`` at 512 blocks and
        seq 1024 engages the backward's default tile by itself (the
        census says so) and agrees with the dense oracle to float32
        tolerance, values and gradients."""
        from chainermn_tpu.ops.pallas_attention import (
            _dense_attention_with_lse,
            launch_census,
        )

        c = launch_census(1024, 1024, 8, 512, 512, interpret=True)
        assert c["fwd"]["tile"] is None and c["bwd"]["tile"] == 256
        assert c["bwd"]["executed_units"] == 1 + 2 * 0.75
        c = launch_census(2048, 2048, 8, interpret=True)
        assert c["fwd"]["tile"] == 512 and c["bwd"]["tile"] == 256
        rng = np.random.RandomState(5)
        q, k, v = (jnp.asarray(rng.randn(1, 1024, 1, 8), jnp.float32) * 0.3
                   for _ in range(3))

        def run(attend):
            def f(q, k, v):
                return jnp.sum(attend(q, k, v) ** 2)

            return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

        (l_s, g_s), (l_d, g_d) = (
            run(lambda q, k, v: flash_attention(
                q, k, v, True, None, 512, 512, True)),
            run(lambda q, k, v: _dense_attention_with_lse(
                q, k, v, True, 8 ** -0.5)[0]))
        np.testing.assert_allclose(float(l_s), float(l_d), rtol=1e-5)
        for a, b in zip(g_s, g_d):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


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
                          interp, g_lse=None):
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
                          interp, g_lse=None):
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
                   g_lse=None):
            seen.append((bq, bk))
            if len(seen) == 1:
                raise RuntimeError("scoped vmem limit exceeded")
            return real(q, k, v, out, lse, g, causal, scale, bq, bk,
                        interp, g_lse=g_lse)

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
