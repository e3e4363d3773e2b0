"""Chunked fused linear+CE vs the dense-logits oracle.

The op exists so the (batch, seq, vocab) logits never materialize; its
contract is numerical agreement with the straightforward
full-logits cross entropy — value AND gradients (both wrt hidden
states and wrt the tied table), including targets falling in every
chunk, and invariance to the chunk count.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from chainermn_tpu.ops import (
    chunked_lm_loss,
    chunked_softmax_cross_entropy,
)

N, D, V = 24, 16, 64


def _data(seed=0):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(N, D), jnp.float32)
    table = jnp.asarray(rng.randn(V, D) * 0.2, jnp.float32)
    targets = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)
    return h, table, targets


def _oracle(h, table, targets):
    logits = h.astype(jnp.bfloat16) @ table.astype(jnp.bfloat16).T
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )


class TestChunkedCE:
    def test_value_matches_oracle(self):
        h, table, targets = _data()
        got = chunked_softmax_cross_entropy(h, table, targets, 8)
        want = _oracle(h, table, targets)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2
        )

    def test_chunk_count_invariant(self):
        h, table, targets = _data(1)
        a = chunked_softmax_cross_entropy(h, table, targets, 1)
        for k in (2, 4, 16):
            b = chunked_softmax_cross_entropy(h, table, targets, k)
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
            )

    def test_targets_in_every_chunk(self):
        h, table, _ = _data(2)
        # targets spread over the full vocab range so every chunk's
        # gather fires (N=24 over V=64: bucket ids 0..7 all hit)
        targets = jnp.asarray(np.arange(N) * V // N, jnp.int32)
        assert len(set(np.asarray(targets) // (V // 8))) == 8
        got = chunked_softmax_cross_entropy(h, table, targets, 8)
        want = _oracle(h, table, targets)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2
        )

    def test_gradients_match_oracle(self):
        h, table, targets = _data(3)

        def f_chunked(h, t):
            return chunked_softmax_cross_entropy(h, t, targets, 8).mean()

        def f_full(h, t):
            return _oracle(h, t, targets).mean()

        (gh_c, gt_c) = jax.grad(f_chunked, argnums=(0, 1))(h, table)
        (gh_f, gt_f) = jax.grad(f_full, argnums=(0, 1))(h, table)
        np.testing.assert_allclose(
            np.asarray(gh_c), np.asarray(gh_f), rtol=5e-2, atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(gt_c), np.asarray(gt_f), rtol=5e-2, atol=1e-3
        )

    def test_weighted_cotangent(self):
        # non-uniform upstream cotangents (e.g. masked means) must
        # propagate per-position
        h, table, targets = _data(4)
        w = jnp.asarray(np.random.RandomState(5).rand(N), jnp.float32)

        def f_chunked(h):
            return (
                chunked_softmax_cross_entropy(h, table, targets, 4) * w
            ).sum()

        def f_full(h):
            return (_oracle(h, table, targets) * w).sum()

        np.testing.assert_allclose(
            np.asarray(jax.grad(f_chunked)(h)),
            np.asarray(jax.grad(f_full)(h)),
            rtol=5e-2, atol=1e-3,
        )

    def test_vocab_not_divisible_raises(self):
        h, table, targets = _data()
        with pytest.raises(ValueError, match="n_chunks"):
            chunked_softmax_cross_entropy(h, table, targets, 7)


class TestChunkedLmLoss:
    def test_matches_full_lm_loss(self):
        from chainermn_tpu.models.transformer import TransformerLM, lm_loss

        model = TransformerLM(
            vocab_size=V, d_model=D, n_heads=2, n_layers=2, max_len=16,
            dtype=jnp.float32,
        )
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, V, (2, 16)), jnp.int32
        )
        # a program each (init, and value with gradient), not op by op
        params = jax.jit(model.init)(jax.random.PRNGKey(0), toks)
        full, g_full = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(model.apply(p, toks), toks)
        ))(params)
        chunked, g_chunk = jax.jit(jax.value_and_grad(
            lambda p: chunked_lm_loss(model, p, toks, n_chunks=8)
        ))(params)
        np.testing.assert_allclose(
            float(chunked), float(full), rtol=2e-2
        )
        # gradients flow to every parameter (incl. the tied table)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_full),
            jax.tree_util.tree_leaves(g_chunk),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=0.1, atol=2e-3
            )

    def test_vocab_parallel_rejected(self):
        from chainermn_tpu.models.transformer import TransformerLM

        model = TransformerLM(
            vocab_size=V, d_model=D, n_heads=2, n_layers=1, max_len=16,
            dtype=jnp.float32, tp_axis="mn_model", vocab_parallel=True,
        )
        with pytest.raises(ValueError, match="vp_lm_loss"):
            chunked_lm_loss(model, {}, jnp.zeros((1, 8), jnp.int32))
