"""Chunked fused linear + softmax cross-entropy (single chip).

The LM loss `lm_loss(model.apply(p, toks), toks)` materializes the full
(batch, seq, vocab) fp32 logits tensor — 2.1 GB for the bench config
(8x2048x32768) and the reason batch 16 OOMs even under remat.  This op
computes the SAME next-token cross entropy by scanning the tied
embedding table in vocab chunks with an online-softmax merge (the
flash-attention recipe applied to the classifier head):

  forward:  per chunk, logits_c = h @ E_c^T (bf16 MXU, fp32 accum),
            running (max, sumexp) merge + target-logit gather —
            peak extra memory is one (N, V/chunks) block.
  backward: recomputes each chunk's probabilities from the saved
            per-position (max + log-sumexp) — dh accumulates
            sum_c P_c @ E_c − E[target], dE accumulates
            P_c^T h − scatter(target, h) — again one block at a time.

This is the single-chip sibling of
:func:`~chainermn_tpu.parallel.vocab_parallel_cross_entropy` (which
avoids the full-vocab row by sharding it over chips; here it is chunked
in time instead).  Numerics note: the chunk matmuls run in bf16 with
fp32 accumulation (`preferred_element_type`), whereas the dense path
upcasts hidden states to fp32 first — losses agree to ~1e-2 relative,
gradients to bf16 tolerance (pinned in tests/test_chunked_ce.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _chunk_logits(h, e_chunk):
    """(N, d) x (Vc, d) -> (N, Vc) in bf16 with fp32 accumulation."""
    return lax.dot_general(
        h.astype(jnp.bfloat16), e_chunk.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_softmax_cross_entropy(h, table, targets, n_chunks=16):
    """Per-position CE of softmax(h @ table.T) against ``targets``.

    Args:
      h: (N, d) hidden states (any float dtype; matmuls run bf16).
      table: (V, d) classifier/embedding table; V % n_chunks == 0.
      targets: (N,) int32 class ids.
      n_chunks: vocab chunks; peak memory ~ N * V / n_chunks floats.
    Returns:
      (N,) fp32 cross-entropy per position.
    """
    ce, _ = _ce_fwd_impl(h, table, targets, n_chunks)
    return ce


def _ce_fwd_impl(h, table, targets, n_chunks):
    n, d = h.shape
    v = table.shape[0]
    if v % n_chunks:
        raise ValueError(f"vocab {v} % n_chunks {n_chunks} != 0")
    vc = v // n_chunks
    e = table.reshape(n_chunks, vc, d)
    chunk_ids = jnp.arange(n_chunks)

    def body(carry, ec_i):
        m, s, tl = carry
        ec, i = ec_i
        logits = _chunk_logits(h, ec)  # (N, Vc) fp32
        cm = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - cm) + jnp.sum(
            jnp.exp(logits - cm[:, None]), axis=-1
        )
        in_c = targets // vc == i
        idx = jnp.clip(targets - i * vc, 0, vc - 1)
        picked = jnp.take_along_axis(
            logits, idx[:, None], axis=1
        )[:, 0]
        tl = tl + jnp.where(in_c, picked, 0.0)
        return (cm, s, tl), None

    from .grouped_matmul import vary_alike

    # inside a vma-checked shard_map a scan's first carry has the type
    # of the carries that follow
    init = vary_alike(
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        like=(h, table, targets),
    )
    (m, s, tl), _ = lax.scan(body, init, (e, chunk_ids))
    lse = m + jnp.log(s)
    return lse - tl, (h, table, targets, lse)


def _ce_fwd(h, table, targets, n_chunks):
    return _ce_fwd_impl(h, table, targets, n_chunks)


def _ce_bwd(n_chunks, res, g):
    h, table, targets, lse = res
    n, d = h.shape
    v = table.shape[0]
    vc = v // n_chunks
    e = table.reshape(n_chunks, vc, d)
    g = g.astype(jnp.float32)
    gh = (g[:, None] * h.astype(jnp.float32)).astype(jnp.float32)

    def bf16_mm(a, b_mat, dims):
        return lax.dot_general(
            a.astype(jnp.bfloat16), b_mat.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32,
        )

    def body(dh, ec_i):
        ec, i = ec_i
        logits = _chunk_logits(h, ec)
        # d(lse)/dlogits = softmax; scaled by the upstream cotangent
        p = jnp.exp(logits - lse[:, None]) * g[:, None]  # (N, Vc)
        # both accumulation matmuls run bf16 on the MXU (fp32 accum) —
        # the same precision class as the forward chunk matmul
        dh = dh + bf16_mm(p, ec, (((1,), (0,)), ((), ())))
        de_c = bf16_mm(p, h, (((0,), (0,)), ((), ())))   # (Vc, d)
        # −target_logit term: subtract where the target is in this chunk
        in_c = targets // vc == i
        idx = jnp.clip(targets - i * vc, 0, vc - 1)
        sel = jnp.where(in_c, 1.0, 0.0)[:, None]
        de_c = de_c.at[idx].add(-sel * gh)
        dh = dh - sel * jnp.take(ec, idx, axis=0).astype(jnp.float32) * \
            g[:, None]
        return dh, de_c

    from .grouped_matmul import sum_to_vma, vary_alike

    dh0, = vary_alike(jnp.zeros((n, d), jnp.float32), like=(h, table, g))
    dh, de = lax.scan(body, dh0, (e, jnp.arange(n_chunks)))
    # a table replicated over the data axes gets the sum of its shards'
    # gradients, as autodiff gives a plain operation's
    return (
        dh.astype(h.dtype),
        sum_to_vma(de.reshape(v, d).astype(table.dtype), table),
        None,
    )


chunked_softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


def chunked_lm_loss(model, params, tokens, n_chunks=16):
    """Next-token CE for a dense ``TransformerLM`` WITHOUT materializing
    the (batch, seq, vocab) logits: runs the model to hidden states
    (``return_hidden=True`` twin) and feeds the weight-tied table
    through :func:`chunked_softmax_cross_entropy`.

    Drop-in for ``lm_loss(model.apply(p, b), b)`` on the single-chip /
    pure-DP path; for vocab-sharded models use ``vp_lm_loss`` (the
    cross-chip form of the same idea).
    """
    if getattr(model, "vocab_parallel", False):
        raise ValueError("chunked_lm_loss is the single-chip tier; "
                         "vocab-parallel models use vp_lm_loss")
    twin = model.clone(return_hidden=True)
    hidden = twin.apply(params, tokens)          # (b, s, d) fp32
    table = params["params"]["embed"]["embedding"]
    b, s, d = hidden.shape
    with jax.named_scope("head_ce"):  # models.transformer.HEAD_CE_SCOPE
        h = hidden[:, :-1].reshape(-1, d)
        targets = tokens[:, 1:].reshape(-1)
        ce = chunked_softmax_cross_entropy(h, table, targets, n_chunks)
        return ce.mean()
