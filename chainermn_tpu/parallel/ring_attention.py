"""Ring attention — sequence/context parallelism over ICI.

The reference predates attention entirely (SURVEY.md section 5.7): its
closest primitives are the differentiable p2p send/recv
(point_to_point_communication.py) and the hidden-state-streaming RNN.
This module is the modern capability those primitives point at: shard the
*sequence* across chips and compute exact attention by rotating key/value
blocks around the ICI ring (Liu et al., "Ring Attention with Blockwise
Transformers"), overlapping each block's compute with the next block's
transfer.

Design: runs inside ``shard_map`` with queries resident and K/V blocks
circulating via ``lax.ppermute``; softmax is computed online (running max
and normalizer), so memory is O(seq_shard) regardless of total sequence
length.  Causal masking uses the ring step to decide block visibility —
entire future blocks are skipped numerically (their contribution is
masked), keeping control flow static for XLA.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _block_attend(q, k, v, bias, scale):
    """One (q_block, k_block) attention partial: returns (unnormalized
    numerator, running max, running denominator) pieces."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)  # (b, h, q, 1)
    p = jnp.exp(s - m)
    num = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    den = jnp.sum(p, axis=-1, keepdims=True)  # (b, h, q, 1)
    return num, den, m


_BLOCK_NEG = -1e30  # finite "minus infinity": exp() underflows cleanly


def _ring_flash(q, k, v, axis_name, causal, scale, block_q, block_k,
                interpret):
    """Ring attention with the Pallas flash kernel as the per-block core.

    Each ring step runs :func:`flash_attention_with_lse` on the resident
    queries against the circulating K/V block; partial outputs merge
    exactly via their log-sum-exp.  Causality at block granularity: the
    diagonal block (owner == self) runs the kernel's causal mode, blocks
    entirely in the past run full attention, blocks entirely in the
    future are skipped (a runtime branch — each chip takes its own).
    Gradients flow through the merge weights because the lse output is
    differentiable (its VJP rides the same backward kernels).
    """
    from chainermn_tpu.ops.pallas_attention import flash_attention_with_lse

    if causal and q.shape[1] != k.shape[1]:
        # Block-granular causality classifies whole blocks by owner
        # index, which is only a global-position mask when q and k
        # shards are the same length; the plain path masks by global
        # position and handles the ragged case.
        raise ValueError(
            f"ring flash with causal=True needs equal q/k shard lengths "
            f"(got {q.shape[1]} vs {k.shape[1]}); use use_flash=False"
        )
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    def block(kb, vb, blk_causal):
        return flash_attention_with_lse(
            q, kb, vb, blk_causal, scale, block_q, block_k, interpret
        )

    def step_out(kb, vb, owner):
        if not causal:
            return block(kb, vb, False)

        def diag(args):
            return block(*args, True)

        def full(args):
            return block(*args, False)

        def skip(args):
            del args
            o = (q * 0).astype(q.dtype)
            # (b, s, h) in the kernel's fp32 lse dtype, q's vma
            lse = (q[..., 0] * 0).astype(jnp.float32) + _BLOCK_NEG
            return o, lse

        return lax.cond(
            owner == my, diag,
            lambda a: lax.cond(owner < my, full, skip, a),
            (kb, vb),
        )

    def body(carry, step):
        kb, vb, o, lse = carry
        owner = (my - step) % n
        o_b, lse_b = step_out(kb, vb, owner)
        # exact two-way online-softmax merge via log-sum-exp
        m = jnp.maximum(lse, lse_b)
        w = jnp.exp(lse - m)
        w_b = jnp.exp(lse_b - m)
        den = w + w_b
        o = (
            o * (w / den)[..., None]
            + o_b.astype(jnp.float32) * (w_b / den)[..., None]
        )
        lse = m + jnp.log(den)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, o, lse), None

    o0 = (q * 0).astype(jnp.float32)
    lse0 = (q[..., 0] * 0).astype(jnp.float32) + _BLOCK_NEG
    (_, _, o, _), _ = lax.scan(body, (k, v, o0, lse0), jnp.arange(n))
    return o.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded along ``axis_name``.

    Args:
      q, k, v: (batch, seq_shard, heads, head_dim) — the local sequence
        block of each chip.  Must be called inside ``shard_map`` with the
        sequence axis bound to ``axis_name``.
      causal: apply a causal mask consistent with the *global* sequence
        order (shard r holds positions [r*S, (r+1)*S)).
      use_flash: run each per-block attention through the Pallas flash
        kernel (:func:`~chainermn_tpu.ops.flash_attention_with_lse`),
        merging blocks via their log-sum-exp — the long-context
        performance tier.  ``None`` auto-selects: flash on a TPU backend
        when the local sequence shard fills a lane tile (>= 128).
        ``block_q``/``block_k``/``interpret`` configure the kernel.
    Returns:
      (batch, seq_shard, heads, head_dim) attention output for the local
      queries, numerically identical to full attention over the gathered
      sequence.
    """
    if use_flash is None:
        use_flash = (
            jax.default_backend() == "tpu"
            and q.shape[1] >= 128
            and k.shape[1] >= 128
            and (not causal or q.shape[1] == k.shape[1])
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, scale, block_q,
                           block_k, interpret)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    s_k = k.shape[1]

    neg = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)

    def causal_bias(kv_owner):
        """Bias for my query block attending kv_owner's key block."""
        # global positions: q_pos = my*s_q + i ; k_pos = kv_owner*s_k + j
        qi = my * s_q + jnp.arange(s_q)[:, None]
        kj = kv_owner * s_k + jnp.arange(s_k)[None, :]
        return jnp.where(qi >= kj, 0.0, neg).astype(q.dtype)[None, None]

    def body(carry, step):
        kb, vb, num, den, mx = carry
        owner = (my - step) % n  # whose block we currently hold
        bias = causal_bias(owner) if causal else None
        bnum, bden, bm = _block_attend(q, kb, vb, bias, scale)
        # online softmax merge
        new_m = jnp.maximum(mx, bm)
        corr_old = jnp.exp(mx - new_m)
        corr_new = jnp.exp(bm - new_m)
        # (b,h,q,1) -> (b,q,h,1) to broadcast against num's (b,q,h,d)
        num = num * jnp.swapaxes(corr_old, 1, 2) + bnum * jnp.swapaxes(
            corr_new, 1, 2
        )
        den = den * corr_old + bden * corr_new
        # rotate K/V to the next chip (overlaps with next iteration's
        # compute under XLA's async collective scheduling)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, num, den, new_m), None

    # Derive the initial carries from q (x*0 keeps the varying-manual-axes
    # marking that fresh zeros would lack — required by vma-checked
    # shard_map, whose scan demands carry-in/carry-out vma equality).
    num0 = q * 0
    col0 = jnp.swapaxes(q[..., :1] * 0, 1, 2)  # (b, h, s_q, 1), q's vma
    den0 = col0
    m0 = col0 + neg
    (_, _, num, den, _), _ = lax.scan(
        body, (k, v, num0, den0, m0), jnp.arange(n)
    )
    out = num / jnp.swapaxes(jnp.maximum(den, 1e-20), 1, 2)
    return out.astype(q.dtype)
