"""Attention core.

Single-device reference attention used as the numerics oracle for
``parallel.ring_attention`` / ``parallel.ulysses_attention`` tests, and as
the default core those wrap.  A Pallas flash-attention kernel can be
slotted in via the ``attention_fn`` hooks once profiling justifies it
(SURVEY.md section 2 native-code obligations: only hand-write what XLA
doesn't already fuse).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def multi_head_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         *, causal: bool = False,
                         scale: Optional[float] = None,
                         window: Optional[int] = None) -> jnp.ndarray:
    """(b, s, h, d) x 3 -> (b, s, h, d), fp32 softmax accumulation.
    ``window`` (with ``causal``): a query sees the last ``window`` keys,
    its own among them."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("a window comes with the causal mask")
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S_q, S_k = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((S_q, S_k), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((S_q, S_k), bool), -window)
        s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
