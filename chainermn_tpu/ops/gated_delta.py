"""Chunked gated delta rule (Gated DeltaNet, arXiv:2412.06464; the
linear-attention layers of Qwen3-Next), in plain XLA.

A value head carries a state ``S (dk, dv)`` over the positions of a
sequence, driven by a key ``k_t`` and a query ``q_t (dk,)`` (both
already normalised; a key head serves ``heads / key_heads`` value
heads), a value ``v_t (dv,)``, a log decay ``g_t <= 0`` and a write
strength ``beta_t`` in (0, 1):

    S_t = e^{g_t} S_{t-1}                           (S_0 = 0)
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T    (the delta rule)
    o_t = S_t^T q_t

:func:`gated_delta_scan` computes exactly that, ``chunk`` positions at a
time.  With ``G`` the running sum of ``g`` inside a chunk, a head:

* ``A_ij = beta_i (k_i . k_j) e^{G_i - G_j}`` for ``j < i``, and ``T =
  (I + A)^-1``: ``A`` is strictly lower triangular, so nilpotent, and
  ``T = (I - A)(I + A^2)(I + A^4)...`` is ``log2(chunk)`` squarings and
  as many products (:func:`_inverse_unit_lower`), in float32 at the
  highest precision;
* ``U = T (beta V)`` and ``W = T (beta e^G K)``: what the chunk would
  write into an empty state, and what it reads of the state it is
  handed;
* with the entering state ``S``: ``V' = U - W S``, ``O = (Q e^G) S +
  ((Q K^T) e^{G_i - G_j} [j <= i]) V'`` and ``S' = e^{G_C} S + (K
  e^{G_C - G})^T V'``.

Three stages: what needs no state, :data:`CHUNKS_PER_PASS` chunks at a
time under ``jax.checkpoint`` (the ``(chunk, chunk)`` float32 tensors of
a head and chunk are 134 MB each at 2 x 8192 positions and 32 heads;
neither pass holds more than a pass's worth and the backward computes
them again); the recurrence over chunks, a ``lax.scan`` whose step is
three small products a head, under ``jax.checkpoint`` too, so that the
backward keeps the carried states alone; and the products inside the
chunks with ``V'``.  Products take ``dtype`` operands and accumulate in
float32; the decays, ``T`` and the carried state are float32.  Every
decay is the exponential of a non-positive number.

Single-device in the sequence and the heads: no sequence-parallel,
tensor-parallel or decode form.  :func:`gated_delta_census` is the
static count of the algorithm's work, as ``ssd_census`` is for the
state-space scan.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .grouped_matmul import vary_alike

#: chunks whose ``(heads, chunk, chunk)`` float32 tensors are live at
#: once in the stages outside the recurrence
CHUNKS_PER_PASS = 32

#: device scope of the scan
GDN_SCAN_SCOPE = "gdn_scan"


def _dot(spec, a, b, dtype):
    """A product with operands in ``dtype`` and a float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _inverse_unit_lower(a):
    """``(I + a)^-1`` for ``a (..., c, c)`` strictly lower triangular:
    ``sum_n (-a)^n = (I - a)(I + a^2)(I + a^4)...`` up to ``a^(c - 1)``,
    float32 products at the highest precision."""
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    c = a.shape[-1]
    inverse = jnp.eye(c, dtype=a.dtype) - a
    power, n = a, 2
    while n < c:
        power = mm(power, power)
        inverse = inverse + mm(inverse, power)
        n *= 2
    return inverse


def _by_key_head(t, key_heads):
    """``(n, c, heads, ...)`` -> ``(n, key_heads, heads / key_heads, c,
    ...)``: the value heads a key head serves side by side."""
    n, c, h = t.shape[:3]
    t = jnp.moveaxis(t, 1, 2)
    return t.reshape(n, key_heads, h // key_heads, c, *t.shape[3:])


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _without_state(q, k, v, g, beta, dtype):
    """The part of a few chunks that needs no state: ``q`` / ``k (n, c,
    hk, dk)``, ``v (n, c, h, dv)``, ``g`` / ``beta (n, c, h)`` float32.
    Returns ``U (n, hk, r, c, dv)``, ``W``, ``Q e^G`` and ``K e^{G_C -
    G}`` ``(n, hk, r, c, dk)`` in ``dtype``, ``e^{G_C} (n, hk, r)``
    float32, and the masked, decayed ``Q K^T (n, hk, r, c, c)`` in
    ``dtype``."""
    hk, c = k.shape[2], k.shape[1]
    dot = functools.partial(_dot, dtype=dtype)
    run = _by_key_head(jnp.cumsum(g, axis=1), hk)      # (n, hk, r, c)
    beta = _by_key_head(beta, hk)
    v = _by_key_head(v, hk)                            # (n, hk, r, c, dv)
    kh = jnp.moveaxis(k, 1, 2)[:, :, None]             # (n, hk, 1, c, dk)
    qh = jnp.moveaxis(q, 1, 2)[:, :, None]
    kk = dot("nihd,njhd->nhij", k, k)[:, :, None]      # (n, hk, 1, c, c)
    qk = dot("nihd,njhd->nhij", q, k)[:, :, None]
    # exp of a masked difference: above the diagonal G_i - G_j is
    # positive and may overflow
    at_or_below = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(
        at_or_below, run[..., :, None] - run[..., None, :], -jnp.inf))
    below = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    inverse = _inverse_unit_lower(
        beta[..., :, None] * kk * decay * below)
    into = jnp.exp(run)                                # e^G <= 1
    to_end = jnp.exp(run[..., -1:] - run)
    written = dot("nhrij,nhrjd->nhrid", inverse, beta[..., None] * v)
    read = dot("nhrij,nhrjd->nhrid", inverse,
               (beta * into)[..., None] * kh)
    cast = lambda t: t.astype(dtype)
    return (cast(written), cast(read), cast(into[..., None] * qh),
            cast(to_end[..., None] * kh), into[..., -1],
            cast(qk * decay))


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _carry_on(state, chunk_of, dtype):
    """One chunk of the recurrence: ``state (b, h, dk, dv)`` float32 in,
    the state after the chunk out, with the chunk's ``V'`` and what the
    entering state adds to its result."""
    written, read, q_in, k_out, through = chunk_of
    dot = functools.partial(_dot, dtype=dtype)
    new = written.astype(jnp.float32) - dot("bhck,bhkv->bhcv", read, state)
    from_state = dot("bhck,bhkv->bhcv", q_in, state)
    state = through[..., None, None] * state \
        + dot("bhck,bhcv->bhkv", k_out, new)
    return state, (new.astype(dtype), from_state.astype(dtype))


@functools.partial(jax.jit, static_argnames=("chunk", "dtype"))
@jax.named_scope(GDN_SCAN_SCOPE)
def gated_delta_scan(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.bfloat16):
    """The recurrence of the module docstring in its chunked form.

    ``q``, ``k (b, s, key_heads, dk)``, normalised as the layer wants
    them (the scan applies no scale); ``v (b, s, heads, dv)``, value
    head ``i`` read and written through key head ``i // (heads /
    key_heads)``; ``g (b, s, heads)``, non-positive, and ``beta (b, s,
    heads)``.  Returns ``o (b, s, heads, dv)`` in ``v``'s dtype;
    ``dtype`` is that of the products' operands.  A length that is no
    multiple of ``chunk`` is padded with ``g = 0, beta = 0`` rows of
    zero keys, which leave every state as it was."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    if h % hk:
        raise ValueError(f"{h} value heads over {hk} key heads")
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    c = (s + pad) // chunk
    # chunks of all sequences on one axis: outside the recurrence they
    # differ in nothing
    cut = lambda t: t.reshape(b * c, chunk, *t.shape[2:])
    passes = math.gcd(b * c, CHUNKS_PER_PASS)
    parts = lax.map(
        lambda args: _without_state(*args, dtype),
        tuple(cut(t).reshape(b * c // passes, passes, chunk, *t.shape[2:])
              for t in (q, k, v, g.astype(jnp.float32),
                        beta.astype(jnp.float32))))
    # (b, c, h, ...) -> the recurrence's (c, b, h, ...)
    by_chunk = lambda t: jnp.moveaxis(
        t.reshape(b, c, h, *t.shape[4:]), 1, 0)
    *per_chunk, decayed_qk = parts
    state0, = vary_alike(jnp.zeros((b, h, dk, dv), jnp.float32),
                         like=(per_chunk[0],))
    _, (new, from_state) = lax.scan(
        lambda state, chunk_of: _carry_on(state, chunk_of, dtype), state0,
        tuple(map(by_chunk, per_chunk)))
    inside = _dot("nhij,nhjv->nhiv",
                  decayed_qk.reshape(b * c, h, chunk, chunk),
                  jnp.moveaxis(new, 0, 1).reshape(b * c, h, chunk, dv),
                  dtype)
    out = inside + jnp.moveaxis(from_state, 0, 1).reshape(
        b * c, h, chunk, dv).astype(jnp.float32)
    out = jnp.moveaxis(out.reshape(b, c, h, chunk, dv), 2, 3)
    return out.reshape(b, s + pad, h, dv)[:, :s].astype(v.dtype)


def gated_delta_census(s: int, chunk: int, heads: int, dk: int, dv: int,
                       key_heads=None, itemsize: int = 2) -> dict:
    """What :func:`gated_delta_scan` computes for one sequence of ``s``
    positions, from the algorithm alone: ``chunks`` (and the padded
    length) and the forward pass's matmul FLOPs by part -- ``kk`` and
    ``qk`` (``K K^T`` and ``Q K^T``, a key head and chunk), ``solve``
    (``T`` applied to ``[beta V | beta e^G K]`` as a forward
    substitution would, ``chunk^2 (dk + dv)`` a head and chunk: the
    least any way of solving needs; the nilpotent series the scan runs
    costs more), ``read`` (``W S``), ``from_state`` (``(Q e^G) S``),
    ``inside`` (the decayed ``Q K^T`` against ``V'``) and ``state``
    (``(K e^{G_C - G})^T V'``) -- their sum ``flops_forward``,
    ``flops_backward`` (two products for each of the forward's, and
    ``kk`` and ``qk`` once more: they are computed again), and
    ``bytes_forward``: ``q``, ``k``, ``v`` and ``o`` once each in
    ``itemsize`` bytes, ``g`` and ``beta`` in float32."""
    key_heads = key_heads or heads
    chunks = -(-s // chunk)
    per_head = float(chunks * heads)
    parts = {
        "kk": 2.0 * chunks * key_heads * chunk * chunk * dk,
        "qk": 2.0 * chunks * key_heads * chunk * chunk * dk,
        "solve": per_head * chunk * chunk * (dk + dv),
        "read": 2.0 * per_head * chunk * dk * dv,
        "from_state": 2.0 * per_head * chunk * dk * dv,
        "inside": 2.0 * per_head * chunk * chunk * dv,
        "state": 2.0 * per_head * chunk * dk * dv,
    }
    forward = sum(parts.values())
    least = float(s) * ((2 * key_heads * dk + 2 * heads * dv) * itemsize
                        + 2 * 4 * heads)
    return {
        "chunks": chunks, "padded": chunks * chunk, "flops": parts,
        "flops_forward": forward,
        "flops_backward": 2.0 * forward + parts["kk"] + parts["qk"],
        "bytes_forward": least,
    }
