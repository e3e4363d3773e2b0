"""Chunked gated delta rule (Gated DeltaNet, arXiv:2412.06464; the
linear-attention layers of Qwen3-Next) in two forms: Pallas kernels on a
TPU and plain XLA everywhere else.

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
  (I + A)^-1``, in float32 (``A`` is strictly lower triangular);
* ``U = T (beta V)`` and ``W = T (beta e^G K)``: what the chunk would
  write into an empty state, and what it reads of the state it is
  handed;
* with the entering state ``S``: ``V' = U - W S``, ``O = (Q e^G) S +
  ((Q K^T) e^{G_i - G_j} [j <= i]) V'`` and ``S' = e^{G_C} S + (K
  e^{G_C - G})^T V'``.

Products take ``dtype`` operands and accumulate in float32; ``U``,
``W``, ``V'``, ``Q e^G``, ``K e^{G_C - G}`` and the decayed ``Q K^T``
are rounded to ``dtype``; the decays, ``T`` and the carried state are
float32, in both forms.  Every decay is the exponential of a
non-positive number.

**Which form runs** is read off the input and the platform
(:func:`_use_kernels`).  The kernels (:mod:`.gated_delta_kernels`) run
on a TPU when the sizes tile -- keys and values 128 wide, a chunk of 64,
whole groups of at most four value heads a key head -- and ``k``, ``v``
and the products' operands are bfloat16 (the cell's launch: 16 key and
32 value heads of 128); with ``interpret=True`` they run interpreted at
any such sizes, in either precision (the unit tests).  Every other call
runs the XLA form: off the TPU, float32 operands on it, a head width, a
chunk or a grouping the kernels do not tile.

* **The kernels** walk a sequence's chunks in order with a key head's
  value heads' states in VMEM, reading ``q``, ``k``, ``v`` and writing
  ``o`` in the mixer's own token-major layout; ``K K^T``, the decays and
  ``A`` are tiles made on the chip, and ``T`` is solved exactly in the
  tile (substitution in the diagonal blocks, then merges of pairs).
  ``gated_delta_scan`` is then a ``jax.custom_vjp``: a forward that is
  not differentiated keeps nothing; the differentiated one keeps its
  operands, the two layouts of ``G`` and ``beta``, the state entering
  every chunk (``(chunks, heads, dk, dv)`` float32 a sequence, 268 MB at
  the cell's shape) and every chunk's ``T`` (67 MB); the backward kernel
  walks the chunks last to first with the states' cotangent in VMEM and
  computes decays, ``U``, ``W`` and ``V'`` again per tile.
* **The XLA form** has three stages: what needs no state,
  :data:`CHUNKS_PER_PASS` chunks at a time under ``jax.checkpoint`` with
  ``T = (I - A)(I + A^2)(I + A^4)...`` (``A`` is nilpotent:
  ``log2(chunk)`` squarings and as many products,
  :func:`_inverse_unit_lower`, at the highest precision; the ``(chunk,
  chunk)`` float32 tensors of a head and chunk are 134 MB each at 2 x
  8192 positions and 32 heads, and neither pass holds more than a pass's
  worth); the recurrence over chunks, a ``lax.scan`` whose step is three
  small products a head, under ``jax.checkpoint`` too, so that the
  backward keeps the carried states alone; and the products inside the
  chunks with ``V'``.  It is what the kernels are tested against.

Single-device in the sequence and the heads: no sequence-parallel,
tensor-parallel or decode form.  :func:`gated_delta_census` is the
static count of the algorithm's work and of the kernel path's launches,
as ``ssd_census`` is for the state-space scan.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import gated_delta_kernels
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


def _use_kernels(k, v, chunk, dtype, interpret) -> bool:
    """Whether :func:`gated_delta_scan` runs the Pallas kernels: the
    sizes tile (:func:`gated_delta_kernels.tiles`) and either the
    kernels are asked for interpreted, or this is a TPU and ``k``, ``v``
    and the products' operands are bfloat16."""
    (hk, dk), (h, dv) = k.shape[2:], v.shape[2:]
    if not gated_delta_kernels.tiles(chunk, h, hk, dk, dv):
        return False
    if interpret is not None:
        return True
    return jax.default_backend() == "tpu" and dtype == jnp.bfloat16 \
        and k.dtype == v.dtype == jnp.bfloat16


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
@jax.named_scope(GDN_SCAN_SCOPE)
def gated_delta_scan(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.bfloat16, interpret: Optional[bool] = None):
    """The recurrence of the module docstring in its chunked form.

    ``q``, ``k (b, s, key_heads, dk)``, normalised as the layer wants
    them (the scan applies no scale); ``v (b, s, heads, dv)``, value
    head ``i`` read and written through key head ``i // (heads /
    key_heads)``; ``g (b, s, heads)``, non-positive, and ``beta (b, s,
    heads)``.  Returns ``o (b, s, heads, dv)`` in ``v``'s dtype;
    ``dtype`` is that of the products' operands.  A length that is no
    multiple of ``chunk`` is padded with ``g = 0, beta = 0`` rows of
    zero keys, which leave every state as it was.  ``interpret=True``
    runs the kernels interpreted wherever the sizes tile (the unit
    tests do)."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    if h % hk:
        raise ValueError(f"{h} value heads over {hk} key heads")
    scan, whole = _scan_xla, chunk
    if _use_kernels(k, v, chunk, dtype, interpret):
        scan = functools.partial(_scan_kernels, interpret=bool(interpret))
        whole = chunk * gated_delta_kernels.CHUNKS_PER_POINT
    pad = -s % whole
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    return scan(q, k, v, g, beta, chunk, dtype)[:, :s]


def _scan_kernels(q, k, v, g, beta, chunk, dtype, interpret):
    """The kernel path over whole chunks: the running sums here, the
    passes in :func:`gated_delta_kernels.gated_delta_chunks`."""
    b, s, h, dv = v.shape
    cum = jnp.cumsum(g.reshape(b, s // chunk, chunk, h), axis=2)
    o = gated_delta_kernels.gated_delta_chunks(
        q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1),
        cum.reshape(b, s, h), beta, chunk, dtype, interpret)
    return o.reshape(b, s, h, dv)


def _scan_xla(q, k, v, g, beta, chunk, dtype):
    """The XLA form over whole chunks."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    c = s // chunk
    # chunks of all sequences on one axis: outside the recurrence they
    # differ in nothing
    cut = lambda t: t.reshape(b * c, chunk, *t.shape[2:])
    passes = math.gcd(b * c, CHUNKS_PER_PASS)
    parts = lax.map(
        lambda args: _without_state(*args, dtype),
        tuple(cut(t).reshape(b * c // passes, passes, chunk, *t.shape[2:])
              for t in (q, k, v, g, beta)))
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
    return out.reshape(b, s, h, dv).astype(v.dtype)


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
    ``itemsize`` bytes, ``g`` and ``beta`` in float32.  ``kernels`` is
    the static account of the kernel path, ``None`` where the sizes do
    not tile (:func:`gated_delta_kernels.tiles`: the XLA form runs): for
    the ``forward`` launch that keeps the entering states and each
    chunk's ``T``, and for the ``backward`` launch, ``grid`` (sequences,
    key heads, grid points of ``CHUNKS_PER_POINT`` chunks), ``tiles`` a
    launch, ``vmem_bytes`` a grid point (scratch and double-buffered
    blocks), ``hbm_bytes`` read and written, and ``hbm_over_least``,
    those over ``bytes_forward``."""
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
    kernels = None
    if gated_delta_kernels.tiles(chunk, heads, key_heads, dk, dv):
        per_point = chunk * gated_delta_kernels.CHUNKS_PER_POINT
        kernels = gated_delta_kernels.launch_account(
            -(-s // per_point) * per_point, chunk, heads, key_heads,
            itemsize)
        for launch in kernels.values():
            launch["hbm_over_least"] = launch["hbm_bytes"] / least
    return {
        "chunks": chunks, "padded": chunks * chunk, "flops": parts,
        "flops_forward": forward,
        "flops_backward": 2.0 * forward + parts["kk"] + parts["qk"],
        "bytes_forward": least,
        "kernels": kernels,
    }
