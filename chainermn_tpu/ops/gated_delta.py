"""Chunked gated delta rule in two variants: a scalar decay a head and
position (Gated DeltaNet, arXiv:2412.06464), and a decay of its own for
each key channel (Kimi Delta Attention, arXiv:2510.26692).  Either
variant has two forms, Pallas kernels on a TPU and plain XLA everywhere
else.

A value head carries a state ``S (dk, dv)`` over the positions of a
sequence, driven by a key ``k_t`` and a query ``q_t (dk,)`` (both
already normalised; a key head serves ``heads / key_heads`` value
heads), a value ``v_t (dv,)``, a log decay ``g_t <= 0`` and a write
strength ``beta_t`` in (0, 1):

    S_t = e^{g_t} S_{t-1}                           (S_0 = 0)
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T    (the delta rule)
    o_t = S_t^T q_t

with ``g_t`` one number a head (``g (b, s, heads)``), or a ``dk``-vector
a head, ``S_t = Diag(e^{g_t}) S_{t-1}`` (``g (b, s, heads, dk)``: each
row of the state decays by its own factor).  Which rule
:func:`gated_delta_scan` computes is read off ``g``'s shape; the second
with every channel's decay the same is the first.  Either is computed
``chunk`` positions at a time.

**The scalar rule.**  With ``G`` the running sum of ``g`` inside a
chunk, a head:

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

**The channel-wise rule.**  ``G`` is a ``dk``-vector a position and the
decay no longer factors out of the contraction over channels: ``A_ij =
beta_i sum_c k_ic k_jc e^{G_ic - G_jc}`` (``j < i``), ``P_ij = sum_c
q_ic k_jc e^{G_ic - G_jc}`` (``j <= i``) in the place of the decayed ``Q
K^T``, ``W = T (beta (K * e^G))``, ``O = (Q * e^G) S + P V'``, ``S' =
Diag(e^{G_C}) S + (K * e^{G_C - G})^T V'``.  Splitting ``A`` as ``(K
e^G)(K e^{-G})^T`` would evaluate ``e^{-G}``, which overflows float32
after a few strongly decayed positions; the rule above, every decay the
exponential of a non-positive number, is kept by cutting the chunk.  The
XLA form cuts it into blocks of :data:`DECAY_BLOCK` positions
(:func:`_channel_decays`): for
``i`` in block ``r`` and ``j`` in an earlier block, ``(K_i * e^{G_i -
G_r0}) . (K_j * e^{G_r0 - G_j})`` with ``r0`` the position before the
block's first (both exponents non-positive, a product in ``dtype``
operands); inside a block the ``block x block x dk`` differences
directly, in float32.  The kernels cut it by halves
(:mod:`.kda_kernels`: for ``i`` above a cut ``m`` and ``j`` at or below
it ``G_i - G_j = (G_i - G_m) + (G_m - G_j)``, one product of decayed
operands a level, ``dtype`` operands where the cut parts
:data:`DECAY_BLOCK` positions or more and float32 ones, to 16 bits of
mantissa, inside a block).

**Which form runs** is read off the input and the platform
(:func:`_use_kernels`).  The kernels run on a TPU when the sizes tile --
keys and values 128 wide, a chunk of 64; under the scalar rule
(:mod:`.gated_delta_kernels`) whole groups of at most four value heads a
key head, under the channel-wise rule (:mod:`.kda_kernels`) a key head a
value head -- and ``k``, ``v`` and the products' operands are bfloat16
(the cells' launches: 16 key and 32 value heads of 128; 32 of each);
with ``interpret=True`` they run interpreted at any such sizes, in
either precision (the unit tests).  Every other call runs the XLA form:
off the TPU, float32 operands on it, a head width, a chunk or a grouping
the kernels do not tile.

* **The kernels** walk a sequence's chunks in order with a key head's
  value heads' states in VMEM, reading ``q``, ``k``, ``v`` and writing
  ``o`` in the mixer's own token-major layout; ``K K^T``, the decays and
  ``A`` are tiles made on the chip, and ``T`` is solved exactly in the
  tile (substitution in the diagonal blocks, then merges of pairs).
  ``gated_delta_scan`` is then a ``jax.custom_vjp``: a forward that is
  not differentiated keeps nothing; the differentiated one keeps its
  operands, the two layouts of ``G`` and ``beta``, the state entering
  every chunk (``(chunks, heads, dk, dv)`` float32 a sequence: 268 MB
  a sequence of 8192 positions and 32 heads of 128, 537 MB a layer at
  the cells' two) and every chunk's ``T`` (67 MB a sequence, 134 MB a
  layer); the backward kernel
  walks the chunks last to first with the states' cotangent in VMEM and
  computes decays, ``U``, ``W`` and ``V'`` again per tile.  The
  channel-wise rule's do the same a value head, with the ``(chunk, dk)``
  tile of ``g`` read as the mixer left it, its running sum and every
  decayed operand made on the chip, and ``dg`` a channel written in
  ``g``'s layout (the same residuals: 32 heads in both cells).  The
  launch's results, ``o`` and the two residuals (805 MB a layer with
  ``o``'s 134), carry the name ``scan_out``
  (:data:`gated_delta_kernels.SCAN_OUT`): a block recomputed under a
  plan that lists it (``models.transformer.remat_plan``) keeps them
  and runs no forward launch again.
* **The XLA form** has three stages: what needs no state,
  :data:`CHUNKS_PER_PASS` chunks at a time (:data:`CHUNKS_PER_PASS_CHANNELS`
  under the channel-wise rule) under ``jax.checkpoint`` with
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

from . import gated_delta_kernels, kda_kernels
from .grouped_matmul import vary_alike

#: chunks whose ``(heads, chunk, chunk)`` float32 tensors are live at
#: once in the stages outside the recurrence
CHUNKS_PER_PASS = 32
#: the same under the channel-wise rule, whose ``(heads, chunk, chunk /
#: DECAY_BLOCK, dk)`` float32 decays of a chunk are 4 MiB at 32 heads of
#: 128
CHUNKS_PER_PASS_CHANNELS = 8
#: positions of a block of the channel-wise rule's chunk: between blocks
#: the decays go into the products' operands, inside one they are taken
#: pair by pair
DECAY_BLOCK = 16

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


def _channel_decays(run, block):
    """The decays of the channel-wise rule's products ``sum_c x_ic k_jc
    e^{G_ic - G_jc}`` (``j <= i``), none with an exponent above 0:
    ``run (n, h, c, dk)`` the running sums ``G`` (non-increasing along
    ``c``) -> ``to_rows (n, h, c / block, block, dk)``, ``e^{G_i -
    G_r0}`` for ``i`` in block ``r`` and ``r0`` the position before its
    first; ``from_cols (n, h, c / block, c, dk)``, ``e^{G_r0 - G_j}``
    for ``j`` at or before ``r0`` and 0 after it; ``within (n, h, c /
    block, block, block, dk)``, ``e^{G_i - G_j}`` pair by pair inside a
    block, 0 above its diagonal."""
    n, h, c, dk = run.shape
    nb = c // block
    run_b = run.reshape(n, h, nb, block, dk)
    # G at r0, a block: 0 before the first
    before = jnp.concatenate([jnp.zeros_like(run_b[:, :, :1, -1]),
                              run_b[:, :, :-1, -1]], axis=2)
    to_rows = jnp.exp(run_b - before[:, :, :, None])
    earlier = jnp.arange(c)[None, :] < block * jnp.arange(nb)[:, None]
    from_cols = jnp.exp(jnp.where(
        earlier[..., None],
        before[:, :, :, None] - run[:, :, None], -jnp.inf))
    at_or_below = jnp.tril(jnp.ones((block, block), bool))
    within = jnp.exp(jnp.where(
        at_or_below[..., None],
        run_b[:, :, :, :, None] - run_b[:, :, :, None], -jnp.inf))
    return to_rows, from_cols, within


def _decayed_products(rows, k, decays, dtype):
    """``sum_c rows_ic k_jc e^{G_ic - G_jc}`` for ``j <= i`` (0 above
    the diagonal): ``rows`` / ``k (n, h, c, dk)`` float32, ``decays``
    :func:`_channel_decays`' -> ``(n, h, c, c)`` float32.  Between
    blocks both factors carry their decay towards ``r0`` and the product
    takes ``dtype`` operands; inside a block the sum is float32."""
    to_rows, from_cols, within = decays
    n, h, c, _ = k.shape
    nb, block = to_rows.shape[2:4]
    blocks = lambda t: t.reshape(n, h, nb, block, -1)
    far = _dot("nhrad,nhrjd->nhraj", blocks(rows) * to_rows,
               k[:, :, None] * from_cols, dtype).reshape(n, h, c, c)
    near = jnp.sum(blocks(rows)[:, :, :, :, None]
                   * blocks(k)[:, :, :, None] * within, axis=-1)
    # the blocks' own products onto the diagonal
    return far + jnp.einsum("nhrab,rs->nhrasb", near,
                            jnp.eye(nb, dtype=near.dtype)
                            ).reshape(n, h, c, c)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _without_state_channels(q, k, v, g, beta, dtype):
    """:func:`_without_state` under the channel-wise rule: ``q`` / ``k
    (n, c, hk, dk)``, ``v (n, c, h, dv)``, ``g (n, c, h, dk)`` and
    ``beta (n, c, h)`` float32.  The same six results with the heads as
    ``(h, 1)`` (no two value heads share a decayed key), ``e^{G_C}`` a
    channel: ``(n, h, 1, dk)``."""
    h, c = v.shape[2], k.shape[1]
    dot = functools.partial(_dot, dtype=dtype)
    by_head = lambda t: jnp.moveaxis(t, 1, 2)          # (n, h, c, ...)
    to_values = lambda t: by_head(jnp.repeat(
        t, h // t.shape[2], axis=2)).astype(jnp.float32)
    q, k = to_values(q), to_values(k)
    run = by_head(jnp.cumsum(g, axis=1))               # (n, h, c, dk)
    beta, v = by_head(beta)[..., None], by_head(v)
    decays = _channel_decays(run, math.gcd(c, DECAY_BLOCK))
    below = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    inverse = _inverse_unit_lower(
        beta * _decayed_products(k, k, decays, dtype) * below)
    into = jnp.exp(run)                                # e^G <= 1
    to_end = jnp.exp(run[:, :, -1:] - run)
    written = dot("nhij,nhjd->nhid", inverse, beta * v)
    read = dot("nhij,nhjd->nhid", inverse, beta * into * k)
    cast = lambda t: t.astype(dtype)[:, :, None]
    return (cast(written), cast(read), cast(into * q), cast(to_end * k),
            into[:, :, None, -1],
            cast(_decayed_products(q, k, decays, dtype)))


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _carry_on(state, chunk_of, dtype):
    """One chunk of the recurrence: ``state (b, h, dk, dv)`` float32 in,
    the state after the chunk out, with the chunk's ``V'`` and what the
    entering state adds to its result.  ``through``, the chunk's whole
    decay, is ``(b, h)`` or, a key channel its own, ``(b, h, dk)``."""
    written, read, q_in, k_out, through = chunk_of
    dot = functools.partial(_dot, dtype=dtype)
    new = written.astype(jnp.float32) - dot("bhck,bhkv->bhcv", read, state)
    from_state = dot("bhck,bhkv->bhcv", q_in, state)
    state = through.reshape(*state.shape[:2], -1, 1) * state \
        + dot("bhck,bhcv->bhkv", k_out, new)
    return state, (new.astype(dtype), from_state.astype(dtype))


def _use_kernels(k, v, chunk, dtype, interpret, channels=False) -> bool:
    """Whether :func:`gated_delta_scan` runs the Pallas kernels, the
    scalar rule's or (``channels``) the channel-wise rule's: the sizes
    tile (:func:`gated_delta_kernels.tiles`, :func:`kda_kernels.tiles`)
    and either the kernels are asked for interpreted, or this is a TPU
    and ``k``, ``v`` and the products' operands are bfloat16."""
    (hk, dk), (h, dv) = k.shape[2:], v.shape[2:]
    family = kda_kernels if channels else gated_delta_kernels
    if not family.tiles(chunk, h, hk, dk, dv):
        return False
    if interpret is not None:
        return True
    return jax.default_backend() == "tpu" and dtype == jnp.bfloat16 \
        and k.dtype == v.dtype == jnp.bfloat16


def runs_kernels(chunk: int, heads: int, key_heads: int, dk: int, dv: int,
                 dtype, channels: bool = False) -> bool:
    """Whether :func:`gated_delta_scan` left to itself (``interpret``
    ``None``) runs its kernels here on ``dtype`` operands of these
    sizes, under the scalar rule or (``channels``) the channel-wise
    one: :func:`_use_kernels`' answer, from sizes alone."""
    of = lambda h, d: jax.ShapeDtypeStruct((1, chunk, h, d), dtype)
    return _use_kernels(of(key_heads, dk), of(heads, dv), chunk, dtype,
                        None, channels)


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
@jax.named_scope(GDN_SCAN_SCOPE)
def gated_delta_scan(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.bfloat16, interpret: Optional[bool] = None):
    """The recurrence of the module docstring in its chunked form.

    ``q``, ``k (b, s, key_heads, dk)``, normalised as the layer wants
    them (the scan applies no scale); ``v (b, s, heads, dv)``, value
    head ``i`` read and written through key head ``i // (heads /
    key_heads)``; ``g``, non-positive, ``(b, s, heads)`` (one decay a
    head) or ``(b, s, heads, dk)`` (one a key channel: the module
    docstring's second rule), and ``beta (b, s, heads)``.  Returns ``o
    (b, s, heads, dv)`` in ``v``'s dtype; ``dtype`` is that of the
    products' operands.  A length that is no multiple of ``chunk`` is
    padded with ``g = 0, beta = 0`` rows of zero keys, which leave every
    state as it was.  ``interpret=True`` runs the kernels interpreted
    wherever the sizes tile (the unit tests do)."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    if h % hk:
        raise ValueError(f"{h} value heads over {hk} key heads")
    if g.shape not in ((b, s, h), (b, s, h, dk)):
        raise ValueError(f"g is (b, s, heads) or (b, s, heads, dk): got "
                         f"{g.shape} for {h} heads of {dk}")
    scan, whole = _scan_xla, chunk
    if _use_kernels(k, v, chunk, dtype, interpret, g.ndim == 4):
        scan = functools.partial(
            _scan_kernels if g.ndim == 3 else _scan_channel_kernels,
            interpret=bool(interpret))
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


def _scan_channel_kernels(q, k, v, g, beta, chunk, dtype, interpret):
    """The channel-wise rule's kernel path over whole grid points
    (:func:`kda_kernels.kda_chunks`: the running sums are made in the
    tile)."""
    flat = lambda t: t.reshape(*t.shape[:2], -1)
    o = kda_kernels.kda_chunks(flat(q), flat(k), flat(v), flat(g), beta,
                               chunk, dtype, interpret)
    return o.reshape(v.shape)


def _scan_xla(q, k, v, g, beta, chunk, dtype):
    """The XLA form over whole chunks."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    c = s // chunk
    # chunks of all sequences on one axis: outside the recurrence they
    # differ in nothing
    cut = lambda t: t.reshape(b * c, chunk, *t.shape[2:])
    without_state, passes = (_without_state, CHUNKS_PER_PASS) \
        if g.ndim == 3 else (_without_state_channels,
                             CHUNKS_PER_PASS_CHANNELS)
    passes = math.gcd(b * c, passes)
    parts = lax.map(
        lambda args: without_state(*args, dtype),
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


def _census_parts(chunks, heads, key_heads, chunk, dk, dv) -> dict:
    """The forward pass's matmul FLOPs by part
    (:func:`gated_delta_census`)."""
    per_head = float(chunks * heads)
    return {
        "kk": 2.0 * chunks * key_heads * chunk * chunk * dk,
        "qk": 2.0 * chunks * key_heads * chunk * chunk * dk,
        "solve": per_head * chunk * chunk * (dk + dv),
        "read": 2.0 * per_head * chunk * dk * dv,
        "from_state": 2.0 * per_head * chunk * dk * dv,
        "inside": 2.0 * per_head * chunk * chunk * dv,
        "state": 2.0 * per_head * chunk * dk * dv,
    }


def gated_delta_census(s: int, chunk: int, heads: int, dk: int, dv: int,
                       key_heads=None, itemsize: int = 2,
                       channel_decay: bool = False) -> dict:
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
    those over ``bytes_forward``.

    ``channel_decay``: the rule with a decay a key channel.  ``kk`` and
    ``qk`` are then a value head's (no two heads share a decayed key),
    ``g`` is ``dk`` float32 numbers a head and position in
    ``bytes_forward`` (as many bytes as ``q``, ``k``, ``v`` and ``o``
    together at 32 heads of 128 in bfloat16), ``kernels`` is the account
    of :mod:`.kda_kernels`' launches (``None`` where :func:`kda_kernels.
    tiles` refuses the sizes), and ``exponentials`` counts what the XLA
    form's blocks evaluate in a forward pass, a head and chunk: ``chunk x
    DECAY_BLOCK x dk`` inside the blocks and ``chunk x (chunk /
    DECAY_BLOCK + 3) x dk`` for the factors between them, ``e^G`` and
    ``e^{G_C - G}``."""
    key_heads = key_heads or heads
    chunks = -(-s // chunk)
    parts = _census_parts(chunks, heads,
                          heads if channel_decay else key_heads, chunk, dk,
                          dv)
    forward = sum(parts.values())
    least = float(s) * ((2 * key_heads * dk + 2 * heads * dv) * itemsize
                        + 4 * heads * ((dk if channel_decay else 1) + 1))
    kernels = None
    family = kda_kernels if channel_decay else gated_delta_kernels
    if family.tiles(chunk, heads, key_heads, dk, dv):
        per_point = chunk * gated_delta_kernels.CHUNKS_PER_POINT
        kernels = gated_delta_kernels.launch_account(
            -(-s // per_point) * per_point, chunk, heads, key_heads,
            itemsize, plan=family.launch_plan)
        for launch in kernels.values():
            launch["hbm_over_least"] = launch["hbm_bytes"] / least
    census = {
        "chunks": chunks, "padded": chunks * chunk, "flops": parts,
        "flops_forward": forward,
        "flops_backward": 2.0 * forward + parts["kk"] + parts["qk"],
        "bytes_forward": least,
        "kernels": kernels,
    }
    if channel_decay:
        block = math.gcd(chunk, DECAY_BLOCK)
        census["exponentials"] = float(chunks * heads) * chunk * dk * (
            block + chunk // block + 3)
    return census
