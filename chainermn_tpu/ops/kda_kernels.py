"""Pallas TPU kernels of the chunked delta rule with a decay a key
channel (Kimi Delta Attention): a pass over the chunks of a sequence in
the mixer's own token-major layout, forward and backward, beside the
scalar rule's (:mod:`.gated_delta_kernels`, whose in-tile solve, row
tiles and launch they share; :mod:`chainermn_tpu.ops.gated_delta` has
the algorithm and chooses between these and its XLA form).

The grid is (sequence, head, grid point of :data:`CHUNKS_PER_POINT`
chunks), the chunks innermost and sequential; a key head serves one
value head.  A grid point reads the head's ``(chunk, 128)`` tiles of
``q``, ``k``, ``v`` and of the float32 log decays ``g`` as columns ``j *
128 ..`` of the ``(b, s, h * 128)`` arrays, as the in-projection, the
convolution and the mixer's ``decay`` left them, and ``beta`` as rows of
one ``(8, chunk)`` tile; it writes the same tile of ``o``.  The head's
state stays in VMEM from chunk to chunk, transposed, ``(dv, dk)``
float32: a key channel's decay is then a factor along the lanes.

**The decays never reach HBM, and no exponent is above 0.**  The running
sum ``G`` of a chunk's ``g`` is a product with the lower triangle of
ones.  ``A_ij = beta_i sum_c k_ic k_jc e^{G_ic - G_jc}`` and ``P_ij =
sum_c q_ic k_jc e^{G_ic - G_jc}`` are gathered level by level
(:data:`HALVES`): a level cuts every block of ``2 half`` positions in
its middle, ``m`` the last position of the lower half; for ``i`` above
the cut and ``j`` at or below it ``G_i - G_j = (G_i - G_m) + (G_m -
G_j)``, both non-positive, so one tile ``E = e^{-|G - G_m|}`` decays the
rows above towards the cut and the rows below from it, and one product
``[K E; Q E] (K E)^T`` masked to the level's pairs gives the level's
part of both.  The six levels of a chunk of 64 leave the diagonal (no
decay).  Cuts at least
:data:`BLOCK` positions apart take ``dtype`` operands, as the XLA form's
products between its blocks do; inside a block, where that form sums
pair by pair in float32, the operands keep 16 bits of mantissa
(:func:`_dot_as`).

``T = (I + A)^-1`` is made in the tile, exactly, by the scalar rule's
:func:`gated_delta_kernels._inverse_unit_lower`, the chunks of a grid
point side by side.

The backward walks the chunks in reverse with the cotangent of the state
leaving a chunk in VMEM.  Residuals of the forward: the state entering
each chunk and each chunk's ``T``; decays, ``U``, ``W`` and ``V'`` are
computed again per tile.  The cotangent of ``G`` needs no ``(i, j, c)``
tensor: a level's product gives ``dG_i += (K E)_i d(K E)_i`` for the
rows above its cut and ``dG_j -= (K E)_j d(K E)_j`` for those below,
sums of the same products, so what an element adds at ``i`` it takes
away at ``j``; the cut's own position cancels.

Rounding points are the XLA form's: products take ``dtype`` operands and
sum in float32; ``U``, ``W``, ``V'``, ``Q e^G``, ``K e^{G_C - G}`` and
``P`` are rounded to ``dtype``; the decays, ``T``, the states and their
cotangents are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gated_delta_kernels as scalar_rule
from .gated_delta_kernels import (
    CHUNK,
    CHUNKS_PER_POINT,
    HEAD_DIM,
    ROWS,
    _columns,
    _dot32,
    _inverse_unit_lower,
    _masks,
)
from .ssd_kernels import _NT, _TN, _dot

#: half the positions of a level's blocks, the narrowest cut first
HALVES = (1, 2, 4, 8, 16, 32)
#: cuts this many positions apart or more take ``dtype`` operands
#: (``gated_delta.DECAY_BLOCK``: the XLA form's blocks)
BLOCK = 16
_F32 = jnp.float32


def tiles(chunk: int, heads: int, key_heads: int, dk: int, dv: int) -> bool:
    """Whether the kernels can tile these sizes: keys and values
    :data:`HEAD_DIM` wide, a chunk of :data:`CHUNK`, a key head a value
    head."""
    return dk == dv == HEAD_DIM and chunk == CHUNK and key_heads == heads > 0


def _cut_sums(run, half):
    """``G`` at the cut of each row's block of ``2 half`` rows: row ``(i
    // 2 half) 2 half + half - 1`` of ``run (c, dk)`` in every row of the
    block.  Whole registers of eight rows take a row spread over them;
    inside a register the rows are rolled into place."""
    c = run.shape[0]
    if 2 * half >= 8:
        return jnp.concatenate(
            [jnp.broadcast_to(run[at + half - 1:at + half],
                              (2 * half, run.shape[1]))
             for at in range(0, c, 2 * half)], axis=0)
    # rows from the cut: -(half - 1) .. half
    off = lax.broadcasted_iota(jnp.int32, run.shape, 0) % (2 * half) \
        - (half - 1)
    cut = run
    for shift in range(-(half - 1), half + 1):
        if shift:
            cut = jnp.where(off == shift, pltpu.roll(run, shift % c, 0), cut)
    return cut


def _apart(c):
    """``i xor j`` below the diagonal of a ``(c, c)`` tile, 0 elsewhere:
    ``half <= i xor j < 2 half`` for the pairs a level gathers, ``i``
    and ``j`` in one block of ``2 half`` positions, ``i`` in its upper
    half and ``j`` in its lower."""
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return jnp.where(i > j, i ^ j, 0)


def _decayed(run, q32, k32, half, dtype):
    """A level's operands: ``E = e^{-|G - G_m|}`` (above the cut ``G_i -
    G_m``, at or below it ``G_m - G_j``: never an exponent above 0), ``K
    E`` and ``Q E``, in ``dtype`` between blocks and float32 inside
    one."""
    above = lax.broadcasted_iota(jnp.int32, run.shape, 0) & half != 0
    cut = _cut_sums(run, half)
    e = jnp.exp(jnp.where(above, run - cut, cut - run))
    operand = dtype if half >= BLOCK else _F32
    return e, (k32 * e).astype(operand), (q32 * e).astype(operand)


def _terms(x, n):
    """Float32 ``x`` as ``n`` bfloat16 terms, the largest first: two hold
    16 bits of its mantissa, three all of it."""
    terms = []
    for _ in range(n):
        terms.append(x.astype(jnp.bfloat16))
        x = x - terms[-1].astype(_F32)
    return terms


def _dot_as(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product that sums in float32.  ``dtype`` operands as they are;
    float32 operands as two bfloat16 terms each and the three products
    of them that matter (a product is off by at most 2^-16 of itself,
    where a bfloat16 operand is off by 2^-9: half the passes of the
    highest precision, and on the chip the same readings, ``PERF.md``
    section 6, PR 44)."""
    dot = lambda x, y: lax.dot_general(x, y, dims,
                                       preferred_element_type=_F32)
    if a.dtype != _F32:
        return dot(a, b)
    (a_high, a_low), (b_high, b_low) = _terms(a, 2), _terms(b, 2)
    return dot(a_high, b_high) + (dot(a_low, b_high) + dot(a_high, b_low))


def _sum_rows(ones, x, dims=(((1,), (0,)), ((), ()))):
    """``ones`` (zeros and ones, bfloat16) against float32 ``x``, exactly
    but for the float32 sums: ``x`` as three bfloat16 terms."""
    return sum(lax.dot_general(ones, term, dims,
                               preferred_element_type=_F32)
               for term in reversed(_terms(x, 3)))


def _pairs(run, q, k, dtype):
    """``sum_c k_ic k_jc e^{G_ic - G_jc}`` below the diagonal and
    ``sum_c q_ic k_jc e^{G_ic - G_jc}`` at and below it, ``(c, c)``
    float32, from ``run (c, dk)`` and the chunk's ``q`` and ``k``; and
    each level's :func:`_decayed` operands, the narrowest cut first."""
    c = run.shape[0]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    apart = _apart(c)
    kk = jnp.zeros((c, c), _F32)
    qk = jnp.where(_masks(c)[2],
                   _dot(q.astype(dtype), k.astype(dtype), _NT), 0.0)
    levels = []
    # a wider cut's pairs over the narrower ones': the widest that parts
    # a pair is its level
    for half in HALVES:
        e, ke, qe = _decayed(run, q32, k32, half, dtype)
        both = _dot_as(jnp.concatenate([ke, qe], axis=0), ke, _NT)
        kk = jnp.where(apart >= half, both[:c], kk)
        qk = jnp.where(apart >= half, both[c:], qk)
        levels.append((half, e, ke, qe))
    return kk, qk, levels


def _chunk_terms(q, k, v, run, bc, inverse, pairs, dtype):
    """What a chunk computes with ``T`` before it meets the state: the
    decays towards the chunk's ends, ``U`` and ``W`` from one product
    with ``[beta V | beta e^G K]``, ``Q e^G`` and ``K e^{G_C - G}``;
    ``pairs``: :func:`_pairs`' of the chunk."""
    c = CHUNK
    kk, qk, _ = pairs
    q32, k32, v32 = (t.astype(_F32) for t in (q, k, v))
    end = run[c - 1:, :]
    t = dict(q32=q32, k32=k32, v32=v32, kk=kk, qk=qk,
             qk_d=qk.astype(dtype), inverse=inverse,
             inverse_d=inverse.astype(dtype),
             into=jnp.exp(run),  # e^G <= 1
             to_end=jnp.exp(end - run), through=jnp.exp(end))
    t["bvk"] = jnp.concatenate(
        [(bc * v32).astype(dtype), ((bc * t["into"]) * k32).astype(dtype)],
        axis=1)
    both = _dot(t["inverse_d"], t["bvk"]).astype(dtype)
    t["written"], t["read"] = both[:, :HEAD_DIM], both[:, HEAD_DIM:]
    t["q_in"] = (t["into"] * q32).astype(dtype)
    t["k_out"] = (t["to_end"] * k32).astype(dtype)
    return t


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, *rest,
                    dtype, keep):
    state = rest[-1]  # (dv, dk)
    c = CHUNK
    n = q_ref.shape[1] // c

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    betas = _columns(rows_ref[0, 0, 0], n)
    sums = _masks(c)[0].astype(jnp.bfloat16)
    chunks = []
    for p in range(n):
        rows = slice(p * c, (p + 1) * c)
        run = _sum_rows(sums, g_ref[0, rows])
        chunks.append((run, _pairs(run, q_ref[0, rows], k_ref[0, rows],
                                   dtype)))
    inverses = _inverse_unit_lower(
        [betas[p] * pairs[0] for p, (_, pairs) in enumerate(chunks)])
    for p, ((run, pairs), inverse) in enumerate(zip(chunks, inverses)):
        rows = slice(p * c, (p + 1) * c)
        t = _chunk_terms(q_ref[0, rows], k_ref[0, rows], v_ref[0, rows],
                         run, betas[p], inverse, pairs, dtype)
        entering = state[...]
        if keep:
            rest[0][0, p, 0] = entering
            rest[1][0, p, 0] = inverse
        # W S and (Q e^G) S in one product
        of_state = _dot(jnp.concatenate([t["read"], t["q_in"]], axis=0),
                        entering.astype(dtype), _NT)
        new = (t["written"].astype(_F32) - of_state[:c]).astype(dtype)
        from_state = of_state[c:].astype(dtype)
        o_ref[0, rows] = (_dot(t["qk_d"], new)
                          + from_state.astype(_F32)).astype(o_ref.dtype)
        state[...] = t["through"] * entering + _dot(new, t["k_out"], _TN)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, do_ref, rows_ref, sin_ref,
                     inv_ref, dq_ref, dk_ref, dv_ref, dg_ref, drows_ref,
                     dstate, *, dtype):
    c = CHUNK
    n = q_ref.shape[1] // c

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    betas = _columns(rows_ref[0, 0, 0], n)
    live, below, diagonal = _masks(c)
    sums, apart = live.astype(jnp.bfloat16), _apart(c)
    last_row = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    sublane = lax.broadcasted_iota(jnp.int32, (ROWS, c), 0)
    # a column (c, 1) as a row (1, c), exactly
    as_row = lambda col: jnp.sum(jnp.where(diagonal, col, 0.0), axis=0,
                                 keepdims=True)
    drows = jnp.zeros((ROWS, c), _F32)
    for p in reversed(range(n)):
        rows = slice(p * c, (p + 1) * c)
        q, k, bc = q_ref[0, rows], k_ref[0, rows], betas[p]
        run = _sum_rows(sums, g_ref[0, rows])
        pairs = _pairs(run, q, k, dtype)
        t = _chunk_terms(q, k, v_ref[0, rows], run, bc, inv_ref[0, p, 0],
                         pairs, dtype)
        q32, k32, into, to_end = t["q32"], t["k32"], t["into"], t["to_end"]
        entering, dleaving = sin_ref[0, p, 0], dstate[...]
        entering_d, dleaving_d = entering.astype(dtype), \
            dleaving.astype(dtype)
        do_d = do_ref[0, rows].astype(dtype)
        new = (t["written"].astype(_F32)
               - _dot(t["read"], entering_d, _NT)).astype(dtype)
        # the chunk's result and its end state, back to V' and the state
        dqk = jnp.where(live, _dot(do_d, new, _NT), 0.0)
        dnew_d = (_dot(t["qk_d"], do_d, _TN)
                  + _dot(t["k_out"], dleaving_d, _NT)).astype(dtype)
        dk_out = _dot(new, dleaving_d)
        # d(Q e^G) and -dW against the state, in one product
        do_dnew = jnp.concatenate([do_d, dnew_d], axis=0)
        to_state = _dot(do_dnew, entering_d)
        dq_in, dread_d = to_state[:c], (-to_state[c:]).astype(dtype)
        dstate[...] = t["through"] * dleaving + _dot(
            do_dnew, jnp.concatenate([t["q_in"], -t["read"]], axis=0), _TN)
        # U = T (beta V) and W = T (beta e^G K), back to T and through
        # the inverse to A: dA = -T^T dT T^T
        duw = jnp.concatenate([dnew_d, dread_d], axis=1)
        dinverse = _dot(duw, t["bvk"], _NT)
        dbvk = _dot(t["inverse_d"], duw, _TN)
        dbv, dbk = dbvk[:, :HEAD_DIM], dbvk[:, HEAD_DIM:]
        da = jnp.where(below, -_dot32(
            t["inverse"], _dot32(dinverse, t["inverse"], _NT), _TN), 0.0)
        dkk = bc * da
        # the diagonal of P carries no decay
        on_diagonal = jnp.sum(jnp.where(diagonal, dqk, 0.0), axis=1,
                              keepdims=True)
        dq = on_diagonal * k32
        dk = on_diagonal * q32
        # the cotangent of the running sum: G_i - G_j in every exponent,
        # so what a product adds at i it takes away at j
        drun = jnp.zeros(run.shape, _F32)
        for half, e, ke, qe in pairs[2]:
            level = (apart >= half) & (apart < 2 * half)
            dpairs = jnp.concatenate(
                [jnp.where(level, dkk, 0.0), jnp.where(level, dqk, 0.0)],
                axis=0).astype(ke.dtype)
            # to the rows above the cut ([K E; Q E]) and to those below
            above = _dot_as(dpairs, ke)
            under = _dot_as(dpairs, jnp.concatenate([ke, qe], axis=0), _TN)
            drun += ke.astype(_F32) * (above[:c] - under) \
                + qe.astype(_F32) * above[c:]
            dk += e * (above[:c] + under)
            dq += e * above[c:]
        dbk_k = dbk * k32
        to_end_lanes = to_end * (dk_out * k32)
        ends = jnp.sum(to_end_lanes, axis=0, keepdims=True) \
            + t["through"] * jnp.sum(dleaving * entering, axis=0,
                                     keepdims=True)
        drun += into * (dq_in * q32 + bc * dbk_k) - to_end_lanes \
            + jnp.where(last_row, ends, 0.0)
        dg_ref[0, rows] = _sum_rows(sums, drun, _TN)
        drows = jnp.where(
            sublane == p,
            as_row(jnp.sum(da * t["kk"], axis=1, keepdims=True)
                   + jnp.sum(dbv * t["v32"] + into * dbk_k, axis=1,
                             keepdims=True)), drows)
        dv_ref[0, rows] = (bc * dbv).astype(dv_ref.dtype)
        dq_ref[0, rows] = (dq + into * dq_in).astype(dq_ref.dtype)
        dk_ref[0, rows] = (dk + to_end * dk_out
                           + (bc * into) * dbk).astype(dk_ref.dtype)
    drows_ref[0, 0, 0] = drows


def _rows(beta, chunk):
    """``beta (b, s, h)`` float32 as the kernels read it: a head's and
    grid point's ``(ROWS, chunk)`` tile, positions on the lanes, row
    ``p`` the point's chunk ``p``: ``(b, h, s / span, ROWS, chunk)``."""
    b, s, h = beta.shape
    n = CHUNKS_PER_POINT
    tile = jnp.transpose(beta.reshape(b, s // (n * chunk), n, chunk, h),
                         (0, 4, 1, 2, 3))
    return jnp.pad(tile, ((0, 0),) * 3 + ((0, ROWS - n), (0, 0)))


def _from_rows(t):
    """The gradient of :func:`_rows`' tile back to ``beta (b, s, h)``."""
    b, h, points, _, chunk = t.shape
    n = CHUNKS_PER_POINT
    return jnp.transpose(t[:, :, :, :n], (0, 2, 3, 4, 1)).reshape(
        b, points * n * chunk, h)


def launch_plan(b, s, chunk, heads, key_heads, backward: bool):
    """:func:`gated_delta_kernels.launch_plan` for these kernels: every
    operand a head's (``key_heads`` is ``heads``), ``g`` and its
    gradient float32 tiles like ``q``'s, ``beta`` a row a chunk
    (``gated_delta_kernels.launch_account(..., plan=launch_plan)`` is
    their static account)."""
    del key_heads
    c, d = s // chunk, HEAD_DIM
    n, span = CHUNKS_PER_POINT, CHUNKS_PER_POINT * chunk
    points = c // n
    at = (lambda ci: points - 1 - ci) if backward else (lambda ci: ci)
    a_tile = lambda bi, ji, ci: (bi, at(ci), ji)
    head = ((b, s, heads * d), (1, span, d), a_tile, None)
    decay = (*head[:3], 4)
    rows = ((b, heads, points, ROWS, chunk), (1, 1, 1, ROWS, chunk),
            lambda bi, ji, ci: (bi, ji, at(ci), 0, 0), 4)
    a_chunk = lambda bi, ji, ci: (bi, at(ci), ji, 0, 0)
    states = ((b, c, heads, d, d), (1, n, 1, d, d), a_chunk, 4)
    inverses = ((b, c, heads, chunk, chunk), (1, n, 1, chunk, chunk),
                a_chunk, 4)
    if not backward:
        ins = {"q": head, "k": head, "v": head, "g": decay, "rows": rows}
        outs = {"o": head, "states": states, "inverses": inverses}
    else:
        ins = {"q": head, "k": head, "v": head, "g": decay, "do": head,
               "rows": rows, "states": states, "inverses": inverses}
        outs = {"dq": head, "dk": head, "dv": head, "dg": decay,
                "drows": rows}
    return (b, heads, points), ins, outs, [(d, d)]


def _dims(v, chunk):
    b, s, width = v.shape
    return (b, s, chunk, width // HEAD_DIM, width // HEAD_DIM)


def _forward(q, k, v, g, rows, chunk, dtype, interpret, keep):
    return scalar_rule._launch(
        functools.partial(_forward_kernel, dtype=dtype, keep=keep),
        "_kda_forward", (q, k, v, g, rows),
        {"o": v.dtype,
         **({"states": _F32, "inverses": _F32} if keep else {})},
        _dims(v, chunk), False, interpret, plan=launch_plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def kda_chunks(q, k, v, g, beta, chunk, dtype, interpret):
    """The channel-wise delta rule over whole grid points: ``q``, ``k``,
    ``v (b, s, h * 128)``, ``g (b, s, h * 128)`` float32 (the log decays
    themselves: the running sums are made in the tile) and ``beta (b, s,
    h)`` float32; ``s`` a multiple of ``CHUNKS_PER_POINT * chunk``.
    Returns ``o`` like ``v``."""
    o, = _forward(q, k, v, g, _rows(beta, chunk), chunk, dtype, interpret,
                  keep=False)
    return o


def _kda_fwd(q, k, v, g, beta, chunk, dtype, interpret):
    rows = _rows(beta, chunk)
    o, states, inverses = scalar_rule.named(*_forward(
        q, k, v, g, rows, chunk, dtype, interpret, keep=True))
    return o, (q, k, v, g, rows, states, inverses)


def _kda_bwd(chunk, dtype, interpret, residuals, do):
    q, k, v, g, rows, states, inverses = residuals
    dq, dk, dv, dg, drows = scalar_rule._launch(
        functools.partial(_backward_kernel, dtype=dtype), "_kda_backward",
        (q, k, v, g, do, rows, states, inverses),
        {"dq": q.dtype, "dk": k.dtype, "dv": v.dtype, "dg": _F32,
         "drows": _F32},
        _dims(v, chunk), True, interpret, plan=launch_plan)
    return dq, dk, dv, dg, _from_rows(drows)


kda_chunks.defvjp(_kda_fwd, _kda_bwd)
