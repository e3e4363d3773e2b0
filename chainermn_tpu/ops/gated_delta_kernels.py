"""Pallas TPU kernels of the chunked gated delta rule: a pass over the
chunks of a sequence in the mixer's own token-major layout, forward and
backward (:mod:`chainermn_tpu.ops.gated_delta` has the algorithm and
chooses between these and its XLA form).

The grid is (sequence, key head, chunk), the chunk innermost and
sequential.  A grid point reads the key head's ``(chunk, dk)`` tiles of
``q`` and ``k`` and the ``(chunk, r dv)`` tile of ``v`` of the ``r``
value heads it serves, as the in-projection and the convolution left
them, and the heads' running sums of ``g`` and ``beta`` as rows of one
``(8, chunk)`` tile a chunk (a head's column is its row against the
identity on the MXU, exactly: a two-wide minor dimension would be
padded 64-fold in HBM); it writes the same tile of ``o``.  The ``r``
states ``(dk, dv)`` float32 stay in VMEM from chunk to chunk.  ``K
K^T``, the masked decay ``exp(G_i - G_j)``, ``A`` and ``T = (I + A)^-1``
are ``(chunk, chunk)`` tiles made on the chip; ``T`` is made exactly, in
float32: forward substitution in the diagonal 16 x 16 blocks on the
vector unit, then merges of block pairs by float32 products
(:func:`_inverse_unit_lower`).

The backward walks the chunks in reverse with the cotangent of the
state leaving a chunk in VMEM.  The state entering each chunk is a
residual of the forward (``(chunks, heads, dk, dv)`` float32 a
sequence); the decays, ``T``, ``U``, ``W`` and ``V'`` are computed again
per tile.  The gradient of a head's running sum comes in both forms
from one tile of products, so that what an element adds at ``i`` it
takes away at ``j`` to the last bit.

Rounding points are the XLA form's: products take ``dtype`` operands
and sum in float32; ``U``, ``W``, ``V'``, ``Q e^G``, ``K e^{G_C - G}``
and the decayed ``Q K^T`` are rounded to ``dtype``; the decays, ``T``,
the states and their cotangents are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import vary_alike
from .pallas_attention import _out_struct
from .ssd_kernels import _NT, _TN, _dot

#: the head widths and the chunk the kernels tile (one lane tile a head;
#: a ``(chunk, chunk)`` float32 tile is eight registers)
HEAD_DIM = 128
CHUNK = 64
#: the most value heads a key head may serve (their states share VMEM)
MAX_SERVED = 4
#: rows of the tile that carries a key head's running sums and ``beta``
ROWS = 2 * MAX_SERVED
#: chunks a grid point works on, one after the other (more of them give
#: the scheduler more independent work and fewer grid steps: a forward
#: launch of the cell with what XLA does around it read 9.5 / 9.1 / 8.8
#: ms at 1 / 2 / 4; ``PERF.md`` section 6, PR 42)
CHUNKS_PER_POINT = 4
#: the diagonal blocks of ``I + A`` solved by substitution before the
#: merges (16 read 8.1 ms a forward launch at two chunks a grid point, 8
#: and 32 read 8.7 and 8.2, the whole 64 by substitution 9.1; the same
#: place)
SOLVE_BLOCK = 16
LANES = 128
_F32 = jnp.float32
#: the name (``jax.ad_checkpoint.checkpoint_name``) of a differentiated
#: forward launch's three results, this rule's and the channel-wise
#: rule's (a model has one or the other): ``o``, the state entering
#: every chunk and every chunk's ``T``, one name for all three, since a
#: launch with one result not kept still runs.  Under a
#: ``jax.checkpoint`` whose policy saves it
#: (``models.transformer.REMAT_NAMES``) the recomputation holds no
#: forward launch: the backward kernel reads the kept states and ``T``,
#: the block's backward the kept ``o``.  Under any other policy, or
#: none, the name is an identity.
SCAN_OUT = "scan_out"


def tiles(chunk: int, heads: int, key_heads: int, dk: int, dv: int) -> bool:
    """Whether the kernels can tile these sizes: keys and values
    :data:`HEAD_DIM` wide, a chunk of :data:`CHUNK`, whole groups of at
    most :data:`MAX_SERVED` value heads a key head."""
    return (dk == dv == HEAD_DIM and chunk == CHUNK and key_heads > 0
            and heads % key_heads == 0
            and heads // key_heads <= MAX_SERVED)


def _dot32(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at float32 accuracy."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _over_lanes(x):
    """``(1, 1) -> (1, 128)``: Mosaic broadcasts along one of the two
    minor dimensions at a time (the sum keeps the two apart)."""
    return x + jnp.zeros((1, LANES), x.dtype)


def _masks(c):
    """``i >= j``, ``i > j`` and ``i == j`` of a ``(c, c)`` tile."""
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return i >= j, i > j, i == j


def _eye(c):
    return _masks(c)[2].astype(_F32)


def _inverse_unit_lower(matrices):
    """``(I + a)^-1`` for each ``a (c, c)`` float32, strictly lower
    triangular, exactly (no series is cut short), in two stages.

    The diagonal blocks of :data:`SOLVE_BLOCK` by forward substitution a
    column at a time on the vector unit: once row ``j`` of a block's
    inverse is final, every row below it in the block takes ``a[i, j]``
    times it away (eight rows a register; a step waits for the one
    before it, so all blocks of all matrices go through their steps
    side by side).  Then pairs of blocks are merged, ``T21 = -T22 A21
    T11``, until one is left: with ``D`` the inverse so far and ``N``
    the blocks of ``a`` under its diagonal blocks, ``D - (D N) D``, two
    float32 products a level for all pairs of a matrix at once."""
    c = matrices[0].shape[0]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cut = lambda t: [t[at:at + 8] for at in range(0, c, 8)]
    solving = [(cut(_eye(c)), cut(a)) for a in matrices]
    size = min(SOLVE_BLOCK, c)
    for step in range(size - 1):
        for rows, cols in solving:
            for first in range(0, c, size):
                at = first + step
                row = rows[at // 8][at % 8:at % 8 + 1, :]
                for t in range(at // 8, (first + size) // 8):
                    rows[t] = rows[t] - cols[t][:, at:at + 1] * row
    inverses = [jnp.concatenate(rows, axis=0) for rows, _ in solving]
    while size < c:
        under = ((i // size) % 2 == 1) & (j // size == i // size - 1)
        inverses = [d - _dot32(_dot32(d, jnp.where(under, a, 0.0)), d)
                    for d, a in zip(inverses, matrices)]
        size *= 2
    return inverses


def _decays(kk, qk, gc, gr):
    """A value head's decays in a chunk, from the key head's ``K K^T``
    and ``Q K^T`` and the head's running sum as a column ``gc (c, 1)``
    and a row ``gr (1, c)``."""
    live, below, _ = _masks(kk.shape[0])
    # exp of a masked difference: above the diagonal G_i - G_j is
    # positive and may overflow
    decay = jnp.exp(jnp.where(live, gc - gr, -jnp.inf))
    end = gc[gc.shape[0] - 1:, :]
    return dict(decay=decay, kkd=jnp.where(below, kk * decay, 0.0),
                decayed_qk=qk * decay, into=jnp.exp(gc),  # e^G <= 1
                to_end=jnp.exp(end - gc), through=jnp.exp(end))


def _products(t, inverse, q32, k32, v32, bc, dtype):
    """What the chunk computes with ``T`` before it meets the state,
    added to :func:`_decays`' terms: ``U`` and ``W`` from one product
    with ``[beta V | beta e^G K]``."""
    t["inverse"], t["inverse_d"] = inverse, inverse.astype(dtype)
    t["bvk"] = jnp.concatenate(
        [(bc * v32).astype(dtype), ((bc * t["into"]) * k32).astype(dtype)],
        axis=1)
    both = _dot(t["inverse_d"], t["bvk"]).astype(dtype)
    t["written"], t["read"] = both[:, :HEAD_DIM], both[:, HEAD_DIM:]
    t["q_in"] = (t["into"] * q32).astype(dtype)
    t["k_out"] = (t["to_end"] * k32).astype(dtype)
    t["decayed_qk_d"] = t["decayed_qk"].astype(dtype)
    return t


def _columns(of_heads, n):
    """The first ``n`` rows of ``of_heads (ROWS, c)`` as columns ``(c,
    1)``: the rows against the identity on the MXU, exactly."""
    cols = _dot32(_eye(of_heads.shape[1]), of_heads, _NT)
    return [cols[:, i:i + 1] for i in range(n)]


def _chunks_terms(q_ref, k_ref, v_ref, rows_ref, inverses, served, dtype):
    """What every chunk of a grid point computes before it meets the
    state: a chunk's ``q`` and ``k`` tiles, its heads' ``beta`` columns
    (a list) and under ``"heads"`` each head's :func:`_decays` and
    :func:`_products`.  ``inverses(p, hh)`` gives a kept ``T`` or
    ``None``: then all are solved here.  The heads' running sums and
    ``beta`` come as rows (:func:`_rows`) and are made columns here
    (:func:`_columns`)."""
    c = CHUNK
    terms = []
    for p in range(q_ref.shape[1] // c):
        rows = slice(p * c, (p + 1) * c)
        q, k = q_ref[0, rows], k_ref[0, rows]
        qd, kd = q.astype(dtype), k.astype(dtype)
        kk, qk = _dot(kd, kd, _NT), _dot(qd, kd, _NT)
        of_heads = rows_ref[0, 0, p]
        cols = _columns(of_heads, 2 * served)
        terms.append(dict(
            q=q, k=k, beta=cols[served:],
            heads=[_decays(kk, qk, cols[hh], of_heads[hh:hh + 1, :])
                   for hh in range(served)]))
    at = [(p, hh) for p in range(len(terms)) for hh in range(served)]
    kept = [inverses(p, hh) for p, hh in at]
    if kept[0] is None:
        kept = _inverse_unit_lower(
            [terms[p]["beta"][hh] * terms[p]["heads"][hh]["kkd"]
             for p, hh in at])
    for (p, hh), inverse in zip(at, kept):
        rows = slice(p * c, (p + 1) * c)
        lanes = slice(hh * HEAD_DIM, (hh + 1) * HEAD_DIM)
        of = terms[p]
        _products(of["heads"][hh], inverse, of["q"].astype(_F32),
                  of["k"].astype(_F32), v_ref[0, rows, lanes].astype(_F32),
                  of["beta"][hh], dtype)
    return terms


def _forward_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest, dtype,
                    keep):
    state = rest[-1]
    served, c = state.shape[0], CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    terms = _chunks_terms(q_ref, k_ref, v_ref, rows_ref,
                          lambda p, hh: None, served, dtype)
    for p, of in enumerate(terms):
        rows = slice(p * c, (p + 1) * c)
        if keep:
            rest[0][0, p, 0] = state[...]
        for hh, t in enumerate(of["heads"]):
            lanes = slice(hh * HEAD_DIM, (hh + 1) * HEAD_DIM)
            if keep:
                rest[1][0, p, 0, hh] = t["inverse"]
            entering = state[hh]
            # W S and (Q e^G) S in one product
            of_state = _dot(jnp.concatenate([t["read"], t["q_in"]], axis=0),
                            entering.astype(dtype))
            new = (t["written"].astype(_F32) - of_state[:c]).astype(dtype)
            from_state = of_state[c:].astype(dtype)
            o_ref[0, rows, lanes] = (
                _dot(t["decayed_qk_d"], new)
                + from_state.astype(_F32)).astype(o_ref.dtype)
            state[hh] = _over_lanes(t["through"]) * entering \
                + _dot(t["k_out"], new, _TN)


def _total(x):
    """The sum of a tile as ``(1, 1)``."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _backward_kernel(q_ref, k_ref, v_ref, do_ref, rows_ref, sin_ref,
                     inv_ref, dq_ref, dk_ref, dv_ref, drows_ref, dstate, *,
                     dtype):
    served, c = dstate.shape[0], CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    terms = _chunks_terms(q_ref, k_ref, v_ref, rows_ref,
                          lambda p, hh: inv_ref[0, p, 0, hh], served, dtype)
    _, below, diagonal = _masks(c)
    last_row = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    sublane = lax.broadcasted_iota(jnp.int32, (ROWS, c), 0)
    # a column (c, 1) as a row (1, c), exactly
    as_row = lambda col: jnp.sum(jnp.where(diagonal, col, 0.0), axis=0,
                                 keepdims=True)
    for p, of in reversed(list(enumerate(terms))):
        rows = slice(p * c, (p + 1) * c)
        q, k = of["q"], of["k"]
        qd, kd = q.astype(dtype), k.astype(dtype)
        q32, k32 = q.astype(_F32), k.astype(_F32)
        dkk = jnp.zeros((c, c), _F32)
        dqk = jnp.zeros((c, c), _F32)
        dq = jnp.zeros(q32.shape, _F32)
        dk = jnp.zeros(k32.shape, _F32)
        # the gradients of the heads' running sums and ``beta``, laid
        # out as ``rows_ref``'s tile
        drows = jnp.zeros((ROWS, c), _F32)
        for hh, t in enumerate(of["heads"]):
            lanes = slice(hh * HEAD_DIM, (hh + 1) * HEAD_DIM)
            v32 = v_ref[0, rows, lanes].astype(_F32)
            bc = of["beta"][hh]
            into, to_end, through = t["into"], t["to_end"], t["through"]
            entering, dleaving = sin_ref[0, p, 0, hh], dstate[hh]
            entering_d, dleaving_d = entering.astype(dtype), \
                dleaving.astype(dtype)
            do_d = do_ref[0, rows, lanes].astype(dtype)
            new = (t["written"].astype(_F32)
                   - _dot(t["read"], entering_d)).astype(dtype)
            # the chunk's result and its end state, back to V' and the
            # state
            ddecayed_qk = _dot(do_d, new, _NT)
            dnew_d = (_dot(t["decayed_qk_d"], do_d, _TN)
                      + _dot(t["k_out"], dleaving_d)).astype(dtype)
            dk_out = _dot(new, dleaving_d, _NT)
            # d(Q e^G) and -dW against the state, in one product
            do_dnew = jnp.concatenate([do_d, dnew_d], axis=0)
            to_state = _dot(do_dnew, entering_d, _NT)
            dq_in, dread_d = to_state[:c], (-to_state[c:]).astype(dtype)
            dstate[hh] = _over_lanes(through) * dleaving + _dot(
                jnp.concatenate([t["q_in"], -t["read"]], axis=0), do_dnew,
                _TN)
            # U = T (beta V) and W = T (beta e^G K), back to T and
            # through the inverse to A: dA = -T^T dT T^T
            duw = jnp.concatenate([dnew_d, dread_d], axis=1)
            dinverse = _dot(duw, t["bvk"], _NT)
            dbvk = _dot(t["inverse_d"], duw, _TN)
            dbv, dbk = dbvk[:, :HEAD_DIM], dbvk[:, HEAD_DIM:]
            da = jnp.where(below, -_dot32(
                t["inverse"], _dot32(dinverse, t["inverse"], _NT), _TN), 0.0)
            da_kkd = da * t["kkd"]
            # G_i - G_j in the exponent: what an element adds at i it
            # takes away at j, both sides sums of the same products
            taken = bc * da_kkd + ddecayed_qk * t["decayed_qk"]
            dkk += bc * da * t["decay"]
            dqk += ddecayed_qk * t["decay"]
            dbk_k = dbk * k32
            to_end_lanes = to_end * (dk_out * k32)
            ends = _total(to_end_lanes) \
                + through * _total(dleaving * entering)
            dcum = jnp.sum(taken, axis=1, keepdims=True) + jnp.sum(
                into * (dq_in * q32 + bc * dbk_k) - to_end_lanes, axis=1,
                keepdims=True)
            drows = jnp.where(
                sublane == hh,
                as_row(dcum + jnp.where(last_row, ends, 0.0))
                - jnp.sum(taken, axis=0, keepdims=True), drows)
            drows = jnp.where(
                sublane == served + hh,
                as_row(jnp.sum(da_kkd, axis=1, keepdims=True)
                       + jnp.sum(dbv * v32 + into * dbk_k, axis=1,
                                 keepdims=True)), drows)
            dv_ref[0, rows, lanes] = (bc * dbv).astype(dv_ref.dtype)
            dq += into * dq_in
            dk += to_end * dk_out + (bc * into) * dbk
        dkk_d, dqk_d = dkk.astype(dtype), dqk.astype(dtype)
        dq_ref[0, rows] = (dq + _dot(dqk_d, kd)).astype(dq_ref.dtype)
        dk_ref[0, rows] = (dk + _dot(dqk_d, qd, _TN) + _dot(dkk_d, kd)
                           + _dot(dkk_d, kd, _TN)).astype(dk_ref.dtype)
        drows_ref[0, 0, p] = drows


def _rows(cum, beta, key_heads, chunk):
    """``cum`` and ``beta (b, s, h)`` float32 as the kernels read them:
    a key head's and chunk's ``(ROWS, chunk)`` tile, positions on the
    lanes, rows ``[0, r)`` the running sums of the ``r`` value heads it
    serves and ``[r, 2 r)`` their ``beta``: ``(b, hk, s / chunk, ROWS,
    chunk)``."""
    b, s, h = cum.shape
    r = h // key_heads
    both = jnp.stack([cum, beta], axis=2).reshape(
        b, s // chunk, chunk, 2, key_heads, r)
    tile = jnp.transpose(both, (0, 4, 1, 3, 5, 2)).reshape(
        b, key_heads, s // chunk, 2 * r, chunk)
    return jnp.pad(tile, ((0, 0),) * 3 + ((0, ROWS - 2 * r), (0, 0)))


def _from_rows(t, served):
    """The gradient of :func:`_rows`' tile back to ``cum`` and ``beta
    (b, s, h)``."""
    b, hk, c, _, chunk = t.shape
    both = t[:, :, :, :2 * served].reshape(b, hk, c, 2, served, chunk)
    both = jnp.transpose(both, (3, 0, 2, 5, 1, 4)).reshape(
        2, b, c * chunk, hk * served)
    return both[0], both[1]


def launch_plan(b, s, chunk, heads, key_heads, backward: bool):
    """``(grid, inputs, outputs, scratch)`` of a launch over ``b``
    sequences of ``s`` positions: an operand is ``name: (array shape,
    block shape, index map, itemsize)``, ``None`` for the itemsize of
    ``q``, ``k`` and ``v``; ``scratch`` the float32 shapes.  The
    backward meets the chunks last to first.  What the ``pallas_call``s
    are built from and :func:`launch_account` counts."""
    c, r, d = s // chunk, heads // key_heads, HEAD_DIM
    n, span = CHUNKS_PER_POINT, CHUNKS_PER_POINT * chunk
    points = c // n
    at = (lambda ci: points - 1 - ci) if backward else (lambda ci: ci)
    a_tile = lambda bi, ji, ci: (bi, at(ci), ji)
    keys = ((b, s, key_heads * d), (1, span, d), a_tile, None)
    values = ((b, s, heads * d), (1, span, r * d), a_tile, None)
    rows = ((b, key_heads, c, ROWS, chunk), (1, 1, n, ROWS, chunk),
            lambda bi, ji, ci: (bi, ji, at(ci), 0, 0), 4)
    a_chunk = lambda bi, ji, ci: (bi, at(ci), ji, 0, 0, 0)
    states = ((b, c, key_heads, r, d, d), (1, n, 1, r, d, d), a_chunk, 4)
    inverses = ((b, c, key_heads, r, chunk, chunk),
                (1, n, 1, r, chunk, chunk), a_chunk, 4)
    if not backward:
        ins = {"q": keys, "k": keys, "v": values, "rows": rows}
        outs = {"o": values, "states": states, "inverses": inverses}
    else:
        ins = {"q": keys, "k": keys, "v": values, "do": values,
               "rows": rows, "states": states, "inverses": inverses}
        outs = {"dq": keys, "dk": keys, "dv": values, "drows": rows}
    return (b, key_heads, points), ins, outs, [(r, d, d)]


def _launch(kernel, name, operands, out_dtypes, dims, backward, interpret,
            plan=launch_plan):
    """One ``pallas_call`` over ``plan(*dims)``, with the outputs
    ``out_dtypes`` names."""
    grid, ins, outs, scratch = plan(*dims, backward)
    spec = lambda entry: pl.BlockSpec(entry[1], entry[2])
    operands = vary_alike(*operands)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(entry) for entry in ins.values()],
        out_specs=[spec(outs[k]) for k in out_dtypes],
        out_shape=[_out_struct(outs[k][0], dtype, *operands)
                   for k, dtype in out_dtypes.items()],
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*operands)


def _forward(q, k, v, cum, beta, chunk, dtype, interpret, keep):
    b, s, _ = q.shape
    heads, key_heads = cum.shape[-1], q.shape[-1] // HEAD_DIM
    rows = _rows(cum, beta, key_heads, chunk)
    out = _launch(
        functools.partial(_forward_kernel, dtype=dtype, keep=keep),
        "_gdn_forward", (q, k, v, rows),
        {"o": v.dtype,
         **({"states": _F32, "inverses": _F32} if keep else {})},
        (b, s, chunk, heads, key_heads), False, interpret)
    return out, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gated_delta_chunks(q, k, v, cum, beta, chunk, dtype, interpret):
    """The delta rule over whole chunks: ``q``, ``k (b, s, hk * 128)``,
    ``v (b, s, h * 128)``, ``cum`` (the running sum of ``g`` inside each
    chunk, inclusive) and ``beta (b, s, h)`` float32; ``s`` a multiple
    of ``chunk``.  Returns ``o`` like ``v``."""
    (o,), _ = _forward(q, k, v, cum, beta, chunk, dtype, interpret,
                       keep=False)
    return o


def named(o, states, inverses):
    """A differentiated forward launch's results under
    :data:`SCAN_OUT`: what the residuals AND the returned ``o`` must be
    made of (a name on the caller's copy keeps a tensor no backward
    rule reads)."""
    return tuple(checkpoint_name(t, SCAN_OUT)
                 for t in (o, states, inverses))


def _gdn_fwd(q, k, v, cum, beta, chunk, dtype, interpret):
    out, rows = _forward(
        q, k, v, cum, beta, chunk, dtype, interpret, keep=True)
    o, states, inverses = named(*out)
    return o, (q, k, v, states, inverses, rows)


def _gdn_bwd(chunk, dtype, interpret, residuals, do):
    q, k, v, states, inverses, rows = residuals
    b, s, _ = q.shape
    heads, key_heads = v.shape[-1] // HEAD_DIM, q.shape[-1] // HEAD_DIM
    dq, dk, dv, drows = _launch(
        functools.partial(_backward_kernel, dtype=dtype),
        "_gdn_backward",
        (q, k, v, do, rows, states, inverses),
        {"dq": q.dtype, "dk": k.dtype, "dv": v.dtype, "drows": _F32},
        (b, s, chunk, heads, key_heads), True, interpret)
    return (dq, dk, dv, *_from_rows(drows, heads // key_heads))


gated_delta_chunks.defvjp(_gdn_fwd, _gdn_bwd)


def launch_account(s: int, chunk: int, heads: int, key_heads: int,
                   itemsize: int = 2, plan=launch_plan) -> dict:
    """The static account of the two launches (``plan``'s: these
    kernels', or the channel-wise rule's) for one sequence of ``s``
    positions (a multiple of ``chunk``), ``forward`` the one that keeps
    the entering states: ``grid``, ``tiles`` a launch, ``vmem_bytes`` a
    grid point (the scratch, and every block twice, as VMEM holds it:
    the last dimension padded to 128 lanes, the one before to 8
    sublanes) and ``hbm_bytes`` read and written (every block is moved
    once a grid point)."""
    def held(block, size):
        *lead, rows, cols = block
        return size * math.prod(lead) * (-(-rows // 8) * 8) \
            * (-(-cols // LANES) * LANES)

    out = {}
    for kind, backward in (("forward", False), ("backward", True)):
        grid, ins, outs, scratch = plan(1, s, chunk, heads, key_heads,
                                        backward)
        operands = [(blk, size or itemsize)
                    for _, blk, _, size in (*ins.values(), *outs.values())]
        points = math.prod(grid)
        out[kind] = {
            "grid": grid, "tiles": points,
            "vmem_bytes": 2 * sum(held(blk, size) for blk, size in operands)
            + sum(held(shape, 4) for shape in scratch),
            "hbm_bytes": float(points * sum(
                math.prod(blk) * size for blk, size in operands)),
        }
    return out
