"""Pallas TPU kernels of the chunked state-space scan: a pass over the
chunks of a sequence in the mixer's own token-major layout, forward and
backward (:mod:`chainermn_tpu.ops.ssd_scan` has the algorithm and
chooses between these and its XLA form).

The grid is (sequence, group of :data:`GROUP` heads, chunk), the chunk
innermost and sequential.  A grid point reads a ``(chunk, GROUP *
head_dim)`` tile of ``x`` as the in-projection and the convolution left
it, the chunk's ``B`` and ``C``, and the group's ``dt`` and running
sums ``cum`` twice, heads on the lanes and heads on the sublanes, so
that a head's column and its row come without a transpose; it writes the
same tile of ``y``.  The group's states stay in VMEM from chunk to
chunk, transposed and two heads to a 128-lane tile: ``(GROUP / 2,
state, 128)`` float32.  Per head the ``(chunk, chunk)`` decay
``exp(cum_i - cum_j)`` under the causal mask is made on the spot, times
the chunk's scores ``C B^T`` and ``dt_j``, rounded to the products'
dtype and multiplied with the pair's 128 lanes of ``x`` (the other
head's half of the result is dropped by a lane select: no 64-wide lane
shift).

The backward walks the chunks in reverse with the cotangent of the
state leaving a chunk in VMEM.  It works on the transposed tiles,
``[j, i]`` where the forward has ``[i, j]``, which makes every product
of a head plain (``W^T dy``) or contracted over lanes (``x dy^T``) and
every per-head reduction of a ``(chunk, chunk)`` tile one over
sublanes; the reductions over a head's 64 lanes are products with a
0/1 matrix on the MXU, their float32 operand split into three bfloat16
terms.  The state entering each chunk is a residual of the forward
(``(chunks, heads / 2, state, 128)`` float32 a sequence); scores and
decays are computed again per tile.

Precisions are the XLA form's: products take ``dtype`` operands and sum
in float32; ``dt``, the running sums, the decays, the states and their
cotangents are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import sum_to_vma, vary_alike
from .pallas_attention import _out_struct

#: heads a grid point works on
GROUP = 8
#: the head width the kernels tile: two heads to a 128-lane tile
HEAD_DIM = 64
LANES = 128
_PAIRS = GROUP * HEAD_DIM // LANES
_F32 = jnp.float32

_NT = (((1,), (1,)), ((), ()))  # a (m, k) against b (n, k)
_TN = (((0,), (0,)), ((), ()))  # a (k, m) against b (k, n)


def tiles(chunk: int, heads: int, head_dim: int, state: int) -> bool:
    """Whether the kernels can tile these sizes: heads of
    :data:`HEAD_DIM` in groups of :data:`GROUP`, a state of 128 (one
    lane tile), a chunk of 128 or 256 (whole lane tiles of the row
    form; a ``(chunk, chunk)`` float32 tile at most 256 KiB)."""
    return (head_dim == HEAD_DIM and heads % GROUP == 0 and state == LANES
            and chunk in (128, 256))


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _of_pair(low, cols, pair):
    """``cols (rows, GROUP)``, a value a head, spread over the pair's
    lanes: head ``2 pair`` on the low 64, ``2 pair + 1`` on the high."""
    return jnp.where(low[:cols.shape[0]], cols[:, 2 * pair:2 * pair + 1],
                     cols[:, 2 * pair + 1:2 * pair + 2])


def _weigh(x32, weights, like):
    """``x`` times the chunk-state weights as the XLA form rounds them,
    the weights to ``x``'s dtype and then the product to it; and the
    rounded weights."""
    weights = weights.astype(like).astype(_F32)
    return (x32 * weights).astype(like), weights


def _chunk_terms(cumc):
    """From a group's ``cum`` columns ``(chunk, GROUP)``: ``exp(cum)``,
    ``exp(cum_end - cum)``, and the last row's ``exp(cum_end)``."""
    end = cumc[cumc.shape[0] - 1:, :]
    return jnp.exp(cumc), jnp.exp(end - cumc), jnp.exp(end)


def _forward_kernel(x_ref, b_ref, c_ref, bt_ref, cumc_ref, cumr_ref,
                    dtc_ref, dtr_ref, d_ref, y_ref, *rest, dtype,
                    keep_states):
    state = rest[-1]
    q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    if keep_states:
        rest[0][0, 0, 0] = state[...]
    cb, bb, btb = (r[0].astype(dtype) for r in (c_ref, b_ref, bt_ref))
    scores = _dot(cb, bb, _NT)  # [i, j]
    live = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    low = lax.broadcasted_iota(jnp.int32, (q, LANES), 1) < HEAD_DIM
    cumc, cumr, dtc, dtr = cumc_ref[0, 0], cumr_ref[0], dtc_ref[0, 0], \
        dtr_ref[0]
    grown, to_end, through = _chunk_terms(cumc)
    for pair in range(_PAIRS):
        lanes = slice(pair * LANES, (pair + 1) * LANES)
        x = x_ref[0, :, lanes]
        x32, xd = x.astype(_F32), x.astype(dtype)
        inside = []
        for hh in (2 * pair, 2 * pair + 1):
            # exp of a masked difference: above the diagonal it may
            # overflow
            decay = jnp.exp(jnp.where(
                live, cumc[:, hh:hh + 1] - cumr[hh:hh + 1, :], -jnp.inf))
            weights = scores * decay * dtr[hh:hh + 1, :]
            inside.append(_dot(weights.astype(dtype), xd))
        entering = state[pair]
        y = jnp.where(low, *inside) \
            + _dot(cb, entering.astype(dtype)) * _of_pair(low, grown, pair) \
            + d_ref[:, lanes] * x32
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        xw, _ = _weigh(x32, _of_pair(low, to_end * dtc, pair), x.dtype)
        state[pair] = _of_pair(low, through, pair) * entering \
            + _dot(btb, xw.astype(dtype))


def _head_picker(pair):
    """The 0/1 matrix that sums a pair's 128 lanes by head: head ``2
    pair + k``'s 64 onto lane ``2 pair + k`` of the result."""
    src = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    dst = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    return (dst == 2 * pair + (src >= HEAD_DIM).astype(jnp.int32)
            ).astype(jnp.bfloat16)


def _head_sums(lanes32, pick):
    """``(rows, 128)`` float32 against :func:`_head_picker`'s matrix on
    the MXU, the operand split into three bfloat16 terms (24 bits of
    it)."""
    total, rest = None, lanes32
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        rest = rest - term.astype(_F32)
        part = _dot(term, pick)
        total = part if total is None else total + part
    return total


def _backward_kernel(x_ref, dy_ref, b_ref, c_ref, ct_ref, cumc_ref,
                     cumr_ref, dtc_ref, d_ref, hin_ref, dx_ref, db_ref,
                     dc_ref, ddt_ref, dcumc_ref, dcumr_ref, dd_ref,
                     dstate, *, dtype):
    q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    cb, bb, ctb = (r[0].astype(dtype) for r in (c_ref, b_ref, ct_ref))
    scores_t = _dot(bb, cb, _NT)  # [j, i]
    live_t = lax.broadcasted_iota(jnp.int32, (q, q), 1) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 0)
    low = lax.broadcasted_iota(jnp.int32, (q, LANES), 1) < HEAD_DIM
    last_row = lax.broadcasted_iota(jnp.int32, (q, LANES), 0) == q - 1
    cumc, cumr, dtc = cumc_ref[0, 0], cumr_ref[0], dtc_ref[0, 0]
    grown, to_end, through = _chunk_terms(cumc)
    dscores_t = jnp.zeros((q, q), _F32)
    db = jnp.zeros(db_ref.shape[2:], _F32)
    dc = jnp.zeros(dc_ref.shape[2:], _F32)
    ddt_cols = jnp.zeros((q, LANES), _F32)
    dcum_cols = jnp.zeros((q, LANES), _F32)
    for pair in range(_PAIRS):
        lanes = slice(pair * LANES, (pair + 1) * LANES)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        x32, dy32, dyd = x.astype(_F32), dy.astype(_F32), dy.astype(dtype)
        entering, dleaving = hin_ref[0, 0, 0, pair], dstate[pair]
        enteringd, dleavingd = entering.astype(dtype), dleaving.astype(dtype)
        grown_p, to_end_p, through_p, dt_p = (
            _of_pair(low, cols, pair)
            for cols in (grown, to_end, through, dtc))
        # what the entering state added to y, and its cotangent's way
        from_state = _dot(cb, enteringd) * grown_p
        dye = (dy32 * grown_p).astype(dtype)
        dc += _dot(dye, enteringd, _NT)
        # the chunk's own end state: x weighted, against B
        xw, w_p = _weigh(x32, to_end_p * dt_p, x.dtype)
        dxw = _dot(bb, dleavingd)
        db += _dot(xw.astype(dtype), dleavingd, _NT)
        # inside the chunk, a head at a time, on transposed tiles
        dx_inside = []
        for k, hh in enumerate((2 * pair, 2 * pair + 1)):
            decay_t = jnp.exp(jnp.where(
                live_t, cumr[hh:hh + 1, :] - cumc[:, hh:hh + 1], -jnp.inf))
            x_head = jnp.where(low if k == 0 else ~low, x32, 0.0)
            dweights_t = _dot(x_head.astype(dtype), dyd, _NT) \
                * dtc[:, hh:hh + 1]
            dscores_t += dweights_t * decay_t
            # the weights without dt_j, rounded once for both uses
            unweighted = (scores_t * decay_t).astype(dtype)
            # cum_i - cum_j: what an element adds at i it takes away at
            # j (``taken`` below).  Both sides are sums of the same
            # products of the same rounded weights, so that they cancel
            # in the running sum's gradient as they do in autodiff's
            dcumr_ref[0, hh:hh + 1, :] = jnp.sum(
                dweights_t * unweighted.astype(_F32), axis=0, keepdims=True)
            dx_inside.append(_dot(unweighted, dyd))
        dx_nodt = jnp.where(low, *dx_inside)
        ddt_inside = x32 * dx_nodt
        ddt_to_end = x32 * dxw * to_end_p
        ddt_lanes = ddt_inside + ddt_to_end
        # likewise cum_end - cum_j of the chunk state's weights
        taken = dt_p * ddt_to_end
        ends = jnp.sum(taken, axis=0, keepdims=True) \
            + through_p * jnp.sum(dleaving * entering, axis=0,
                                  keepdims=True)
        dcum_lanes = dy32 * from_state - dt_p * ddt_inside - taken \
            + jnp.where(last_row, ends, 0.0)
        pick = _head_picker(pair)
        ddt_cols += _head_sums(ddt_lanes, pick)
        dcum_cols += _head_sums(dcum_lanes, pick)
        d_lanes = d_ref[:, lanes]
        dx_ref[0, :, lanes] = (dt_p * dx_nodt + w_p * dxw
                               + d_lanes * dy32).astype(dx_ref.dtype)
        dd_ref[0, 0, :, lanes] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
        dstate[pair] = through_p * dleaving + _dot(ctb, dye)
    dscores_td = dscores_t.astype(dtype)
    db_ref[0, 0] = db + _dot(dscores_td, cb)
    dc_ref[0, 0] = dc + _dot(dscores_td, bb, _TN)
    ddt_ref[0, 0] = ddt_cols[:, :GROUP]
    dcumc_ref[0, 0] = dcum_cols[:, :GROUP]


def _layouts(cum, dt):
    """``(b, s, h)`` float32 in the kernels' two forms: heads on the
    lanes a group ``(b, h / GROUP, s, GROUP)`` and heads on the sublanes
    ``(b, h, s)``."""
    b, s, h = cum.shape
    cols = lambda t: t.reshape(b, s, h // GROUP, GROUP).swapaxes(1, 2)
    rows = lambda t: t.swapaxes(1, 2)
    return cols(cum), rows(cum), cols(dt), rows(dt)


def _from_cols(t):
    """``(b, h / GROUP, s, GROUP) -> (b, s, h)``."""
    b, g, s, _ = t.shape
    return t.swapaxes(1, 2).reshape(b, s, g * GROUP)


def launch_plan(b, s, chunk, heads, state, backward: bool):
    """``(grid, inputs, outputs, scratch)`` of a launch over ``b``
    sequences of ``s`` positions: an operand is ``name: (array shape,
    block shape, index map, itemsize)``, ``None`` for ``x``'s itemsize;
    ``scratch`` the float32 shapes.  The backward meets the chunks last
    to first.  What the ``pallas_call``s are built from and
    :func:`launch_account` counts."""
    c, groups, width = s // chunk, heads // GROUP, GROUP * HEAD_DIM
    inner = heads * HEAD_DIM
    at = (lambda ci: c - 1 - ci) if backward else (lambda ci: ci)
    tile = ((b, s, inner), (1, chunk, width),
            lambda bi, gi, ci: (bi, at(ci), gi), None)
    bc = ((b, s, state), (1, chunk, state),
          lambda bi, gi, ci: (bi, at(ci), 0), None)
    bct = ((b, state, s), (1, state, chunk),
           lambda bi, gi, ci: (bi, 0, at(ci)), None)
    a_group = lambda bi, gi, ci: (bi, gi, at(ci), 0)
    cols = ((b, groups, s, GROUP), (1, 1, chunk, GROUP), a_group, 4)
    per_group = ((b, groups, s, state), (1, 1, chunk, state), a_group, 4)
    rows = ((b, heads, s), (1, GROUP, chunk),
            lambda bi, gi, ci: (bi, gi, at(ci)), 4)
    skip = ((1, inner), (1, width), lambda bi, gi, ci: (0, gi), 4)
    states = ((b, c, groups, _PAIRS, state, LANES),
              (1, 1, 1, _PAIRS, state, LANES),
              lambda bi, gi, ci: (bi, at(ci), gi, 0, 0, 0), 4)
    if not backward:
        ins = {"x": tile, "B": bc, "C": bc, "Bt": bct, "cum_cols": cols,
               "cum_rows": rows, "dt_cols": cols, "dt_rows": rows,
               "D": skip}
        outs = {"y": tile, "states": states}
    else:
        ins = {"x": tile, "dy": tile, "B": bc, "C": bc, "Ct": bct,
               "cum_cols": cols, "cum_rows": rows, "dt_cols": cols,
               "D": skip, "states": states}
        outs = {"dx": tile, "dB": per_group, "dC": per_group,
                "ddt_cols": cols, "dcum_cols": cols, "dcum_rows": rows,
                "dD": ((b, groups, 8, width), (1, 1, 8, width),
                       lambda bi, gi, ci: (bi, gi, 0, 0), 4)}
    return (b, groups, c), ins, outs, [(_PAIRS, state, LANES)]


def _launch(kernel, name, operands, out_dtypes, dims, backward, interpret):
    """One ``pallas_call`` over ``launch_plan(*dims)``, with the outputs
    ``out_dtypes`` names."""
    grid, ins, outs, scratch = launch_plan(*dims, backward)
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


def _forward(x, dt, cum, B, C, D, chunk, dtype, interpret, keep_states):
    b, s, inner = x.shape
    heads, state = dt.shape[-1], B.shape[-1]
    cumc, cumr, dtc, dtr = _layouts(cum, dt)
    d_lanes = jnp.repeat(D.astype(_F32), HEAD_DIM)[None]
    out = _launch(
        functools.partial(_forward_kernel, dtype=dtype,
                          keep_states=keep_states),
        "_ssd_forward",
        (x, B, C, B.swapaxes(1, 2), cumc, cumr, dtc, dtr, d_lanes),
        {"y": x.dtype, **({"states": _F32} if keep_states else {})},
        (b, s, chunk, heads, state), False, interpret)
    return out, (cumc, cumr, dtc, d_lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def ssd_chunks(x, dt, cum, B, C, D, chunk, dtype, interpret):
    """The scan over whole chunks: ``x (b, s, h * 64)``, ``dt`` and
    ``cum (b, s, h)`` float32 (``cum`` the running sum of ``dt A``
    inside each chunk, inclusive), ``B``, ``C (b, s, 128)``, ``D (h,)``;
    ``s`` a multiple of ``chunk``.  Returns ``y`` like ``x``."""
    (y,), _ = _forward(x, dt, cum, B, C, D, chunk, dtype, interpret,
                       keep_states=False)
    return y


def _ssd_fwd(x, dt, cum, B, C, D, chunk, dtype, interpret):
    (y, states), laid_out = _forward(x, dt, cum, B, C, D, chunk, dtype,
                                     interpret, keep_states=True)
    return y, (x, B, C, D, states, laid_out)


def _ssd_bwd(chunk, dtype, interpret, residuals, dy):
    x, B, C, D, states, (cumc, cumr, dtc, d_lanes) = residuals
    b, s, inner = x.shape
    heads, state = inner // HEAD_DIM, B.shape[-1]
    dx, db, dc, ddt, dcumc, dcumr, dd = _launch(
        functools.partial(_backward_kernel, dtype=dtype),
        "_ssd_backward",
        (x, dy, B, C, C.swapaxes(1, 2), cumc, cumr, dtc, d_lanes, states),
        {"dx": x.dtype, "dB": _F32, "dC": _F32, "ddt_cols": _F32,
         "dcum_cols": _F32, "dcum_rows": _F32, "dD": _F32},
        (b, s, chunk, heads, state), True, interpret)
    dD = dd[:, :, 0].reshape(b, heads, HEAD_DIM).sum((0, 2))
    return (dx, _from_cols(ddt), _from_cols(dcumc) + dcumr.swapaxes(1, 2),
            db.sum(1).astype(B.dtype), dc.sum(1).astype(C.dtype),
            sum_to_vma(dD, D).astype(D.dtype))


ssd_chunks.defvjp(_ssd_fwd, _ssd_bwd)


def launch_account(s: int, chunk: int, heads: int, state: int,
                   itemsize: int = 2) -> dict:
    """The static account of the two launches for one sequence of ``s``
    positions (a multiple of ``chunk``), ``forward`` the one that keeps
    the entering states: ``grid``, ``tiles`` a launch, ``vmem_bytes`` a
    grid point (the scratch, and every block twice, as VMEM holds it:
    the last dimension padded to 128 lanes, the one before to 8
    sublanes) and ``hbm_bytes`` read and written (a block is moved when
    its index changes: ``B`` and ``C`` once a group and chunk, ``D`` once
    a group)."""
    def held(block, size):
        *lead, rows, cols = block
        return size * math.prod(lead) * (-(-rows // 8) * 8) \
            * (-(-cols // LANES) * LANES)

    out = {}
    for kind, backward in (("forward", False), ("backward", True)):
        grid, ins, outs, scratch = launch_plan(1, s, chunk, heads, state,
                                               backward)
        operands = [(blk, index, size or itemsize)
                    for _, blk, index, size in (*ins.values(),
                                                *outs.values())]
        points = math.prod(grid)
        out[kind] = {
            "grid": grid, "tiles": points,
            "vmem_bytes": 2 * sum(held(blk, size)
                                  for blk, _, size in operands)
            + sum(held(shape, 4) for shape in scratch),
            "hbm_bytes": float(sum(
                math.prod(blk) * size
                * (points if index(0, 0, 0) != index(0, 0, 1)
                   else grid[0] * grid[1])
                for blk, index, size in operands)),
        }
    return out
