"""Pallas TPU kernel of the backward pass of the mixers' short
convolution with its activation, ``SiLU(causal_conv1d(x, taps, bias))``:
one pass over ``x`` and ``dy`` that writes ``dx`` once
(:mod:`chainermn_tpu.ops.ssd_scan` has the forward, which stays plain
XLA, and chooses between this and autodiff of that form).

Autodiff of ``sum_j padded[:, j:j + s] * taps[j]`` transposes each slice
into a pad, and XLA materialises the pads' operands: a tensor the size
of ``x`` a tap, written and read again, beside the float32 pre-activation
(11.1 ms on a v5e at ``bf16[2, 8192, 12288]``, where 1.2 GB have to move
and this pass takes 2.5: ``PERF.md`` section 6, PR 45).  Here a grid point holds a ``(rows, cols)`` tile of
``x`` and ``dy`` in the mixer's own row-major ``(b, s, c)`` layout and,
through second ``BlockSpec``s on the same arrays, the :data:`SLAB` rows
before the tile (of ``x``) and after it (of ``x`` and ``dy``); outside
the sequence they count as zeros.  With ``k`` taps it

* computes the pre-activation again in float32 as the forward sums it
  (``j = 0 .. k - 1``, then the bias), for the tile's rows and the ``k -
  1`` after them;
* forms ``dpre_t = dy_t silu'(pre_t)``;
* writes ``dx_t = sum_j taps[j] dpre_{t + (k - 1) - j}`` once, in ``x``'s
  dtype;
* adds the tile's rows to ``dtaps[j] = sum_t dpre_t x_{t - (k - 1) +
  j}`` and ``dbias = sum_t dpre_t``, float32 blocks that stay in VMEM
  while the grid walks the sequence and the batch (eight partial sums a
  channel, one a sublane: the caller adds them).

``x`` may be wider than the convolution: the kernel reads columns
``start ..`` of it by block index, so a mixer that convolves a column
range of its in-projection's result hands that result over as it is and
XLA makes no copy of the range.

The rows are walked :data:`CHUNK` at a time so that a chunk's values
stay in registers; a row shift is a rotation of a chunk's window (loaded
with the eight rows around it) along the sublanes, never a second pass
over HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import vary_alike
from .pallas_attention import _out_struct

LANES = 128
#: rows of the neighbouring tile a grid point reads: a whole bfloat16
#: tile of 16 sublanes
SLAB = 16
#: the float32 sublane tile: a window's margin, ``dpre``'s rows beyond
#: the tile, and the partial sums a channel of ``dtaps``
SUBLANES = 8
#: the most taps: ``k - 1`` rows must lie inside a window's margin
MAX_TAPS = SUBLANES
#: rows worked on at a time (on the chip 32 read 2.98 ms a launch at
#: ``bf16[2, 8192, 12288]``, 16 read 3.18, 64 3.05: ``PERF.md`` section
#: 6, PR 45, as every time quoted in this file)
CHUNK = 32
#: the widest tile, and the elements of the largest (1024 x 512 read
#: 2.93 ms where 512 x 512 read 2.98 and 256 x 512 3.16; as large and
#: narrower, 2048 x 256, 2.78)
MAX_COLS = 512
TILE_ELEMENTS = 1024 * 512
#: the VMEM the launch declares (v5e's default scope)
VMEM_LIMIT_BYTES = 16 * 2 ** 20
_F32 = jnp.float32


def tiles(s: int, width: int, start: int = 0,
          taps: int = 4) -> Optional[tuple]:
    """The ``(rows, cols)`` tile for ``width`` channels from column
    ``start`` over ``s`` positions, ``None`` where the kernel cannot
    tile them: channels and offset whole lane tiles, a length of whole
    slabs, at most :data:`MAX_TAPS` taps."""
    if not 1 <= taps <= MAX_TAPS or width <= 0:
        return None
    if width % LANES or start % LANES or s % SLAB:
        return None
    cols = next(c for c in (MAX_COLS, 256, LANES)
                if width % c == 0 and start % c == 0)
    rows = SLAB
    while rows * 2 * cols <= TILE_ELEMENTS and s % (rows * 2) == 0:
        rows *= 2
    return rows, cols


def _rows(window, first, n):
    """Rows ``first .. first + n`` of a window of whole sublane tiles:
    a rotation of the window's sublanes and its first ``n`` rows (2.98
    ms a launch where an unaligned static slice read 3.46)."""
    if first % SUBLANES:
        window = pltpu.roll(window, window.shape[0] - first, 0)
        first = 0
    return window[first:first + n]


def _silu_slope(pre):
    """``d silu(pre) / d pre`` in float32, the sigmoid by its hyperbolic
    tangent (one transcendental and no division: 2.55 ms a launch for
    2.98)."""
    sig = 0.5 * jnp.tanh(0.5 * pre) + 0.5
    return sig * (1.0 + pre * (1.0 - sig))


def _backward_kernel(xp_ref, x_ref, xn_ref, dy_ref, dyn_ref, w_ref,
                     dx_ref, dw_ref, xe, de, *, taps, has_bias):
    k, rows = taps, x_ref.shape[1]
    margin, chunk = SUBLANES, min(CHUNK, rows)
    ri, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & (ri == 0))
    def _first_tile():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # the tile between its neighbours' rows, float32; zeros before the
    # sequence, and no cotangent after it
    xe[0:SLAB] = jnp.where(ri == 0, 0.0, xp_ref[0].astype(_F32))
    xe[SLAB:SLAB + rows] = x_ref[0].astype(_F32)
    xe[SLAB + rows:] = xn_ref[0].astype(_F32)
    w = [w_ref[j:j + 1, :] for j in range(k + has_bias)]

    def dpre_of(at, n, dy32):
        """``dpre`` of ``n`` rows from row ``at`` of the tile, and the
        ``k`` shifted copies of ``x`` that met the taps there."""
        window = xe[pl.ds(at + SLAB - margin, n + margin), :]
        shifted = [_rows(window, margin - (k - 1) + j, n) for j in range(k)]
        pre = shifted[0] * w[0]
        for j in range(1, k):
            pre = pre + shifted[j] * w[j]
        if has_bias:
            pre = pre + w[k]
        return dy32 * _silu_slope(pre), shifted

    def by_sublane(t):
        return t.reshape(-1, SUBLANES, t.shape[-1]).sum(0)

    def first_pass(c, sums):
        at = pl.multiple_of(c * chunk, chunk)
        dpre, shifted = dpre_of(at, chunk,
                                dy_ref[0, pl.ds(at, chunk), :].astype(_F32))
        de[pl.ds(at, chunk), :] = dpre
        sums = [acc + by_sublane(dpre * shifted[j])
                for j, acc in enumerate(sums[:k])] \
            + [acc + by_sublane(dpre) for acc in sums[k:]]
        return tuple(sums)

    zero = jnp.zeros((SUBLANES, x_ref.shape[2]), _F32)
    sums = lax.fori_loop(0, rows // chunk, first_pass,
                         (zero,) * (k + has_bias))
    for j, acc in enumerate(sums):
        dw_ref[j] += acc
    # the rows after the tile that its last rows' dx reads: the next
    # tile adds them to dtaps
    beyond = jnp.where(ri == last, 0.0, dyn_ref[0].astype(_F32))[:margin]
    de[rows:] = dpre_of(rows, margin, beyond)[0]

    def second_pass(c, _):
        at = pl.multiple_of(c * chunk, chunk)
        window = de[pl.ds(at, chunk + margin), :]
        dx = _rows(window, k - 1, chunk) * w[0]
        for j in range(1, k):
            dx = dx + _rows(window, k - 1 - j, chunk) * w[j]
        dx_ref[0, pl.ds(at, chunk), :] = dx.astype(dx_ref.dtype)
        return 0

    lax.fori_loop(0, rows // chunk, second_pass, 0)


def launch_plan(b: int, s: int, width: int, taps: int, start: int = 0,
                total: Optional[int] = None, has_bias: bool = False,
                tile: Optional[tuple] = None):
    """``(grid, inputs, outputs, scratch)`` of the backward launch over
    ``b`` sequences of ``s`` positions and ``width`` channels from column
    ``start`` of an ``x`` of ``total`` columns: an operand is ``name:
    (array shape, block shape, index map, itemsize)``, ``None`` for
    ``x``'s itemsize; ``scratch`` the float32 shapes.  The grid is
    (channel tile, sequence, row tile).  What the ``pallas_call`` is
    built from and :func:`launch_account` counts."""
    total = width if total is None else total
    rows, cols = tile or tiles(s, width, start, taps)
    first, per_tile, slabs = start // cols, rows // SLAB, s // SLAB
    before = lambda ri: jnp.maximum(ri * per_tile - 1, 0)
    after = lambda ri: jnp.minimum((ri + 1) * per_tile, slabs - 1)
    wide, narrow = (b, s, total), (b, s, width)
    ins = {
        "x_before": (wide, (1, SLAB, cols),
                     lambda ci, bi, ri: (bi, before(ri), first + ci), None),
        "x": (wide, (1, rows, cols),
              lambda ci, bi, ri: (bi, ri, first + ci), None),
        "x_after": (wide, (1, SLAB, cols),
                    lambda ci, bi, ri: (bi, after(ri), first + ci), None),
        "dy": (narrow, (1, rows, cols),
               lambda ci, bi, ri: (bi, ri, ci), None),
        "dy_after": (narrow, (1, SLAB, cols),
                     lambda ci, bi, ri: (bi, after(ri), ci), None),
        "weights": ((taps + has_bias, width), (taps + has_bias, cols),
                    lambda ci, bi, ri: (0, ci), 4),
    }
    outs = {
        "dx": (narrow, (1, rows, cols),
               lambda ci, bi, ri: (bi, ri, ci), None),
        "dweights": ((taps + has_bias, SUBLANES, width),
                     (taps + has_bias, SUBLANES, cols),
                     lambda ci, bi, ri: (0, 0, ci), 4),
    }
    scratch = [(SLAB + rows + SLAB, cols), (rows + SUBLANES, cols)]
    return (width // cols, b, s // rows), ins, outs, scratch


def conv_backward(x, dy, taps, bias=None, start: int = 0,
                  interpret: bool = False, tile: Optional[tuple] = None):
    """The cotangents of ``SiLU(causal_conv1d(x[..., start:start + c],
    taps, bias))`` for the output's cotangent ``dy (b, s, c)``: ``dx (b,
    s, c)`` in ``x``'s dtype, ``dtaps (k, c)`` and ``dbias (c,)``
    float32 (``None`` without a bias).  The sizes must tile
    (:func:`tiles`; ``tile`` puts another ``(rows, cols)`` in its
    place)."""
    b, s, total = x.shape
    k, width = taps.shape
    has_bias = bias is not None
    weights = taps.astype(_F32)
    if has_bias:
        weights = jnp.concatenate([weights, bias.astype(_F32)[None]])
    grid, ins, outs, scratch = launch_plan(b, s, width, k, start, total,
                                           has_bias, tile)
    spec = lambda entry: pl.BlockSpec(entry[1], entry[2])
    x, dy, weights = vary_alike(x, dy, weights)
    dx, dw = pl.pallas_call(
        functools.partial(_backward_kernel, taps=k, has_bias=has_bias),
        grid=grid,
        in_specs=[spec(entry) for entry in ins.values()],
        out_specs=[spec(entry) for entry in outs.values()],
        out_shape=[_out_struct(outs["dx"][0], x.dtype, x, dy, weights),
                   _out_struct(outs["dweights"][0], _F32, x, dy, weights)],
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="_conv_backward",
    )(x, x, x, dy, dy, weights)
    dw = dw.sum(1)
    return dx, dw[:k], (dw[k] if has_bias else None)


def launch_account(b: int, s: int, width: int, taps: int = 4,
                   start: int = 0, total: Optional[int] = None,
                   has_bias: bool = False, itemsize: int = 2) -> dict:
    """The static account of the backward launch: ``grid``, ``tile``
    (rows, cols), ``tiles`` a launch, ``vmem_bytes`` a grid point (the
    scratch, and every block twice, as VMEM holds it: the last dimension
    padded to 128 lanes, the one before to 8 sublanes), ``vmem_limit``
    the launch declares, ``hbm_bytes`` read and written (a block is
    moved when its index changes: the weights and their gradient once a
    channel tile), and ``hbm_over_least``, those over ``x`` and ``dy``
    read and ``dx`` written once."""
    def held(block, size):
        *lead, rows, cols = block
        return size * math.prod(lead) * (-(-rows // 8) * 8) \
            * (-(-cols // LANES) * LANES)

    grid, ins, outs, scratch = launch_plan(b, s, width, taps, start, total,
                                           has_bias)
    operands = [(name, blk, size or itemsize)
                for name, (_, blk, _, size) in (*ins.items(), *outs.items())]
    points = math.prod(grid)
    least = 3.0 * b * s * width * itemsize
    moved = float(sum(
        math.prod(blk) * size * (grid[0] if "weights" in name else points)
        for name, blk, size in operands))
    return {
        "grid": grid, "tile": ins["x"][1][1:], "tiles": points,
        "vmem_bytes": 2 * sum(held(blk, size) for _, blk, size in operands)
        + sum(held(shape, 4) for shape in scratch),
        "vmem_limit": VMEM_LIMIT_BYTES,
        "hbm_bytes": moved, "hbm_over_least": moved / least,
    }
