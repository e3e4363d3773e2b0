"""Pallas TPU kernel: a matrix product grouped by row blocks.

The expert layer of a mixture of experts multiplies each token row by
the weights of the expert it was routed to.  With the rows sorted by
expert and every expert's run padded to whole blocks of ``block_rows``
rows, that is one product a row block against the weights the block's
``block_group`` entry names:

    out[b * m:(b + 1) * m] = x[b * m:(b + 1) * m] @ w[block_group[b]]

The grid is static (one step a block, whatever the routing was), so a
layer built on it does the same work every step; consecutive blocks of
one group name the same weight block, which the pipeline then fetches
once.  ``block_group`` must be ascending and name every group at least
once (the weight gradient writes a group's block when it meets it).

:func:`grouped_matmul` is differentiable in ``x`` and ``w``: the input
gradient is the same kernel against the transposed weights, the weight
gradient a second kernel that sums ``x_b^T dy_b`` over each group's
blocks in float32.

Off the TPU (``interpret=None``) the same products run as plain XLA
(a gather of the blocks' weights and a batched product): the Pallas
interpreter cannot slice a scalar-prefetched operand under a
vma-checked ``shard_map``, which is where a train step calls this.
``interpret=True`` runs the kernels interpreted (the unit tests do).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Communication goes through the audited wrappers (analysis.lint gate).
from chainermn_tpu.functions import collectives as _cc

from .pallas_attention import _out_struct

#: bytes a weight-gradient block may hold in VMEM (it is double-buffered)
_DW_BLOCK_BYTES = 13 * 2 ** 18  # 3.25 MiB
#: bytes of a group's weights that the row products still take whole
#: (double-buffered, beside the row block, the result and its float32
#: product: 2048 x 1408 in bfloat16, 5.5 MiB, runs so; 3072 x 1024, 6
#: MiB, was refused ahead of time at 16.61 of 16 MiB of scoped VMEM),
#: and of a tile of its result's columns where they are more
_ROWS_WHOLE_BYTES = 23 * 2 ** 18  # 5.75 MiB
_ROWS_TILE_BYTES = 3 * 2 ** 20


def sum_to_vma(cotangent, primal):
    """A custom gradient's cotangent for ``primal`` inside ``shard_map``:
    summed over the mesh axes it varies over and ``primal`` does not (a
    parameter replicated over the data axes gets the sum of its shards'
    gradients), which autodiff does itself for plain operations."""
    extra = tuple(sorted(jax.typeof(cotangent).vma
                         - jax.typeof(primal).vma))
    return _cc.psum(cotangent, extra) if extra else cotangent


def vary_alike(*operands, like=()):
    """The operands of a ``pallas_call`` or the first carries of a scan
    inside ``shard_map``, each made to vary over every mesh axis any of
    them, or of ``like``, varies over (a matter of types: nothing
    moves)."""
    vma = frozenset().union(
        *(jax.typeof(x).vma for x in (*operands, *like)))
    return tuple(
        lax.pcast(x, tuple(sorted(vma - jax.typeof(x).vma)), to="varying")
        if vma - jax.typeof(x).vma else x for x in operands)


def _rows_kernel(bg_ref, x_ref, w_ref, o_ref, *, transpose_w: bool):
    del bg_ref  # read by the index maps
    dims = (((1,), (1,)), ((), ())) if transpose_w \
        else (((1,), (0,)), ((), ()))
    o_ref[...] = lax.dot_general(
        x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _dw_kernel(bg_ref, x_ref, dy_ref, o_ref):
    b = pl.program_id(1)
    first = (b == 0) | (bg_ref[b] != bg_ref[jnp.maximum(b - 1, 0)])

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _use_kernel(interpret) -> bool:
    return interpret is not None or jax.default_backend() == "tpu"


def _rows_product(x, w, block_group, block_rows, transpose_w, interpret):
    """``x (R, K)`` against ``w (G, K, N)`` (``(G, N, K)`` transposed)."""
    if not _use_kernel(interpret):
        blocks = x.reshape(-1, block_rows, x.shape[1])
        out = jnp.einsum("bmk,bnk->bmn" if transpose_w else "bmk,bkn->bmn",
                         blocks, w[block_group],
                         preferred_element_type=jnp.float32)
        return out.astype(x.dtype).reshape(x.shape[0], -1)
    block_group, x, w = vary_alike(block_group, x, w)
    r, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tn = _rows_tile(k, n, w.dtype.itemsize)
    if tn != n:
        # a tile of the result's columns a grid point, the tiles
        # outermost: a group's tile of the weights is still fetched
        # once, the row blocks once a tile
        w_tile, w_at = ((1, tn, k), lambda j, b, bg: (bg[b], j, 0)) \
            if transpose_w else ((1, k, tn), lambda j, b, bg: (bg[b], 0, j))
        return pl.pallas_call(
            functools.partial(_rows_kernel, transpose_w=transpose_w),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n // tn, r // block_rows),
                in_specs=[
                    pl.BlockSpec((block_rows, k), lambda j, b, bg: (b, 0)),
                    pl.BlockSpec(w_tile, w_at),
                ],
                out_specs=pl.BlockSpec((block_rows, tn),
                                       lambda j, b, bg: (b, j)),
            ),
            out_shape=_out_struct((r, n), x.dtype, x, w),
            interpret=bool(interpret),
            name="_grouped_matmul",
        )(block_group, x, w)
    w_block = (1, n, k) if transpose_w else (1, k, n)
    return pl.pallas_call(
        functools.partial(_rows_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // block_rows,),
            in_specs=[
                pl.BlockSpec((block_rows, k), lambda b, bg: (b, 0)),
                pl.BlockSpec(w_block, lambda b, bg: (bg[b], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, n), lambda b, bg: (b, 0)),
        ),
        out_shape=_out_struct((r, n), x.dtype, x, w),
        interpret=bool(interpret),
        name="_grouped_matmul",
    )(block_group, x, w)


def _rows_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of the result a grid point of the row products computes:
    all ``n`` while a group's ``(k, n)`` weights fit their VMEM share,
    else ``n``'s largest divisor that is a multiple of 128 and whose
    tile of the weights fits :data:`_ROWS_TILE_BYTES`."""
    if k * n * itemsize <= _ROWS_WHOLE_BYTES:
        return n
    fits = [t for t in range(128, n, 128)
            if n % t == 0 and k * t * itemsize <= _ROWS_TILE_BYTES]
    return max(fits) if fits else n


def _dw_tile(k: int, n: int) -> int:
    """Rows of a weight-gradient block: all of ``k``, or its largest
    divisor that is a multiple of 128 and fits the VMEM share."""
    fits = [t for t in range(128, k + 1, 128)
            if k % t == 0 and t * n * 4 <= _DW_BLOCK_BYTES]
    return k if k * n * 4 <= _DW_BLOCK_BYTES or not fits else max(fits)


def _weight_gradient(x, dy, block_group, groups, block_rows, interpret):
    """float32 ``(G, K, N)``: the sum over each group's blocks of
    ``x_b^T dy_b``."""
    if not _use_kernel(interpret):
        per_block = jnp.einsum(
            "bmk,bmn->bkn", x.reshape(-1, block_rows, x.shape[1]),
            dy.reshape(-1, block_rows, dy.shape[1]),
            preferred_element_type=jnp.float32)
        return jax.ops.segment_sum(per_block, block_group,
                                   num_segments=groups)
    block_group, x, dy = vary_alike(block_group, x, dy)
    r, k = x.shape
    n = dy.shape[1]
    tk = _dw_tile(k, n)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, r // block_rows),
            in_specs=[
                pl.BlockSpec((block_rows, tk), lambda i, b, bg: (b, i)),
                pl.BlockSpec((block_rows, n), lambda i, b, bg: (b, 0)),
            ],
            out_specs=pl.BlockSpec((1, tk, n),
                                   lambda i, b, bg: (bg[b], i, 0)),
        ),
        out_shape=_out_struct((groups, k, n), jnp.float32, x, dy),
        interpret=bool(interpret),
        name="_grouped_matmul_dw",
    )(block_group, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, block_group, block_rows: int,
                   interpret: Optional[bool] = None):
    """``x (R, K)`` in blocks of ``block_rows`` rows, block ``b``
    multiplied by ``w[block_group[b]]`` of ``w (G, K, N)`` -> ``(R, N)``
    in ``x``'s dtype.  The product takes ``w`` in ``x``'s dtype (float32
    master weights are rounded once, outside the kernel) and accumulates
    in float32; the gradient of ``w`` comes back in ``w``'s own dtype
    from a float32 sum."""
    return _rows_product(x, w.astype(x.dtype), block_group, block_rows,
                         False, interpret)


def _gm_fwd(x, w, block_group, block_rows, interpret):
    w_low = w.astype(x.dtype)
    out = _rows_product(x, w_low, block_group, block_rows, False,
                        interpret)
    # a zero of w's dtype tells the backward what to hand back
    return out, (x, w_low, block_group, jnp.zeros((), w.dtype))


def _gm_bwd(block_rows, interpret, residuals, dy):
    x, w_low, block_group, like = residuals
    dy = dy.astype(x.dtype)
    dx = _rows_product(dy, w_low, block_group, block_rows, True, interpret)
    dw = _weight_gradient(x, dy, block_group, w_low.shape[0], block_rows,
                          interpret)
    return (sum_to_vma(dx, x), sum_to_vma(dw, w_low).astype(like.dtype),
            None)


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)
