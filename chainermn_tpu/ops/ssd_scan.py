"""Chunked state-space scan (Mamba-2's SSD form) and the causal
depthwise convolution in front of it: the scan in two forms, Pallas
kernels on a TPU and plain XLA everywhere else; the convolution's
forward one XLA form everywhere, and the gradient of its activated form
(:func:`causal_conv1d` with ``silu=True``, which every mixer calls) one
Pallas pass over ``x`` and ``dy`` on a TPU (:mod:`.conv_kernels`, PR 45:
bfloat16 ``x``, channels and first column multiples of 128, a length
that is a multiple of 16, at most 8 taps) and autodiff of the XLA form
everywhere else (:func:`_use_conv_kernel`).

A Mamba-2 head carries a state ``S (p, n)`` over the positions of a
sequence (``p`` the head's width, ``n`` the state size), driven by a
positive step ``dt_t``, a negative rate ``A`` (one a head), an input
``x_t (p,)`` and two vectors ``B_t``, ``C_t (n,)`` that all heads share
(one group):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (S_{-1} = 0)
    y_t = S_t C_t + D x_t

:func:`ssd_scan` computes exactly that, ``chunk`` positions at a time
(arXiv:2405.21060, section 6).  With ``a_t = dt_t A`` and ``cum`` its
running sum inside a chunk:

* inside a chunk, ``y_i += sum_{j <= i} (C_i . B_j) L_ij dt_j x_j`` with
  ``L_ij = exp(cum_i - cum_j)``: one ``(chunk, chunk)`` score product a
  chunk for all heads, and a masked product of it with ``x`` a head;
* the state a chunk alone would leave, ``sum_j exp(cum_end - cum_j) dt_j
  x_j B_j^T``, one product a head and chunk;
* the recurrence over those chunk states, ``S <- exp(cum_end) S +
  state``, ``s / chunk`` element-wise steps in float32;
* what the state carried into a chunk adds: ``y_i += exp(cum_i) S C_i``.

Products take bfloat16 operands and accumulate in float32; ``dt``, the
running sums, ``L`` and the carried states are float32, in both forms.

**Which form runs** is read off the input and the platform
(:func:`_use_kernels`).  The kernels (:mod:`.ssd_kernels`) run on a TPU
when the sizes tile -- heads 64 wide, their number a multiple of 8, a
state of 128, a chunk of 128 or 256 -- and ``x`` and the products'
operands are bfloat16 (the cell's launch: chunk 256, 64 heads of 64,
state 128); with ``interpret=True`` they run interpreted at any such
sizes, in either precision (the unit tests).  Every other call runs the
XLA form: off the TPU, float32 operands on it, a head width, head count,
state or chunk the kernels do not tile.

* **The kernels** walk a sequence's chunks in order with a group of 8
  heads' states in VMEM, reading and writing the mixer's own token-major
  ``(s, heads * 64)`` tensors: no operand is transposed, widened to
  float32 or zero-filled in HBM.  ``ssd_scan`` is then a
  ``jax.custom_vjp``: a forward that is not differentiated keeps
  nothing; the differentiated one keeps its operands, the two layouts of
  ``cum`` and ``dt`` and the state entering every chunk (``(chunks,
  heads / 2, 128, 128)`` float32 a sequence, 67 MB at the cell's shape);
  the backward kernel walks the chunks last to first with the states'
  cotangent in VMEM and computes scores and decays again per tile.
* **The XLA form** computes each chunk's own end state for all chunks,
  carries them through a ``lax.scan`` and runs the part inside the
  chunks :data:`CHUNKS_PER_PASS` chunks at a time under
  ``jax.checkpoint``: a ``(chunks, heads, chunk, chunk)`` float32 tensor
  is 537 MB a layer at 8192 positions and 64 heads, so neither pass
  holds more than a pass's worth of it and the backward (autodiff's)
  computes it again.  It is what the kernels are tested against.

The scan is single-device in the sequence and the heads: it has no
sequence-parallel, tensor-parallel or decode form.

:func:`ssd_census` is the static count of the algorithm's work (chunks,
matmul FLOPs by part, least bytes) and of the kernel path's launches
(grid, tiles, VMEM a grid point, bytes moved), as ``launch_census`` is
for the flash kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import conv_kernels, ssd_kernels
from .grouped_matmul import sum_to_vma, vary_alike

#: chunks whose ``(heads, chunk, chunk)`` decay tensors are live at once
#: (on the chip 8 read 468.1 ms a step of the cell against 470.0 at 4:
#: fewer, larger fusions; ``PERF.md`` section 6, PR 37)
CHUNKS_PER_PASS = 8

#: device scopes of the convolution and of the scan
SSM_CONV_SCOPE = "ssm_conv"
SSM_SCAN_SCOPE = "ssm_scan"


def _conv_xla(x, taps, bias, silu):
    """:func:`causal_conv1d`'s XLA form: float32 sums of ``k`` shifted
    copies, rounded to ``x``'s dtype, then the activation."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = y.astype(x.dtype)
    return jax.nn.silu(y) if silu else y


def _columns(x, taps, start):
    """Columns ``start .. start + c`` of ``x``, ``c`` the taps'.  (Cut
    out of each shifted copy instead, XLA:TPU reads the range in place:
    4.9 ms a step less in one cell, 5.0 more in another; ``PERF.md``
    section 6, PR 45.)"""
    return x[..., start:start + taps.shape[1]]


def _use_conv_kernel(x, taps, start, interpret) -> bool:
    """Whether the gradient of the activated convolution runs the Pallas
    kernel: the sizes tile (:func:`conv_kernels.tiles`) and either the
    kernel is asked for (``interpret`` given), or this is a TPU and
    ``x`` is bfloat16 (what the tile's VMEM is sized for)."""
    if not conv_kernels.tiles(x.shape[1], taps.shape[1], start,
                              taps.shape[0]):
        return False
    if interpret is not None:
        return True
    return jax.default_backend() == "tpu" and x.dtype == jnp.bfloat16


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_silu(x, taps, bias, start, interpret):
    """``SiLU(conv(x[..., start:start + c]))`` with the kernel for its
    gradient; the forward is the XLA form, as everywhere."""
    return _conv_xla(_columns(x, taps, start), taps, bias, True)


def _conv_silu_fwd(x, taps, bias, start, interpret):
    # nothing float32 and nothing the size of x but x, which the blocks
    # keep already
    return (_conv_xla(_columns(x, taps, start), taps, bias, True),
            (x, taps, bias))


def _conv_silu_bwd(start, interpret, residuals, dy):
    x, taps, bias = residuals
    dx, dtaps, dbias = conv_kernels.conv_backward(
        x, dy, taps, bias, start, interpret)
    after = x.shape[-1] - start - taps.shape[1]
    if start or after:  # what autodiff makes of the column slice
        dx = jnp.pad(dx, ((0, 0), (0, 0), (start, after)))
    return (dx, sum_to_vma(dtaps, taps).astype(taps.dtype),
            None if bias is None
            else sum_to_vma(dbias, bias).astype(bias.dtype))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv1d(x, taps, bias=None, scope: str = SSM_CONV_SCOPE,
                  silu: bool = False, first_column: int = 0,
                  interpret: Optional[bool] = None):
    """Depthwise causal convolution over the sequence: ``x (b, s, c)``,
    ``taps (k, c)``, ``bias (c,)``;

        y_t = bias + sum_{j < k} taps[j] x_{t - (k - 1) + j}

    with zeros before the sequence (``taps[k - 1]`` meets ``x_t``: a
    ``torch.nn.Conv1d(c, c, k, groups=c, padding=k - 1)`` cut to ``s``).
    Float32 sums of ``k`` shifted copies, the result in ``x``'s dtype;
    ``silu``: ``SiLU`` of that result, as every mixer wants it.  ``x``
    may be wider than the taps: columns ``first_column .. first_column +
    c`` of it are convolved (a mixer hands its in-projection's whole
    result, so that the gradient's kernel reads the range where it
    lies).  ``scope``: the device scope it is traced under (a mixer of
    another kind gives its own).

    The forward is one XLA form everywhere.  **Which gradient runs** is
    read off the input and the platform (:func:`_use_conv_kernel`): with
    the activation inside, on a TPU, for a bfloat16 ``x`` whose sizes
    tile (channels and ``first_column`` multiples of 128, a length that is a
    multiple of 16, at most 8 taps), the backward is one pass of
    :mod:`.conv_kernels` over ``x`` and ``dy`` under a
    ``jax.custom_vjp`` whose residuals are the operands; every other
    call -- off the TPU, float32 operands, sizes that do not tile, no
    activation -- is differentiated by autodiff as it was.
    ``interpret=True`` runs the kernel interpreted wherever the sizes
    tile, in either precision (the unit tests do)."""
    if first_column + taps.shape[1] > x.shape[-1]:
        raise ValueError(f"{taps.shape[1]} taps from column {first_column} "
                         f"of {x.shape[-1]}")
    with jax.named_scope(scope):
        if silu and _use_conv_kernel(x, taps, first_column, interpret):
            return _conv_silu(x, taps, bias, first_column, bool(interpret))
        return _conv_xla(_columns(x, taps, first_column), taps, bias, silu)


def _dot(spec, a, b, dtype):
    """A product with operands in ``dtype`` and a float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _weighted(x, weights):
    """``x (g, q, h, p)`` times ``weights (g, q, h)`` in ``x``'s dtype,
    the weights rounded to it first: left in float32, XLA:TPU makes a
    float32 copy of ``x`` and lays that out again for the product (two
    passes over twice the bytes, 8.7 ms of the cell's step: ``PERF.md``
    section 6, PR 37); the product is a bfloat16 operand either way."""
    return x * weights.astype(x.dtype)[..., None]


@functools.partial(jax.checkpoint, static_argnums=(7,))
def _within_chunks(x, dt, cum, B, C, carried, D, dtype):
    """``y`` of a few chunks: ``x (g, q, h, p)``, ``dt`` / ``cum (g, q,
    h)``, ``B`` / ``C (g, q, n)``, ``carried (g, h, p, n)`` the state
    entering each chunk, ``D (h,)``.  ``(g, q, h, p)`` in ``x``'s dtype,
    summed in float32."""
    q = x.shape[1]
    dot = functools.partial(_dot, dtype=dtype)
    scores = dot("gin,gjn->gij", C, B)
    # exp of a masked difference: above the diagonal cum_i - cum_j is
    # positive and may overflow
    live = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(
        live, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
    weights = scores[..., None] * decay * dt[:, None, :, :]  # (g, i, j, h)
    inside = dot("gijh,gjhp->gihp", weights, x)
    from_state = dot("gin,ghpn->gihp", C, carried) \
        * jnp.exp(cum)[..., None]
    skip = D[:, None] * x.astype(jnp.float32)
    return (inside + from_state + skip).astype(x.dtype)


def _use_kernels(x, B, chunk, dtype, interpret) -> bool:
    """Whether :func:`ssd_scan` runs the Pallas kernels: the sizes tile
    (:func:`ssd_kernels.tiles`) and either the kernels are asked for
    interpreted, or this is a TPU and ``x`` and the products' operands
    are bfloat16 (what the tiles' VMEM is sized for)."""
    _, _, h, p = x.shape
    if not ssd_kernels.tiles(chunk, h, p, B.shape[-1]):
        return False
    if interpret is not None:
        return True
    return jax.default_backend() == "tpu" \
        and x.dtype == jnp.bfloat16 and dtype == jnp.bfloat16


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
@jax.named_scope(SSM_SCAN_SCOPE)
def ssd_scan(x, dt, A, B, C, D, chunk: int = 256, dtype=jnp.bfloat16,
             interpret: Optional[bool] = None):
    """The recurrence of the module docstring in its chunked form.

    ``x (b, s, h, p)``; ``dt (b, s, h)``, positive; ``A (h,)``,
    negative; ``B``, ``C (b, s, n)``, shared by the heads; ``D (h,)``.
    Returns ``y (b, s, h, p)`` in ``x``'s dtype; ``dtype`` is that of
    the products' operands.  A length that is no multiple of ``chunk``
    is padded with ``dt = 0`` rows (decay 1, no input), which leave
    every state as it was.  ``interpret=True`` runs the kernels
    interpreted wherever the sizes tile (the unit tests do)."""
    if _use_kernels(x, B, chunk, dtype, interpret):
        return _scan_kernels(x, dt, A, B, C, D, chunk, dtype,
                             bool(interpret))
    return _scan_xla(x, dt, A, B, C, D, chunk, dtype)


def _padded(x, dt, B, C, chunk):
    """The operands with ``dt = 0`` rows up to a whole chunk."""
    pad = -x.shape[1] % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    return x, dt, B, C


def _scan_kernels(x, dt, A, B, C, D, chunk, dtype, interpret):
    """The kernel path: padding and the running sums here, the passes
    over the chunks in :func:`ssd_kernels.ssd_chunks`."""
    b, s, h, p = x.shape
    x, dt, B, C = _padded(x, dt, B, C, chunk)
    padded = x.shape[1]
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(
        (dt * A.astype(jnp.float32)).reshape(b, padded // chunk, chunk, h),
        axis=2).reshape(b, padded, h)
    y = ssd_kernels.ssd_chunks(x.reshape(b, padded, h * p), dt, cum, B, C,
                               D, chunk, dtype, interpret)
    return y.reshape(b, padded, h, p)[:, :s]


def _scan_xla(x, dt, A, B, C, D, chunk, dtype):
    """The XLA form."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    c = (s + pad) // chunk
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    # chunks of all sequences on one axis: they differ only in the state
    # they are handed
    xc = x.reshape(b * c, chunk, h, p)
    dtc = dt.reshape(b * c, chunk, h)
    Bc, Cc = B.reshape(b * c, chunk, n), C.reshape(b * c, chunk, n)
    cum = jnp.cumsum(dtc * A, axis=1)  # (bc, q, h), inclusive

    # the state each chunk alone would leave at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc
    own = _dot("gjhp,gjn->ghpn", _weighted(xc, to_end), Bc, dtype)

    def carry_on(state, chunk_of):
        own_c, decay_c = chunk_of
        return decay_c[..., None, None] * state + own_c, state

    own = own.reshape(b, c, h, p, n).swapaxes(0, 1)
    through = jnp.exp(cum[:, -1, :]).reshape(b, c, h).swapaxes(0, 1)
    # (inside shard_map the first carry varies as the chunk states do)
    state0, = vary_alike(jnp.zeros((b, h, p, n), jnp.float32), like=(own,))
    _, carried = lax.scan(carry_on, state0, (own, through))
    carried = carried.swapaxes(0, 1).reshape(b * c, h, p, n)

    g = math.gcd(b * c, CHUNKS_PER_PASS)
    passes = lambda t: t.reshape(b * c // g, g, *t.shape[1:])
    D = D.astype(jnp.float32)
    y = lax.map(lambda args: _within_chunks(*args, D, dtype),
                tuple(map(passes, (xc, dtc, cum, Bc, Cc, carried))))
    return y.reshape(b, s + pad, h, p)[:, :s]


def ssd_census(s: int, chunk: int, heads: int, head_dim: int,
               state: int, itemsize: int = 2) -> dict:
    """What :func:`ssd_scan` computes for one sequence of ``s``
    positions, from the algorithm alone: ``chunks`` (and the padded
    length), the forward pass's matmul FLOPs by part -- ``scores`` (``C
    B^T``, once a chunk for all heads), ``inside`` (the masked scores
    against ``x``), ``states`` (each chunk's own end state) and
    ``carried`` (``C`` against the state handed in) -- their sum
    ``flops_forward``, ``flops_backward`` (two products for each of the
    forward's, and the scores once more: they are computed again), and
    ``bytes_forward``: ``x`` and ``y`` once each in ``itemsize`` bytes,
    ``B`` and ``C`` once, ``dt`` in float32.  ``kernels`` is the static
    account of the kernel path, ``None`` where the sizes do not tile
    (:func:`ssd_kernels.tiles`: the XLA form runs): for the ``forward``
    launch that keeps the entering states and for the ``backward``
    launch, ``grid`` (sequences, head groups, chunks), ``tiles`` a
    launch, ``vmem_bytes`` a grid point (scratch and double-buffered
    blocks), ``hbm_bytes`` read and written, and ``hbm_over_least``,
    those over ``bytes_forward``."""
    chunks = -(-s // chunk)
    inner = heads * head_dim
    parts = {
        "scores": 2.0 * chunks * chunk * chunk * state,
        "inside": 2.0 * chunks * chunk * chunk * inner,
        "states": 2.0 * chunks * chunk * inner * state,
        "carried": 2.0 * chunks * chunk * inner * state,
    }
    forward = sum(parts.values())
    least = float(s) * (2 * inner * itemsize + 2 * state * itemsize
                        + 4 * heads)
    kernels = None
    if ssd_kernels.tiles(chunk, heads, head_dim, state):
        kernels = ssd_kernels.launch_account(chunks * chunk, chunk, heads,
                                             state, itemsize)
        for launch in kernels.values():
            launch["hbm_over_least"] = launch["hbm_bytes"] / least
    return {
        "chunks": chunks, "padded": chunks * chunk, "flops": parts,
        "flops_forward": forward,
        "flops_backward": 2.0 * forward + parts["scores"],
        "bytes_forward": least,
        "kernels": kernels,
    }
