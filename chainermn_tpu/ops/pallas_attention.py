"""Pallas TPU kernels: flash attention and fused cast/scale.

SURVEY.md section 2, native-code obligations: the reference's only
embedded device kernels are the fp16 cast/scale ElementwiseKernels inside
PureNcclCommunicator (#11) and the pack/unpack copy loops (#15).  The TPU
rebuild's counterparts are (a) :func:`fused_cast_scale` — one pass over a
gradient buffer instead of separate cast and divide ops — and (b)
:func:`flash_attention` — a blocked online-softmax attention kernel whose
K/V residency is one (block_k, d) tile per grid step (the S x S score
matrix never exists in HBM; MXU matmuls, fp32 accumulation).
``ulysses_attention`` accepts it through its ``attention_fn`` hook
(``ring_attention`` has its own online-merge core and takes no hook).

Kernels run compiled on TPU and fall back to interpret mode elsewhere
(tests exercise them on CPU via ``interpret=True``).  The backward pass
is a pair of Pallas kernels in the FlashAttention-2 shape: the forward
saves the per-row log-sum-exp, the dq kernel sweeps key blocks, the
dk/dv kernel sweeps query blocks, each recomputing its score tile in
VMEM — training memory stays O(s), never O(s^2), and causally-dead
blocks are skipped entirely.  Tiny compiled shapes (< one 128 lane tile)
take a dense-recompute fallback instead.

All three kernels are DIAGONAL-SPLIT (round 6): each (q block, k block)
grid point is classified dead / interior / masked, and interior blocks
(the fully-unmasked majority at long sequence) run a fast branch with
no iota/mask/select work — see the "Block taxonomy" section below.
A masked block that the diagonal crosses squarely is computed in tiles,
the ones above the diagonal skipped ("Compute tile" below).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

#: the name (``jax.ad_checkpoint.checkpoint_name``) of a forward launch's
#: two results as the backward rules hold them: the output and its
#: log-sum-exp, one name for both, so that no policy keeps one without
#: the other.  Under a ``jax.checkpoint`` whose policy saves it
#: (``models.transformer.REMAT_NAMES``) the recomputation holds no
#: forward launch: the backward kernels read the kept pair.  Under any
#: other policy, or none, the name is an identity.
ATTN_OUT = "attn_out"


def _named(out, lse):
    """``(out, lse)`` of a forward launch under :data:`ATTN_OUT`: what
    the residuals AND the returned pair must be made of (a name on the
    caller's copy keeps a tensor no backward rule reads).  Heads
    narrower than the 128 lanes are named side by side, ``(b, s, h
    dv)``: kept as ``(b, s, h, dv)`` they are padded to the lanes in
    HBM (at width 64 the kept result held twice its bytes); at whole
    lanes that form is the slower one (``PERF.md`` section 6, PR 48)."""
    if out.shape[-1] % 128:
        b, s, h, dv = out.shape
        out = checkpoint_name(out.reshape(b, s, h * dv), ATTN_OUT).reshape(
            b, s, h, dv)
    else:
        out = checkpoint_name(out, ATTN_OUT)
    return out, checkpoint_name(lse, ATTN_OUT)


def _should_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry for a ``pallas_call`` on ``operands``: varying
    over every mesh axis an operand varies over, which ``shard_map``'s
    vma checking requires a kernel's outputs to declare (outside
    ``shard_map`` the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _effective_q_block(block_q: int, s_q: int, interpret: bool) -> int:
    """Clamp the q block for the lse layout: its blocks put bq in the
    lane position, which compiled TPU requires to be a multiple of 128
    OR the full (padded) axis — so for long sequences the q block floors
    at 128 regardless of the requested size."""
    bq = min(block_q, _round_up(s_q, 8))
    if not interpret and _round_up(s_q, 8) >= 128:
        bq = max(bq, 128)
    return bq


# Default block geometry.
# Public entry points take block_q/block_k=None so "caller passed
# nothing" is distinguishable from "caller asked for exactly 1024".
_DEFAULT_BLOCK = 1024
_warned_geometries: set = set()


def _clamp_blocks_for_dim(block_q, block_k, d: int, warn: bool = True,
                          _context: str = "fwd"):
    """Head-dim-aware block clamp (``None`` block = the default).  The
    backward kernel holds three (bq, bk) fp32 score tiles plus
    d-proportional operand/accumulator tiles in scoped VMEM (16 MB hard
    limit; 1024x2048 at d=128 already exceeds it).

    Threshold history: rounds 1-4 clamped every d > 128 on an
    extrapolated VMEM model; the round-5 probe COMPILED AND RAN the
    full 1024x1024 geometry (fwd and bwd) at d=192 and d=256 on v5e,
    so the measured feasibility boundary is d <= 256 and the clamp now
    engages only beyond it (ceil(d/256) shrink — still extrapolated
    out there, stated honestly).

    Explicitly requested blocks that get shrunk emit a ``UserWarning``
    (once per geometry, forward pass only — ``warn=False`` in the
    backward avoids a fwd+bwd double fire) so a tuning sweep at large
    d can see its requested geometry was overridden rather than
    silently measuring the clamp.  Defaults clamp silently."""
    explicit = block_q is not None or block_k is not None
    block_q = _DEFAULT_BLOCK if block_q is None else block_q
    block_k = _DEFAULT_BLOCK if block_k is None else block_k
    if d > 256:
        shrink = -(-d // 256)  # ceil: 384 -> /2, 512 -> /2, 1024 -> /4

        def down(b):
            return max(b // shrink // 128 * 128, 256)

        new_q, new_k = down(block_q), down(block_k)
        if warn and explicit and (new_q, new_k) != (block_q, block_k):
            # key includes the caller context: a bwd-override warning
            # must not suppress a later forward warning for the same
            # geometry (each names a different knob to fix)
            key = (_context, block_q, block_k, d)
            if key not in _warned_geometries:
                _warned_geometries.add(key)
                import warnings

                warnings.warn(
                    f"flash_attention: requested blocks "
                    f"{block_q}x{block_k} clamped to {new_q}x{new_k} "
                    f"for head dim {d} (VMEM budget extrapolated from "
                    "dh<=256 measurements; pass blocks that fit to "
                    "silence)"
                )
        block_q, block_k = new_q, new_k
    return block_q, block_k


# ----------------------------------------------------------------------
# Block taxonomy (the diagonal split)
# ----------------------------------------------------------------------
# Every (q block, k block) grid point falls into exactly one class:
#
#   dead      strictly above the causal diagonal — contributes nothing;
#             skipped entirely (no matmul, no softmax) since round 1.
#   interior  fully unmasked: every (q, k) pair in the block is causally
#             live and unpadded.  The fast branch — no iota, no mask
#             compare, no select; and at the FIRST k step (where the
#             running max is provably monotone because the running
#             state is empty) no rescale of the accumulator either.
#   masked    the diagonal-straddling blocks plus the ragged-tail
#             blocks (k or q padding) — the only blocks that pay the
#             masked online-softmax path.  Per q row this is ~1/q_blocks
#             of the live work at square geometry.


def _when(pred):
    """``pl.when`` that folds statically-known predicates: a Python
    ``True`` emits the body unconditionally, ``False`` emits nothing
    (e.g. the masked branch of a non-causal, non-ragged launch)."""
    if isinstance(pred, bool):
        if pred:
            return lambda f: f()
        return lambda f: None
    return pl.when(pred)


def _and(a, b):
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


def _block_class(first_q, first_k, *, s_k, s_kp, causal, block_q, block_k,
                 s_q=None, s_qp=None, window=None):
    """THE taxonomy predicate: (interior, masked) for one block.

    The single source of truth for block classification — the split
    kernels evaluate it on traced program ids, :func:`block_census`
    on Python ints (``_and``/``_not`` fold either way), so the census
    cannot drift from what the kernels execute.

    The forward leaves ``s_q``/``s_qp`` unset: it never masks q
    (padded q rows are garbage the caller slices off).  The backward
    kernels pass them, so a ragged q tail
    reclassifies its whole block row as masked (its recomputed p would
    otherwise contribute to dk/dv and its garbage lse to dq).  Each
    tail predicate is emitted only when the corresponding padding
    exists (static), so an aligned launch never compares indices.

    ``window`` (static; ``None`` on every launch without one, which
    then traces exactly what it did before the argument): key ``j`` is
    live for query ``i`` only while ``j > i - window``.  A block whose
    last key lies at or below its first row's bound holds no live pair
    (dead: wholly below the window); one whose first key lies at or
    below its LAST row's bound holds pairs on both sides of it
    (masked)."""
    live = (first_k <= first_q + block_q - 1) if causal else True
    needs_mask = (first_k + block_k - 1 > first_q) if causal else False
    if window is not None:
        live = _and(live, first_k + block_k - 1 > first_q - window)
        needs_mask = needs_mask | (
            first_k + window <= first_q + block_q - 1)
    if s_k < s_kp:
        needs_mask = needs_mask | (first_k + block_k > s_k)
    if s_q is not None and s_q < s_qp:
        needs_mask = needs_mask | (first_q + block_q > s_q)
    return _and(live, _not(needs_mask)), _and(live, needs_mask)


# Compute tile (PR 28): a masked block that the causal diagonal crosses
# squarely -- block_q == block_k, first_q == first_k, no padding in the
# launch -- keeps its DMA block and is COMPUTED in t x t tiles: tiles
# above the diagonal issue nothing, tiles below it run the interior
# arithmetic, and only the t x t tiles on the diagonal pay the mask.  At
# 1024 blocks a tile of 256 executes 10 of the block's 16 tiles and
# masks 4; a tile of 512, 3 of 4 and 2.
#
# Measured on v5e at [48, 2048, 128] bf16 (PERF.md, PR 28; us a call,
# whole / 512 / 256 / 128): dq 713 / 614 / 563 / 562, dk/dv 956 / 785 /
# 775 / 800, forward 590 / 544 / 600 / 713.  The forward's time follows
# its rows (each pays its online-softmax update once a strip), not its
# elements: finer strips remove matmul work it was not waiting for and
# add a pipeline bubble each, so it takes the coarse tile.
_COMPUTE_TILE = {"fwd": 512, "bwd": 256}


def _compute_tile(block_q, block_k, kind, *, causal, aligned, tile=None):
    """THE compute-tile rule: the tile a launch's diagonal blocks are
    computed in, or ``None`` where every block is computed whole
    (today's code: non-causal, ``block_q != block_k``, padding on an
    axis the kernel masks, blocks under two tiles).  ``aligned`` is "no
    padding on a masked axis" -- then, with square blocks, the masked
    class of :func:`_block_class` is exactly the blocks on the diagonal.
    ``kind`` is ``"fwd"`` / ``"bwd"`` as in :func:`block_census`;
    ``tile`` overrides the default (the private entry points' static
    argument: interpret-mode tests at tiny shapes)."""
    t = _COMPUTE_TILE[kind] if tile is None else tile
    if not (causal and aligned and block_q == block_k):
        return None
    if block_q % t or block_q < 2 * t:
        return None
    return t


def _class_name(interior, masked) -> str:
    return "masked" if masked else ("interior" if interior else "dead")


def _tile_classes(block, t):
    """Class of each t x t tile (row r, column c) of an aligned diagonal
    block: :func:`_block_class` itself at tile granularity (the block's
    own offset cancels, first_q == first_k), so the kernels' strips and
    the census count from one predicate."""
    n = block // t
    return [[_class_name(*_block_class(
        r * t, c * t, s_k=block, s_kp=block, causal=True, block_q=t,
        block_k=t)) for c in range(n)] for r in range(n)]


#: a block computed whole: one strip, the mask (if any) over all of it
_WHOLE = slice(None)


def _strips(block, tile, masked, below=False):
    """The compute schedule of a block: ``(rows, cols, masked cols)``
    strips.  Without a tile (or for an interior block) the block whole;
    with one, an aligned diagonal block as one strip per q tile, each
    against the run of its live k tiles as ONE rectangle (one matmul a
    product) whose last tile -- ``masked cols``, relative to the run --
    the diagonal crosses.  Dead tiles appear nowhere.  ``below``: the
    block a window's lower bound crosses squarely (its keys a whole
    window before its queries: live strictly above its own diagonal),
    the mirror image: a q tile against the run from its own k tile on,
    whose FIRST tile the bound crosses."""
    if tile is None or not masked:
        return [(_WHOLE, _WHOLE, _WHOLE if masked else None)]
    if below:
        return [(slice(r * tile, (r + 1) * tile), slice(r * tile, block),
                 slice(0, tile)) for r in range(block // tile)]
    strips = []
    for r, line in enumerate(_tile_classes(block, tile)):
        n_live = len(line) - line.count("dead")
        assert line[:n_live] == ["interior"] * (n_live - 1) + ["masked"]
        strips.append((slice(r * tile, (r + 1) * tile),
                       slice(0, n_live * tile),
                       slice((n_live - 1) * tile, n_live * tile)))
    return strips


def _select(mask, x, fill, masked):
    """``where(mask, x, fill)`` on the ``masked`` columns of ``x``
    (``None``: nowhere; the whole: everywhere) -- the columns before
    them (after them, in a strip of ``_strips(..., below=True)``) pass
    through untouched, whole lane tiles sliced off and put back."""
    if masked is None:
        return x
    if masked == _WHOLE:
        return jnp.where(mask, x, fill)
    if masked.stop != x.shape[1]:  # a window block's strip: its first tile
        assert not masked.start
        return jnp.concatenate([
            jnp.where(mask, x[:, :masked.stop], fill), x[:, masked.stop:]],
            axis=1)
    tail = jnp.where(mask, x[:, masked.start:], fill)
    if not masked.start:
        return tail
    return jnp.concatenate([x[:, :masked.start], tail], axis=1)


def block_census(s_q: int, s_k: int, block_q: int, block_k: int,
                 causal: bool, kind: str = "fwd", tile=None,
                 window=None) -> dict:
    """Static census of the block taxonomy for one (batch*head) program:
    how many blocks of each class a launch executes.

    ``kind``: the forward kernel masks only the k axis (padded q rows
    are garbage that gets sliced off), the backward kernels mask q too
    (padded q rows would otherwise contribute to dk/dv) — so a ragged
    q tail reclassifies its row of blocks only for ``kind="bwd"``.
    Mirrors the kernels' run-time predicates exactly
    (``test_block_census_matches_brute_force``).

    The compute tile's counters (``tile`` as in :func:`_compute_tile`):
    ``tile`` is the tile the launch's diagonal blocks are computed in
    (``None``: whole blocks); ``tiles_executed`` / ``tiles_masked`` /
    ``tiles_skipped`` count the tiles of the masked blocks that issue
    matmuls, that also pay the mask, and that issue nothing (all 0
    without a tile); ``executed_units`` / ``masked_units`` are the
    block-units of work and of masked work a program runs (seq 2048 at
    1024 blocks: 3 and 2 whole, 2.5 and 1 at tile 512, 2.25 and 0.5 at
    tile 256).

    ``window``: the census of a window launch of the block-causal
    family (:func:`block_causal_attention_with_lse`; square blocks that
    tile the sequence and the window).  Its grid sweeps ``window //
    block + 1`` k blocks a q block (:func:`_swept_k`, what the kernels
    run), not all of them: the classes count the grid points
    **visited** (``visited``: their number; ``dead``: the points a
    sweep near the sequence's start runs past the diagonal), ``live``
    the (q block, k block) points that hold a live pair by the
    predicate over ALL points, visited or not, and ``below_window`` the
    visited points whose k block lies wholly below the window (0: the
    sweep starts at the window's first block)."""
    if kind not in ("fwd", "bwd"):
        raise ValueError(f"kind must be fwd/bwd, got {kind!r}")
    s_qp, s_kp = _round_up(s_q, block_q), _round_up(s_k, block_k)
    n_q, n_k = s_qp // block_q, s_kp // block_k
    census = {"dead": 0, "interior": 0, "masked": 0,
              "n_q_blocks": n_q, "n_k_blocks": n_k}
    if window is not None:
        if not (causal and s_q == s_k == s_qp and block_q == block_k
                and window % block_q == 0):
            raise ValueError(
                "a window launch is causal over square blocks that tile "
                "the sequence and the window")
        n_t = window // block_q + 1
        points = [(j, _swept_k(j, t, n_t, window))
                  for j in range(n_q) for t in range(n_t)]
        whole = [(j, kb) for j in range(n_q) for kb in range(n_k)]
        is_live = lambda j, kb: any(_bc_class(j, kb, block_q, s_q, window))
        census.update(
            visited=len(points), k_blocks_swept=n_t,
            live=sum(is_live(j, kb) for j, kb in whole),
            below_window=sum((kb + 1) * block_k - 1 <= j * block_q - window
                             for j, kb in points))
    else:
        points = [(j, kb) for j in range(n_q) for kb in range(n_k)]
    for j, kb in points:
        interior, masked = _block_class(
            j * block_q, kb * block_k, s_k=s_k, s_kp=s_kp,
            causal=causal, block_q=block_q, block_k=block_k,
            s_q=s_q if kind == "bwd" else None, s_qp=s_qp, window=window,
        )
        census[_class_name(interior, masked)] += 1
    t = _compute_tile(
        block_q, block_k, kind, causal=causal, tile=tile,
        aligned=s_k == s_kp and (kind == "fwd" or s_q == s_qp),
    )
    # a masked block computed whole counts as one tile on the diagonal
    tiles = sum(_tile_classes(block_q, t) if t else [["masked"]], [])
    live, on = len(tiles) - tiles.count("dead"), tiles.count("masked")
    n_masked = census["masked"]
    census.update(
        tile=t,
        tiles_executed=n_masked * live if t else 0,
        tiles_masked=n_masked * on if t else 0,
        tiles_skipped=n_masked * tiles.count("dead"),
        executed_units=census["interior"] + n_masked * live / len(tiles),
        masked_units=n_masked * on / len(tiles),
    )
    return census


def launch_census(s_q: int, s_k: int, d: int, block_q=None, block_k=None,
                  bwd_block_q=None, bwd_block_k=None,
                  causal: bool = True, interpret: bool = False) -> dict:
    """Census of the geometry a launch will ACTUALLY run: resolves
    ``None`` blocks to the defaults, then applies every clamp the entry
    points apply — the head-dim clamp (:func:`_clamp_blocks_for_dim`),
    the q-block lane-tile floor (:func:`_effective_q_block`; compiled
    TPU floors bq at 128), and the k sequence clamp — and the compute
    tile (:func:`_compute_tile`), and returns
    ``{"fwd": census, "bwd": census}``.  ``chip_smoke.py`` prints this
    instead of calling :func:`block_census` on the *requested* blocks,
    so a clamped launch cannot print a census for a geometry it never
    ran.

    Two run-time escapes are NOT reflected (they depend on the backend,
    not the geometry): the backward's scoped-VMEM retry can ceil-shrink
    its blocks further on generations where the d-clamp is too loose
    (``_backward_with_vmem_retry`` warns when it does — a capture that
    saw that warning must not divide by this census), and sequences
    below one lane tile take the dense-recompute fallback with no
    blocks at all."""
    fbq, fbk = _clamp_blocks_for_dim(block_q, block_k, d, warn=False)
    bq = block_q if bwd_block_q is None else bwd_block_q
    bk = block_k if bwd_block_k is None else bwd_block_k
    bbq, bbk = _clamp_blocks_for_dim(bq, bk, d, warn=False)

    def eff(b_q, b_k):
        # exactly _flash_forward/_flash_backward's block resolution
        return (_effective_q_block(b_q, s_q, interpret),
                min(b_k, _round_up(s_k, 8)))

    return {
        "fwd": block_census(s_q, s_k, *eff(fbq, fbk), causal, "fwd"),
        "bwd": block_census(s_q, s_k, *eff(bbq, bbk), causal, "bwd"),
    }


# ----------------------------------------------------------------------
# Flash attention — forward kernel
# ----------------------------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, s_k: int, s_kp: int, causal: bool,
                      scale: float, block_q: int, block_k: int, tile=None):
    """Diagonal-split forward kernel.

    Grid (batch*head, q_blocks, k_blocks); the k dimension is innermost
    and sequential on TPU, so the fp32 accumulator / running max /
    denominator live in VMEM scratch across k steps.  K/V residency is
    one (block_k, d) tile per step.  Each (j, kb) grid point routes to
    one of the taxonomy branches (see module section "Block taxonomy").
    The interior branch carries no iota/mask/select (the mask is
    provably all-true there, so ``where(mask, s, -inf)`` would be the
    identity), and the first k step (kb == 0, always live) writes the
    running state directly instead of rescaling an empty accumulator —
    with m_old = -inf the rescale factor exp(m_old - m_new) is exactly
    0 in fp32, so skipping it is bit-identical, and it removes the
    separate init pass plus one (bq, d) multiply-add per q row.

    ``tile`` (:func:`_compute_tile`): a masked block is then an aligned
    diagonal block and runs strip by strip (:func:`_strips`): each q
    tile takes ONE online-softmax update against its live keys only,
    the mask on the last tile of the strip.  Tiles above the diagonal
    contributed exact zeros (``exp(-1e30 - m)``), so nothing of the
    mathematics is left out; only fp32 summation order inside the block
    can differ."""
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    first_q = j * block_q
    first_k = kb * block_k
    interior, masked = _block_class(
        first_q, first_k, s_k=s_k, s_kp=s_kp, causal=causal,
        block_q=block_q, block_k=block_k,
    )

    def _attend(with_mask):
        mask = _piece_mask(
            first_q, first_k, tile, s_k=s_k, s_kp=s_kp, causal=causal,
            block_q=block_q, block_k=block_k,
        ) if with_mask else None
        for rows, cols, masked in _strips(block_q, tile, with_mask):
            q = q_ref[0, rows, :].astype(jnp.float32) * scale  # (rows, d)
            k_blk = k_ref[0, cols, :].astype(jnp.float32)      # (cols, d)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            s = lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (rows, cols)
            s = _select(mask, s, _NEG_INF, masked)
            _softmax_update(rows, s, v_blk)

    def _softmax_update(rows, s, v_blk):
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        stat_shape = (m_blk.shape[0], m_ref.shape[1])

        @pl.when(kb == 0)
        def _first():
            # First k block (always live): the running state is empty,
            # so the online-softmax rescale is provably a no-op — write
            # the block statistics directly.
            p = jnp.exp(s - m_blk)
            m_ref[rows, :] = jnp.broadcast_to(m_blk, stat_shape)
            l_ref[rows, :] = jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), stat_shape
            )
            acc_ref[rows, :] = lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(kb != 0)
        def _rest():
            m_old = m_ref[rows, 0:1]
            m_new = jnp.maximum(m_old, m_blk)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_ref[rows, 0:1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            l_ref[rows, :] = jnp.broadcast_to(l_new, stat_shape)
            m_ref[rows, :] = jnp.broadcast_to(m_new, stat_shape)
            acc_ref[rows, :] = alpha * acc_ref[rows, :] + lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @_when(interior)
    def _fast():
        _attend(with_mask=False)

    @_when(masked)
    def _slow():
        _attend(with_mask=True)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        # Fully-masked rows (query padding) have l == 0.
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
        ).astype(o_ref.dtype)
        # Per-row log-sum-exp of the (scaled) scores — the backward's
        # softmax statistic.  Stored broadcast over 8 sublanes because a
        # TPU block's second-to-last dim must be a multiple of 8.
        # Garbage on padded rows; the backward masks those by q index.
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30)))[
                None, :
            ],
            lse_ref.shape[1:],
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret",
                     "tile"),
)
def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   tile=None):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _clamp_blocks_for_dim(block_q, block_k, d)
    bq = _effective_q_block(block_q, s_q, interpret)
    bk = min(block_k, _round_up(s_k, 8))

    def to_bh(x, s, blk):
        # (b, s, h, d) -> (b*h, s_padded_to_blk, d) [fwd]
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
        pad = _round_up(s, blk) - s
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x

    qb = to_bh(q, s_q, bq)
    kb_, vb = to_bh(k, s_k, bk), to_bh(v, s_k, bk)
    s_qp, s_kp = qb.shape[1], kb_.shape[1]

    kernel = functools.partial(
        _flash_fwd_kernel, s_k=s_k, s_kp=s_kp, causal=causal,
        scale=scale, block_q=bq, block_k=bk,
        tile=_compute_tile(
            bq, bk, "fwd", causal=causal, aligned=s_k == s_kp, tile=tile,
        ),
    )
    grid = (b * h, s_qp // bq, s_kp // bk)
    kv_index = _kv_index(bq, bk, causal)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            _out_struct((b * h, s_qp, d), q.dtype, q, k, v),
            _out_struct((b * h, 8, s_qp), jnp.float32, q, k, v),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 8, bq), lambda i, j, kb: (i, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),    # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denom (col 0)
        ],
        interpret=interpret,
        name="_flash_forward",
    )(qb, kb_, vb)
    out = out[:, :s_q].reshape(b, h, s_q, d)
    return jnp.moveaxis(out, 1, 2), lse[:, 0, :s_q]  # (b,s,h,d), (bh,s_q)


# ----------------------------------------------------------------------
# Flash attention — backward kernels (FlashAttention-2 shape)
# ----------------------------------------------------------------------
def _tail_mask(first_q, first_k, *, s_k, s_kp, causal, block_q, block_k,
               s_q=None, s_qp=None):
    """THE masked-branch mask, statically thinned: each padding compare
    exists only when that padding exists (s < s_padded, static), so an
    aligned causal launch's diagonal blocks pay only the causal
    compare.  Dropped compares are provably all-true there, so the
    thinning is bit-identical to the full mask.  Same
    ``s_q``/``s_qp`` convention as :func:`_block_class`: the forward
    leaves them unset (it never masks q), the backward passes them."""
    mask_q = s_q is not None and s_q < s_qp
    need_q = causal or mask_q
    need_k = causal or s_k < s_kp
    q_idx = first_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) if need_q else None
    k_idx = first_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    ) if need_k else None
    mask = True
    if s_k < s_kp:
        mask = _and(mask, k_idx < s_k)
    if mask_q:
        mask = _and(mask, q_idx < s_q)
    if causal:
        mask = _and(mask, k_idx <= q_idx)
    return mask


def _kv_index(bq, bk, causal):
    """K/V block index of grid point (i, j, kb), k innermost.  A dead
    grid point (k block wholly above the diagonal) names the row's last
    live block again: the pipeline sees an unchanged index and fetches
    nothing, there and -- the next row starting at block 0 again -- at
    the step after it, which a dead step's missing compute would leave
    exposed (v5e, [48, 2048, 128]: dq 563 -> 499 us a call, forward
    600 -> 572; PERF.md, PR 28).  The dk/dv kernel's dead points come
    first in their sweep and hide behind the previous step already."""
    if not causal:
        return lambda i, j, kb: (i, kb, 0)
    return lambda i, j, kb: (
        i, jnp.minimum(kb, (j * bq + bq - 1) // bk), 0)


def _piece_mask(first_q, first_k, tile, **geometry):
    """Mask of a masked piece: the whole block's :func:`_tail_mask`, or
    with a compute tile the t x t diagonal tile's -- the same causal
    compare at offsets (0, 0), because an aligned diagonal block has
    first_q == first_k and every tile on its diagonal the same again
    (one mask serves them all)."""
    if tile is None:
        return _tail_mask(first_q, first_k, **geometry)
    return _tail_mask(0, 0, s_k=tile, s_kp=tile, causal=True,
                      block_q=tile, block_k=tile)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, s_q: int, s_qp: int,
                         s_k: int, s_kp: int, causal: bool, scale: float,
                         block_q: int, block_k: int, tile=None):
    """Diagonal-split dq kernel (grid (batch*head, q_blocks, k_blocks);
    k innermost/sequential, dq accumulated in VMEM): interior blocks
    recompute the (bq, bk) probability tile straight from q, k and the
    saved row log-sum-exp with no iota/mask/select work (the mask is
    all-true there, so ``where(mask, p, 0)`` would be the identity);
    only the diagonal/tail blocks pay the masked path, whose explicit
    zeroing is needed because padded rows carry garbage lse.  With a
    compute ``tile`` a masked block runs q strip by q strip against its
    live keys only (:func:`_strips`)."""
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    first_q = j * block_q
    first_k = kb * block_k
    interior, masked = _block_class(
        first_q, first_k, s_q=s_q, s_qp=s_qp, s_k=s_k, s_kp=s_kp,
        causal=causal, block_q=block_q, block_k=block_k,
    )

    def _accum(with_mask):
        mask = _piece_mask(
            first_q, first_k, tile, s_q=s_q, s_qp=s_qp, s_k=s_k,
            s_kp=s_kp, causal=causal, block_q=block_q, block_k=block_k,
        ) if with_mask else None
        for rows, cols, masked in _strips(block_q, tile, with_mask):
            q = q_ref[0, rows, :].astype(jnp.float32)
            k_blk = k_ref[0, cols, :].astype(jnp.float32)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            do = do_ref[0, rows, :].astype(jnp.float32)
            s = lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            p = _select(mask, p, 0.0, masked)
            dp = lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dq_acc[rows, :] += lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    @_when(interior)
    def _fast():
        _accum(with_mask=False)

    @_when(masked)
    def _slow():
        _accum(with_mask=True)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, s_q: int,
                          s_qp: int, s_k: int, s_kp: int, causal: bool,
                          scale: float, block_q: int, block_k: int, tile=None):
    """Diagonal-split dk/dv kernel (grid (batch*head, k_blocks,
    q_blocks); q innermost/sequential) — same taxonomy routing as the
    split dq kernel, the same q strips with a compute ``tile`` (a strip
    adds into the dk/dv rows of its live keys only)."""
    kb = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    first_q = j * block_q
    first_k = kb * block_k
    interior, masked = _block_class(
        first_q, first_k, s_q=s_q, s_qp=s_qp, s_k=s_k, s_kp=s_kp,
        causal=causal, block_q=block_q, block_k=block_k,
    )

    def _accum(with_mask):
        mask = _piece_mask(
            first_q, first_k, tile, s_q=s_q, s_qp=s_qp, s_k=s_k,
            s_kp=s_kp, causal=causal, block_q=block_q, block_k=block_k,
        ) if with_mask else None
        for rows, cols, masked in _strips(block_q, tile, with_mask):
            q = q_ref[0, rows, :].astype(jnp.float32)
            k_blk = k_ref[0, cols, :].astype(jnp.float32)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            do = do_ref[0, rows, :].astype(jnp.float32)
            s = lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            p = _select(mask, p, 0.0, masked)
            dv_acc[cols, :] += lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dk_acc[cols, :] += lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    @_when(interior)
    def _fast():
        _accum(with_mask=False)

    @_when(masked)
    def _slow():
        _accum(with_mask=True)

    @pl.when(j == n_j - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret",
                     "tile"),
)
def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, g_lse=None, tile=None):
    """(b, s, h, d)-layout backward via the two kernels above.

    ``g_lse``: optional (b*h, s_q) cotangent of the log-sum-exp output
    (for :func:`flash_attention_with_lse`).  Since
    ``d lse_i / d s_ij = p_ij``, the lse cotangent enters the score
    gradient as ``ds += p * g_lse`` — algebraically identical to
    replacing ``delta`` with ``delta - g_lse``, so the kernels are
    reused unchanged."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _clamp_blocks_for_dim(block_q, block_k, d,
                                             warn=False)
    bq = _effective_q_block(block_q, s_q, interpret)
    bk = min(block_k, _round_up(s_k, 8))

    def to_bh(x, s, blk):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
        pad = _round_up(s, blk) - s
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x

    qb = to_bh(q, s_q, bq)
    dob = to_bh(g, s_q, bq)
    ob = to_bh(out, s_q, bq)
    kb_, vb = to_bh(k, s_k, bk), to_bh(v, s_k, bk)
    s_qp, s_kp = qb.shape[1], kb_.shape[1]

    delta = jnp.sum(
        dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1
    )  # (bh, s_qp)
    if g_lse is not None:
        pad_d = s_qp - s_q
        gl = jnp.pad(g_lse, ((0, 0), (0, pad_d))) if pad_d else g_lse
        delta = delta - gl.astype(jnp.float32)
    pad_q = s_qp - s_q
    lse_p = jnp.pad(lse, ((0, 0), (0, pad_q))) if pad_q else lse
    # 8-sublane broadcast layout (TPU blocks need sublane-dim % 8 == 0)
    bh = b * h
    delta = jnp.broadcast_to(delta[:, None], (bh, 8, s_qp))
    lse_p = jnp.broadcast_to(lse_p[:, None], (bh, 8, s_qp))

    n_q, n_k = s_qp // bq, s_kp // bk
    kwargs = dict(s_q=s_q, s_qp=s_qp, s_k=s_k, s_kp=s_kp,
                  causal=causal, scale=scale, block_q=bq, block_k=bk,
                  tile=_compute_tile(
                      bq, bk, "bwd", causal=causal, tile=tile,
                      aligned=s_k == s_kp and s_q == s_qp,
                  ))

    kv_index = _kv_index(bq, bk, causal)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kwargs),
        out_shape=_out_struct((b * h, s_qp, d), q.dtype, q, k, v, g),
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),   # q
            pl.BlockSpec((1, bk, d), kv_index),                     # k
            pl.BlockSpec((1, bk, d), kv_index),                     # v
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),   # do
            pl.BlockSpec((1, 8, bq), lambda i, j, kb: (i, 0, j)),   # lse
            pl.BlockSpec((1, 8, bq), lambda i, j, kb: (i, 0, j)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="_flash_backward_dq",
    )(qb, kb_, vb, dob, lse_p, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kwargs),
        out_shape=[
            _out_struct((b * h, s_kp, d), k.dtype, q, k, v, g),
            _out_struct((b * h, s_kp, d), v.dtype, q, k, v, g),
        ],
        grid=(b * h, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, kb, j: (i, j, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda i, kb, j: (i, kb, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda i, kb, j: (i, kb, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda i, kb, j: (i, j, 0)),   # do
            pl.BlockSpec((1, 8, bq), lambda i, kb, j: (i, 0, j)),   # lse
            pl.BlockSpec((1, 8, bq), lambda i, kb, j: (i, 0, j)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, bk, d), lambda i, kb, j: (i, kb, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="_flash_backward_dkdv",
    )(qb, kb_, vb, dob, lse_p, delta)

    def from_bh(x, s):
        return jnp.moveaxis(x[:, :s].reshape(b, h, s, d), 1, 2)

    return from_bh(dq, s_q), from_bh(dk, s_k), from_bh(dv, s_k)


# ----------------------------------------------------------------------
# Public API
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=None,
                    bwd_block_q=None, bwd_block_k=None):
    """Blocked flash attention: (b, s, h, d) x 3 -> (b, s, h, d).

    Numerics match :func:`chainermn_tpu.ops.multi_head_attention` (fp32
    online softmax).  ``interpret=None`` auto-selects: compiled on TPU,
    interpreter elsewhere.

    Default blocks (``None``) resolve to 1024x1024 (what both LM cells
    run; 1024x2048 exceeds the 16 MB scoped-vmem limit in the
    backward).  Blocks are clamped to the (padded) sequence length,
    so short sequences are unaffected, and shrunk for head dims beyond
    the measured d <= 256 feasibility boundary
    (``_clamp_blocks_for_dim``) so the backward stays inside scoped
    VMEM at geometries no sweep has covered — explicitly passed blocks
    warn when shrunk; defaults clamp silently.

    ``bwd_block_q`` / ``bwd_block_k``: SEPARATE backward block
    geometry (``None`` = inherit the forward's).  The scoped-VMEM
    limit binds only the backward (it holds three (bq, bk) fp32 score
    tiles; the forward holds one), so the forward can stream wider K/V
    blocks than the backward survives — e.g. fwd 1024x2048 with bwd
    1024x1024 (compiles for v5e: ``tests/test_tpu_compile.py``; not
    timed on this installation).

    Compute tile (no argument: :func:`_compute_tile` resolves it from
    the launch's shapes): a causal launch whose diagonal blocks are
    square, aligned and at least two tiles wide computes them in q
    strips against their live keys only, masking the one tile a strip
    has on the diagonal -- tile 256 in the backward kernels, 512 in the
    forward.  At seq 2048 with the default blocks a program then
    executes 2.25 (forward 2.5) block-units instead of 3 and masks 0.5
    (1) instead of 2; at seq 8192, 33 (34) instead of 36
    (:func:`launch_census` reports it).  Measured on v5e at
    ``[48, 2048, 128]``: forward, dq and dk/dv together -20.9 %
    (PERF.md, PR 28).  Same result up to fp32 summation order inside a
    diagonal block; every other launch runs exactly the code it ran
    before.

    The backward rule's residuals are ``(q, k, v, out, lse)`` with the
    forward launch's two results named :data:`ATTN_OUT`: a
    ``jax.checkpoint`` around the caller whose policy saves that name
    keeps them, and its recomputation then holds no forward launch
    (under any other policy, or none, the name is an identity).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            _should_interpret(interpret))
    return out


def _is_vmem_oom(e: Exception) -> bool:
    """Recognize a scoped-VMEM budget failure from the Mosaic compiler
    (the backward holds three (bq, bk) fp32 score tiles; on hardware
    generations beyond the measured v5e d<=256 boundary the default
    geometry can exceed the 16 MB scoped limit at compile time)."""
    s = str(e).lower()
    return "vmem" in s and any(
        m in s for m in ("scoped", "exceed", "limit", "budget")
    )


def _shrink_blocks(bq: int, bk: int):
    """One retry notch: halve both blocks, floored at the 128 lane tile
    (blocks already at or below the floor stay put — shrinking cannot
    GROW a sub-tile block).  Returns None when the geometry cannot
    shrink further."""
    def down(b):
        return min(max(b // 2 // 128 * 128, 128), b)

    nq, nk = down(bq), down(bk)
    return None if (nq, nk) == (bq, bk) else (nq, nk)


_bwd_probe_cache: dict = {}


def _bwd_compile_blocked(arrays, causal, scale, bq, bk) -> bool:
    """AOT-compile probe: does the backward at this geometry compile on
    the real backend?  Needed because the production path wraps the step
    in an outer ``jax.jit`` — there the Mosaic compile error would
    surface during the STEP's compilation, after the vjp rule returned,
    where no try/except can reach it.  Probing via
    ``_flash_backward.lower(...).compile()`` with abstract shapes raises
    the scoped-VMEM failure at trace time instead, where the shrink loop
    can act.  Cached per (shapes, geometry); any probe *infrastructure*
    error counts as "not blocked" — the probe must never break a path
    that would have run."""
    key = (
        tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
        causal, scale, bq, bk,
    )
    if key in _bwd_probe_cache:
        return _bwd_probe_cache[key]
    blocked = False
    try:
        sds = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
        _flash_backward.lower(
            *sds, causal, scale, bq, bk, False
        ).compile()
    except Exception as e:
        blocked = _is_vmem_oom(e)
    _bwd_probe_cache[key] = blocked
    return blocked


def _backward_with_vmem_retry(q, k, v, out, lse, g, causal, scale,
                              block_q, block_k, interp, g_lse=None):
    """Run the backward kernels; on a scoped-VMEM compile failure retry
    with progressively ceil-shrunk block geometry (ADVICE round-5: the
    d<=256 clamp boundary was measured on v5e only — other generations
    may reject the default 1024x1024 backward at compile time).  The
    measured fast path is untouched: the first attempt is exactly the
    requested/default geometry, and the shrink loop only runs after a
    recognized VMEM failure — caught directly on the eager path, or via
    the AOT compile probe (:func:`_bwd_compile_blocked`) on the
    compiled-TPU path where the failure would otherwise surface outside
    this frame.  Same retry-on-failure shape as the resilience layer's
    transport retries, applied to kernel compilation.
    """
    d = q.shape[-1]
    bq = _DEFAULT_BLOCK if block_q is None else block_q
    bk = _DEFAULT_BLOCK if block_k is None else block_k
    probe = not interp and jax.default_backend() == "tpu"
    tried = set()
    while True:
        # the geometry that will actually run (post head-dim clamp) —
        # dedupe on it so a shrink that clamps to the same program
        # doesn't loop forever
        eff = _clamp_blocks_for_dim(bq, bk, d, warn=False)
        tried.add(eff)
        try:
            if probe and _bwd_compile_blocked(
                (q, k, v, out, lse, g), causal, scale, bq, bk
            ):
                raise RuntimeError(
                    f"scoped vmem limit exceeded at {eff[0]}x{eff[1]} "
                    "(AOT compile probe)"
                )
            return _flash_backward(q, k, v, out, lse, g, causal, scale,
                                   bq, bk, interp, g_lse=g_lse)
        except Exception as e:
            if not _is_vmem_oom(e):
                raise
            shrunk = _shrink_blocks(*eff)
            if shrunk is None or shrunk in tried:
                raise
            import warnings

            warnings.warn(
                f"flash_attention backward: geometry {eff[0]}x{eff[1]} "
                f"exceeded scoped VMEM on this device; retrying with "
                f"{shrunk[0]}x{shrunk[1]}"
            )
            try:  # observable on any attached resilience log
                from ..resilience.log import emit

                emit("kernel_retry", "pallas.flash_backward",
                     from_blocks=eff, to_blocks=shrunk)
            except Exception:
                pass
            bq, bk = shrunk


def _resolve_bwd_blocks(block_q, block_k, bwd_block_q, bwd_block_k, d):
    """Backward block geometry: inherit the forward's unless
    overridden.  EXPLICIT bwd overrides get the clamp WARNING here
    (inside ``_flash_backward`` the clamp is warn=False, tuned for the
    shared case where the forward already warned) — but the returned
    blocks stay UNCLAMPED: ``_flash_backward`` applies the one real
    clamp, so the geometry that runs is exactly the geometry the
    warning names (a clamp here too would shrink twice — the clamp is
    not idempotent: 1024 -> 512 -> 256 at d=384).  Shared by both
    backward rules so the policy cannot diverge between entry points."""
    explicit_bwd = bwd_block_q is not None or bwd_block_k is not None
    bq = block_q if bwd_block_q is None else bwd_block_q
    bk = block_k if bwd_block_k is None else bwd_block_k
    if explicit_bwd:
        _clamp_blocks_for_dim(bq, bk, d, warn=True,
                              _context="bwd")  # warning only
    return bq, bk


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
                    bwd_block_q=None, bwd_block_k=None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _named(*_flash_forward(
        q, k, v, causal, scale, block_q, block_k,
        _should_interpret(interpret)))
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret,
                    bwd_block_q, bwd_block_k, residuals, g):
    q, k, v, out, lse = residuals
    if scale is None:
        scale = q.shape[-1] ** -0.5
    interp = _should_interpret(interpret)
    if not interp and (q.shape[1] < 128 or k.shape[1] < 128):
        # Compiled path with sub-lane-tile sequences (explicit small
        # *blocks* are clamped by _effective_q_block, but a sequence
        # shorter than a lane tile cannot be): a dense recompute is both
        # safe and cheap at these sizes.
        from .attention import multi_head_attention

        _, vjp = jax.vjp(
            lambda q, k, v: multi_head_attention(
                q, k, v, causal=causal, scale=scale
            ),
            q, k, v,
        )
        return vjp(g)
    bq, bk = _resolve_bwd_blocks(block_q, block_k, bwd_block_q,
                                 bwd_block_k, q.shape[-1])
    return _backward_with_vmem_retry(q, k, v, out, lse, g, causal,
                                     scale, bq, bk, interp)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _dense_attention_with_lse(q, k, v, causal, scale):
    """Plain-JAX (out, lse) attention — the differentiable small-shape
    fallback for :func:`flash_attention_with_lse` (fp32 softmax)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        kj = lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where((kj <= qi)[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / den, v.astype(jnp.float32))
    lse = (m + jnp.log(den))[..., 0]  # (b, h, s_q)
    return out.astype(q.dtype), jnp.moveaxis(lse, 1, 2)  # lse (b, s_q, h)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=None, block_k=None, interpret=None,
                             bwd_block_q=None, bwd_block_k=None):
    """Flash attention returning ``(out, lse)`` with BOTH outputs
    differentiable — ``lse`` is the per-row log-sum-exp of the scaled
    scores, shaped (b, s_q, h).

    This is the building block for blockwise/ring composition
    (:func:`chainermn_tpu.parallel.ring_attention` with
    ``use_flash=True``): partial outputs over K/V blocks merge exactly
    via their lse, and gradients flow through the merge weights because
    the lse VJP is folded into the same backward kernels (see
    ``_flash_backward``'s ``g_lse``)."""
    out, lse = _flash_with_lse_fwd_rule(
        q, k, v, causal, scale, block_q, block_k, interpret,
    )[0]
    return out, lse


def _flash_with_lse_fwd_rule(q, k, v, causal, scale, block_q, block_k,
                             interpret, bwd_block_q=None,
                             bwd_block_k=None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    interp = _should_interpret(interpret)
    if not interp and (q.shape[1] < 128 or k.shape[1] < 128):
        # Sub-lane-tile compiled shapes: dense path for value AND grads.
        out, lse = _dense_attention_with_lse(q, k, v, causal, scale)
        return (out, lse), (q, k, v, None, None)
    out, lse_bh = _named(*_flash_forward(q, k, v, causal, scale, block_q,
                                         block_k, interp))
    b, s_q, h, _ = q.shape
    lse = jnp.moveaxis(lse_bh.reshape(b, h, s_q), 1, 2)  # (b, s_q, h)
    return (out, lse), (q, k, v, out, lse_bh)


def _flash_with_lse_bwd_rule(causal, scale, block_q, block_k, interpret,
                             bwd_block_q, bwd_block_k, residuals, g):
    q, k, v, out, lse_bh = residuals
    g_out, g_lse = g
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if out is None:  # dense fallback residuals
        _, vjp = jax.vjp(
            lambda q, k, v: _dense_attention_with_lse(
                q, k, v, causal, scale
            ),
            q, k, v,
        )
        return vjp((g_out, g_lse))
    b, s_q, h, _ = q.shape
    g_lse_bh = jnp.moveaxis(g_lse, 1, 2).reshape(b * h, s_q)
    bq, bk = _resolve_bwd_blocks(block_q, block_k, bwd_block_q,
                                 bwd_block_k, q.shape[-1])
    return _backward_with_vmem_retry(
        q, k, v, out, lse_bh, g_out, causal, scale, bq, bk,
        _should_interpret(interpret), g_lse=g_lse_bh,
    )


flash_attention_with_lse.defvjp(
    _flash_with_lse_fwd_rule, _flash_with_lse_bwd_rule
)


def flash_attention_fn(block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       bwd_block_q: Optional[int] = None,
                       bwd_block_k: Optional[int] = None):
    """Adapter producing the ``attention_fn`` signature used by
    ``ulysses_attention``: ``(q, k, v, causal, scale)``."""

    def fn(q, k, v, causal, scale):
        return flash_attention(q, k, v, causal, scale, block_q, block_k,
                               interpret, bwd_block_q, bwd_block_k)

    return fn


# ----------------------------------------------------------------------
# Flash decode — q_len=1 against a PAGED KV cache (serving tier)
# ----------------------------------------------------------------------
def _flash_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, n_heads: int,
                         page_size: int, scale: float):
    """Decode-geometry flash kernel: ONE query row per (batch, head)
    program against that request's pages, walked page-by-page through
    the block table (scalar-prefetched — the index map reads it, so
    only the request's OWN pages are ever fetched into VMEM).

    Grid (batch*heads, pages_per_slot); the page dimension is innermost
    and sequential, so the online-softmax running state lives in VMEM
    scratch exactly like the training kernel's k loop.  The query is
    pre-broadcast to 8 sublanes (TPU tile floor — same trick as the
    forward kernel's lse layout); row 0 of the output block is the
    answer.  Pages past the request's length are dead (skipped
    entirely); the partial tail page masks by position.  A length-0
    slot (padded batch slot) has no live pages — finalize writes zeros.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    b = i // n_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    live = j * page_size < length

    @pl.when(live)
    def _attend():
        q = q_ref[0].astype(jnp.float32) * scale      # (8, d)
        k_blk = k_ref[0, 0].astype(jnp.float32)        # (bs, d)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (8, bs)
        pos = j * page_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(pos < length, s, _NEG_INF)
        m_old = m_ref[:, 0:1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = alpha * acc_ref[:] + lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _flash_decode(q, k_pages, v_pages, block_tables, lengths, scale,
                  interpret):
    b, h, d = q.shape
    n_pages, page_size = k_pages.shape[0], k_pages.shape[1]
    pages_per_slot = block_tables.shape[1]
    # head-major page layout so the kernel block's trailing dims are
    # (page_size, d) — the sublane/lane tile the hardware wants
    kh = jnp.moveaxis(k_pages, 2, 0)  # (h, n_pages, bs, d)
    vh = jnp.moveaxis(v_pages, 2, 0)
    # 8-sublane broadcast of the single query row (TPU tile floor)
    q8 = jnp.broadcast_to(
        q.reshape(b * h, 1, d), (b * h, 8, d)
    )
    grid = (b * h, pages_per_slot)
    kernel = functools.partial(
        _flash_decode_kernel, n_heads=h, page_size=page_size,
        scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 8, d), lambda i, j, bt, ln: (i, 0, 0)),
                pl.BlockSpec(
                    (1, 1, page_size, d),
                    lambda i, j, bt, ln, h=h: (i % h, bt[i // h, j], 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, page_size, d),
                    lambda i, j, bt, ln, h=h: (i % h, bt[i // h, j], 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 8, d), lambda i, j, bt, ln: (i, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((8, d), jnp.float32),    # acc
                pltpu.VMEM((8, 128), jnp.float32),  # running max (col 0)
                pltpu.VMEM((8, 128), jnp.float32),  # running denom
            ],
        ),
        out_shape=_out_struct((b * h, 8, d), q.dtype, q, k_pages, v_pages),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q8, kh, vh)
    return out[:, 0].reshape(b, h, d)


def flash_decode(q, k_pages, v_pages, block_tables, lengths,
                 scale: Optional[float] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-token paged-cache attention (the serving tier's decode
    geometry): each batch slot's one query attends against the pages
    its block table names.

    Args:
      q: (batch, heads, d) — one query per decode slot.
      k_pages / v_pages: (num_pages, page_size, heads, d) — the shared
        page pool (``serving.kv_cache.PagedKVCache`` layout for one
        layer).
      block_tables: (batch, pages_per_slot) int32 page ids per slot.
      lengths: (batch,) int32 — live cache positions per slot (the new
        token's k/v already written, so a decoding slot passes
        ``cached + 1``).  Length-0 slots (padding) return zeros.
    Returns:
      (batch, heads, d) in ``q.dtype``.

    Numerics: fp32 online softmax over pages, like the training
    kernel — agrees with :func:`paged_decode_reference` (one exact fp32
    softmax over the gathered cache) to float roundoff, and exactly
    when a request fits one page (single-block online softmax is the
    dense computation).  The serving decode *step* uses the dense
    paged attend for its bit-exactness contract; this kernel is the
    TPU fast path (``DecodeEngine(attention_impl="flash")``).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_decode(q, k_pages, v_pages, block_tables, lengths,
                         float(scale), _should_interpret(interpret))


def paged_decode_reference(q, k_pages, v_pages, block_tables, lengths,
                           scale: Optional[float] = None) -> jnp.ndarray:
    """Dense oracle for :func:`flash_decode`: gather every slot's pages
    into a contiguous buffer and run one exact fp32 softmax.  Same
    masking contract (positions >= length dead; length 0 -> zeros)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b = q.shape[0]
    page_size = k_pages.shape[1]
    kg = k_pages[block_tables]  # (b, n, bs, h, d)
    vg = v_pages[block_tables]
    n_tot = kg.shape[1] * page_size
    kg = kg.reshape(b, n_tot, *kg.shape[3:])
    vg = vg.reshape(b, n_tot, *vg.shape[3:])
    s = jnp.einsum(
        "bhd,bkhd->bhk", q.astype(jnp.float32) * scale,
        kg.astype(jnp.float32),
    )
    pos = jnp.arange(n_tot)[None, None, :]
    mask = pos < lengths[:, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    den = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    # divide AFTER the PV product — the kernel's finalize order, so a
    # single-page request (where online softmax IS the dense softmax)
    # matches bit for bit (pinned by test)
    out = jnp.einsum(
        "bhk,bkhd->bhd", p, vg.astype(jnp.float32)
    ) / den
    # length-0 (padded) slots: with EVERY position masked the max IS
    # the mask value, so exp(s - m) == 1 everywhere and the softmax
    # degenerates to a mean of garbage — zero them explicitly, matching
    # the kernel (whose pages are all dead there, acc == 0)
    out = jnp.where(lengths[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# Fused cast + scale (the reference's PureNccl fp16 kernels, #11)
# ----------------------------------------------------------------------
def _cast_scale_kernel(x_ref, o_ref, *, scale: float):
    o_ref[:] = (x_ref[:].astype(jnp.float32) * scale).astype(o_ref.dtype)


def fused_cast_scale(x: jnp.ndarray, scale: float, dtype,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """``(x * scale).astype(dtype)`` in one VMEM pass.

    Parity: the cast-and-scale ElementwiseKernels PureNcclCommunicator
    launches around its fp16 allreduce (divide-by-size fused with the
    cast-back).  Any shape; internally flattened to lane-aligned tiles.
    """
    if x.size == 0:
        return (x.astype(jnp.float32) * scale).astype(dtype)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lane = 128
    rows = _round_up((n + lane - 1) // lane, 8)
    pad = rows * lane - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    tiled = flat.reshape(rows, lane)
    block_rows = min(rows, 512)
    rows_p = _round_up(rows, block_rows)
    if rows_p != rows:
        tiled = jnp.pad(tiled, ((0, rows_p - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_cast_scale_kernel, scale=scale),
        out_shape=_out_struct((rows_p, lane), jnp.dtype(dtype), x),
        grid=(rows_p // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
        interpret=_should_interpret(interpret),
    )(tiled)
    return out.reshape(-1)[:n].reshape(shape)


# ----------------------------------------------------------------------
# Block-causal, grouped-query flash attention (block-diffusion training)
# ----------------------------------------------------------------------
# A second mask family on the same taxonomy.  With the sequence cut into
# blocks of ``block`` positions, key j is live for query i iff
#
#   inclusive   j < block * (i // block + 1)   (own block and earlier)
#   strict      j < block * (i // block)       (earlier blocks only)
#
# Both differ from the causal mask only inside the tiles the diagonal
# crosses (``block`` divides every tile), so :func:`_block_class` at
# ``causal=True`` classifies their grid points and tiles exactly, and
# :func:`_strips` / :func:`_compute_tile` skip the same dead tiles.  The
# kernels below are the split kernels' bodies with that mask on the
# diagonal tile and with ``group`` query heads sharing one key/value
# head: the forward and dq kernels name K/V block ``i // group`` (K/V is
# never repeated in HBM), the dk/dv kernel sweeps the query blocks of
# all ``group`` heads of its K/V head and sums over them.  Launches are
# aligned by contract (``s_q == s_k``, a whole number of square blocks).
#
# A strict launch leaves the queries of block 0 with no live key: their
# rows come out as a finite average with ``lse = -1e30``, which a merge
# by lse (:func:`block_diffusion_attention`) weighs with exactly 0.


def _block_causal_mask(size: int, block: int, strict: bool):
    """The diagonal piece's mask at offsets (0, 0): every diagonal block
    and tile starts at a multiple of ``block``, so one mask serves all."""
    q_idx = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    k_idx = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    first = q_idx - lax.rem(q_idx, block)
    return k_idx < (first if strict else first + block)


def _bc_class(j, kb, bs, s, window=None):
    interior, masked = _block_class(
        j * bs, kb * bs, s_q=s, s_qp=s, s_k=s, s_kp=s, causal=True,
        block_q=bs, block_k=bs, window=window)
    if window is not None:
        # the dk/dv sweep of a window launch runs past the last q block
        inside = j * bs < s
        interior, masked = _and(inside, interior), _and(inside, masked)
    return interior, masked


# A window launch (``window`` keys a query, its own among them; block
# length 1, inclusive) cuts the sequence into blocks of at most the
# window that divide it.  Of q block ``j``'s row of k blocks the live
# ones are then the last ``window // bs + 1`` up to the diagonal: the
# diagonal block under the causal mask, the block a whole window before
# it under the mirror image (key ``c`` of it live for query ``r`` iff
# ``c > r``), whole blocks between.  The grid's innermost axis sweeps
# exactly those: no k block wholly below the window is visited or
# fetched.  Near the sequence's start the sweep begins at block 0 and its
# last points fall past the diagonal (dead, nothing fetched for them).


def _swept_k(j, t, n_t, window):
    """The k block of sweep point ``t`` (of ``n_t``) of q block ``j``:
    ``t`` itself without a window."""
    if window is None:
        return t
    first = j - (n_t - 1)
    return (max(first, 0) if isinstance(first, int)
            else jnp.maximum(first, 0)) + t


def _swept_q(kb, t, n_q, window):
    """The q block of sweep point ``t`` of k block ``kb`` in the dk/dv
    kernel, whose innermost axis holds ``n_q`` points a query head of the
    group: all q blocks without a window, with one the ``n_q`` from the
    diagonal on (past the sequence's end: dead)."""
    j = lax.rem(t, n_q)
    return j if window is None else kb + j


def _bc_mask(size, block, strict, below):
    """The mask of a masked block or tile at offsets (0, 0): the causal
    family's on the diagonal, its mirror image (``below``) where a
    window's lower bound crosses."""
    if not below:
        return _block_causal_mask(size, block, strict)
    q_idx = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    k_idx = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    return k_idx > q_idx


def _when_masked(masked, kb, j, window, body):
    """Run ``body(below)`` on a masked block: without a window the
    diagonal's mask; with one, the diagonal's or the lower bound's by
    where the block lies."""
    if window is None:
        _when(masked)(lambda: body(False))
        return
    _when(_and(masked, kb == j))(lambda: body(False))
    _when(_and(masked, kb != j))(lambda: body(True))


def _bc_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                   l_ref, *, s: int, scale: float, bs: int, block: int,
                   strict: bool, tile, window=None):
    j = pl.program_id(1)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    kb = _swept_k(j, t, n_t, window)
    interior, masked = _bc_class(j, kb, bs, s, window)

    def _attend(with_mask, below=False):
        mask = _bc_mask(tile or bs, block, strict, below) \
            if with_mask else None
        for rows, cols, masked_cols in _strips(bs, tile, with_mask, below):
            q = q_ref[0, rows, :].astype(jnp.float32) * scale
            k_blk = k_ref[0, cols, :].astype(jnp.float32)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            sc = lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sc = _select(mask, sc, _NEG_INF, masked_cols)
            m_blk = jnp.max(sc, axis=-1, keepdims=True)
            stat_shape = (m_blk.shape[0], m_ref.shape[1])

            @pl.when(t == 0)
            def _first():
                p = jnp.exp(sc - m_blk)
                m_ref[rows, :] = jnp.broadcast_to(m_blk, stat_shape)
                l_ref[rows, :] = jnp.broadcast_to(
                    jnp.sum(p, axis=-1, keepdims=True), stat_shape)
                acc_ref[rows, :] = lax.dot_general(
                    p, v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            @pl.when(t != 0)
            def _rest():
                m_old = m_ref[rows, 0:1]
                m_new = jnp.maximum(m_old, m_blk)
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m_old - m_new)
                l_new = alpha * l_ref[rows, 0:1] + jnp.sum(
                    p, axis=-1, keepdims=True)
                l_ref[rows, :] = jnp.broadcast_to(l_new, stat_shape)
                m_ref[rows, :] = jnp.broadcast_to(m_new, stat_shape)
                acc_ref[rows, :] = alpha * acc_ref[rows, :] \
                    + lax.dot_general(
                        p, v_blk, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

    @_when(interior)
    def _fast():
        _attend(with_mask=False)

    _when_masked(masked, kb, j, window,
                 lambda below: _attend(with_mask=True, below=below))

    @pl.when(t == n_t - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
        ).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30)))[
                None, :],
            lse_ref.shape[1:],
        )


def _bc_probabilities(q_ref, k_ref, lse_ref, rows, cols, mask, masked_cols,
                      scale):
    """The recomputed probability piece both backward kernels start
    from, and its operands."""
    q = q_ref[0, rows, :].astype(jnp.float32)
    k_blk = k_ref[0, cols, :].astype(jnp.float32)
    sc = lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.exp(sc - lse_ref[0, 0, rows][:, None])
    return q, k_blk, _select(mask, p, 0.0, masked_cols)


def _bc_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *, s: int, scale: float, bs: int,
                      block: int, strict: bool, tile, window=None):
    j = pl.program_id(1)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    kb = _swept_k(j, t, n_t, window)

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    interior, masked = _bc_class(j, kb, bs, s, window)

    def _accum(with_mask, below=False):
        mask = _bc_mask(tile or bs, block, strict, below) \
            if with_mask else None
        for rows, cols, masked_cols in _strips(bs, tile, with_mask, below):
            _, k_blk, p = _bc_probabilities(
                q_ref, k_ref, lse_ref, rows, cols, mask, masked_cols, scale)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            do = do_ref[0, rows, :].astype(jnp.float32)
            dp = lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dq_acc[rows, :] += lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    @_when(interior)
    def _fast():
        _accum(with_mask=False)

    _when_masked(masked, kb, j, window,
                 lambda below: _accum(with_mask=True, below=below))

    @pl.when(t == n_t - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bc_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, s: int,
                       scale: float, bs: int, block: int, strict: bool,
                       tile, n_q: int, window=None):
    """Grid (batch * kv heads, k blocks, group * q blocks): the innermost
    axis sweeps the query blocks of each of the group's heads in turn
    (of a window launch the ``n_q`` blocks from the diagonal on:
    :func:`_swept_q`), so dk and dv of the shared head accumulate over
    the whole group."""
    kb = pl.program_id(1)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    j = _swept_q(kb, t, n_q, window)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    interior, masked = _bc_class(j, kb, bs, s, window)

    def _accum(with_mask, below=False):
        mask = _bc_mask(tile or bs, block, strict, below) \
            if with_mask else None
        for rows, cols, masked_cols in _strips(bs, tile, with_mask, below):
            q, _, p = _bc_probabilities(
                q_ref, k_ref, lse_ref, rows, cols, mask, masked_cols, scale)
            v_blk = v_ref[0, cols, :].astype(jnp.float32)
            do = do_ref[0, rows, :].astype(jnp.float32)
            dv_acc[cols, :] += lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dk_acc[cols, :] += lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    @_when(interior)
    def _fast():
        _accum(with_mask=False)

    _when_masked(masked, kb, j, window,
                 lambda below: _accum(with_mask=True, below=below))

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bc_block(s, d, block_size, interpret, window=None):
    """The square kernel block of a launch over ``s`` positions at head
    width ``d``: ``block_size`` (default 1024) under the family's
    clamps, and at most the window."""
    size = _clamp_blocks_for_dim(block_size, block_size, d, warn=False)[0]
    if block_size is None and d > 128:
        # these bodies keep a (bs, d) float32 accumulator beside whole
        # strips of scores: at d = 256 a 1024 block asks for 16.9 MB of
        # the 16 MB of scoped VMEM inside a whole train step (refused
        # ahead of time for a described v5e; the launch alone
        # compiles); half the block fits
        size = min(size, _DEFAULT_BLOCK // 2)
    if window is not None:
        size = min(size, window)
    return _effective_q_block(size, s, interpret)


def window_launch_census(s: int, window: int, d: int, block_size=None,
                         interpret: bool = False) -> dict:
    """:func:`block_census` of the geometry a window launch over ``s``
    positions ACTUALLY runs (its block resolved as the launch resolves
    it), ``{"block", "fwd", "bwd"}``; ``{}`` where the window reaches
    the whole sequence and the launch runs without one."""
    if _live_window(window, s) is None:
        return {}
    bs = _bc_block(s, d, block_size, interpret, window)
    return {"block": bs, **{
        kind: block_census(s, s, bs, bs, True, kind, window=window)
        for kind in ("fwd", "bwd")}}


def _bc_geometry(q, k, block, block_size, interpret, kind, tile,
                 window=None):
    """``(group, bs, tile)`` of a launch, after the contract's checks.
    A window launch's blocks are at most the window and divide it."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if k.shape != (b, s, hkv, d) or hq % hkv:
        raise ValueError(
            f"block-causal attention needs q (b, s, hq, d) and k "
            f"(b, s, hkv, d) with hq % hkv == 0; got {q.shape}, {k.shape}")
    bs = _bc_block(s, d, block_size, interpret, window)
    if s % bs or bs % block:
        raise ValueError(
            f"sequence length {s} must be a whole number of {bs}-blocks, "
            f"each a whole number of mask blocks of {block}")
    if window is not None and (window % bs or block != 1):
        raise ValueError(
            f"a window of {window} keys must be a whole number of "
            f"{bs}-blocks, under the causal mask (block 1, not {block})")
    t = _compute_tile(bs, bs, kind, causal=True, aligned=True, tile=tile)
    if t is not None and t % block:
        t = None
    return hq // hkv, bs, t


def _kernel_name(kind: str, window) -> str:
    """A launch's name in a device trace: the window launches apart
    from the others."""
    return f"_{'bd' if window is None else 'swa'}flash_{kind}"


def _to_bh(x):
    """(b, s, h, d) -> (b * h, s, d)."""
    b, s, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)


@functools.partial(
    jax.jit,
    static_argnames=("block", "strict", "scale", "block_size", "interpret",
                     "tile", "window"),
)
def _bc_forward(q, k, v, block, strict, scale, block_size, interpret,
                tile=None, window=None):
    b, s, hq, d = q.shape
    group, bs, t = _bc_geometry(q, k, block, block_size, interpret, "fwd",
                                tile, window)
    dv = v.shape[-1]
    n = s // bs
    kv_index = lambda i, j, kb: (i // group, jnp.minimum(kb, j), 0)
    kwargs, n_t = {}, n
    if window is not None:
        if strict:
            raise ValueError("a window launch is inclusive, not strict")
        kwargs, n_t = {"window": window}, window // bs + 1
        kv_index = lambda i, j, t: (
            i // group, jnp.minimum(_swept_k(j, t, n_t, window), j), 0)
    out, lse = pl.pallas_call(
        functools.partial(_bc_fwd_kernel, s=s, scale=scale, bs=bs,
                          block=block, strict=strict, tile=t, **kwargs),
        out_shape=[
            _out_struct((b * hq, s, dv), q.dtype, q, k, v),
            _out_struct((b * hq, 8, s), jnp.float32, q, k, v),
        ],
        grid=(b * hq, n, n_t),
        in_specs=[
            pl.BlockSpec((1, bs, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, bs, d), kv_index),
            pl.BlockSpec((1, bs, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 8, bs), lambda i, j, kb: (i, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, dv), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("forward", window),
    )(_to_bh(q), _to_bh(k), _to_bh(v))
    return (jnp.moveaxis(out.reshape(b, hq, s, dv), 1, 2),
            lse[:, 0])  # (b, s, hq, dv), (b * hq, s)


@functools.partial(
    jax.jit,
    static_argnames=("block", "strict", "scale", "block_size", "interpret",
                     "tile", "window"),
)
def _bc_backward(q, k, v, out, lse, g, g_lse, block, strict, scale,
                 block_size, interpret, tile=None, window=None):
    """dq, dk, dv of one launch; ``g_lse`` (b * hq, s) is the cotangent
    of the log-sum-exp, folded into ``delta`` as in
    :func:`_flash_backward`."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group, bs, t = _bc_geometry(q, k, block, block_size, interpret, "bwd",
                                tile, window)
    dv = v.shape[-1]
    n = s // bs
    # a window launch sweeps the blocks its window reaches, not all n
    n_t = n if window is None else window // bs + 1
    qb, kb_, vb, dob = _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g)
    delta = jnp.sum(dob.astype(jnp.float32)
                    * _to_bh(out).astype(jnp.float32), axis=-1) \
        - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, None], (b * hq, 8, s))
    lse8 = jnp.broadcast_to(lse[:, None], (b * hq, 8, s))
    kwargs = dict(s=s, scale=scale, bs=bs, block=block, strict=strict,
                  tile=t)

    kv_index = lambda i, j, kb: (i // group, jnp.minimum(kb, j), 0)
    if window is not None:
        kwargs["window"] = window
        kv_index = lambda i, j, t: (
            i // group, jnp.minimum(_swept_k(j, t, n_t, window), j), 0)
    row = lambda i, j, kb: (i, j, 0)
    stat = lambda i, j, kb: (i, 0, j)
    dq = pl.pallas_call(
        functools.partial(_bc_bwd_dq_kernel, **kwargs),
        out_shape=_out_struct((b * hq, s, d), q.dtype, q, k, v, g),
        grid=(b * hq, n, n_t),
        in_specs=[
            pl.BlockSpec((1, bs, d), row),
            pl.BlockSpec((1, bs, d), kv_index),
            pl.BlockSpec((1, bs, dv), kv_index),
            pl.BlockSpec((1, bs, dv), row),
            pl.BlockSpec((1, 8, bs), stat),
            pl.BlockSpec((1, 8, bs), stat),
        ],
        out_specs=pl.BlockSpec((1, bs, d), row),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("backward_dq", window),
    )(qb, kb_, vb, dob, lse8, delta)

    # query block of sweep point t under K/V head i: head i * group +
    # t // n, block t % n -- a dead point (the head's sweep starts above
    # the diagonal) names the head's first live block again, so the
    # pipeline fetches nothing for it
    def q_of(i, kb, t):
        return i * group + t // n, jnp.maximum(lax.rem(t, n), kb)

    if window is not None:
        # past the sequence's end the last block again: nothing fetched
        def q_of(i, kb, t):  # noqa: F811
            return (i * group + t // n_t,
                    jnp.minimum(_swept_q(kb, t, n_t, window), n - 1))

    q_row = lambda i, kb, t: (*q_of(i, kb, t), 0)
    q_stat = lambda i, kb, t: (q_of(i, kb, t)[0], 0, q_of(i, kb, t)[1])
    kv_row = lambda i, kb, t: (i, kb, 0)
    dk, dv_out = pl.pallas_call(
        functools.partial(_bc_bwd_dkv_kernel, n_q=n_t, **kwargs),
        out_shape=[
            _out_struct((b * hkv, s, d), k.dtype, q, k, v, g),
            _out_struct((b * hkv, s, dv), v.dtype, q, k, v, g),
        ],
        grid=(b * hkv, n, group * n_t),
        in_specs=[
            pl.BlockSpec((1, bs, d), q_row),
            pl.BlockSpec((1, bs, d), kv_row),
            pl.BlockSpec((1, bs, dv), kv_row),
            pl.BlockSpec((1, bs, dv), q_row),
            pl.BlockSpec((1, 8, bs), q_stat),
            pl.BlockSpec((1, 8, bs), q_stat),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, d), kv_row),
            pl.BlockSpec((1, bs, dv), kv_row),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, d), jnp.float32),
            pltpu.VMEM((bs, dv), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("backward_dkdv", window),
    )(qb, kb_, vb, dob, lse8, delta)

    def from_bh(x, h):
        return jnp.moveaxis(x.reshape(b, h, s, -1), 1, 2)

    return from_bh(dq, hq), from_bh(dk, hkv), from_bh(dv_out, hkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def block_causal_attention_with_lse(q, k, v, block, strict=False,
                                    scale=None, block_size=None,
                                    interpret=None, tile=None, window=None):
    """Block-causal grouped-query flash attention returning ``(out,
    lse)``, both differentiable.

    ``q`` is (b, s, hq, d), ``k`` (b, s, hkv, d) and ``v`` (b, s, hkv,
    dv) with ``hq`` a multiple of ``hkv``: query head ``h`` reads
    key/value head ``h // (hq // hkv)``, which is never repeated in HBM.
    The values' width ``dv`` is their own (``out`` is (b, s, hq, dv):
    keys of 192 against values of 128 run as they are).  Key ``j`` is
    live
    for query ``i`` iff ``j < block * (i // block + 1)``, or with
    ``strict`` iff ``j < block * (i // block)`` (a strict launch's first
    ``block`` queries see no key: ``lse`` is ``-1e30`` there and ``out``
    a finite average to be weighed with 0).  ``lse`` is (b, s, hq).
    ``s`` must be a whole number of square kernel blocks (``block_size``,
    default 1024, clamped to ``s``), each a whole number of ``block``.

    ``window`` (with ``block=1``, inclusive): key ``j`` is live for
    query ``i`` iff ``i - window < j <= i``, ``window`` keys, the
    query's own among them.  The launch (kernels ``_swaflash_*``) cuts
    the sequence into blocks of at most ``window`` that divide it and
    visits only the k blocks a q block's window reaches
    (``block_census(..., window=...)`` counts them); a window that
    reaches the whole sequence (``window >= s``) is none, and runs the
    launch without one.

    The backward rule's residuals are ``(q, k, v, out, lse)``, the
    launch's two results named :data:`ATTN_OUT`: a block recomputed
    under a plan that lists the name (``models.transformer.remat_plan``)
    keeps the pair, and its recomputation runs ``q``, ``k`` and ``v``
    again for the backward kernels but no forward launch.
    """
    return _bc_fwd_rule(q, k, v, block, strict, scale, block_size,
                        interpret, tile, window)[0]


def _live_window(window, s):
    """``window``, or ``None`` where it reaches the whole sequence."""
    return None if window is None or window >= s else window


def _bc_fwd_rule(q, k, v, block, strict, scale, block_size, interpret,
                 tile, window):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    out, lse = _named(*_bc_forward(
        q, k, v, block, strict, scale, block_size,
        _should_interpret(interpret), tile,
        window=_live_window(window, q.shape[1])))
    b, s, hq, _ = q.shape
    return ((out, jnp.moveaxis(lse.reshape(b, hq, s), 1, 2)),
            (q, k, v, out, lse))


def _bc_bwd_rule(block, strict, scale, block_size, interpret, tile,
                 window, residuals, g):
    q, k, v, out, lse = residuals
    g_out, g_lse = g
    b, s, hq, _ = q.shape
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _bc_backward(
        q, k, v, out, lse, g_out,
        jnp.moveaxis(g_lse, 1, 2).reshape(b * hq, s), block, strict,
        scale, block_size, _should_interpret(interpret), tile,
        window=_live_window(window, s))


block_causal_attention_with_lse.defvjp(_bc_fwd_rule, _bc_bwd_rule)


def block_causal_mask(s: int, block: int, strict: bool = False,
                      window=None):
    """The dense (s, s) mask of the definition: the tests' and the dense
    form's side of :func:`block_causal_attention_with_lse`."""
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    live = j < block * (i // block + (0 if strict else 1))
    return live if window is None else live & (j > i - window)


def block_diffusion_mask(s: int, block: int):
    """Dense (2s, 2s) mask of block-diffusion training over the
    concatenation [clean; noised] of one sequence: a clean query of
    block b sees the clean keys of blocks <= b; a noised query of block
    b the clean keys of blocks < b and the noised keys of block b."""
    same = (jnp.arange(s)[:, None] // block) == (jnp.arange(s)[None, :]
                                                  // block)
    top = jnp.concatenate(
        [block_causal_mask(s, block), jnp.zeros((s, s), bool)], axis=1)
    bottom = jnp.concatenate(
        [block_causal_mask(s, block, strict=True), same], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def block_diffusion_attention_dense(q, k, v, block, scale=None,
                                    query_rows=None):
    """:func:`block_diffusion_attention` as a dense masked softmax in
    float32 (no kernel): the oracle, and the form for sizes a kernel
    block cannot tile.  ``query_rows``: that many queries at a time (the
    (2s, 2s) scores of a long sequence would not fit whole)."""
    b, s2, hq, d = q.shape
    hkv = k.shape[2]
    rows = s2 if query_rows is None else query_rows
    scale = d ** -0.5 if scale is None else scale
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    @jax.checkpoint
    def some(args):
        q_blk, live = args
        q_blk = q_blk.reshape(b, rows, hkv, hq // hkv, d)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk.astype(jnp.float32),
                        k32) * scale
        sc = jnp.where(live[None, None, None], sc, -jnp.inf)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, axis=-1),
                         v32)
        return out.reshape(b, rows, hq, d)

    out = lax.map(some, (
        jnp.moveaxis(q.reshape(b, s2 // rows, rows, hq, d), 1, 0),
        block_diffusion_mask(s2 // 2, block).reshape(s2 // rows, rows, s2)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape).astype(q.dtype)


def block_diffusion_attention(q, k, v, block, scale=None, block_size=None,
                              interpret=None, tile=None):
    """Attention of block-diffusion training (BD3-LM, arXiv:2503.09573)
    over the concatenation [clean; noised] of every sequence: ``q`` is
    (b, 2s, hq, d), ``k`` / ``v`` (b, 2s, hkv, d), the result (b, 2s,
    hq, d) under :func:`block_diffusion_mask`.

    Three pieces, none of which builds an (s, s) array: the clean
    queries over the clean keys (inclusive block-causal kernel); the
    noised queries over the clean keys (strict kernel, with its lse);
    the noised queries over the ``block`` noised keys of their own
    block (a (block, block) softmax a block, plain XLA), merged with the
    strict piece by their log-sum-exps."""
    b, s2, hq, d = q.shape
    s, hkv = s2 // 2, k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    kernel = functools.partial(
        block_causal_attention_with_lse, block=block, scale=scale,
        block_size=block_size, interpret=interpret, tile=tile)
    k_clean, v_clean = k[:, :s], v[:, :s]
    clean, _ = kernel(q[:, :s], k_clean, v_clean, strict=False)
    far, far_lse = kernel(q[:, s:], k_clean, v_clean, strict=True)

    @jax.checkpoint  # float32 inside; only its operands are kept
    def with_own_block(qn, kn, vn, far, far_lse):
        nb, group = s // block, hq // hkv
        qn = qn.reshape(b, nb, block, hkv, group, d).astype(jnp.float32)
        kn = kn.reshape(b, nb, block, hkv, d).astype(jnp.float32)
        vn = vn.reshape(b, nb, block, hkv, d).astype(jnp.float32)
        sc = jnp.einsum("bnqhgd,bnkhd->bnqhgk", qn, kn) * scale
        near_lse = jax.nn.logsumexp(sc, axis=-1)
        near = jnp.einsum("bnqhgk,bnkhd->bnqhgd",
                          jnp.exp(sc - near_lse[..., None]), vn)
        near = near.reshape(b, s, hq, d)
        near_lse = near_lse.reshape(b, s, hq)
        top = jnp.maximum(far_lse, near_lse)
        w_far = jnp.exp(far_lse - top)
        w_near = jnp.exp(near_lse - top)
        return ((far.astype(jnp.float32) * w_far[..., None]
                 + near * w_near[..., None])
                / (w_far + w_near)[..., None]).astype(q.dtype)

    noised = with_own_block(q[:, s:], k[:, s:], v[:, s:], far, far_lse)
    return jnp.concatenate([clean, noised], axis=1)
