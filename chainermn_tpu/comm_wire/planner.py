"""Deterministic bucket planner for the gradient wire.

The reference's ``PureNcclCommunicator`` packed the whole gradient set
into one contiguous device buffer before calling ``ncclAllReduce``
(``_assign``/``_pack_params_to_buffer`` in pure_nccl_communicator.py);
our compiled tier instead issued one ``lax.psum`` per gradient leaf —
267 collectives for ResNet-50 (pinned by the HLO census tests).  This
module restores the flat-wire
idea as a *plan*: a pure function of the gradient pytree's shapes and
dtypes that groups leaves, in tree-flatten order, into contiguous
dtype-homogeneous buckets of a target byte size.  Each bucket then
costs ONE collective.

Determinism contract
--------------------
The plan depends only on ``(leaf shapes, leaf dtypes, bucket_bytes,
max_buckets)`` — never on values, rank, process index, or iteration —
so every process of a multi-controller job computes the identical plan
from its local view of the model.  :func:`BucketPlan.plan_hash` is the
cross-process agreement token (exchanged by
:func:`~chainermn_tpu.comm_wire.plan_agreement`).

Why a bucket-count ceiling as well as a byte target: the byte target
(default 4 MiB) keeps each transfer big enough to amortize collective
launch latency, but a 100 MB model would still shatter into ~25
buckets.  ``max_buckets`` (default 6) coalesces upward — the effective
bucket size grows until the plan fits the slot budget — so a compiled
train step's collective count stays bounded by a constant (buckets +
the loss pmean) regardless of model size, which is also what the HLO
op-count tests pin.

Where that holds, and what the chip showed of it (PR 51): the constant
bounds the leaves the wire PACKS, which on a CPU mesh and on one chip is
every leaf (every budget of ``analysis.budgets`` is traced there).  The
target amortizes a launch, so it only argues for packing leaves UNDER
it; the ceiling then coalesced upward past it, and on four v5e chips the
2.36 GB of Cerebras-GPT-590M's gradients in four buckets of 321-784 MB
cost a third of the step (ledger, PR 50: 333 ms a step, 24 614
tokens/s/chip, where autodiff's all-reduce a leaf gives 250 ms and
32 792), none of it the launches the target was set against: copies
into and out of the flat buffers, all-reduces that wait for a bucket's
last leaf, an update that no longer rides the matmul that made the
gradient.
On such a mesh ``optimizers._split_wire`` therefore leaves a leaf
already at ``bucket_bytes`` out of the plan (74 of that model's 184
leaves, all but 1.0 MB of its bytes) and plans the rest here as before
(my chip run, PR 51: 262.5 ms, 31 256 tokens/s/chip; the same leaves in
place without the async options 285.8 ms, the buckets with them 326.6).
"""

from __future__ import annotations

import hashlib
from typing import Any, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024
DEFAULT_MAX_BUCKETS = 6


class LeafSlot(NamedTuple):
    """Where one gradient leaf lives inside its bucket."""

    index: int  # position in tree-flatten order
    offset: int  # element offset into the bucket's flat buffer
    size: int  # element count
    shape: Tuple[int, ...]


class Bucket(NamedTuple):
    dtype: str  # canonical dtype name (buckets are dtype-homogeneous)
    size: int  # total elements
    slots: Tuple[LeafSlot, ...]


class BucketPlan(NamedTuple):
    """The full wire layout: an ordered tuple of buckets covering every
    leaf exactly once, leaves appearing in tree-flatten order within
    and across the buckets of each dtype."""

    buckets: Tuple[Bucket, ...]
    n_leaves: int

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def plan_hash(self) -> str:
        """Stable content hash — the cross-process agreement token."""
        h = hashlib.sha256()
        h.update(f"n_leaves={self.n_leaves}".encode())
        for b in self.buckets:
            h.update(f"|{b.dtype}:{b.size}".encode())
            for s in b.slots:
                h.update(f";{s.index},{s.offset},{s.size},{s.shape}".encode())
        return h.hexdigest()

    def describe(self) -> str:
        """One line per bucket, for logs and bench fingerprints."""
        return " ".join(
            f"[{i}]{b.dtype}x{b.size}({len(b.slots)} leaves)"
            for i, b in enumerate(self.buckets)
        )


def _leaf_spec(leaf) -> Tuple[Tuple[int, ...], Any]:
    """(shape, dtype) of a leaf, working on arrays, tracers, numpy
    scalars and ShapeDtypeStructs alike."""
    shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        dtype = jnp.result_type(leaf)
    return shape, jnp.dtype(dtype)


def make_plan(
    leaves: Sequence[Any],
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    max_buckets: int = DEFAULT_MAX_BUCKETS,
) -> BucketPlan:
    """Plan buckets for ``leaves`` (tree-flatten order).

    Greedy walk in leaf order with one open bucket per dtype: a leaf
    joins its dtype's open bucket unless that would exceed the
    effective bucket size, in which case the bucket closes and a new
    one opens.  A single leaf larger than the target gets a bucket of
    its own (still one collective).  When the greedy plan exceeds
    ``max_buckets``, the effective bucket size doubles and the walk
    reruns — deterministic, and converges in O(log(total/target))
    iterations.  ``max_buckets`` bounds the count only as far as
    dtype-homogeneity allows: the floor is one bucket per distinct
    dtype.
    """
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes must be >= 1, got {bucket_bytes}")
    specs = [_leaf_spec(l) for l in leaves]
    if not specs:
        return BucketPlan(buckets=(), n_leaves=0)

    def walk(eff_bytes: int) -> List[Bucket]:
        open_slots: dict = {}  # dtype name -> (slots list, elems, bytes)
        done: List[Tuple[int, Bucket]] = []  # (first leaf index, bucket)

        def close(name):
            slots, elems, _ = open_slots.pop(name)
            done.append((slots[0].index, Bucket(name, elems, tuple(slots))))

        for i, (shape, dtype) in enumerate(specs):
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            nbytes = size * dtype.itemsize
            name = dtype.name
            if name in open_slots:
                slots, elems, bts = open_slots[name]
                if bts + nbytes > eff_bytes and bts > 0:
                    close(name)
            if name not in open_slots:
                open_slots[name] = ([], 0, 0)
            slots, elems, bts = open_slots[name]
            slots.append(LeafSlot(i, elems, size, tuple(shape)))
            open_slots[name] = (slots, elems + size, bts + nbytes)
        for name in list(open_slots):
            close(name)
        # buckets ordered by their first leaf's flatten position, so the
        # plan (and the collective issue order) is reproducible
        done.sort(key=lambda t: t[0])
        return [b for _, b in done]

    eff = int(bucket_bytes)
    if max_buckets:
        total = sum(
            (int(np.prod(s, dtype=np.int64)) if s else 1) * d.itemsize
            for s, d in specs
        )
        eff = max(eff, -(-total // int(max_buckets)))
    buckets = walk(eff)
    while max_buckets and len(buckets) > int(max_buckets):
        n_dtypes = len({d.name for _, d in specs})
        if len(buckets) <= n_dtypes:
            break  # dtype-homogeneity floor reached
        eff *= 2
        buckets = walk(eff)
    return BucketPlan(buckets=tuple(buckets), n_leaves=len(specs))


def plan_of_tree(
    tree,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    max_buckets: int = DEFAULT_MAX_BUCKETS,
) -> BucketPlan:
    return make_plan(
        jax.tree_util.tree_leaves(tree), bucket_bytes, max_buckets
    )


# ----------------------------------------------------------------------
# cost-model hookup (ISSUE 6): bucket sizing from the analyzer's
# per-collective cost records
# ----------------------------------------------------------------------
# Collective launch latency per hop class, relative to an intra-slice
# ICI hop.  Inter-slice (DCN-class) launches cost roughly an order of
# magnitude more setup (PAPERS.md: DynamiQ and the multi-node inference
# comm study both measure inter-node collective latency dominating at
# small payloads), so amortizing them takes proportionally larger
# buckets.  "flat"/"mixed" axes may cross slices — treated as one notch
# below inter rather than assumed cheap.
_HOP_LATENCY_SCALE = {
    "intra": 1,
    "local": 1,
    "flat": 2,
    "mixed": 2,
    "inter": 4,
}


def tune_wire_for_trace(
    records,
    base_bytes: int = DEFAULT_BUCKET_BYTES,
    max_buckets: int = DEFAULT_MAX_BUCKETS,
    profile=None,
    schedule: str = "auto",
    shape: str = "allreduce",
):
    """``(bucket_bytes, max_buckets)`` tuned from a program's
    :class:`~chainermn_tpu.analysis.trace.CollectiveRecord` cost fields
    — the decision path that consumes ``bytes_on_wire`` + ``hop``.

    With ``profile=None`` (default) the analytic rules apply, both
    derived from the byte/latency accounting the records carry:

    * the byte target scales with the worst hop class any *reduction*
      record crosses (``_HOP_LATENCY_SCALE``): an inter-slice launch
      amortizes over 4x the bytes of an intra-slice one, so fewer,
      larger buckets win there (DynamiQ's regime);
    * when the total reduction ``bytes_on_wire`` fits inside ONE scaled
      bucket, the slot budget collapses to 1 — a small model gains
      nothing from splitting, and every extra bucket is a pure launch
      latency loss.

    With a :class:`~chainermn_tpu.comm_wire.autotune.BandwidthProfile`,
    the analytic scaling is replaced by MEASURED minimization: for each
    candidate slot budget ``B`` in ``1..max_buckets`` the total
    gradient payload is split into ``B`` buckets and the synchronous
    wire time is predicted — each candidate priced as what the wire
    would ACTUALLY issue for it under ``schedule`` (the flat psum, or
    the staged triple; a pinned schedule is priced as pinned —
    :func:`~chainermn_tpu.comm_wire.autotune.predict_bucket_sync`);
    the cheapest ``B`` wins (ties to the smaller count).
    Candidates never exceed ``max_buckets``, so a tuned plan can only
    REDUCE collective counts — every ``analysis.budgets`` ceiling that
    held for the constants holds for any tune.  Falls back to the
    analytic rules when the profile cannot price the trace (unknown
    axis sizes, no curve for the hop).

    Records whose ``bytes_on_wire`` is ``None`` (meshless traces — axis
    sizes unknown at trace time) fall back to their ``payload_bytes``
    with ONE warning per call: silently dropping them let a
    partially-seeded trace under-count its traffic and tune toward a
    1-bucket plan sized for a fraction of the real payload.
    """
    reductions = [
        r for r in records
        if getattr(r, "cls", None) in ("all_reduce", "reduce_scatter")
    ]
    if profile is not None:
        tuned = _tune_with_profile(reductions, max_buckets, profile,
                                   schedule, shape)
        if tuned is not None:
            return tuned
    # analytic rules — also the fallback when the profile cannot price
    # the trace.  The meshless-payload warning lives HERE, after the
    # profile branch: a successful measured tune consults payload_bytes
    # directly, so warning about an analytic fallback it never took
    # would be a false diagnostic.
    scale = max(
        (_HOP_LATENCY_SCALE.get(getattr(r, "hop", "flat"), 2)
         for r in reductions),
        default=1,
    )
    bucket_bytes = int(base_bytes) * scale
    total = 0
    unpriced = 0
    for r in reductions:
        bow = getattr(r, "bytes_on_wire", None)
        if bow is not None:
            # 0 is a PRICED value (a world-1 axis ships nothing), not a
            # missing one — only None means the trace couldn't price it
            total += int(bow)
        else:
            unpriced += int(getattr(r, "payload_bytes", 0) or 0)
    if unpriced:
        import warnings

        warnings.warn(
            "tune_wire_for_trace: reduction record(s) carry no "
            "bytes_on_wire (meshless trace — seed axis_sizes= at trace "
            "time to price them); falling back to their payload bytes "
            f"({unpriced} B) so the tune cannot under-count traffic",
            stacklevel=2,
        )
        total += unpriced
    if total and total <= bucket_bytes:
        return bucket_bytes, 1
    return bucket_bytes, max_buckets


def _tune_with_profile(reductions, max_buckets, profile,
                       schedule: str = "auto",
                       shape: str = "allreduce"):
    """Measured bucket sizing: minimize predicted synchronous wire time
    over candidate slot budgets.  ``None`` when the profile cannot
    price the trace — the caller then applies the analytic rules —
    and when ``max_buckets`` is the falsy no-cap sentinel: the caller
    explicitly asked for an UNBOUNDED plan, and "tune within the cap"
    has no cap to tune within (the analytic path preserves the
    sentinel; silently substituting the default 6 would make the same
    arguments plan differently with and without a profile).

    The gradient payload is the LARGEST per-class total, not the sum
    over all reduction records: a trace of an already-hier-staged step
    carries each bucket twice (a full-payload intra reduce_scatter AND
    a shard-payload inter all_reduce), and summing both legs would
    tune for ~1.25x the real traffic.  Candidates are priced by
    :func:`~chainermn_tpu.comm_wire.autotune.predict_bucket_sync` over
    the UNION of the trace's sync axes — what the wire would actually
    issue for that bucket (the flat psum, or the staged triple with
    the slow inter hop priced on its own curve) — not by a flat
    all_reduce over whichever single record happened to be largest
    (which, on a staged trace, was the intra-only reduce_scatter and
    silently dropped the inter bottleneck from the minimization)."""
    from .autotune import is_wire_record, predict_bucket_sync

    slots = int(max_buckets or 0)
    if slots < 1:
        return None
    per_cls: dict = {}
    sizes_env: dict = {}
    for r in reductions:
        if not is_wire_record(r):
            # activation-shaped (>=2-D operand) all_reduce: a forward
            # TP/MoE psum, not wire traffic — the gradient wire ships
            # FLAT buckets (1-D; the loss pmean is 0-D, ZeRO's blocked
            # (n, k) reduce_scatters keep their own class).  Counting
            # activations would size buckets for bytes the wire never
            # carries and union in tensor-parallel axes the sync never
            # crosses.
            continue
        pb = int(getattr(r, "payload_bytes", 0) or 0)
        cls = getattr(r, "cls", "all_reduce")
        per_cls[cls] = per_cls.get(cls, 0) + pb
        for a, s in zip(getattr(r, "axes", ()),
                        getattr(r, "axis_sizes", ())):
            if int(s) > 0:
                sizes_env[str(a)] = int(s)
    payload_total = max(per_cls.values(), default=0)
    if not payload_total or not sizes_env:
        return None
    axes = tuple(sorted(sizes_env))
    sizes = tuple(sizes_env[a] for a in axes)
    best = None  # (predicted seconds, B)
    for b in range(1, slots + 1):
        per = -(-payload_total // b)
        t_one = predict_bucket_sync(profile, per, axes, sizes,
                                    schedule=schedule, shape=shape)
        if t_one is None:
            return None
        t = b * t_one
        # ties go to FEWER buckets, robustly: the ring formula's
        # per-bucket int() truncation can make a larger B "win" by
        # nanoseconds on a genuine tie, so a larger B must beat the
        # incumbent by a real relative margin to displace it
        if best is None or t < best[0] * (1 - 1e-6):
            best = (t, b)
    _, b = best
    return max(-(-payload_total // b), 1), b


def plan_for_trace(
    trace,
    tree,
    base_bytes: int = DEFAULT_BUCKET_BYTES,
    max_buckets: int = DEFAULT_MAX_BUCKETS,
    mesh=None,
    schedule: str = "auto",
    profile=None,
    shape: str = "allreduce",
):
    """Plan buckets for ``tree`` with the byte target / slot budget
    tuned by a :class:`CollectiveTrace`'s cost records (typically the
    trace of the step that will ship these gradients).

    With ``mesh`` given, the plan additionally carries the
    cost-model-chosen per-bucket collective schedule
    (:func:`~chainermn_tpu.comm_wire.schedules.schedule_for_bucket` —
    flat psum vs the hier rs→ar→ag triple) and returns a
    :class:`~chainermn_tpu.comm_wire.schedules.WirePlan` whose hash
    covers layout AND schedule; without it the bare
    :class:`BucketPlan` is returned as before.  ``profile`` (a
    ``comm_wire.autotune.BandwidthProfile``) switches both the bucket
    sizing and the schedule decision onto the measured cost model and
    folds its content hash into the plan hash.
    """
    bucket_bytes, slots = tune_wire_for_trace(
        trace.records, base_bytes, max_buckets, profile=profile,
        schedule=schedule, shape=shape,
    )
    if mesh is None:
        return plan_of_tree(tree, bucket_bytes, slots)
    from .codecs import WireConfig
    from .schedules import plan_wire

    return plan_wire(
        tree,
        WireConfig(bucket_bytes=bucket_bytes, max_buckets=slots,
                   schedule=schedule),
        mesh,
        profile=profile,
        shape=shape,
    )


def flatten_to_buckets(plan: BucketPlan, tree) -> List[jnp.ndarray]:
    """Pack the tree's leaves into the plan's flat wire buffers.

    Within a bucket, leaf data is concatenated in tree-flatten order —
    the documented element order that makes the bucketed psum
    bit-identical to the per-leaf psum (the reduction is elementwise,
    so grouping changes neither the summands nor their rank order).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != plan.n_leaves:
        raise ValueError(
            f"plan covers {plan.n_leaves} leaves; tree has {len(leaves)}"
        )
    out = []
    for b in plan.buckets:
        parts = [jnp.reshape(leaves[s.index], (-1,)) for s in b.slots]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if flat.dtype != jnp.dtype(b.dtype):
            raise ValueError(
                f"leaf dtype drifted from plan: bucket is {b.dtype}, "
                f"got {flat.dtype} (replan on shape/dtype change)"
            )
        out.append(flat)
    return out


def pack_stacked(plan: BucketPlan, leaves, size: int, xp=jnp):
    """Pack stacked ``(size, ...)`` leaves into per-bucket
    ``(size, bucket_size)`` wire buffers — the eager tiers' analogue of
    :func:`flatten_to_buckets` (``plan`` made on the per-rank portion,
    so slot sizes are per-rank element counts).  ``xp`` selects the
    array backend (``jnp`` for device buffers, ``numpy`` for the
    host-staged tier) so every caller shares ONE column layout."""
    return [
        xp.concatenate(
            [xp.reshape(leaves[s.index], (size, -1)) for s in b.slots],
            axis=1,
        )
        for b in plan.buckets
    ]


def unpack_stacked(plan: BucketPlan, buckets, shapes, xp=jnp):
    """Scatter per-bucket ``(size, bucket_size)`` buffers back into
    stacked leaves of ``shapes`` — inverse of :func:`pack_stacked`."""
    out: List[Any] = [None] * plan.n_leaves
    for b, flat in zip(plan.buckets, buckets):
        col = 0
        for s in b.slots:
            out[s.index] = xp.reshape(
                flat[:, col : col + s.size], shapes[s.index]
            )
            col += s.size
    return out


def unflatten_from_buckets(plan: BucketPlan, buckets, tree_like):
    """Scatter flat wire buffers back into ``tree_like``'s structure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    if len(leaves) != plan.n_leaves:
        raise ValueError(
            f"plan covers {plan.n_leaves} leaves; tree has {len(leaves)}"
        )
    out: List[Any] = [None] * plan.n_leaves
    for b, flat in zip(plan.buckets, buckets):
        for s in b.slots:
            # static slice: offsets are plan constants, so XLA sees a
            # plain slice, not a dynamic gather
            piece = flat[s.offset : s.offset + s.size]
            out[s.index] = jnp.reshape(piece, s.shape)
    return jax.tree_util.tree_unflatten(treedef, out)
