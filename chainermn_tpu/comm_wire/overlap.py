"""Bucket-granularity comm/compute overlap engine for the gradient wire.

Why
---
Double-buffering (the reference's overlap story) hides gradient sync by
delaying *every* gradient a full step — and has never cleared its
>=1.05x bench gate (0.97x on VGG across BENCH_r03-r05).  The flat-wire
layer already gives the right overlap *unit*: a handful of
deterministic, hash-agreed buckets, each reduced by ONE collective.
What the synchronous wire lacks is *when* those collectives are issued:
``_sync_grads_wire`` runs after the whole VJP, so every bucket psum
sits at the tail of the step program, serialized behind the full
backward pass.  Yet bucket k's psum depends only on the gradients of
bucket k's leaves — data that backward produces long before it
finishes (the last layers' grads, i.e. the *last* buckets in planner
order, close first).  Issuing each bucket's reduction at that moment
hides communication under the remaining backward compute
("Optimizing Allreduce Operations for Modern Heterogeneous
Architectures", PAPERS.md), and is the program shape DynamiQ-style
multi-hop compressed schedules require (PAPERS.md).

How: a jaxpr scheduling pass
----------------------------
``loss_fn`` is opaque (any jittable function), so the backward pass
cannot be segmented at the source level.  It does not need to be: the
step's jaxpr IS the segmented form.  :func:`schedule_jaxpr` re-emits
the equations of the compiled step in dependency-ASAP order — for each
collective, its minimal producer closure (the backward segment that
feeds it, plus the bucket's pack/encode chain), then the collective
*immediately*, then the next segment — walking collectives in
readiness order (reverse-planner order for the grad buckets, since
backward finalizes the last buckets' leaves first).  Equivalently: the
backward pass is partitioned into per-bucket segments and each
bucket's fused psum (codec wire format, error feedback included) is
dispatched the moment its bucket's leaves are all produced, while
earlier segments keep computing.  XLA's latency-hiding scheduler then
interleaves the async collective start/done pairs with the remaining
compute.

Because the pass only *reorders* equations (a topological re-sort of
the identical equation set):

* numerics are **bit-identical** to the synchronous bucketed wire —
  same buckets, same codec, same summands, same reduction order within
  each collective (pinned at 0 tolerance by ``tests/test_overlap.py``);
* the collective **census is unchanged** (5 psums for ResNet-50) —
  every mnlint budget pin passes as-is.  Only the trace *ordering*
  moves, which :func:`bucket_issue_report` makes checkable: in the
  scheduled program every bucket psum has issue ``delay == 0`` (no
  foreign equation sits between its operands' readiness and its
  dispatch), i.e. every bucket's reduction is in flight before the
  remaining backward segments complete.

Scope and honesty
-----------------
The pass schedules the *authored program order*, which is what our own
trace/ordering checks observe and what XLA's scheduler takes as input;
actual on-wire overlap additionally needs a backend whose collectives
run async (TPU ICI; the CPU mesh serializes them, so the CI A/B bounds
machinery cost, not the win).  The int8 codec's batched scale ``pmax``
deliberately stays ONE collective (census contract) — it depends on
every bucket's absmax, so int8 buckets cannot start before the last
segment ends and the overlap window is the decode/update tail only.
``scan``/``cond``/``while`` bodies are left untouched (collectives
inside them, e.g. ring attention's ppermute chain, keep their loop
order); equations with effects disable the pass for their jaxpr.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.extend import core

OVERLAP_MODES = ("none", "bucket")

# primitive names treated as collectives by the scheduler — must stay a
# superset of the wire's emissions (psum buckets, int8 scale pmax, ZeRO
# psum_scatter/all_gather, the loss pmean's psum) and is deliberately
# the same family analysis.trace classifies, so the scheduler and the
# trace walker cannot disagree about what a collective is.
_COLLECTIVE_PRIMS = frozenset((
    "psum", "pmax", "pmin",
    "all_gather", "all_gather_invariant", "pgather",
    "reduce_scatter", "psum_scatter",
    "ppermute", "pshuffle", "all_to_all",
))

# sub-jaxpr carriers the pass rebuilds and descends into.  scan / cond /
# while are intentionally absent: reordering inside a loop body changes
# per-iteration issue order, which is never the wire's program shape
# (grad-wire collectives live inline in the shard_map body).
_DESCEND_PRIMS = ("jit", "shard_map")


def resolve_overlap(overlap) -> str:
    """Normalize/validate the ``overlap=`` knob ("none"/None/"bucket")."""
    if overlap is None:
        return "none"
    if overlap in OVERLAP_MODES:
        return overlap
    raise ValueError(
        f"overlap must be one of {OVERLAP_MODES}; got {overlap!r}"
    )


# ----------------------------------------------------------------------
# the scheduling pass
# ----------------------------------------------------------------------
def _blocks_reorder(eff) -> bool:
    """True for effects that pin program order (IO, ordered callbacks)
    — those disable the pass for their jaxpr.  ``NamedAxisEffect`` (how
    collectives advertise the mesh axes they use) and other unordered
    effects constrain nothing: dataflow alone orders them, exactly what
    the scheduler preserves."""
    try:
        from jax._src import effects as _fx

        return _fx.ordered_effects.contains(type(eff))
    except Exception:
        # unknown effects API: refuse to reorder anything effectful
        return type(eff).__name__ != "NamedAxisEffect"


def _producers(eqns) -> dict:
    prod = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            prod[id(v)] = i
    return prod


def _deps_of(eqn, prod) -> Tuple[int, ...]:
    """Direct producer indices of one eqn (invars only; literals and
    jaxpr invars/constvars produce nothing)."""
    out = set()
    for v in eqn.invars:
        if isinstance(v, core.Literal):
            continue
        i = prod.get(id(v))
        if i is not None:
            out.add(i)
    return tuple(sorted(out))


def _schedule_eqns(eqns) -> Optional[List[int]]:
    """ASAP emission order for one equation list, or ``None`` when the
    pass must not touch it (no collectives / effectful eqns).

    Collectives are visited in readiness order (the original index at
    which their last operand is produced — backward makes the last
    buckets ready first); each visit emits the collective's not-yet-
    emitted ancestor closure (its backward segment + pack/encode
    chain, original order within) and then the collective itself
    IMMEDIATELY.  Everything else (decode, unflatten, optimizer update,
    metrics) follows in original order.  The result is a topological
    order of the same equations — producers always precede consumers —
    so evaluation is value-identical; only issue positions move.
    """
    n = len(eqns)
    if any(
        _blocks_reorder(eff)
        for e in eqns
        for eff in (getattr(e, "effects", None) or ())
    ):
        return None
    prod = _producers(eqns)
    deps = [_deps_of(e, prod) for e in eqns]
    colls = [
        i for i, e in enumerate(eqns)
        if e.primitive.name in _COLLECTIVE_PRIMS
    ]
    if not colls:
        return None

    emitted = [False] * n
    order: List[int] = []

    def emit(i: int) -> None:
        # iterative DFS over producers (bodies run to thousands of eqns;
        # recursion would hit the interpreter limit on ResNet-50)
        stack = [(i, iter(deps[i]))]
        while stack:
            j, it = stack[-1]
            nxt = next((d for d in it if not emitted[d]), None)
            if nxt is None:
                stack.pop()
                if not emitted[j]:
                    emitted[j] = True
                    order.append(j)
            else:
                stack.append((nxt, iter(deps[nxt])))

    # readiness order by ASAP dataflow depth, NOT by original index:
    # in the synchronous program every bucket's pack sits at the tail
    # in plan order, so original indices would replay plan order.  The
    # ASAP level (longest producer chain from the inputs) is a pure
    # dataflow quantity: the loss pmean is shallowest (forward only),
    # then the buckets in the order backward truly finalizes them —
    # the LAST buckets (last layers' leaves) have the shortest
    # backward chains and issue first, i.e. reverse-planner order for
    # sequential models.  Ties fall back to original order, so the
    # schedule is a deterministic pure function of the program — every
    # rank computes the identical ordering.
    asap = [0] * n
    for i in range(n):
        asap[i] = 1 + max((asap[d] for d in deps[i]), default=-1)
    for c in sorted(colls, key=lambda c: (asap[c], c)):
        emit(c)
    for i in range(n):
        if not emitted[i]:
            emit(i)
    return order


def schedule_jaxpr(jaxpr_like):
    """Recursively apply the overlap schedule to a (closed) jaxpr.

    Descends through ``pjit``/``shard_map`` eqn params (where the train
    step's collectives live), re-emits each visited equation list in
    dependency-ASAP order, and rebuilds the enclosing structures.  A
    jaxpr with no collectives (or with effectful eqns) is returned
    unchanged at that level.
    """
    if isinstance(jaxpr_like, core.ClosedJaxpr):
        inner = schedule_jaxpr(jaxpr_like.jaxpr)
        if inner is jaxpr_like.jaxpr:  # keep the identity fast path
            return jaxpr_like
        return jaxpr_like.replace(jaxpr=inner)
    jaxpr = jaxpr_like
    new_eqns = []
    changed = False
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _DESCEND_PRIMS:
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                new_sub = schedule_jaxpr(sub)
                if new_sub is not sub:
                    eqn = eqn.replace(
                        params=dict(eqn.params, jaxpr=new_sub)
                    )
                    changed = True
        new_eqns.append(eqn)
    order = _schedule_eqns(new_eqns)
    if order is not None:
        new_eqns = [new_eqns[i] for i in order]
        changed = True
    if not changed:
        return jaxpr
    return jaxpr.replace(eqns=new_eqns)


# ----------------------------------------------------------------------
# ordering report + check material
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class IssueRecord:
    """Where one collective is issued relative to its readiness, inside
    one (sub-)jaxpr's equation list."""

    primitive: str
    index: int            # eqn position in the jaxpr
    ready_index: int      # position of its last direct producer
    operand_shapes: Tuple[Tuple[int, ...], ...]
    operand_dtypes: Tuple[str, ...]
    context: Tuple[str, ...]  # enclosing sub-jaxpr path
    # mesh axes the collective runs over — what disambiguates a hier
    # bucket's inter-hop psum (over the inter axis only) from a flat
    # bucket's fused psum (over every sync axis) when their operand
    # sizes collide
    axes: Tuple[str, ...] = ()

    @property
    def delay(self) -> int:
        """Equations sitting between operand readiness and dispatch.
        In a jaxpr (topological order) every transitive ancestor
        precedes the last direct producer, so ANY equation in that gap
        is foreign compute delaying the issue; the overlap schedule
        drives this to 0 for the wire's bucket reductions."""
        return self.index - self.ready_index - 1

    def is_bucket_psum(self, bucket_sizes: Sequence[int]) -> bool:
        """True when this record is one of the wire's fused bucket
        reductions: a flat 1-D psum whose element count matches a plan
        bucket (the loss pmean is scalar, the int8 scale pmax is the
        stacked ``(n_buckets,)`` vector — neither matches)."""
        if self.primitive != "psum":
            return False
        if len(self.operand_shapes) != 1:
            return False
        shape = self.operand_shapes[0]
        return len(shape) == 1 and int(shape[0]) in set(
            int(s) for s in bucket_sizes
        )


def issue_report(jaxpr_like, context: Tuple[str, ...] = ()
                 ) -> List[IssueRecord]:
    """Every collective's :class:`IssueRecord`, walking ``pjit``/
    ``shard_map`` sub-jaxprs (the same descent the scheduler performs).
    Static: nothing compiles or executes."""
    jaxpr = getattr(jaxpr_like, "jaxpr", jaxpr_like)
    eqns = jaxpr.eqns
    prod = _producers(eqns)
    out: List[IssueRecord] = []
    for i, eqn in enumerate(eqns):
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            deps = _deps_of(eqn, prod)
            shapes, dtypes = [], []
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "dtype"):
                    continue
                shapes.append(tuple(int(d) for d in aval.shape))
                dtypes.append(str(aval.dtype))
            ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if ax is None:
                ax = ()
            elif isinstance(ax, (str, int)):
                ax = (str(ax),)
            else:
                ax = tuple(str(a) for a in ax)
            out.append(IssueRecord(
                primitive=name,
                index=i,
                ready_index=max(deps, default=-1),
                operand_shapes=tuple(shapes),
                operand_dtypes=tuple(dtypes),
                context=context,
                axes=ax,
            ))
        if name in _DESCEND_PRIMS:
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                out.extend(issue_report(sub, context + (name,)))
    return out


def _plan_units(plan):
    """Normalize a ``BucketPlan`` or a schedule-carrying ``WirePlan``
    into per-bucket issue units: ``(schedule, head_prims, head_size,
    head_axes, shard_size)``.  A flat bucket's readiness unit is its
    fused psum (over every sync axis); a ``hier_rs_ag`` bucket's unit
    is HEADED by the intra ``psum_scatter`` (operand = the zero-padded
    bucket) with the inter psum and intra all-gather chained behind it
    — ONE readiness unit, because the tail collectives are
    data-dependent on the head (they cannot issue earlier than the rs
    completes, so only the head's issue position is an overlap property
    of the program).  ``head_axes`` is ``None`` for a bare BucketPlan
    (sync axes unknown — size-only matching, the pre-schedule
    contract); a WirePlan pins them, which is what keeps a flat
    bucket's psum from masquerading as a hier bucket's inter hop (or
    vice versa) when their operand sizes collide."""
    schedules = tuple(getattr(plan, "schedules", ()))
    buckets = plan.buckets
    if not schedules:
        return [("flat", ("psum",), b.size, None, None) for b in buckets]
    split = plan.split()
    units = []
    for i, (b, s) in enumerate(zip(buckets, schedules)):
        if s == "hier_rs_ag":
            # jax's lax.psum_scatter binds the reduce_scatter primitive
            # (older tiers may spell it psum_scatter) — match either
            units.append((s, ("reduce_scatter", "psum_scatter"),
                          plan.padded_size(i), (split.intra,),
                          plan.shard_size(i)))
        else:
            units.append((s, ("psum",), b.size, tuple(plan.axes), None))
    return units


def _is_unit_head(rec: IssueRecord, units) -> bool:
    if len(rec.operand_shapes) != 1:
        return False
    shape = rec.operand_shapes[0]
    if len(shape) != 1:
        return False
    return any(
        rec.primitive in prims
        and int(shape[0]) == int(size)
        and (axes is None or tuple(rec.axes) == tuple(axes))
        for _, prims, size, axes, _ in units
    )


def bucket_issue_report(jaxpr_like, plan) -> List[IssueRecord]:
    """The :class:`IssueRecord`\\ s of ``plan``'s bucket HEAD
    collectives (the fused psum of a flat bucket, the intra
    ``psum_scatter`` of a ``hier_rs_ag`` bucket), in program order —
    the raw material of the ordering-aware check
    (:func:`chainermn_tpu.analysis.checks.check_overlap`).  Accepts a
    bare ``BucketPlan`` (every bucket flat, the pre-schedule contract)
    or a ``WirePlan``."""
    units = _plan_units(plan)
    return [
        r for r in issue_report(jaxpr_like) if _is_unit_head(r, units)
    ]


def order_violations(jaxpr_like, plan) -> List[str]:
    """The ordering contract, in one place: every bucket's HEAD
    collective issued the moment its operands are ready (``delay == 0``
    — dispatched before the remaining backward segments complete), the
    program carrying one readiness unit per plan bucket, and — for
    ``hier_rs_ag`` buckets — the full rs→ar→ag triple present (an
    inter psum and an intra all_gather at the bucket's shard size).
    Returns one message per violation (empty = contract holds).  Both
    spellings of the check — :func:`assert_overlap_order` here and the
    ``Finding``-style :func:`chainermn_tpu.analysis.checks.
    check_overlap` — consume THIS list, so the contract cannot drift
    between them.  The synchronous wire fails for any multi-bucket
    plan (buckets pack first, then every head collective queues at the
    tail).

    Only the head's issue position is checked: a hier bucket's inter
    psum and all-gather are data-dependent on the head (they cannot
    issue before it completes), so the scheduler treating the triple
    as one readiness unit is exactly what lets ``assert_overlap_order``
    hold on the overlapped multi-hop program — and an equation from
    ANOTHER bucket's segment legally interleaving between a bucket's
    rs and its ar is overlap working, not a violation.
    """
    units = _plan_units(plan)
    # ONE dependency-frontier walk serves both the head-delay check and
    # the triple-completeness counts (the walk is linear in the jaxpr,
    # which runs to thousands of eqns on real train steps)
    all_recs = issue_report(jaxpr_like)
    recs = [r for r in all_recs if _is_unit_head(r, units)]
    out: List[str] = []
    if len(recs) < plan.n_buckets:
        out.append(
            f"found {len(recs)} bucket head collective(s) for a "
            f"{plan.n_buckets}-bucket plan — the program does not carry "
            "the wire's fused reductions"
        )
    for r in recs:
        if r.delay > 0:
            out.append(
                f"bucket {r.primitive} at eqn {r.index} "
                f"(shape {r.operand_shapes}) issued late — {r.delay} "
                f"foreign eqn(s) after its operands were ready (eqn "
                f"{r.ready_index}): communication is serialized behind "
                "compute instead of overlapping the remaining backward "
                "segments"
            )
    # hier buckets: the rs→ar→ag triple must be complete — a psum over
    # the INTER axis and an all_gather over the INTRA axis at shard
    # size per hier bucket (the inter psum's operand is the encoded
    # shard: 1-D, shard length, any dtype; the axes requirement is what
    # keeps a same-sized flat bucket's fused psum from masking a
    # genuinely lost inter hop)
    hier_shards = [s for sch, _, _, _, s in units if sch == "hier_rs_ag"]
    if hier_shards:
        split = plan.split()

        def count(prim, size, axes):
            return sum(
                1 for r in all_recs
                if r.primitive == prim
                and len(r.operand_shapes) == 1
                and len(r.operand_shapes[0]) == 1
                and int(r.operand_shapes[0][0]) == int(size)
                and tuple(r.axes) == tuple(axes)
            )

        for size in sorted(set(hier_shards)):
            want = hier_shards.count(size)
            for prim, axes, label in (
                ("psum", (split.inter,), "inter all-reduce"),
                ("all_gather", (split.intra,), "intra all-gather"),
            ):
                got = count(prim, size, axes)
                if got < want:
                    out.append(
                        f"hier_rs_ag triple incomplete: {got} {label}"
                        f"(s) at shard size {size} for {want} hier "
                        "bucket(s) — the multi-hop schedule lost a hop"
                    )
    return out


def assert_overlap_order(jaxpr_like, plan, *, label: str = "step") -> None:
    """Assert-style spelling of :func:`order_violations`: raises
    ``AssertionError`` listing every violation."""
    violations = order_violations(jaxpr_like, plan)
    if violations:
        raise AssertionError(
            f"{label}: overlap ordering contract violated — "
            + "; ".join(violations)
        )


# ----------------------------------------------------------------------
# the compiled-step wrapper
# ----------------------------------------------------------------------
class _Entry:
    __slots__ = ("fn", "fn_undonated", "out_tree", "closed")

    def __init__(self, fn, fn_undonated, out_tree, closed):
        self.fn = fn
        self.fn_undonated = fn_undonated
        self.out_tree = out_tree
        self.closed = closed


def _aval_sig(leaves) -> tuple:
    return tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
        for l in leaves
    )


class OverlappedStep:
    """Callable wrapper giving a traced function the overlap schedule.

    Behaves like the ``jax.jit`` object :func:`~chainermn_tpu.
    optimizers.build_train_step` otherwise returns: call it with
    ``(params, opt_state, batch)`` pytrees; ``.lower(...)`` exposes the
    lowered module for census cross-checks.  The schedule is built
    lazily per argument-shape signature (exactly like jit retraces):
    trace -> :func:`schedule_jaxpr` -> jit of the scheduled program.

    ``donate_subtrees``: how many leading arguments' buffers to donate
    (the step donates params and opt_state).  Donation is skipped when
    the wrapper is itself being traced (abstract args own no buffers).
    """

    def __init__(self, fn, *, donate_subtrees: int = 0,
                 label: str = "overlapped_step"):
        self._fn = fn
        self._donate_subtrees = int(donate_subtrees)
        self._label = label
        self._cache: dict = {}

    def _entry(self, args) -> _Entry:
        flat, in_tree = jax.tree_util.tree_flatten(args)
        key = (in_tree, _aval_sig(flat))
        entry = self._cache.get(key)
        if entry is None:
            closed, out_shape = jax.make_jaxpr(
                self._fn, return_shape=True
            )(*args)
            scheduled = schedule_jaxpr(closed)
            out_tree = jax.tree_util.tree_structure(out_shape)
            run = core.jaxpr_as_fun(scheduled)
            n_donate = sum(
                len(jax.tree_util.tree_leaves(a))
                for a in args[: self._donate_subtrees]
            )
            donated = jax.jit(
                run, donate_argnums=tuple(range(n_donate))
            ) if n_donate else jax.jit(run)
            entry = _Entry(donated, jax.jit(run), out_tree, scheduled)
            self._cache[key] = entry
        return entry

    def __call__(self, *args):
        entry = self._entry(args)
        flat = jax.tree_util.tree_leaves(args)
        fn = entry.fn
        if any(isinstance(l, jax.core.Tracer) for l in flat):
            # under an outer trace the flat args own no buffers; the
            # donated variant would only warn "donated buffers not
            # usable" on every trace_collectives walk
            fn = entry.fn_undonated
        return jax.tree_util.tree_unflatten(entry.out_tree, fn(*flat))

    def lower(self, *args):
        """Lowered module of the scheduled program (undonated variant,
        so census cross-checks can lower without consuming buffers)."""
        entry = self._entry(args)
        return entry.fn_undonated.lower(*jax.tree_util.tree_leaves(args))

    def scheduled_jaxpr(self, *args):
        """The scheduled ClosedJaxpr for these arguments — the object
        :func:`bucket_issue_report` / ``analysis.checks.check_overlap``
        inspect."""
        return self._entry(args).closed
