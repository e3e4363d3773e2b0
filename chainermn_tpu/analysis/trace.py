"""Collective trace extraction from jaxprs.

The SPMD contract under every communicator tier is that all ranks
execute the *same ordered sequence of collectives*; one divergent psum
deadlocks the job or silently mixes wire layouts.  This module makes
that sequence a first-class object: :func:`trace_collectives` traces any
jittable function (a compiled train step, an eager communicator method,
a bare shard_map body) to a :class:`CollectiveTrace` — the ordered list
of collective primitives with axis names, dtypes, shapes, and the
enclosing control-flow context — by walking the closed jaxpr recursively
through ``pjit`` / ``scan`` / ``cond`` / ``while`` / ``shard_map``
sub-jaxprs.

The walk is static: nothing is compiled or executed, so tracing even a
ResNet-50 train step costs milliseconds.  Counting is per jaxpr
*occurrence* — a collective inside ``scan`` appears once, exactly as it
appears once in the lowered HLO while-loop body — which is what lets the
trace census cross-check against the HLO text census
(:mod:`chainermn_tpu.analysis.hlo`) instead of replacing one grep with
another.

Two audits are gathered during the same walk (they need dataflow and
branch structure that the flat record list no longer has):

* narrowing casts feeding a reduction (the wire audit's raw material) —
  ``convert_element_type`` eqns that shrink the element and whose result
  is consumed by a psum-family reduction, annotated with the cast's
  source file so :func:`~chainermn_tpu.analysis.checks.check_wire` can
  exempt the sanctioned ``comm_wire`` codecs;
* per-branch collective signatures of every ``cond`` (the deadlock
  lint's raw material) — a data-dependent branch whose arms trace
  different collective sequences is the canonical SPMD deadlock.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import numpy as np

# strips the walk-global cond counter out of branch-/loop-relative
# signatures (see _Walker._walk_cond / _Walker._walk_while — while
# bodies label their contexts "while/cond"/"while/body" with no
# counter, so nested cond ids are the only ids to strip in both)
_COND_ID_RE = re.compile(r"cond#\d+")

# Communication primitives and the HLO op class each lowers to.  pmean
# has no primitive of its own (psum + divide), pgather/all_gather_invariant
# are folded into the gather class.  psum_invariant is what a psum of a
# varying value is under a vma-checked shard_map (jax 0.9): every
# gradient reduction autodiff inserts on the ``param_specs`` body.
# axis_index / axis_size move nothing, and pvary / pbroadcast only
# retype a value for the vma check: *not* communication, deliberately
# absent.
COLLECTIVE_CLASS = {
    "psum": "all_reduce",
    "psum_invariant": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "pgather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "ppermute": "collective_permute",
    "pshuffle": "collective_permute",
    "all_to_all": "all_to_all",
}

# classes whose semantics are a cross-rank *reduction* (the wire audit
# only cares about narrowed inputs to these — a narrowed ppermute
# payload loses precision locally, it does not corrupt a sum)
REDUCTION_CLASSES = ("all_reduce", "reduce_scatter")

# eqn params that distinguish two otherwise-identical collectives (a
# ppermute with a different perm is a different program)
_DETAIL_PARAMS = (
    "axis_index_groups",
    "all_gather_dimension",
    "scatter_dimension",
    "split_axis",
    "concat_axis",
    "axis_size",
    "tiled",
    "perm",
)


def _axes_of(params) -> Tuple[str, ...]:
    axes = params.get("axes", params.get("axis_name", ()))
    if axes is None:
        return ()
    if isinstance(axes, (str, int)):
        return (str(axes),)
    return tuple(str(a) for a in axes)


# ----------------------------------------------------------------------
# per-collective cost model (ISSUE 6): bytes-on-wire + hop class
# ----------------------------------------------------------------------
# Hop classification follows the hierarchical communicator's axis naming
# (``communicators/_topology.py`` derives the ('mn_inter', 'mn_intra')
# pair): an axis whose name carries "inter" crosses node/slice
# boundaries (DCN-class links), "intra" stays on one ICI island, and a
# topology-agnostic axis ("mn") is "flat" — a single axis spanning the
# whole communicator, intra-slice on one-slice worlds.  The comm_wire
# planner consumes this to size buckets per link class (DynamiQ-style
# byte/latency accounting, PAPERS.md).
def hop_class(axes) -> str:
    """"inter" / "intra" / "mixed" / "flat" / "local" for a collective's
    mesh axis tuple."""
    if not axes:
        return "local"
    kinds = set()
    for a in axes:
        a = str(a)
        if "inter" in a:
            kinds.add("inter")
        elif "intra" in a:
            kinds.add("intra")
        else:
            kinds.add("flat")
    if kinds == {"flat"}:
        return "flat"
    if len(kinds) > 1:
        return "mixed"
    return kinds.pop()


def _world_of(axis_sizes: Tuple[int, ...]) -> Optional[int]:
    """Total ranks spanned by a collective's axis tuple; None when any
    size is unknown (0).  The ONE definition behind both
    ``CollectiveRecord.world`` and the walker's wire pricing."""
    if not axis_sizes or any(s <= 0 for s in axis_sizes):
        return None
    n = 1
    for s in axis_sizes:
        n *= s
    return n


def wire_bytes(cls: str, payload_bytes: int,
               world: Optional[int]) -> Optional[int]:
    """Per-rank bytes shipped for one collective under the standard ring
    algorithms; ``None`` when the axis size (``world``) is unknown.

    ``payload_bytes`` is the operand bytes as the record carries them
    (per-shard input for all_reduce/all_gather/ppermute, the full block
    being scattered for reduce_scatter).  Formulas: ring all-reduce
    moves ``2p(n-1)/n`` per rank (reduce-scatter + all-gather halves),
    reduce-scatter/all-to-all ``p(n-1)/n``, all-gather receives the
    other ``n-1`` shards (``p(n-1)``), collective-permute is one hop
    (``p``).
    """
    if world is None or world <= 0:
        return None
    n = world
    if cls == "all_reduce":
        return int(2 * payload_bytes * (n - 1) / n)
    if cls in ("reduce_scatter", "all_to_all"):
        return int(payload_bytes * (n - 1) / n)
    if cls == "all_gather":
        return int(payload_bytes * (n - 1))
    if cls == "collective_permute":
        return int(payload_bytes)
    return int(payload_bytes)


def _source_of(eqn) -> Optional[str]:
    """``file:line`` of the user frame that issued this eqn, if known."""
    from jax._src import source_info_util as siu

    # jax 0.9: user_frame takes the traceback, not the SourceInfo
    fr = siu.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None
    return f"{fr.file_name}:{fr.start_line}"


@dataclass(frozen=True)
class CollectiveRecord:
    """One collective primitive occurrence in program order."""

    primitive: str          # jaxpr primitive name (psum, all_gather, ...)
    cls: str                # HLO op class (all_reduce, all_to_all, ...)
    axes: Tuple[str, ...]   # mesh axis names reduced/permuted over
    dtypes: Tuple[str, ...]  # operand dtypes, in operand order
    shapes: Tuple[Tuple[int, ...], ...]  # operand shapes
    context: Tuple[str, ...]  # enclosing sub-jaxpr path, outermost first
    detail: str = ""        # canonicalized distinguishing params
    source: Optional[str] = None  # file:line of the issuing call
    # -- cost model (derived; excluded from signature()/hash) ----------
    axis_sizes: Tuple[int, ...] = ()  # size per axis in `axes` (0 unknown)
    payload_bytes: int = 0  # operand bytes entering the collective
    bytes_on_wire: Optional[int] = None  # per-rank wire bytes (ring)
    hop: str = "local"      # "inter"/"intra"/"mixed"/"flat"/"local"

    @property
    def world(self) -> Optional[int]:
        """Total ranks this collective spans (None if any axis size is
        unknown at trace time)."""
        return _world_of(self.axis_sizes)

    def signature(self, context_from: int = 0) -> str:
        """Canonical string for hashing/comparison.  Excludes ``source``
        (formatting-only edits must not change the trace hash) and keeps
        everything that changes the compiled program.  ``context_from``
        drops that many leading context elements — the cond deadlock
        lint compares branch bodies *relative to the branch*, so two
        arms with identical collectives compare equal even though their
        absolute contexts carry different branch labels."""
        return "|".join(
            (
                self.primitive,
                ",".join(self.axes),
                ",".join(self.dtypes),
                ";".join("x".join(map(str, s)) for s in self.shapes),
                "/".join(self.context[context_from:]),
                self.detail,
            )
        )

    def in_cond(self) -> bool:
        return any(c.startswith("cond#") for c in self.context)


@dataclass(frozen=True)
class NarrowingCast:
    """A dtype-narrowing ``convert_element_type`` feeding a reduction."""

    collective: CollectiveRecord
    src_dtype: str
    dst_dtype: str
    cast_source: Optional[str]  # file:line of the cast


@dataclass(frozen=True)
class CondBranchReport:
    """Per-branch collective signatures of one ``cond`` eqn."""

    cond_id: str                 # "cond#<k>" — unique within the trace
    context: Tuple[str, ...]     # context of the cond eqn itself
    branch_signatures: Tuple[Tuple[str, ...], ...]
    source: Optional[str] = None

    @property
    def has_collectives(self) -> bool:
        return any(self.branch_signatures)

    @property
    def diverges(self) -> bool:
        """True when the arms trace different collective sequences —
        rank-dependent predicates then deadlock or mis-pair wires."""
        sigs = self.branch_signatures
        return any(s != sigs[0] for s in sigs[1:])


@dataclass(frozen=True)
class WhileReport:
    """Collective signatures of one ``while`` eqn's cond/body jaxprs —
    the deadlock lint's raw material for data-dependent loops.

    A collective inside a ``while`` body executes once per iteration:
    rank-divergent trip counts issue rank-divergent collective sequences
    (the while analogue of divergent ``cond`` arms).  Two statically
    checkable mitigations are recorded:

    * ``counter_only_predicate`` — the exit predicate reads only carry
      slots that the body advances by a constant (the ``fori_loop``
      shape), so the trip count is a pure function of loop-invariant
      inputs (assumed rank-uniform, as for ``cond`` predicates);
    * ``cond_has_reduction`` — the predicate itself is computed through
      a cross-rank reduction (the convergence-loop shape: every rank
      agrees on the continue/exit decision by construction).
    """

    while_id: str                 # "while#<k>" — unique within the trace
    context: Tuple[str, ...]      # context of the while eqn itself
    cond_signatures: Tuple[str, ...]
    body_signatures: Tuple[str, ...]
    counter_only_predicate: bool
    cond_has_reduction: bool
    source: Optional[str] = None

    @property
    def has_collectives(self) -> bool:
        return bool(self.cond_signatures or self.body_signatures)

    @property
    def trip_count_agreed(self) -> bool:
        """True when the trip count is statically rank-uniform (counter
        predicate) or rank-agreed (reduction inside the predicate)."""
        return self.counter_only_predicate or self.cond_has_reduction


@dataclass(frozen=True)
class CollectiveTrace:
    """Ordered collective records of one traced program + walk-time
    audit material.  Immutable; all checks live in ``analysis.checks``.
    """

    records: Tuple[CollectiveRecord, ...]
    narrowing_casts: Tuple[NarrowingCast, ...] = ()
    cond_reports: Tuple[CondBranchReport, ...] = ()
    label: str = "trace"
    while_reports: Tuple[WhileReport, ...] = ()

    def __len__(self) -> int:
        return len(self.records)

    def __iter__(self):
        return iter(self.records)

    def census(self) -> dict:
        """``{hlo_op_class: count}`` over all records (zero counts
        omitted) — the analyzer-side half of the HLO cross-check."""
        out: dict = {}
        for r in self.records:
            out[r.cls] = out.get(r.cls, 0) + 1
        return out

    def count(self, cls: str) -> int:
        return self.census().get(cls, 0)

    def wire_census(self, by_class: bool = False) -> dict:
        """``{hop_class: total bytes_on_wire}`` over records whose axis
        sizes were known at trace time (zero totals omitted) — the
        aggregate the comm_wire planner's hop-aware bucket sizing
        consumes.

        ``by_class=True`` keys the totals ``"{hop}/{op_class}"`` (e.g.
        ``"intra/reduce_scatter"``, ``"inter/all_reduce"``) — the
        per-hop attribution of a multi-hop schedule's rs→ar→ag triple,
        which is how a hier-scheduled step SHOWS its inter-hop byte
        saving: the flat step's bytes sit under ``mixed/all_reduce``,
        the staged step's under intra rs/ag plus a small
        ``inter/all_reduce``."""
        out: dict = {}
        for r in self.records:
            if r.bytes_on_wire:
                key = f"{r.hop}/{r.cls}" if by_class else r.hop
                out[key] = out.get(key, 0) + r.bytes_on_wire
        return out

    def axis_names(self) -> Tuple[str, ...]:
        seen: list = []
        for r in self.records:
            for a in r.axes:
                if a not in seen:
                    seen.append(a)
        return tuple(seen)

    def canonical(self) -> str:
        """Canonical multi-line serialization (one signature per record,
        program order) — the thing the divergence guard hashes.  Pure
        function of the traced program: values, device placement, and
        source locations do not enter."""
        return "\n".join(r.signature() for r in self.records)

    def trace_hash(self) -> str:
        """sha256 of :meth:`canonical` — the cross-process agreement
        token (salted ``hash()`` would differ per interpreter)."""
        return hashlib.sha256(self.canonical().encode()).hexdigest()


# ----------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------
def _eqns(jaxpr_like):
    """Eqn list of a Jaxpr or ClosedJaxpr (shard_map carries an open
    Jaxpr; pjit/scan/cond carry ClosedJaxprs)."""
    inner = getattr(jaxpr_like, "jaxpr", jaxpr_like)
    return inner.eqns, inner


def _avals(eqn):
    dtypes, shapes = [], []
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        dtypes.append(str(aval.dtype))
        shapes.append(tuple(int(d) for d in aval.shape))
    return tuple(dtypes), tuple(shapes)


def _detail_of(params) -> str:
    parts = []
    for k in _DETAIL_PARAMS:
        if k in params and params[k] is not None:
            parts.append(f"{k}={params[k]}")
    return ";".join(parts)


_CTX_LABELS = {
    "jit": "pjit",
    "scan": "scan",
    "shard_map": "shard_map",
    "remat": "remat",
    "remat2": "remat",
    "checkpoint": "remat",
    "custom_jvp_call": "custom_jvp",
    "custom_vjp_call": "custom_vjp",
    "custom_vjp_call_jaxpr": "custom_vjp",
}


def _is_jaxpr(x) -> bool:
    return hasattr(x, "eqns") or hasattr(x, "jaxpr")


class _Walker:
    def __init__(self, axis_sizes=None):
        self.records: list = []
        self.narrowing: list = []
        self.cond_reports: list = []
        self.while_reports: list = []
        self._cond_counter = 0
        self._while_counter = 0
        # mesh axis name -> size, for the cost model.  Seeded by the
        # caller (eager paths whose mesh is not in the jaxpr) and
        # updated authoritatively from every shard_map eqn's mesh param.
        self._axis_env: dict = dict(axis_sizes or {})

    def walk(self, jaxpr_like, context: Tuple[str, ...] = (),
             narrow_in: Optional[dict] = None) -> None:
        """``narrow_in``: vars of this scope known (from the caller's
        scope) to carry a narrowing-cast result, mapped to their
        (src_dtype, dst_dtype, source) provenance."""
        eqns, jaxpr = _eqns(jaxpr_like)
        narrow: dict = dict(narrow_in or {})
        for eqn in eqns:
            name = eqn.primitive.name
            params = eqn.params

            if name == "convert_element_type":
                self._note_cast(eqn, narrow)
            elif name in COLLECTIVE_CLASS:
                rec = self._record(eqn, context)
                self.records.append(rec)
                if rec.cls in REDUCTION_CLASSES:
                    for v in eqn.invars:
                        if id(v) in narrow:
                            src, dst, where = narrow[id(v)]
                            self.narrowing.append(
                                NarrowingCast(rec, src, dst, where)
                            )

            if name == "cond" and "branches" in params:
                self._walk_cond(eqn, context, narrow)
            elif name == "while":
                self._walk_while(eqn, context)
            else:
                self._walk_generic_subs(eqn, context, narrow)

    # -- helpers -------------------------------------------------------
    def _record(self, eqn, context) -> CollectiveRecord:
        dtypes, shapes = _avals(eqn)
        axes = _axes_of(eqn.params)
        cls = COLLECTIVE_CLASS[eqn.primitive.name]
        sizes = tuple(int(self._axis_env.get(a, 0)) for a in axes)
        payload = 0
        for dt, sh in zip(dtypes, shapes):
            n = 1
            for d in sh:
                n *= int(d)
            payload += n * np.dtype(dt).itemsize
        world = _world_of(sizes)
        return CollectiveRecord(
            primitive=eqn.primitive.name,
            cls=cls,
            axes=axes,
            dtypes=dtypes,
            shapes=shapes,
            context=context,
            detail=_detail_of(eqn.params),
            source=_source_of(eqn),
            axis_sizes=sizes,
            payload_bytes=payload,
            bytes_on_wire=wire_bytes(cls, payload, world),
            hop=hop_class(axes),
        )

    def _note_cast(self, eqn, narrow) -> None:
        inv = eqn.invars[0]
        outv = eqn.outvars[0]
        src = getattr(getattr(inv, "aval", None), "dtype", None)
        dst = getattr(getattr(outv, "aval", None), "dtype", None)
        if src is None or dst is None:
            return
        if np.dtype(dst).itemsize < np.dtype(src).itemsize:
            narrow[id(outv)] = (str(src), str(dst), _source_of(eqn))
        elif id(inv) in narrow:
            # widening a previously-narrowed value does not undo the
            # precision loss (int8 -> int32 before an integer psum is
            # still an int8 wire): provenance follows the value
            narrow[id(outv)] = narrow[id(inv)]

    def _walk_cond(self, eqn, context, narrow) -> None:
        self._cond_counter += 1
        cond_id = f"cond#{self._cond_counter}"
        sigs = []
        for i, branch in enumerate(eqn.params["branches"]):
            label = f"{cond_id}[{i}]"
            start = len(self.records)
            sub_narrow = self._map_into(eqn, branch, narrow,
                                        skip_leading=1)  # predicate
            self.walk(branch, context + (label,), sub_narrow)
            # branch-RELATIVE signatures: arms with identical
            # collective bodies must compare equal despite carrying
            # different branch labels in their absolute contexts — and
            # despite NESTED conds drawing different ids from the
            # global counter (arm 0's inner cond is cond#2, arm 1's
            # identical one cond#3), so the ids are stripped here; the
            # trace hash keeps them (the counter sequence is a
            # deterministic function of the program, so equal programs
            # still hash equal)
            sigs.append(tuple(
                _COND_ID_RE.sub("cond", r.signature(
                    context_from=len(context) + 1
                ))
                for r in self.records[start:]
            ))
        self.cond_reports.append(CondBranchReport(
            cond_id=cond_id,
            context=context,
            branch_signatures=tuple(sigs),
            source=_source_of(eqn),
        ))

    def _walk_while(self, eqn, context) -> None:
        """Trace a ``while`` eqn's cond/body and file a
        :class:`WhileReport` (the while half of the deadlock lint —
        PR 4 only analyzed ``cond`` arms)."""
        self._while_counter += 1
        wid = f"while#{self._while_counter}"
        params = eqn.params
        sigs, recs = {}, {}
        for key, lbl in (("cond_jaxpr", "while/cond"),
                         ("body_jaxpr", "while/body")):
            start = len(self.records)
            if key in params:
                self.walk(params[key], context + (lbl,))
            recs[key] = self.records[start:]
            # loop-relative signatures, nested-cond ids stripped (same
            # treatment as cond arms): informational, stable across
            # unrelated edits
            sigs[key] = tuple(
                _COND_ID_RE.sub("cond", r.signature(
                    context_from=len(context) + 1
                ))
                for r in recs[key]
            )
        cond_recs_reduce = any(
            r.cls == "all_reduce" for r in recs["cond_jaxpr"]
        )
        self.while_reports.append(WhileReport(
            while_id=wid,
            context=context,
            cond_signatures=sigs.get("cond_jaxpr", ()),
            body_signatures=sigs.get("body_jaxpr", ()),
            counter_only_predicate=_predicate_is_counter_only(params),
            cond_has_reduction=cond_recs_reduce,
            source=_source_of(eqn),
        ))

    def _walk_generic_subs(self, eqn, context, narrow) -> None:
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                try:
                    self._axis_env.update(
                        {str(k): int(v) for k, v in dict(shape).items()}
                    )
                except Exception:
                    pass
        label_base = _CTX_LABELS.get(
            eqn.primitive.name, eqn.primitive.name
        )
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for i, sub in enumerate(vals):
                if not _is_jaxpr(sub):
                    continue
                label = (
                    label_base
                    if len(vals) == 1
                    else f"{label_base}:{key}[{i}]"
                )
                self.walk(
                    sub,
                    context + (label,),
                    self._map_into(eqn, sub, narrow),
                )

    @staticmethod
    def _map_into(eqn, sub, narrow, skip_leading: int = 0) -> dict:
        """Translate narrowing provenance across a sub-jaxpr boundary by
        positional invar alignment (exact for pjit / shard_map / cond
        branches; scan's const/carry/xs packing is skipped rather than
        guessed — a missed propagation under-reports, never
        mis-reports)."""
        if not narrow:
            return {}
        inner = getattr(sub, "jaxpr", sub)
        outer = list(eqn.invars)[skip_leading:]
        inner_vars = list(inner.invars)
        if len(outer) != len(inner_vars):
            return {}
        out = {}
        for o, s in zip(outer, inner_vars):
            if id(o) in narrow:
                out[id(s)] = narrow[id(o)]
        return out


def _predicate_is_counter_only(while_params) -> bool:
    """True when the ``while`` exit predicate reads ONLY carry slots the
    body advances by a constant (the ``fori_loop`` shape) — the trip
    count is then a pure function of loop-invariant inputs, which the
    lint assumes rank-uniform (the same assumption it makes for ``cond``
    predicates built from replicated values).

    Conservative in the safe direction: any slot the analysis cannot
    prove counter-like makes the predicate data-dependent.
    """
    try:
        cond_jaxpr = while_params["cond_jaxpr"].jaxpr
        body_jaxpr = while_params["body_jaxpr"].jaxpr
        cond_nconsts = int(while_params.get("cond_nconsts", 0))
        body_nconsts = int(while_params.get("body_nconsts", 0))
    except (KeyError, AttributeError):
        return False

    # vars the predicate transitively depends on, within the cond jaxpr
    needed = {id(v) for v in cond_jaxpr.outvars if not hasattr(v, "val")}
    for eqn in reversed(cond_jaxpr.eqns):
        if any(id(ov) in needed for ov in eqn.outvars):
            needed.update(
                id(iv) for iv in eqn.invars if not hasattr(iv, "val")
            )
    carry_in = list(cond_jaxpr.invars)[cond_nconsts:]
    read_slots = [i for i, v in enumerate(carry_in) if id(v) in needed]

    body_carry_in = list(body_jaxpr.invars)[body_nconsts:]
    body_consts = {id(v) for v in body_jaxpr.constvars}
    producers = {}
    for eqn in body_jaxpr.eqns:
        for ov in eqn.outvars:
            producers[id(ov)] = eqn

    def counter_like(slot: int) -> bool:
        if slot >= len(body_jaxpr.outvars) or slot >= len(body_carry_in):
            return False
        out = body_jaxpr.outvars[slot]
        src = body_carry_in[slot]
        if out is src:  # unchanged slot: loop-invariant value
            return True
        eqn = producers.get(id(out))
        if eqn is None or eqn.primitive.name not in ("add", "sub"):
            return False
        ids = [iv for iv in eqn.invars]
        has_self = any(iv is src for iv in ids)
        others_const = all(
            iv is src or hasattr(iv, "val") or id(iv) in body_consts
            for iv in ids
        )
        return has_self and others_const

    return all(counter_like(i) for i in read_slots)


def trace_jaxpr(jaxpr_like, label: str = "trace",
                axis_sizes=None) -> CollectiveTrace:
    """Walk an already-made (closed) jaxpr into a
    :class:`CollectiveTrace`.  ``axis_sizes`` seeds the cost model's
    mesh-axis sizes for programs whose jaxpr carries no shard_map mesh
    (every shard_map eqn's own mesh overrides the seed)."""
    w = _Walker(axis_sizes=axis_sizes)
    w.walk(jaxpr_like)
    return CollectiveTrace(
        records=tuple(w.records),
        narrowing_casts=tuple(w.narrowing),
        cond_reports=tuple(w.cond_reports),
        label=label,
        while_reports=tuple(w.while_reports),
    )


def trace_collectives(fn: Callable, *args, label: Optional[str] = None,
                      axis_sizes=None, **kwargs) -> CollectiveTrace:
    """Trace ``fn(*args, **kwargs)`` to its ordered collective sequence.

    ``fn`` is anything jax can trace: a plain function, a jitted train
    step, a ``shard_map``-wrapped body, or an eager communicator method
    whose dispatch is built from cached jit programs (the jaxpr then
    contains ``pjit`` eqns that the walker descends into).  Args may be
    arrays or ``jax.ShapeDtypeStruct``\\ s — only shapes/dtypes matter.

    Nothing is compiled or executed; no collective runs.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return trace_jaxpr(
        jaxpr, label=label or getattr(fn, "__name__", "trace"),
        axis_sizes=axis_sizes,
    )
