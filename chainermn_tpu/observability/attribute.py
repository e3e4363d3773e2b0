"""Static-vs-measured join: match collective spans to CollectiveRecords.

The analyzer prices every collective statically — ``CollectiveRecord``
carries ``payload_bytes`` and ``bytes_on_wire`` (ring formulas); the
other term is what each collective *achieved* at runtime.
:func:`attribute` joins the timeline's measured collective spans to a trace's records and
computes per-record achieved bytes/sec, the number "Optimizing
Allreduce Operations for Modern Heterogeneous Architectures"
(PAPERS.md) compares against the link ceiling to localize a slow wire.

Matching is class-aware and payload-aware: a span named
``collective.psum`` (an eager-tier bucket reduction) pairs with the
first unmatched ``all_reduce`` record whose per-shard payload bytes
equal the span's ``bytes`` arg; when no byte-exact record exists the
first unmatched record of the class is taken in program order (the
wire's buckets are deterministic, so program order IS bucket order).
Staged buckets are triple-aware (ISSUE 12): the eager hier wire times
one compiled program that executes a whole rs→ar→ag triple, and marks
its span ``schedule="hier_rs_ag"`` with the shard payload — the span
then consumes the bucket's reduce_scatter record (byte-exact on the
full bucket) plus the shard-payload all_reduce and all_gather legs as
ONE attribution whose wire bytes are the triple's total, instead of
mis-pairing with a lone all_reduce and stranding the rs/ag records.
Unmatched records and spans are reported, not silently dropped —
attribution that quietly loses a collective would hide exactly the
discrepancies it exists to surface.

:func:`measured_issue_report` is the measured analogue of
``analysis.check_overlap``'s ``delay``: for each eager
``collective.allreduce_grad`` dispatch, did bucket ``k``'s psum issue
at its readiness frontier (its payload staged AND the previous bucket
dispatched), or did foreign host work sit in between?
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# span name -> HLO op class of the record it measures.  The eager
# ``bcast``/``send`` implementations lower to masked psums, so their
# spans honestly attribute to all_reduce records.
SPAN_CLASS = {
    "collective.psum": "all_reduce",
    "collective.allreduce": "all_reduce",
    "collective.bcast": "all_reduce",
    "collective.send": "all_reduce",
    "collective.allgather": "all_gather",
    "collective.alltoall": "all_to_all",
    "collective.reduce_scatter": "reduce_scatter",
}


@dataclass(frozen=True)
class Attribution:
    """One measured collective span joined to one static record."""

    record: object               # analysis.trace.CollectiveRecord
    span_name: str
    span_args: dict
    duration_s: float
    measured_bytes: Optional[int]    # per-rank payload the span reported
    bytes_on_wire: Optional[int]     # the record's ring-model wire bytes
    achieved_bytes_per_sec: Optional[float]
    byte_exact: bool             # payload bytes matched exactly

    @property
    def bucket(self) -> Optional[int]:
        b = self.span_args.get("bucket")
        return int(b) if b is not None else None


@dataclass
class AttributionReport:
    """:func:`attribute`'s result: the joined pairs plus everything
    that failed to join (the interesting part of a mismatch)."""

    matched: List[Attribution] = field(default_factory=list)
    unmatched_records: List[object] = field(default_factory=list)
    unmatched_spans: List[dict] = field(default_factory=list)

    @property
    def n_matched(self) -> int:
        return len(self.matched)

    def buckets(self) -> List[Attribution]:
        return [a for a in self.matched if a.bucket is not None]

    def total_achieved_bytes_per_sec(self) -> Optional[float]:
        """Aggregate wire bandwidth over the byte-priced matches."""
        tot_b, tot_t = 0, 0.0
        for a in self.matched:
            if a.bytes_on_wire and a.duration_s > 0:
                tot_b += a.bytes_on_wire
                tot_t += a.duration_s
        return tot_b / tot_t if tot_t > 0 else None

    def bandwidth_points(self) -> List[tuple]:
        """``(hop, cls, payload_bytes, achieved_bytes_per_sec,
        duration_s)`` per byte-priced match — the curve export the
        measured-feedback autotuner bins into a ``BandwidthProfile``
        (``comm_wire.autotune.profile_from_attribution`` consumes
        either this report or the raw timeline+trace pair).

        Staged-bucket matches (a span covering a whole hier rs→ar→ag
        triple, marked ``schedule="hier_rs_ag"``) are EXCLUDED: the
        composite duration spans three collectives over two hop
        classes, so it belongs to no single (hop, class) curve —
        binning it under the head record's (intra, reduce_scatter)
        would poison the intra curve with inter-bound timings."""
        out = []
        for a in self.matched:
            if not a.achieved_bytes_per_sec:
                continue
            if a.span_args.get("schedule") == "hier_rs_ag":
                continue
            rec = a.record
            out.append((
                getattr(rec, "hop", "flat"),
                getattr(rec, "cls", "all_reduce"),
                int(getattr(rec, "payload_bytes", 0) or 0),
                float(a.achieved_bytes_per_sec),
                float(a.duration_s),
            ))
        return out


def _collective_spans(timeline) -> List[dict]:
    return [
        s for s in timeline.spans() if s["name"] in SPAN_CLASS
    ]


def attribute(timeline, trace) -> AttributionReport:
    """Join measured collective spans (time order) to ``trace``'s
    :class:`CollectiveRecord`\\ s (program order).

    ``timeline`` is an ``observability.Timeline`` (or ``Telemetry`` —
    its timeline is taken); ``trace`` an ``analysis.CollectiveTrace``.
    Neither side is mutated.
    """
    tl = getattr(timeline, "timeline", timeline)
    spans = _collective_spans(tl)
    records = list(trace)
    taken = [False] * len(records)
    report = AttributionReport()

    def span_bytes(sp):
        b = sp["args"].get("bytes")
        return int(b) if isinstance(b, (int, float)) and b else None

    # pass 1: byte-exact pairs for every byte-carrying span FIRST — a
    # single greedy pass would let an earlier bytes-less span consume
    # (in program order) the record a later span matches exactly,
    # mispricing both
    picks: Dict[int, Tuple[int, bool]] = {}  # span idx -> (rec idx, exact)
    extras: Dict[int, List[int]] = {}  # span idx -> extra record idxs

    def take_exact(cls, nb, hop=None):
        for i, r in enumerate(records):
            if taken[i] or r.cls != cls or \
                    int(r.payload_bytes) != int(nb):
                continue
            if hop is not None and getattr(r, "hop", None) != hop:
                # triple legs are HOP-pinned: a tiny staged bucket's
                # 4-byte ar leg must not consume the 4-byte loss pmean
                # (a mixed-hop record) just because the bytes collide
                continue
            taken[i] = True
            return i
        return None

    # pass 1a: staged-bucket spans (the eager hier wire marks them with
    # schedule="hier_rs_ag" + per-leg operand bytes) consume their
    # whole rs->ar->ag record TRIPLE: the span times ONE compiled
    # program that executes three collectives, so pairing it with a
    # single all_reduce record — the shard-payload inter hop, or worse
    # the loss pmean — would misprice both sides and leave the rs/ag
    # records spuriously unmatched.  Each leg matches on ITS OWN
    # disclosed bytes (rs: intra-padded native bucket; ar: wire-cast
    # shard; ag: native shard), so padding and cast codecs cannot
    # defeat the byte-exact pairing.
    for si, sp in enumerate(spans):
        args = sp["args"]
        if args.get("schedule") != "hier_rs_ag":
            continue
        leg_bytes = [args.get(k) for k in
                     ("rs_bytes", "ar_bytes", "ag_bytes")]
        if any(b is None for b in leg_bytes):
            continue
        head = take_exact("reduce_scatter", leg_bytes[0], "intra")
        if head is None:
            continue  # no staged record: the generic passes handle it
        legs = [
            take_exact("all_reduce", leg_bytes[1], "inter"),
            take_exact("all_gather", leg_bytes[2], "intra"),
        ]
        picks[si] = (head, True)
        extras[si] = [i for i in legs if i is not None]
    for si, sp in enumerate(spans):
        if si in picks:
            continue
        nb = span_bytes(sp)
        if nb is None:
            continue
        cls = SPAN_CLASS[sp["name"]]
        for i, r in enumerate(records):
            if taken[i] or r.cls != cls:
                continue
            if int(r.payload_bytes) == nb:
                taken[i] = True
                picks[si] = (i, True)
                break
    # pass 2: order fallback for whatever remains on either side
    for si, sp in enumerate(spans):
        if si in picks:
            continue
        cls = SPAN_CLASS[sp["name"]]
        for i, r in enumerate(records):
            if not taken[i] and r.cls == cls:
                taken[i] = True
                picks[si] = (i, False)
                break

    for si, sp in enumerate(spans):
        if si not in picks:
            report.unmatched_spans.append(sp)
            continue
        i, exact = picks[si]
        rec = records[i]
        dur = float(sp["dur"])
        bow = rec.bytes_on_wire
        for j in extras.get(si, ()):
            # a staged span's wire bytes are the TRIPLE's total — the
            # head rs record plus its consumed ar/ag legs
            leg = records[j].bytes_on_wire
            if bow is not None and leg is not None:
                bow += leg
        report.matched.append(Attribution(
            record=rec,
            span_name=sp["name"],
            span_args=dict(sp["args"]),
            duration_s=dur,
            measured_bytes=span_bytes(sp),
            bytes_on_wire=bow,
            achieved_bytes_per_sec=(
                bow / dur if bow and dur > 0 else None
            ),
            byte_exact=exact,
        ))
    report.unmatched_records = [
        r for i, r in enumerate(records) if not taken[i]
    ]
    return report


# ----------------------------------------------------------------------
# KV handoff pricing (serving.disagg's transfer-once wire tier)
# ----------------------------------------------------------------------
# The disaggregated serving tier's handoff is not a collective — there
# is no CollectiveRecord to join against — but its spans carry exact
# wire bytes the same way bucket psums do, so the same pricing question
# applies: what did the transfer achieve against the link ceiling?

KV_SPANS = ("kv.export", "kv.ship", "kv.import")


def kv_transfer_points(timeline) -> List[tuple]:
    """``(name, wire_bytes, achieved_bytes_per_sec, duration_s)`` per
    byte-carrying ``kv.*`` span — the handoff analogue of
    :meth:`AttributionReport.bandwidth_points`, ready to compare a
    disaggregated pool's KV shipping against the bandwidth profile.
    Spans without a ``bytes`` arg (a ship that failed before packing)
    are skipped; zero-duration spans price at ``None`` rather than inf.
    """
    tl = getattr(timeline, "timeline", timeline)
    out = []
    for sp in tl.spans():
        if sp["name"] not in KV_SPANS:
            continue
        b = sp["args"].get("bytes")
        if not isinstance(b, (int, float)) or b <= 0:
            continue
        dur = float(sp["dur"])
        out.append((
            sp["name"],
            int(b),
            (float(b) / dur) if dur > 0 else None,
            dur,
        ))
    return out


# ----------------------------------------------------------------------
# measured issue delays (the runtime analogue of check_overlap's delay)
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class MeasuredIssue:
    """One bucket psum's measured issue delay within one dispatch."""

    bucket: int
    delay_s: float       # gap between readiness frontier and issue
    issue_t: float       # span start, timeline-relative seconds
    duration_s: float


def measured_issue_report(timeline) -> List[List[MeasuredIssue]]:
    """Per eager ``collective.allreduce_grad`` dispatch, each bucket
    psum's measured issue delay.

    Readiness frontier of bucket ``k`` = max(end of its ``wire.ship``
    span, end of bucket ``k-1``'s psum span) — its payload must be
    staged and the (serial) dispatch loop must have reached it; for
    bucket 0 the previous-psum term is the ``wire.pack`` end.  A large
    delay means foreign host work sat between readiness and issue —
    the measured twin of ``analysis.check_overlap``'s equation-count
    ``delay``, with the same reading: the wire was ready, the program
    wasn't issuing.
    """
    tl = getattr(timeline, "timeline", timeline)
    spans = tl.spans()
    groups: Dict[int, dict] = {}
    for sp in spans:
        if sp["name"] == "collective.allreduce_grad":
            groups[sp["sid"]] = {"pack": None, "ships": {}, "psums": []}
    if not groups:
        return []

    by_id = {s["sid"]: s for s in spans}

    def enclosing(sp) -> Optional[int]:
        p = sp.get("parent", 0)
        # parent chains are shallow here (grad -> pack/ship/psum), but
        # walk up through any intermediate spans to the dispatch span
        while p:
            if p in groups:
                return p
            parent = by_id.get(p)
            if parent is None:
                return None
            p = parent.get("parent", 0)
        return None

    for sp in spans:
        gid = enclosing(sp)
        if gid is None:
            continue
        g = groups[gid]
        if sp["name"] == "wire.pack":
            g["pack"] = sp
        elif sp["name"] == "wire.ship":
            g["ships"][sp["args"].get("bucket")] = sp
        elif sp["name"] == "collective.psum":
            g["psums"].append(sp)

    out: List[List[MeasuredIssue]] = []
    for gid in sorted(groups):
        g = groups[gid]
        psums = sorted(g["psums"], key=lambda s: s["t"])
        issues: List[MeasuredIssue] = []
        prev_end = (
            g["pack"]["t"] + g["pack"]["dur"] if g["pack"] else None
        )
        for sp in psums:
            k = sp["args"].get("bucket")
            ready = prev_end
            ship = g["ships"].get(k)
            if ship is not None:
                ship_end = ship["t"] + ship["dur"]
                ready = ship_end if ready is None else max(
                    ready, ship_end
                )
            delay = (sp["t"] - ready) if ready is not None else 0.0
            issues.append(MeasuredIssue(
                bucket=int(k) if k is not None else -1,
                delay_s=max(float(delay), 0.0),
                issue_t=sp["t"] - tl.t0,
                duration_s=float(sp["dur"]),
            ))
            prev_end = sp["t"] + sp["dur"]
        out.append(issues)
    return out
