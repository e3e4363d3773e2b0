"""Measured-feedback autotuner: close the loop from telemetry to the
wire planner.

Why
---
The repo measures achieved bytes/sec per collective
(``observability.attribute`` joins spans to the analyzer's records) and
plans per-bucket multi-hop schedules from an analytic ring model
(:mod:`.schedules`) — but until this module nothing connected them: the
bucket byte target was a fixed 4 MiB / 6-slot constant and the
flat-vs-hier decision trusted ring formulas that "Optimizing Allreduce
Operations for Modern Heterogeneous Architectures" (PAPERS.md) shows are
topology- AND size-dependent, i.e. a measurement problem.  The
:class:`BandwidthProfile` artifact carries what one topology actually
achieved — per (hop class, collective class) bandwidth curves over a
payload-size grid plus per-hop launch-latency estimates — and the
planner consumes it wherever it previously consulted a constant:

* :func:`~chainermn_tpu.comm_wire.planner.tune_wire_for_trace`\\
  ``(..., profile=)`` derives ``bucket_bytes``/``max_buckets`` by
  minimizing *predicted* sync time;
* :func:`~chainermn_tpu.comm_wire.schedules.schedule_for_bucket`\\
  ``(..., profile=)`` replaces the ``MIN_HIER_INTER_SAVINGS`` byte
  heuristic with predicted flat-vs-hier time (bit-identical analytic
  fallback when ``profile=None``);
* ``create_multi_node_optimizer(..., profile=...)`` threads the profile
  into every wire plan, folds :meth:`BandwidthProfile.profile_hash`
  into ``WirePlan.plan_hash()``, and exchanges it through the existing
  lockstep-retried ``plan_agreement`` — so ranks provably cannot tune
  apart, and a rank missing the profile file raises
  :class:`ProfileMissingError` before the first collective instead of
  silently planning flat.

Where profiles come from
------------------------
Two constructors, one artifact:

* :func:`profile_from_attribution` — scrape any telemetry export: bin
  the byte-priced matches of ``observability.attribute(timeline,
  trace)`` into log2 payload-size bins per (hop, class), keeping the
  best achieved bandwidth per bin (noise only subtracts bandwidth) and
  the smallest observed duration per hop as the launch-latency bound;
* :func:`calibrate` — a short self-contained sweep that times real
  ``psum`` / ``psum_scatter`` / ``all_gather`` launches over each of
  the communicator's mesh-axis groups (each single axis plus the full
  set — on a hierarchical mesh that yields genuine ``inter`` /
  ``intra`` / ``mixed`` hop curves), using the bench tier's paired
  min-of-N timing protocol (``utils.benchmarking.time_steps``).

Profiles serialize to JSON (:meth:`BandwidthProfile.save` /
:meth:`BandwidthProfile.load`); :meth:`BandwidthProfile.profile_hash`
is a content hash over the canonicalized curves, latencies AND the mesh
signature — invariant to JSON key order and float formatting (hashing
happens over parsed values, floats via ``repr(float(x))``), and
deliberately excluding the free-text ``label``/``source`` metadata so a
relabel is not a retune.

CLI::

    python -m chainermn_tpu.comm_wire.autotune --calibrate out.json \\
        [--comm tpu] [--sizes 65536,1048576,4194304] [--repeats 2]

Honesty note: on the CPU test mesh these curves measure XLA dispatch
latency, not interconnect bandwidth — they exercise the machinery; the
first on-chip calibration capture is what gives the tuner real ICI/DCN
numbers (none taken: no cell runs the bucketed wire, ``PERF.md``
section 7, row 0).
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import warnings
from typing import Dict, Optional, Sequence, Tuple

#: env var ``profile="auto"`` reads the profile path from
PROFILE_ENV = "CHAINERMN_TPU_WIRE_PROFILE"

#: launch-latency fallback when a profile carries no latency estimate at
#: all (seconds; the order of an XLA collective dispatch — only ever
#: used for profiles built by hand without latency data)
DEFAULT_LAUNCH_LATENCY_S = 50e-6

#: payload sizes (bytes) the calibration sweep times by default — small
#: enough that a full sweep stays in seconds on the CPU mesh, wide
#: enough to span the launch-bound -> bandwidth-bound transition
DEFAULT_CALIBRATION_SIZES = (64 * 1024, 1024 * 1024, 4 * 1024 * 1024)

#: collective classes the calibration sweep times, with the primitive
#: each maps onto (the three the gradient wire's schedules issue)
CALIBRATED_CLASSES = ("all_reduce", "reduce_scatter", "all_gather")


class ProfileMissingError(FileNotFoundError):
    """A named wire profile could not be loaded.  Raised at optimizer
    construction — BEFORE the first collective — so a rank whose launch
    environment lost the profile file fails loudly instead of silently
    planning with the analytic constants while its peers tune (the
    divergence would otherwise surface only as a
    ``WirePlanMismatchError`` at plan agreement, or as a deadlock in
    worlds that skip the exchange)."""


def _canon_float(x) -> str:
    """Canonical float spelling for hashing: ``repr(float(x))`` — the
    shortest round-trip repr, so "2.0", "2.000" and 2 hash alike."""
    return repr(float(x))


def _ring_wire_bytes(cls: str, payload_bytes: int,
                     world: Optional[int]) -> Optional[int]:
    """Per-rank ring wire bytes — one lazy import of the analyzer's
    single-source formula (``analysis.trace.wire_bytes``)."""
    from ..analysis.trace import wire_bytes

    return wire_bytes(cls, int(payload_bytes), world)


def _hop_of(axes) -> str:
    from ..analysis.trace import hop_class

    return hop_class(tuple(axes))


class BandwidthProfile:
    """Measured link capability of ONE topology.

    ``mesh_axes``: ``((axis_name, size), ...)`` sorted by axis name
    (canonicalized by :meth:`mesh_signature` whatever order the caller
    passes) — the signature the hash covers so a profile captured on a
    (2, 4) mesh can never silently tune a (4, 2) one.
    ``curves``: ``{(hop, cls): ((payload_bytes, bytes_per_sec), ...)}``
    sorted by payload — achieved wire bandwidth per (hop class, HLO op
    class) over the payload-size grid.
    ``latency``: ``{hop: seconds}`` — per-hop collective launch-latency
    estimate (the duration floor of the smallest calibrated payload).

    The artifact is plain data: construction never touches a device,
    and every consumer (:func:`predict_collective`,
    ``schedule_for_bucket``, ``tune_wire_for_trace``) is a pure
    function of its contents — which is what lets the content hash
    stand in for the whole tuning configuration in ``plan_agreement``.
    """

    @staticmethod
    def mesh_signature(mesh) -> Tuple[Tuple[str, int], ...]:
        """Canonical (axis, size) signature of a mesh (or axis→size
        mapping, or an (axis, size) pair iterable): sorted by axis
        name, so every construction path — calibration, telemetry
        scrape, hand-built — produces the same signature (and hence
        the same hash) for the same mesh regardless of iteration
        order."""
        shape = getattr(mesh, "shape", mesh)
        items = shape.items() if hasattr(shape, "items") else shape
        return tuple(sorted((str(a), int(s)) for a, s in items))

    def matches_mesh(self, mesh) -> bool:
        """True when this profile was captured on ``mesh``'s exact
        topology — the guard the bench's pinned-profile path uses."""
        return self.mesh_axes == self.mesh_signature(mesh)

    def __init__(self, mesh_axes, curves, latency=None,
                 label: str = "profile", source: str = "constructed"):
        self.mesh_axes: Tuple[Tuple[str, int], ...] = (
            self.mesh_signature(mesh_axes)
        )
        self.curves: Dict[Tuple[str, str], Tuple[Tuple[int, float], ...]] = {}
        for key, points in dict(curves).items():
            if isinstance(key, tuple):
                parts = key
            else:
                parts = str(key).split("/", 1)
            if len(parts) != 2:
                raise ValueError(
                    f"malformed curve key {key!r}: expected "
                    "'<hop>/<class>' (e.g. 'inter/all_reduce')"
                )
            hop, cls = parts
            # dedupe repeated payloads keeping the BEST bandwidth (two
            # calibration sizes can pad to one payload; noise only
            # subtracts bandwidth, and duplicates would otherwise
            # resolve inconsistently between the clamp and the
            # interior interpolation)
            by_payload: Dict[int, float] = {}
            for p, b in points:
                p, b = int(p), float(b)
                if b > 0 and b > by_payload.get(p, 0.0):
                    by_payload[p] = b
            if by_payload:
                self.curves[(str(hop), str(cls))] = tuple(
                    sorted(by_payload.items())
                )
        self.latency: Dict[str, float] = {
            str(h): float(s) for h, s in dict(latency or {}).items()
        }
        self.label = str(label)
        self.source = str(source)

    # -- identity ------------------------------------------------------
    def canonical(self) -> str:
        """Canonical serialization the hash covers: mesh signature +
        curves + latencies, keys sorted, floats in round-trip repr.
        ``label``/``source`` are metadata and deliberately excluded."""
        parts = ["mesh=" + ",".join(f"{a}:{s}" for a, s in self.mesh_axes)]
        for (hop, cls) in sorted(self.curves):
            pts = ";".join(
                f"{p}@{_canon_float(b)}" for p, b in self.curves[(hop, cls)]
            )
            parts.append(f"curve={hop}/{cls}:{pts}")
        for hop in sorted(self.latency):
            parts.append(f"lat={hop}@{_canon_float(self.latency[hop])}")
        return "|".join(parts)

    def profile_hash(self) -> str:
        """sha256 of :meth:`canonical` — the token
        ``WirePlan.plan_hash()`` folds in and ``plan_agreement``
        therefore exchanges."""
        return hashlib.sha256(self.canonical().encode()).hexdigest()

    def describe(self) -> str:
        hops = sorted({h for h, _ in self.curves})
        return (
            f"BandwidthProfile({self.label}: "
            f"mesh={'x'.join(str(s) for _, s in self.mesh_axes)}, "
            f"{len(self.curves)} curve(s) over hops {hops}, "
            f"hash={self.profile_hash()[:12]})"
        )

    __repr__ = describe

    # -- lookup --------------------------------------------------------
    def curve_for(self, hop: str, cls: str):
        """The curve priced for (hop, cls), walking a deterministic
        fallback chain when the exact pair was never measured: same hop
        with ``all_reduce`` (every sweep measures it), same hop any
        class (sorted), any hop same class (sorted), else ``None``.
        Deterministic by construction — every rank holding the same
        profile resolves the same curve, so fallback pricing is as
        agreement-safe as exact pricing."""
        for key in (
            (hop, cls),
            (hop, "all_reduce"),
        ):
            if key in self.curves:
                return self.curves[key]
        for (h, c) in sorted(self.curves):
            if h == hop:
                return self.curves[(h, c)]
        for (h, c) in sorted(self.curves):
            if c == cls:
                return self.curves[(h, c)]
        return None

    def bandwidth(self, hop: str, cls: str,
                  payload_bytes: int) -> Optional[float]:
        """Achieved bytes/sec for a collective of ``cls`` over ``hop``
        links at ``payload_bytes`` — piecewise-linear interpolation in
        log-payload space between curve points, clamped to the end
        points outside the measured grid (extrapolating a trend past
        the grid would let one noisy endpoint invent bandwidth)."""
        curve = self.curve_for(hop, cls)
        if not curve:
            return None
        p = max(int(payload_bytes), 1)
        if p <= curve[0][0]:
            return curve[0][1]
        if p >= curve[-1][0]:
            return curve[-1][1]
        x = math.log(p)
        for (p0, b0), (p1, b1) in zip(curve, curve[1:]):
            if p0 <= p <= p1:
                if p1 == p0:
                    return b1
                t = (x - math.log(p0)) / (math.log(p1) - math.log(p0))
                return b0 + t * (b1 - b0)
        return curve[-1][1]  # unreachable; curve is sorted

    def launch_latency(self, hop: str) -> float:
        """Per-hop launch latency (seconds).  Unknown hops fall back to
        the profile's worst measured latency (conservative — an
        unmeasured hop is not assumed cheap), then to the documented
        default for latency-less profiles."""
        if hop in self.latency:
            return self.latency[hop]
        if self.latency:
            return max(self.latency.values())
        return DEFAULT_LAUNCH_LATENCY_S

    # -- (de)serialization ---------------------------------------------
    def to_json(self) -> dict:
        return {
            "format": "chainermn_tpu.wire_profile.v1",
            "label": self.label,
            "source": self.source,
            "mesh_axes": [[a, s] for a, s in self.mesh_axes],
            "curves": {
                f"{hop}/{cls}": [[p, b] for p, b in pts]
                for (hop, cls), pts in sorted(self.curves.items())
            },
            "latency_s": dict(sorted(self.latency.items())),
            "profile_hash": self.profile_hash(),
        }

    @classmethod
    def from_json(cls, obj: dict) -> "BandwidthProfile":
        if not isinstance(obj, dict) or "curves" not in obj:
            raise ValueError(
                "not a wire profile: expected a JSON object with "
                f"'curves'; got {type(obj).__name__}"
            )
        prof = cls(
            mesh_axes=obj.get("mesh_axes", ()),
            curves=obj["curves"],
            latency=obj.get("latency_s", {}),
            label=obj.get("label", "profile"),
            source=obj.get("source", "loaded"),
        )
        embedded = obj.get("profile_hash")
        if embedded and embedded != prof.profile_hash():
            raise ValueError(
                "wire profile content does not match its embedded "
                f"profile_hash ({embedded[:12]}... vs "
                f"{prof.profile_hash()[:12]}...): the file was edited "
                "after capture — recapture or drop the stale hash"
            )
        return prof

    def save(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "BandwidthProfile":
        try:
            with open(path, encoding="utf-8") as f:
                obj = json.load(f)
        except OSError as e:
            raise ProfileMissingError(
                f"wire profile {path!r} could not be read: {e}"
            ) from e
        except json.JSONDecodeError as e:
            raise ValueError(
                f"wire profile {path!r} is not valid JSON: {e}"
            ) from e
        return cls.from_json(obj)


def resolve_profile(profile) -> Optional[BandwidthProfile]:
    """Normalize the ``profile=`` argument of the multi-node optimizer.

    ``None`` -> no profile (the analytic constants, bit-identical
    pre-autotuner plans).  A :class:`BandwidthProfile` passes through.
    ``"auto"`` loads the path named by :data:`PROFILE_ENV` — an unset
    env var OR a missing/unreadable file raises
    :class:`ProfileMissingError` (the rank asked for measured tuning;
    silently planning flat while its peers tune is the divergence this
    layer exists to prevent).  Any other string is a profile path.
    """
    if profile is None:
        return None
    if isinstance(profile, BandwidthProfile):
        return profile
    if profile == "auto":
        path = os.environ.get(PROFILE_ENV)
        if not path:
            raise ProfileMissingError(
                f'profile="auto" but {PROFILE_ENV} is unset: every rank '
                "must point at the same profile file (export it in the "
                "launch environment), or pass profile=None for the "
                "analytic constants"
            )
    elif isinstance(profile, (str, os.PathLike)):
        path = os.fspath(profile)
    else:
        raise ValueError(
            "profile must be None, 'auto', a path, or a "
            f"BandwidthProfile; got {profile!r}"
        )
    if not os.path.exists(path):
        raise ProfileMissingError(
            f"wire profile file {path!r} does not exist on this rank "
            "(raised before the first collective: a rank planning with "
            "the analytic constants while its peers tune would "
            "mismatch at plan agreement anyway — fail at the cause)"
        )
    return BandwidthProfile.load(path)


# ----------------------------------------------------------------------
# the measured cost model
# ----------------------------------------------------------------------
def predict_collective(profile: BandwidthProfile, cls: str,
                       payload_bytes: int, axes: Sequence[str],
                       axis_sizes: Sequence[int],
                       bytes_on_wire: Optional[int] = None,
                       ) -> Optional[float]:
    """Predicted seconds for ONE collective of ``cls`` carrying
    ``payload_bytes`` over ``axes``: ring wire bytes over the
    interpolated achieved bandwidth, floored by the hop's launch
    latency.

    The curves are EFFECTIVE bandwidth — both constructors divide wire
    bytes by a *measured duration that includes the launch*, so the
    launch cost at each payload scale is already inside the curve;
    adding the latency on top would double-count it (re-predicting the
    exact point just calibrated would return 2x its measurement).  The
    latency enters as a FLOOR instead: below the measured grid the
    clamped bandwidth would predict times that shrink without bound,
    but no collective beats its launch — which is also what keeps
    over-splitting penalized in the bucket tuner (B tiny buckets pay B
    launch floors).  ``None`` when the profile cannot price it
    (unknown world or no curve even through the fallback chain) —
    callers fall back to the analytic rule rather than guessing."""
    if profile is None:
        return None
    axes = tuple(str(a) for a in axes)
    sizes = tuple(int(s) for s in axis_sizes)
    hop = _hop_of(axes)
    if bytes_on_wire is None:
        world = 1
        for s in sizes:
            if s <= 0:
                return None
            world *= s
        bytes_on_wire = _ring_wire_bytes(cls, payload_bytes, world)
    if bytes_on_wire is None:
        return None
    lat = profile.launch_latency(hop)
    if bytes_on_wire <= 0:
        return lat  # degenerate world: launch cost only
    bw = profile.bandwidth(hop, cls, payload_bytes)
    if bw is None or bw <= 0:
        return None
    return max(float(bytes_on_wire) / bw, lat)


def predict_cost(record, profile: BandwidthProfile) -> Optional[float]:
    """Predicted seconds for one
    :class:`~chainermn_tpu.analysis.trace.CollectiveRecord` under
    ``profile`` — the measured twin of the record's ring
    ``bytes_on_wire`` pricing.  Uses the record's own wire bytes when
    it carries them, the ring formula otherwise; ``None`` when the
    record (unknown axis sizes) or the profile (no curve) cannot
    price it."""
    if profile is None:
        return None
    return predict_collective(
        profile,
        getattr(record, "cls", "all_reduce"),
        int(getattr(record, "payload_bytes", 0) or 0),
        getattr(record, "axes", ()),
        getattr(record, "axis_sizes", ()),
        bytes_on_wire=getattr(record, "bytes_on_wire", None),
    )


def predict_hier_triple(profile: BandwidthProfile, payload_bytes: int,
                        split) -> Optional[float]:
    """Predicted seconds for ONE bucket's hier rs→ar→ag triple: the
    full-precision intra reduce-scatter, the inter all-reduce on the
    1/K shard, and the intra all-gather — each leg priced on its own
    hop's curve.  ``split`` is a ``schedules.AxisSplit`` (only its
    inter/intra names and sizes are read).  ``None`` when any leg is
    unpriceable.  The ONE source of the triple's pricing — the
    schedule decision and the bucket tuner both consume it, so they
    cannot disagree about what a staged bucket costs."""
    shard = -(-int(payload_bytes) // split.intra_size)
    legs = (
        ("reduce_scatter", int(payload_bytes),
         (split.intra,), (split.intra_size,)),
        ("all_reduce", shard, (split.inter,), (split.inter_size,)),
        ("all_gather", shard, (split.intra,), (split.intra_size,)),
    )
    total = 0.0
    for cls, p, ax, sz in legs:
        t = predict_collective(profile, cls, p, ax, sz)
        if t is None:
            return None
        total += t
    return total


def predict_bucket_sync(profile: BandwidthProfile, payload_bytes: int,
                        axes: Sequence[str],
                        axis_sizes: Sequence[int],
                        schedule: str = "auto",
                        shape: str = "allreduce") -> Optional[float]:
    """Predicted seconds to sync ONE bucket of ``payload_bytes`` over
    ``axes`` — priced as whatever the wire would ACTUALLY issue for it
    under the requested ``schedule`` and program ``shape``:
    ``"allreduce"`` (the gradient wire — flat psum, or the hier triple
    when the decision/pin stages it) or ``"zero"`` (the blocked ZeRO
    path — rs+ag down/up flat, 2rs+2ag staged).  The bucket tuner's
    candidate pricer: a candidate sized into the staged regime is
    priced with the slow inter hop on its own curve, a PINNED schedule
    is priced as pinned (a flat-pinned wire never issues the triple),
    and a ZeRO wire pays its two-collective flat launch floors rather
    than being modeled as one psum."""
    from .schedules import axis_split, schedule_for_bucket

    axes = tuple(str(a) for a in axes)
    sizes = tuple(int(s) for s in axis_sizes)
    sched = schedule_for_bucket(
        int(payload_bytes), dict(zip(axes, sizes)), axes=axes,
        requested=schedule, profile=profile, shape=shape,
    )
    if sched == "hier_rs_ag":
        split = axis_split(axes, sizes)
        if split is None:  # pragma: no cover - decision implies a split
            return None
        if shape == "zero":
            return predict_zero_hier(profile, payload_bytes, split)
        return predict_hier_triple(profile, payload_bytes, split)
    if shape == "zero":
        return predict_zero_flat(profile, payload_bytes, axes, sizes)
    return predict_collective(
        profile, "all_reduce", int(payload_bytes), axes, sizes
    )


def predict_zero_flat(profile: BandwidthProfile, payload_bytes: int,
                      axes: Sequence[str],
                      axis_sizes: Sequence[int]) -> Optional[float]:
    """Predicted seconds for ONE ZeRO bucket's FLAT path: a
    reduce-scatter down plus an all-gather of the updated ``1/N``
    shard back up, both over the full axis set — what the blocked path
    actually issues (it never runs the gradient wire's single psum, so
    pricing it as one would mis-shape the flat-vs-hier comparison)."""
    world = 1
    for s in axis_sizes:
        if int(s) <= 0:
            return None
        world *= int(s)
    rs = predict_collective(
        profile, "reduce_scatter", int(payload_bytes), axes, axis_sizes
    )
    ag = predict_collective(
        profile, "all_gather", -(-int(payload_bytes) // world),
        axes, axis_sizes,
    )
    if rs is None or ag is None:
        return None
    return rs + ag


def predict_zero_hier(profile: BandwidthProfile, payload_bytes: int,
                      split) -> Optional[float]:
    """Predicted seconds for ONE ZeRO bucket's STAGED path: intra
    reduce-scatter (full payload) → inter reduce-scatter (1/K) down,
    then inter all-gather (1/(K·I)) → intra all-gather (1/K) up — the
    four collectives ``_ZeroRedundancyOptimizer``'s staged
    scatter/gather actually issue."""
    p = int(payload_bytes)
    k, i = split.intra_size, split.inter_size
    legs = (
        ("reduce_scatter", p, (split.intra,), (k,)),
        ("reduce_scatter", -(-p // k), (split.inter,), (i,)),
        ("all_gather", -(-p // (k * i)), (split.inter,), (i,)),
        ("all_gather", -(-p // k), (split.intra,), (k,)),
    )
    total = 0.0
    for cls, pl, ax, sz in legs:
        t = predict_collective(profile, cls, pl, ax, sz)
        if t is None:
            return None
        total += t
    return total


#: the wire classes a gradient sync is made of: flat buckets are one
#: all_reduce, ZeRO splits into reduce_scatter + all_gather, hier
#: buckets stage all three — the sync-wall prediction must cover the
#: whole set or hier rows under-predict by their all_gather leg.
#: Deliberately the SAME set the sweep calibrates: a class priced here
#: but never measured would silently resolve through the curve
#: fallback chain onto a wrong-class bandwidth.
SYNC_CLASSES = CALIBRATED_CLASSES


#: source-path fragments that identify the wire's own collective call
#: sites — the modules that ISSUE gradient-sync traffic (the bucket
#: codecs and staged schedules in ``comm_wire``, the eager tiers in
#: ``communicators``, ZeRO's blocked scatter/gather in ``optimizers``).
#: A sync-class collective sourced anywhere else (the
#: ``functions.collectives`` wrappers feeding sync-BN's per-channel
#: moment psums, ``parallel``/``models`` TP and MoE activation
#: all_gathers) is statistics/activation traffic the wire never ships.
_WIRE_SOURCE_FRAGMENTS = ("comm_wire", "communicators", "optimizers")


def _comm_layer_source(record) -> bool:
    """False only when the record carries a ``source`` that lies
    OUTSIDE the comm layer — provenance-less records stay inclusive
    (no source, no accusation)."""
    src = getattr(record, "source", None)
    return src is None or any(
        frag in str(src) for frag in _WIRE_SOURCE_FRAGMENTS
    )


def is_wire_record(record) -> bool:
    """True for records that look like gradient-WIRE traffic: flat
    (0/1-D operand) all_reduces — the wire's bucket psums and the loss
    pmean — plus the wire's staged and ZeRO reduce_scatter/all_gather
    legs (incl. blocked 2-D operands).  Excluded as traffic the wire
    never ships: a >=2-D all_reduce (forward TP/MoE activation psum);
    a 1-D all_reduce sourced outside the comm layer
    (:data:`_WIRE_SOURCE_FRAGMENTS`) — sync-BN's per-channel ``(C,)``
    moments would otherwise inflate the tuned payload exactly like the
    >=2-D activations one rank lower; and a reduce_scatter/all_gather
    sourced outside the comm layer — forward TP/MoE activation
    all_gathers carry model-sized payloads over tensor-parallel axes
    the sync never crosses (rs/ag cannot use the shape rule: ZeRO's
    blocked legs are legitimately 2-D, so provenance is the only
    discriminator there).  0-D all_reduces (the loss pmean) and
    provenance-less records keep the inclusive behavior.  The ONE
    predicate shared by the bucket tuner and :func:`predict_sync_time`,
    so the minimized objective and the reported forecast cannot
    disagree about what counts as sync."""
    if getattr(record, "cls", "all_reduce") != "all_reduce":
        return _comm_layer_source(record)
    shapes = getattr(record, "shapes", ())
    if any(len(s) > 1 for s in shapes):
        return False
    if any(len(s) == 1 for s in shapes):
        return _comm_layer_source(record)
    return True


def predict_sync_time(records, profile: BandwidthProfile,
                      ) -> Optional[float]:
    """Predicted total seconds for a program's gradient-sync
    collectives (:data:`SYNC_CLASSES`, filtered to
    :func:`is_wire_record` — the wall the tuner minimizes; permutes,
    point-to-point, and activation-shaped psums are not sync).
    ``None`` if any sync collective is unpriceable."""
    total = 0.0
    priced = False
    for r in records:
        if getattr(r, "cls", None) not in SYNC_CLASSES:
            continue
        if not is_wire_record(r):
            continue
        t = predict_cost(r, profile)
        if t is None:
            return None
        total += t
        priced = True
    return total if priced else None


# ----------------------------------------------------------------------
# profile construction: telemetry scrape
# ----------------------------------------------------------------------
def _log2_bin(payload: int) -> int:
    return int(math.log2(max(int(payload), 1)))


def profile_from_attribution(timeline, trace=None, mesh=None,
                             label: str = "attribution",
                             ) -> BandwidthProfile:
    """Build a :class:`BandwidthProfile` from measured telemetry — the
    attribution join's byte-priced matches binned into log2
    payload-size bins per (hop, collective class).

    ``timeline``: an ``observability.Timeline``/``Telemetry``, or an
    already-joined ``AttributionReport`` (then ``trace`` is ignored).
    ``trace``: the program's ``CollectiveTrace`` (required unless a
    report is passed).  ``mesh``: optional mesh whose signature the
    profile carries; defaults to the axis/size union of the trace's
    records — which covers only the axes the traced collectives
    actually crossed, so on a hybrid (e.g. DP x TP) mesh pass the
    communicator's mesh explicitly or the factory's
    ``matches_mesh`` check will reject the profile on the very
    topology it was captured on.

    Per bin the BEST achieved bandwidth is kept (measurement noise only
    subtracts bandwidth — the max is the capability estimate, the same
    reasoning as the bench tier's min-of-N timing), at the payload
    coordinate of the winning sample.  Per hop the smallest observed
    span duration bounds the launch latency from above.  Raises
    ``ValueError`` when no byte-priced match exists — an empty profile
    would "tune" every choice through the fallback chain of nothing.
    Staged-triple matches (composite ``hier_rs_ag`` spans covering
    three collectives over two hop classes) belong to no single curve
    and are excluded with a ``RuntimeWarning`` — a staged-schedule
    run's export misses its wire buckets' inter/intra curves, so
    scrape a flat-schedule capture or ``calibrate()`` instead.
    """
    report = timeline
    if not hasattr(report, "matched"):
        if trace is None:
            raise ValueError(
                "profile_from_attribution needs a CollectiveTrace when "
                "given a timeline (pass attribute()'s report directly "
                "to skip the join)"
            )
        from ..observability import attribute

        report = attribute(timeline, trace)

    # curve points come from the report's own export — ONE place reads
    # the match/pricing fields, so the documented "raw export the
    # binner consumes" cannot diverge from what is actually binned
    best: Dict[Tuple[str, str, int], Tuple[int, float]] = {}
    for hop, cls, payload, bw, _dur in report.bandwidth_points():
        if not payload:
            continue
        key = (hop, cls, _log2_bin(payload))
        if key not in best or bw > best[key][1]:
            best[key] = (payload, bw)
    # the latency bound and mesh signature scan the non-composite
    # matches (a span with no wire pricing still cannot beat its
    # launch).  Staged-triple spans are skipped exactly as
    # bandwidth_points() skips them: the composite duration covers
    # three launches over two hop classes, so min-ing it into the head
    # record's hop would inflate e.g. the intra floor with inter-bound
    # timings and bias every staged-schedule prediction.
    latency: Dict[str, float] = {}
    mesh_axes: Dict[str, int] = {}
    for a in report.matched:
        if a.span_args.get("schedule") == "hier_rs_ag":
            continue
        rec = a.record
        hop = getattr(rec, "hop", "flat")
        dur = float(a.duration_s)
        if dur > 0:
            latency[hop] = min(latency.get(hop, dur), dur)
        for ax, s in zip(getattr(rec, "axes", ()),
                         getattr(rec, "axis_sizes", ())):
            if int(s) > 0:
                mesh_axes[str(ax)] = int(s)
    n_staged = sum(
        1 for a in report.matched
        if a.span_args.get("schedule") == "hier_rs_ag"
    )
    if not best:
        raise ValueError(
            "no byte-priced attribution matches to build a profile "
            "from: the timeline's collective spans never joined the "
            "trace's records with wire bytes (attribute() reported "
            f"{len(report.unmatched_spans)} unmatched span(s), "
            f"{len(report.unmatched_records)} unmatched record(s), "
            f"{n_staged} staged-triple match(es) — composites span "
            "two hop classes and belong to no single curve)"
        )
    if n_staged:
        # the same disclosure contract as calibrate()'s untimeable
        # classes: a profile scraped from a STAGED-schedule run is
        # missing exactly the wire buckets' inter/intra curves (their
        # matches are composite), so later predictions for those
        # (hop, class) keys resolve through the wrong-class fallback
        # chain — say so at scrape time, not at tune time.
        warnings.warn(
            f"profile_from_attribution: {n_staged} staged-triple "
            "match(es) (schedule=hier_rs_ag) carry no single-curve "
            "bandwidth and were excluded — a profile scraped from a "
            "staged-schedule run misses its wire buckets' inter/intra "
            "curves; calibrate() on this mesh (or a flat-schedule "
            "capture) measures them directly",
            RuntimeWarning,
            stacklevel=2,
        )
    curves: Dict[Tuple[str, str], list] = {}
    for (hop, cls, _), (payload, bw) in sorted(best.items()):
        curves.setdefault((hop, cls), []).append((payload, bw))
    sig = BandwidthProfile.mesh_signature(
        mesh if mesh is not None else mesh_axes
    )
    return BandwidthProfile(
        mesh_axes=sig, curves=curves, latency=latency,
        label=label, source="attribution",
    )


# ----------------------------------------------------------------------
# profile construction: calibration sweep
# ----------------------------------------------------------------------
def _axis_groups(mesh) -> list:
    """The axis tuples a calibration sweep times: each single mesh axis
    (its own hop class) plus — on multi-axis meshes — the full set (the
    hop the flat wire's one-psum-over-everything actually crosses:
    ``mixed`` on a hierarchical mesh)."""
    names = tuple(str(a) for a in mesh.axis_names)
    groups = [(a,) for a in names]
    if len(names) > 1:
        groups.append(names)
    return groups


def calibrate(comm, sizes: Optional[Sequence[int]] = None,
              repeats: int = 2, steps: int = 2,
              label: str = "calibration") -> BandwidthProfile:
    """Time real collective launches on ``comm``'s mesh and return the
    measured :class:`BandwidthProfile`.

    For every axis group (:func:`_axis_groups`) and every class in
    :data:`CALIBRATED_CLASSES`, a float32 payload of each size in
    ``sizes`` (bytes; padded up so ``psum_scatter``'s split is even) is
    reduced by a jitted ``shard_map`` program and timed under the bench
    tier's paired k/2k min-of-N protocol
    (``utils.benchmarking.time_steps`` — the one sanctioned timing
    source outside ``observability``).  Achieved bandwidth is the ring
    wire bytes over the measured seconds; the per-hop launch latency is
    the smallest measured duration at the smallest payload.

    Deterministic in *structure* (same mesh -> same curve keys and
    payload grid); the VALUES are measurements, so two ranks must share
    one profile file rather than each calibrating — which is exactly
    what the hash-in-``plan_agreement`` wiring enforces.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..utils.benchmarking import time_steps

    mesh = comm.mesh
    shape = dict(mesh.shape)
    sizes = tuple(int(s) for s in (sizes or DEFAULT_CALIBRATION_SIZES))
    if not sizes or min(sizes) < 4:
        raise ValueError(f"calibration sizes must be >= 4 bytes: {sizes}")

    def build(cls, axes_t):
        axis_arg = axes_t if len(axes_t) > 1 else axes_t[0]

        def body(x):
            if cls == "all_reduce":
                return lax.psum(x, axis_arg)
            if cls == "reduce_scatter":
                return lax.psum_scatter(
                    x, axis_arg, scatter_dimension=0, tiled=True
                )
            return lax.all_gather(x, axis_arg, axis=0, tiled=True)

        out_spec = P(axes_t) if cls == "reduce_scatter" else P()
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=out_spec,
            check_vma=False,
        ))

    curves: Dict[Tuple[str, str], list] = {}
    latency: Dict[str, float] = {}
    timing_failures: Dict[Tuple[str, str], str] = {}
    for axes_t in _axis_groups(mesh):
        hop = _hop_of(axes_t)
        world = 1
        for a in axes_t:
            world *= int(shape[a])
        if world <= 1:
            continue  # a width-1 axis has no wire to measure
        for cls in CALIBRATED_CLASSES:
            points = []
            for size in sorted(sizes):
                n = -(-size // 4)
                n = -(-n // world) * world  # even psum_scatter split
                payload = n * 4
                x = jnp.zeros((n,), jnp.float32)
                try:
                    fn = build(cls, axes_t)
                    dt, _ = time_steps(
                        lambda: fn(x), steps, warmup=1, repeats=repeats
                    )
                except Exception as e:  # pragma: no cover - backend-specific
                    timing_failures[(hop, cls)] = repr(e)
                    continue  # curve simply lacks this class
                if dt <= 0:
                    continue
                if size == min(sizes):
                    latency[hop] = min(latency.get(hop, dt), dt)
                wire = _ring_wire_bytes(cls, payload, world)
                if wire:
                    points.append((payload, wire / dt))
            if points:
                curves[(hop, cls)] = points
    if timing_failures:
        # a curve silently missing a class would later price that
        # class through curve_for's fallback chain onto a DIFFERENT
        # class's bandwidth (the exact degradation the SYNC_CLASSES
        # contract warns about) — a degraded profile must say so at
        # capture time, not at tune time.
        dropped = sorted(
            f"{h}/{c}" for (h, c) in timing_failures if (h, c) not in curves
        )
        partial = sorted(
            f"{h}/{c}" for (h, c) in timing_failures if (h, c) in curves
        )
        detail = "; ".join(
            f"{k}: {timing_failures[k]}" for k in sorted(timing_failures)
        )
        warnings.warn(
            "calibration could not time every collective class"
            + (f" — curves DROPPED entirely: {dropped} (predictions for "
               "these classes will resolve through the wrong-class "
               "fallback chain)" if dropped else "")
            + (f" — curves missing some payload points: {partial}"
               if partial else "")
            + f" [{detail}]",
            RuntimeWarning,
            stacklevel=2,
        )
    if not curves:
        raise RuntimeError(
            "calibration produced no bandwidth curve: every timed "
            "launch failed or the mesh has no axis wider than 1"
        )
    return BandwidthProfile(
        mesh_axes=BandwidthProfile.mesh_signature(mesh),
        curves=curves, latency=latency, label=label, source="calibration",
    )


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m chainermn_tpu.comm_wire.autotune",
        description=(
            "Calibrate a wire BandwidthProfile on this host's "
            "communicator and save it as JSON (point "
            f"{PROFILE_ENV} at the file and pass profile='auto')."
        ),
    )
    ap.add_argument("--calibrate", metavar="OUT.json", required=True,
                    help="output profile path")
    ap.add_argument("--comm", default="tpu",
                    help="communicator name (default: tpu)")
    ap.add_argument("--sizes", default=None,
                    help="comma-separated payload bytes "
                         f"(default: {DEFAULT_CALIBRATION_SIZES})")
    ap.add_argument("--repeats", type=int, default=2,
                    help="min-of-N repeats per point (default: 2)")
    ap.add_argument("--label", default="calibration")
    args = ap.parse_args(argv)

    from .. import create_communicator

    comm = create_communicator(args.comm)
    sizes = (
        tuple(int(s) for s in args.sizes.split(","))
        if args.sizes else None
    )
    prof = calibrate(comm, sizes=sizes, repeats=args.repeats,
                     label=args.label)
    prof.save(args.calibrate)
    print(json.dumps({
        "profile": args.calibrate,
        "profile_hash": prof.profile_hash(),
        "mesh_axes": [list(t) for t in prof.mesh_axes],
        "hops": sorted({h for h, _ in prof.curves}),
        "n_curves": len(prof.curves),
        "latency_s": prof.latency,
    }), flush=True)
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised by CI smoke
    raise SystemExit(main())
