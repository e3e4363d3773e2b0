"""HLO-text collective census — the analyzer's cross-check.

The jaxpr walker (:mod:`.trace`) sees the program *before* XLA; this
module counts collective ops in the *lowered* text (StableHLO or HLO),
so the two censuses verify each other: a walker bug (a missed sub-jaxpr
param) under-counts the trace, a lowering surprise (GSPMD inserting a
reduce behind our back) over-counts the HLO.  ``TestHLOCollectiveCensus``
pins both sides against each other on the ResNet-50 and transformer
train steps.

Counting caveats, so the cross-check is honest about what it can see:

* one record an equation, one ``all_reduce`` op an equation in the
  *lowered* text (jax 0.9 binds a tuple ``psum`` leaf by leaf); the
  *compiled* text may glue several into one tuple all-reduce, so a
  count is compared against the lowered text and bytes against the
  compiled one;
* a collective inside ``scan`` appears once in the while-loop body on
  both sides;
* the GSPMD path (``use_shard_map=False``) materializes collectives the
  jaxpr never contained — the cross-check is only meaningful for
  explicitly-partitioned (shard_map) programs, which is what every
  communicator tier builds.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import List, Mapping, Optional

from .trace import CollectiveTrace

# op class -> (stablehlo spelling, classic-HLO spelling)
_PATTERNS = {
    "all_reduce": (r"stablehlo\.all_reduce", r"\ball-reduce(?:-start)?\("),
    "all_gather": (r"stablehlo\.all_gather", r"\ball-gather(?:-start)?\("),
    "reduce_scatter": (
        r"stablehlo\.reduce_scatter",
        r"\breduce-scatter(?:-start)?\(",
    ),
    "collective_permute": (
        r"stablehlo\.collective_permute",
        r"\bcollective-permute(?:-start)?\(",
    ),
    "all_to_all": (r"stablehlo\.all_to_all", r"\ball-to-all(?:-start)?\("),
}

# metadata={op_name="..." source_file="..." source_line=N} on classic-HLO
# ops: XLA stamps every op — including the collectives the SPMD
# partitioner inserts — with the jaxpr equation it came from, which is
# exactly the citation the implicit-collective attribution needs.
_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op>[^"]*)"'
    r'(?:[^}]*?source_file="(?P<file>[^"]*)")?'
    r"(?:[^}]*?source_line=(?P<line>\d+))?"
)


@dataclass(frozen=True)
class HloCollectiveOp:
    """One collective op occurrence in lowered/compiled program text."""

    cls: str                      # HLO op class (all_reduce, ...)
    line_no: int                  # 1-based line in the text
    op_name: Optional[str] = None  # metadata op_name (the jaxpr eqn)
    source: Optional[str] = None   # "file:line" of the issuing eqn

    def citation(self) -> str:
        """Human-readable provenance for findings/errors."""
        parts = [self.cls, f"hlo line {self.line_no}"]
        if self.op_name:
            parts.append(f"eqn {self.op_name!r}")
        if self.source:
            parts.append(f"at {self.source}")
        return " ".join(parts)


def hlo_collective_ops(text: str) -> List[HloCollectiveOp]:
    """Every collective op in lowered (StableHLO) or compiled (classic
    HLO) text, in textual order, each carrying the XLA op metadata when
    the dialect records it (classic HLO does; StableHLO's pretty form
    drops locations).  ``-done`` halves of async pairs are not counted
    (the ``-start`` op is the one occurrence)."""
    dialect = 0 if "stablehlo" in text else 1
    ops: List[HloCollectiveOp] = []
    for i, line in enumerate(text.splitlines(), start=1):
        for cls, pats in _PATTERNS.items():
            if not re.search(pats[dialect], line):
                continue
            op_name = source = None
            m = _METADATA_RE.search(line)
            if m:
                op_name = m.group("op") or None
                if m.group("file") and m.group("line"):
                    source = f"{m.group('file')}:{m.group('line')}"
            ops.append(HloCollectiveOp(
                cls=cls, line_no=i, op_name=op_name, source=source
            ))
    return ops


def hlo_census(text: str) -> dict:
    """``{op_class: count}`` over lowered program text (zero counts
    omitted).  Accepts StableHLO (``lowered.as_text()``) and classic
    HLO (``compiled.as_text()``) spellings."""
    dialect = 0 if "stablehlo" in text else 1
    out = {}
    for cls, pats in _PATTERNS.items():
        n = len(re.findall(pats[dialect], text))
        if n:
            out[cls] = n
    return out


def lowered_census(jitted, *args, **kwargs) -> dict:
    """Census of ``jitted.lower(*args).as_text()`` (compiles nothing)."""
    return hlo_census(jitted.lower(*args, **kwargs).as_text())


def assert_census_agreement(trace: CollectiveTrace, hlo_text: str,
                            classes=("all_reduce",)) -> Mapping[str, int]:
    """Assert the walker census equals the HLO-text census for the
    given op classes; returns the agreed counts.  Default compares only
    ``all_reduce`` — the class whose count is the wire-format contract —
    because XLA may legally rewrite between the gather-ish classes
    (all_gather <-> all_to_all decompositions on some backends)."""
    mine = trace.census()
    theirs = hlo_census(hlo_text)
    agreed = {}
    for cls in classes:
        a, b = mine.get(cls, 0), theirs.get(cls, 0)
        assert a == b, (
            f"census disagreement on {cls}: jaxpr walker counts {a}, "
            f"HLO text counts {b} (walker={mine}, hlo={theirs})"
        )
        agreed[cls] = a
    return agreed


# ----------------------------------------------------------------------
# Scheduled-program census: which collectives of a COMPILED program are
# asynchronous, and whether the scheduler put compute inside them.
# ----------------------------------------------------------------------
#: classic-HLO opcode -> op class ("all-reduce" -> "all_reduce")
_CLASSIC = {cls.replace("_", "-"): cls for cls in _PATTERNS}
#: all-reduces under this size are gains, biases and the loss, not a
#: weight matrix's gradient: the ``min_bytes`` of a gradient census
WEIGHT_GRADIENT_BYTES = 1 << 20
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
#: instructions of a fusion body that move or name data, not compute
_PLUMBING = frozenset({
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "custom-call", "copy", "fusion",
})
_ARRAY_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMPUTATION_RE = re.compile(r"^(ENTRY )?%([\w.\-]+) \(")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CHAIN_RE = re.compile(r'chain_id="(\d+)"')
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")


def _type_bytes(type_text: str) -> int:
    """Bytes of an HLO result type (array or tuple of arrays)."""
    total = 0
    for dtype, dims in _ARRAY_RE.findall(type_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 0)
    return total


def _split_instruction(rest: str):
    """``(type, opcode, tail)`` of the text right of ``%name = ``."""
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_text, rest = rest[: end + 1], rest[end + 2:]
    else:
        type_text, _, rest = rest.partition(" ")
    opcode, _, tail = rest.partition("(")
    return type_text, opcode, tail


def _computations(text: str):
    """``({name: [(instr, type, opcode, tail)]}, entry_name)``."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            current = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        m = _INSTRUCTION_RE.match(line)
        if m and current is not None:
            current.append((m.group(1),) + _split_instruction(m.group(2)))
    return comps, entry


@dataclass(frozen=True)
class ScheduledCollective:
    """One collective of a scheduled program's entry computation."""

    cls: str                  # all_reduce, collective_permute, ...
    nbytes: int               # bytes of its result
    asynchronous: bool        # a start / done pair, not one blocking op
    compute_inside: int       # between the two: matmul fusions, kernels,
    #                           steps fused onto elementwise compute
    op_name: Optional[str] = None


@dataclass(frozen=True)
class CollectiveSchedule:
    """Where the collectives of a compiled program sit in its schedule.

    ``condensed``: the entry computation in program order, a character
    an event — ``S`` / ``D`` an asynchronous collective's start / done,
    ``R`` a synchronous collective, ``m`` a fusion with a matmul or
    convolution in it, ``k`` a kernel (``tpu_custom_call``), ``.`` any
    other fusion.  The TPU compiler advances an asynchronous collective
    in steps it fuses onto compute: such a step reads ``m`` on a matmul
    fusion, ``e`` on an elementwise one (an optimizer update), ``s``
    when the fusion holds nothing else.
    """

    ops: tuple
    condensed: str

    def census(self, min_bytes: int = 0) -> dict:
        """Counts and bytes of the collectives of at least ``min_bytes``
        (a weight gradient's, not a gain's): synchronous against
        asynchronous, and of the asynchronous ones those with compute
        scheduled between start and done."""
        ops = [o for o in self.ops if o.nbytes >= min_bytes]
        asyn = [o for o in ops if o.asynchronous]
        over = [o for o in asyn if o.compute_inside]
        total, async_bytes, over_bytes = (
            sum(o.nbytes for o in group) for group in (ops, asyn, over))
        return {
            "n_sync": len(ops) - len(asyn),
            "n_async": len(asyn),
            "n_overlapped": len(over),
            "sync_bytes": total - async_bytes,
            "async_bytes": async_bytes,
            "overlapped_bytes": over_bytes,
            "async_bytes_share": async_bytes / total if total else 0.0,
            "overlapped_bytes_share": over_bytes / total if total else 0.0,
        }


def _op_name(tail: str) -> Optional[str]:
    m = _METADATA_RE.search(tail)
    return (m.group("op") or None) if m else None


def collective_schedule(text: str) -> CollectiveSchedule:
    """Read a scheduled program (``compiled.as_text()``) for the form of
    its collectives.  Known asynchronous forms: XLA's ``<op>-start`` /
    ``<op>-done`` pairs, and the TPU compiler's asynchronous collective
    fusions — fusions of the entry computation whose called computation
    holds the collective, the pieces of one collective sharing its
    ``chain_id``: the first in program order starts it, the last
    completes it."""
    comps, entry = _computations(text)
    if entry is None:
        return CollectiveSchedule((), "")

    def inner(comp):  # (compute kind, collective or None) of a fusion body
        kind, coll = "", None
        for _name, type_text, opcode, tail in comps.get(comp, ()):
            if opcode in ("convolution", "dot"):
                kind = "m"
            elif opcode in _CLASSIC:
                chain = _CHAIN_RE.search(tail)
                coll = (_CLASSIC[opcode], _type_bytes(type_text),
                        _op_name(tail), chain.group(1) if chain else None)
            elif opcode not in _PLUMBING and kind != "m":
                kind = "e"
        return kind, coll

    # events of the entry computation, in program order
    events = []   # [kind, key, payload]: kind in S D R m k e s .
    chains = {}   # chain key -> indices into events
    starts = {}   # instruction name -> index of its start event
    source = {}   # instruction name -> first operand (to follow a done)
    for name, type_text, opcode, tail in comps[entry]:
        first = _OPERAND_RE.search(tail)
        source[name] = first.group(1) if first else None
        base = opcode[:-6] if opcode.endswith("-start") else None
        if base in _CLASSIC:
            starts[name] = len(events)
            events.append(["S", name, (_CLASSIC[base], 0, _op_name(tail))])
        elif opcode.endswith("-done") and opcode[:-5] in _CLASSIC:
            start = source[name]
            while start is not None and start not in starts:
                start = source.get(start)
            if start is not None:
                cls, _, op = events[starts[start]][2]
                events[starts[start]][2] = (cls, _type_bytes(type_text), op)
            events.append(["D", start, None])
        elif opcode in _CLASSIC:
            events.append(["R", name, (_CLASSIC[opcode],
                                       _type_bytes(type_text),
                                       _op_name(tail))])
        elif opcode == "fusion":
            calls = _CALLS_RE.search(tail)
            kind, coll = inner(calls.group(1)) if calls else ("", None)
            if coll is not None:
                key = ("chain", coll[3] if coll[3] is not None else name)
                chains.setdefault(key, []).append(len(events))
                events.append([kind or "s", key, coll[:3]])
            else:
                events.append(["m" if kind == "m" else ".", name, None])
        elif opcode == "custom-call" and "tpu_custom_call" in tail:
            events.append(["k", name, None])
    for key, where in chains.items():
        if len(where) == 1:  # the whole collective in one fusion: blocking
            events[where[0]][0] = "R"
            continue
        events[where[0]][0], events[where[-1]][0] = "S", "D"

    ops, open_at = [], {}
    for i, (kind, key, payload) in enumerate(events):
        if kind == "R":
            ops.append(ScheduledCollective(payload[0], payload[1], False, 0,
                                           payload[2]))
        elif kind == "S":
            open_at[key] = (i, payload)
        elif kind == "D" and key in open_at:
            at, (cls, nbytes, op) = open_at.pop(key)
            inside = sum(e[0] in "mke" for e in events[at + 1:i])
            ops.append(ScheduledCollective(cls, nbytes, True, inside, op))
    return CollectiveSchedule(tuple(ops), "".join(e[0] for e in events))
