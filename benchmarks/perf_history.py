"""perf_history — diff committed bench captures, flag regressions.

First slice of the ROADMAP perf-gate item: the repo commits one
``BENCH_r<NN>.json`` per revision (the bench driver's captured stdout
tail — JSON-lines rows, each carrying the min-of-N protocol fields the
``untimed-row`` lint enforces).  This tool diffs the two newest captures
and flags any row whose metric moved in the *worse* direction by more
than its own recorded noise bound (``spread_max_over_min``), so a perf
regression fails loudly at review time instead of surfacing three
revisions later as an unexplained trend.

Run from the repo root (tier-1 runs it as a smoke via
``tests/test_perf_history.py``)::

    python benchmarks/perf_history.py            # two newest captures
    python benchmarks/perf_history.py A.json B.json   # explicit pair

Exit status 0 = no regressions beyond spread, 1 = regressions listed.

Direction is inferred per metric: ``*_ms`` / ``*_s`` / ``*sec_per*``
keys and units are lower-is-better (the recovery-latency rows —
``fleet_recovery.recover_peer_s`` and friends — ride the ``_s``
spelling); throughputs, MFU, and speedup ratios are higher-is-better
(``*_per_s`` wins over the ``_s`` suffix by precedence).  Rows without a recorded spread use the default
tolerance (``DEFAULT_TOLERANCE``, 10 % — roughly the worst spread the
committed captures have recorded on the virtual-mesh configs).  Rows
whose value is null (failed capture) are skipped, not compared.

Variant-shaped rows (``{"variant": ..., "step_time_ms": ...}`` — the
``comm_overlap_bench`` rungs, including the ISSUE 8 ``overlap_off/on``
A/B) carry no ``value``; the loader synthesizes one from
``step_time_ms`` (unit ``ms``, lower-is-better) so a captured overlap
trajectory is regression-gated exactly like the metric rows, spread-
gated by the row's own ``spread_max_over_min``.  Speedup-ratio rows
(``vgg16_overlap_speedup``) are higher-is-better via the ``speedup``
spelling.

Phase-summary rows (ISSUE 10): ``MetricsReport`` appends
``{"phase": "step", "p50_ms": ..., "p99_ms": ...}`` rows to its JSONL;
each ``*_ms`` statistic loads as its own ``phase.<name>.<stat>``
pseudo-metric (unit ms, lower-is-better, DEFAULT tolerance — the phase
row's recorded spread is cross-rank imbalance, not repeat noise), so a
committed per-phase trajectory — data-wait creep, a step-time p99
regression — fails the gate like any bench row.

Profile provenance (ISSUE 12): tuned wire rows carry a
``profile_hash`` (the ``BandwidthProfile`` content hash their plan was
tuned against).  When a metric's profile hash DIFFERS between the two
captures — a retune, or a profile appearing/disappearing — the row is
still compared but its regressions are ANNOTATED instead of gated
(printed as ``RETUNED``, exit status unaffected): a retune is a
*disclosed* configuration change, and gating it would punish every
honest recalibration; silent drift is precisely a regression under an
UNCHANGED hash, and that still fails the gate.  Every shared row whose
profile hash moved is listed (``retune_notes``) even when nothing
regressed, so a capture diff always shows which rows were re-tuned.
"""

from __future__ import annotations

import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

DEFAULT_TOLERANCE = 1.10

_BENCH_NAME_RE = re.compile(r"^BENCH_r(\d+)(_local)?\.json$")
# throughput spellings win first ("images_per_sec_per_chip" contains
# the substring "sec_per" — _per_sec must take precedence)
_HIGHER_BETTER_RE = re.compile(
    r"(_per_sec|_per_s$|per_chip|speedup|mfu|\.v$)"
)
_LOWER_BETTER_RE = re.compile(
    r"(_ms$|\.ms$|(^|_)ms(_|$)|^sec_|_time|_s$)"
)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_files(root: Optional[str] = None) -> List[str]:
    """Committed captures, oldest first.  Primary captures order
    before ``_local`` fallbacks of the same revision; both are returned
    so the differ can fall back when a primary capture holds a null
    row."""
    root = root or repo_root()
    found: List[Tuple[int, int, str]] = []
    for name in os.listdir(root):
        m = _BENCH_NAME_RE.match(name)
        if m:
            found.append((
                int(m.group(1)),
                1 if m.group(2) else 0,
                os.path.join(root, name),
            ))
    found.sort()
    return [p for _, _, p in found]


def _revision_of(path: str) -> int:
    m = _BENCH_NAME_RE.match(os.path.basename(path))
    return int(m.group(1)) if m else -1


def load_rows(path: str) -> Dict[str, dict]:
    """``{metric_name: row}`` from one capture.

    Two committed shapes: a driver capture wrapping the bench stdout
    tail (rows are the JSON-parseable lines — the tail may open
    mid-line, unparseable lines are skipped — plus the driver's
    ``parsed`` copy of the last row), and a bare row dict (the
    ``_local`` fallback captures commit the final bench row directly).
    The final row's nested ``summary`` / ``configs`` maps are
    flattened to ``<key>.v`` pseudo-metrics so every tracked config
    participates in the diff.
    """
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    rows: Dict[str, dict] = {}

    def add(row: dict) -> None:
        name = row.get("metric") or row.get("variant")
        if not isinstance(name, str):
            # MetricsReport phase-summary rows (ISSUE 10): shaped
            # {"phase": "step", "p50_ms": ..., "p99_ms": ...} with no
            # metric/variant name.  Each *_ms summary statistic becomes
            # its own pseudo-metric ("phase.step.p50_ms", unit ms —
            # lower-is-better by the existing direction inference), so
            # a captured per-phase trajectory is regression-gated
            # direction-aware like every other row.  The phase row's
            # own spread_max_over_min is deliberately NOT inherited:
            # MetricsReport computes it as max/min of per-PROCESS
            # means (cross-rank imbalance, potentially huge on a
            # straggler capture), which is not repeat noise of the
            # statistic being diffed — the pseudo-metric uses the
            # default tolerance instead.  Repeated reports of the same
            # phase keep the LAST row (end-of-run summary), matching
            # the variant-row convention.
            phase = row.get("phase")
            if isinstance(phase, str):
                for key in ("p50_ms", "p99_ms", "mean_ms", "max_ms"):
                    if isinstance(row.get(key), (int, float)):
                        rows[f"phase.{phase}.{key}"] = {
                            "metric": f"phase.{phase}.{key}",
                            "value": row[key],
                            "unit": "ms",
                        }
            return
        if (
            "variant" in row
            and "metric" not in row
            and "value" not in row
            and isinstance(row.get("step_time_ms"), (int, float))
        ):
            # variant-shaped rows (the comm_overlap_bench rungs, incl.
            # the ISSUE 8 overlap_off/on A/B) carry step_time_ms but no
            # "value": synthesize one so the overlap trajectory is
            # regression-gated like every metric row.  Unit "ms" makes
            # the direction explicit (lower is better), and the row's
            # own spread_max_over_min keeps the gate noise-aware.
            # Strictly the VARIANT shape: a metric row whose value is
            # null is a FAILED capture and must stay skipped (the
            # documented contract) — synthesizing its step_time_ms
            # would compare a time against a throughput baseline.
            row = dict(row, value=row["step_time_ms"], unit="ms")
        rows[name] = row
        nested = row.get("summary") or row.get("configs") or {}
        if isinstance(nested, dict):
            # only the normalized per-chip values ("v") compare across
            # revisions — step_time_ms moves with batch/seq config
            # changes even when per-chip throughput improves
            for key, sub in nested.items():
                if not isinstance(sub, dict):
                    continue
                if "v" in sub or "value" in sub:
                    rows[f"{key}.v"] = {
                        "metric": f"{key}.v",
                        "value": sub.get("v", sub.get("value")),
                        "unit": sub.get("u", sub.get("unit", "")),
                    }

    for line in str(doc.get("tail", "")).splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict):
            add(row)
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        add(parsed)
    if "metric" in doc or "variant" in doc:  # bare-row (_local) shape
        add(doc)
    return rows


def lower_is_better(name: str, row: dict) -> bool:
    unit = str(row.get("unit", ""))
    if _HIGHER_BETTER_RE.search(name) or "per_sec" in unit:
        return False
    return bool(_LOWER_BETTER_RE.search(name) or unit in ("ms", "s"))


@dataclass(frozen=True)
class Regression:
    metric: str
    old: float
    new: float
    ratio: float     # worsening factor (>= 1.0)
    allowed: float   # the tolerance it exceeded
    direction: str   # "lower-better" / "higher-better"
    # ISSUE 12: True when the row's wire-profile hash differs between
    # the captures — a disclosed retune, reported but NOT gated
    disclosed: bool = False

    def __str__(self) -> str:
        return (
            f"{self.metric}: {self.old:g} -> {self.new:g} "
            f"({self.direction}, worsened {self.ratio:.3f}x > allowed "
            f"{self.allowed:.3f}x)"
        )


def _profile_of(row: dict) -> Optional[str]:
    ph = row.get("profile_hash")
    return str(ph) if isinstance(ph, str) and ph else None


def _retuned(old_row: dict, new_row: dict) -> bool:
    """True when the row's tuning profile changed between captures —
    including a profile appearing where the row was previously
    constant-planned (or vice versa): either way the measured config
    moved and a perf delta is disclosed, not drift."""
    op, np_ = _profile_of(old_row), _profile_of(new_row)
    return (op is not None or np_ is not None) and op != np_


def retune_notes(old: Dict[str, dict],
                 new: Dict[str, dict]) -> List[str]:
    """One line per shared row whose profile hash moved — printed even
    when nothing regressed, so every retune is visible in the diff."""
    out = []
    for name in sorted(set(old) & set(new)):
        if _retuned(old[name], new[name]):
            out.append(
                f"{name}: profile {_profile_of(old[name]) or '(none)'} "
                f"-> {_profile_of(new[name]) or '(none)'}"
            )
    return out


def _tolerance(old_row: dict, new_row: dict) -> float:
    spreads = [
        r.get("spread_max_over_min")
        for r in (old_row, new_row)
        if isinstance(r.get("spread_max_over_min"), (int, float))
    ]
    if spreads:
        return max(float(max(spreads)), 1.0)
    return DEFAULT_TOLERANCE


def diff_rows(old: Dict[str, dict],
              new: Dict[str, dict]) -> List[Regression]:
    """Rows present in both captures whose metric worsened beyond its
    recorded spread (or the default tolerance when none is recorded)."""
    out: List[Regression] = []
    for name in sorted(set(old) & set(new)):
        ov, nv = old[name].get("value"), new[name].get("value")
        if not isinstance(ov, (int, float)) or not isinstance(
            nv, (int, float)
        ):
            continue
        if ov <= 0:
            continue  # no positive baseline to compare against
        lower = lower_is_better(name, new[name])
        if nv <= 0:
            if lower:
                continue  # a zero/negative time is bogus, not slower
            # a throughput collapsing to zero is the WORST regression —
            # it must fail the gate, not be skipped as unratioable
            out.append(Regression(
                metric=name, old=float(ov), new=float(nv),
                ratio=float("inf"), allowed=_tolerance(
                    old[name], new[name]
                ),
                direction="higher-better",
                disclosed=_retuned(old[name], new[name]),
            ))
            continue
        ratio = (nv / ov) if lower else (ov / nv)
        allowed = _tolerance(old[name], new[name])
        if ratio > allowed:
            out.append(Regression(
                metric=name,
                old=float(ov),
                new=float(nv),
                ratio=float(ratio),
                allowed=float(allowed),
                direction="lower-better" if lower else "higher-better",
                disclosed=_retuned(old[name], new[name]),
            ))
    return out


def newest_comparable_pair(
    root: Optional[str] = None,
) -> Optional[Tuple[str, str]]:
    """The two newest captures of DISTINCT revisions that actually
    carry comparable rows — walking back past failed captures (null
    rows) rather than 'comparing' an outage to a measurement, and
    never pairing a revision with its own ``_local`` fallback (first
    parseable capture per revision wins: primary before local)."""
    files = bench_files(root)
    best: Dict[int, str] = {}  # revision -> first comparable capture
    for p in files:
        rev = _revision_of(p)
        if rev in best:
            continue
        rows = load_rows(p)
        if any(
            isinstance(r.get("value"), (int, float)) for r in rows.values()
        ):
            best[rev] = p
    if len(best) < 2:
        return None
    revs = sorted(best)
    return best[revs[-2]], best[revs[-1]]


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    if len(argv) == 2:
        old_path, new_path = argv
    elif not argv:
        pair = newest_comparable_pair()
        if pair is None:
            print("perf_history: fewer than two comparable captures")
            return 0
        old_path, new_path = pair
    else:
        print("usage: perf_history.py [OLD.json NEW.json]",
              file=sys.stderr)
        return 2
    old, new = load_rows(old_path), load_rows(new_path)
    if len(argv) == 2:
        # explicit pair: an unreadable/empty capture must NOT pass the
        # gate green as "0 shared rows" — that is the outage-read-as-
        # measurement trap the no-args path walks around
        for path, rows in ((old_path, old), (new_path, new)):
            if not rows:
                print(
                    f"perf_history: {path} has no parseable rows "
                    "(missing file or truncated capture)",
                    file=sys.stderr,
                )
                return 2
    shared = sorted(set(old) & set(new))
    regressions = diff_rows(old, new)
    gated = [r for r in regressions if not r.disclosed]
    disclosed = [r for r in regressions if r.disclosed]
    print(
        f"perf_history: {os.path.basename(old_path)} -> "
        f"{os.path.basename(new_path)}: {len(shared)} shared row(s), "
        f"{len(gated)} regression(s), {len(disclosed)} retuned"
    )
    for note in retune_notes(old, new):
        print(f"  RETUNE NOTE {note}")
    for r in disclosed:
        # a retune is a disclosed config change: reported, not gated
        print(f"  RETUNED {r}")
    for r in gated:
        print(f"  REGRESSION {r}")
    return 1 if gated else 0


if __name__ == "__main__":
    sys.exit(main())
