"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use.  ``load`` reads the planes with nothing but JAX; ``reduce`` works on
plain lists, so the test runs it on a recorded trace kept as JSON
(``tests/recorded_lm_trace.json.gz``: three steps of the one-chip LM
cell on a v5e)."""

from __future__ import annotations

import glob
import os
import re

#: lines of a TPU device plane that are read: the step programs, the
#: operations in program order, and the asynchronous ones beside them
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
#: lines of the host plane that say what the main thread was doing: the
#: Python tracer's calls
HOST_LINES = ("python",)
#: HLO operations that are collectives
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str, chips: int) -> dict:
    """``{"devices": [{line name: [(name, start_ns, dur_ns), ...]} per
    chip], "host": [...]}`` from the trace under ``trace_dir``: the
    device planes' operation and module lines, and the calls the
    Python tracer saw on the host."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            index = int(plane.name.rsplit(":", 1)[1])
            devices[index] = {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines if line.name in DEVICE_LINES}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name in HOST_LINES:
                    host += [(e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events]
    return {"devices": [devices[i] for i in sorted(devices)][:chips],
            "host": host}


# -- interval arithmetic on (start, end) pairs -----------------------------
def merge(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def family(name: str) -> str:
    """``%fusion.9 = f32[50257,1536]{...} fusion(...)`` ->
    ``fusion f32[50257,1536]``: the trace's name without its serial
    number, with the result's first shape, so that the same operation
    of every layer falls into one family."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return (head.lstrip("%").rstrip("0123456789").rstrip(".")
            + " " + shape).strip()


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name.partition(" = ")[0]))


def _host_at(host, t):
    """The innermost traced Python call on the host at ``t``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no traced host call"


def reduce(loaded: dict) -> dict:
    """The numbers the readers use, over the whole steps the trace
    holds: the window runs from the first step program's start on chip 0
    to the last one's end.  Times in seconds; ``ops`` and the gaps are
    chip 0's."""
    devices = loaded["devices"]
    empty = {"steps": 0, "window_s": 0.0, "busy_s": 0.0, "ops": {},
             "launch_gap_ms": None, "collective_s": 0.0,
             "collective_exposed_s": 0.0,
             "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not devices or not devices[0].get("XLA Modules"):
        return empty
    modules = devices[0]["XLA Modules"]
    counts = {}
    for name, _, _ in modules:
        counts[name] = counts.get(name, 0) + 1
    step_name = max(counts, key=counts.get)
    steps = sorted((s, s + d) for n, s, d in modules if n == step_name)
    # the profiler starts in the middle of a step: a first step program
    # that reads shorter than the others is only its tail
    typical = sorted(e - s for s, e in steps)[len(steps) // 2]
    if len(steps) > 2 and steps[0][1] - steps[0][0] < 0.9 * typical:
        steps = steps[1:]
    lo, hi = steps[0][0], steps[-1][1]

    busy = []
    for dev in devices:
        spans = _spans(dev.get("XLA Ops", [])) \
            + _spans(dev.get("Async XLA Ops", []))
        busy.append(merge(clip(spans, lo, hi)))
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]

    ops = {}
    for name, s, d in devices[0].get("XLA Ops", []):
        if lo <= s and s + d <= hi:
            ent = ops.setdefault(name, [0, 0])
            ent[0] += d
            ent[1] += 1
    dev0 = devices[0]
    coll = merge(clip(_spans(
        [e for line in ("XLA Ops", "Async XLA Ops")
         for e in dev0.get(line, []) if is_collective(e[0])]), lo, hi))
    compute = merge(clip(_spans(
        [e for e in dev0.get("XLA Ops", []) if not is_collective(e[0])]),
        lo, hi))
    idle = sorted(subtract([[lo, hi]], busy[0]),
                  key=lambda g: g[0] - g[1])[:10]
    families = {}
    for name, (ns, _) in ops.items():
        families[family(name)] = families.get(family(name), 0) + ns
    top = sorted(families.items(), key=lambda kv: -kv[1])[:10]
    return {
        "steps": len(steps),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(total(b) for b in busy) / len(busy) / 1e9,
        "ops": {n: (ns / 1e9, c) for n, (ns, c) in ops.items()},
        # the median: the profiler itself stalls a few launches of a
        # host-fed cell for hundreds of ms, which a mean would report
        "launch_gap_ms": sorted(gaps)[len(gaps) // 2] / 1e6
        if gaps else None,
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": total(subtract(coll, compute)) / 1e9,
        "breakdown": {
            "device_ops": [[n, ns / 1e9] for n, ns in top],
            "idle_gaps": [[_host_at(loaded.get("host", []), s),
                           (e - s) / 1e9] for s, e in idle],
        },
    }
