"""The traced run's ``.xplane.pb`` with what ``trace_reduce.load`` leaves
out: the *metadata* of a device event (where the profiler keeps an
operation's ``op_name``, the path of ``jax.named_scope``s and flax
modules it was traced under; ``ProfileData`` shows an event's own stats
only) and the program's annotations on the host's lines.

The file is an ``XSpace`` message (tsl ``xplane.proto``); it is read
here with the standard library alone, field by field, and only the
lines that are asked for are decoded.  ``load(ctx)`` reads the newest
trace a traced run left under the temporary directory
(``layers.traced_window`` makes it there and removes it after the
readers ran), once for all readers of a run, and takes it only if it
holds the step programs the run's own reduction counted.  Whatever is missing reads
as empty: a reader built on this returns None and never raises.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import struct
import tempfile

from .. import trace_reduce

#: the stat of a device event's metadata that holds the operation's
#: ``op_name``, as ``<op_name>:<op type>`` (the type is empty for a JAX
#: program)
OP_NAME_STAT = "tf_op"


# -- the wire format -----------------------------------------------------
def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf, pos, end):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` pair for a length-delimited field, raw bytes for a
    fixed one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif kind == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif kind == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """One ``XStat`` as ``(name, value)``."""
    name = value = None
    for no, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no in (5, 6):
            value = _text(buf, v)
        elif no == 7:  # a string kept once, as a stat's name
            value = stat_names.get(v, str(v))
    return name, value


def _id_map(buf, spans, read):
    """A ``map<int64, Message>`` field: ``{key: read(message span)}``."""
    out = {}
    for span in spans:
        key = inner = None
        for no, v in _fields(buf, *span):
            if no == 1:
                key = v
            elif no == 2:
                inner = v
        if inner is not None:
            out[key] = read(inner)
    return out


def _name_of(buf, span):
    for no, v in _fields(buf, *span):
        if no == 2:
            return _text(buf, v)
    return ""


@dataclasses.dataclass
class Plane:
    """One ``XPlane``, its lines still undecoded."""

    buf: object
    name: str
    lines: dict          # line name -> [message span, ...]
    event_names: dict    # metadata id -> event name
    _event_meta: dict    # metadata id -> message span
    stat_names: dict

    def meta_stats(self, metadata_id) -> dict:
        """The stats the plane keeps once for every event with this
        metadata."""
        span = self._event_meta.get(metadata_id)
        if span is None:
            return {}
        return dict(_stat(self.buf, v, self.stat_names)
                    for no, v in _fields(self.buf, *span) if no == 5)

    def events(self, line: str, keep=None, with_stats: bool = False):
        """``(metadata id, start_ps, duration_ps[, stats])`` of the
        events on the lines of that name (those whose metadata id is in
        ``keep``, if given), on the trace's one clock."""
        buf, out = self.buf, []
        for span in self.lines.get(line, ()):
            base, events = 0, []
            for no, v in _fields(buf, *span):
                if no == 3:
                    base = _signed(v) * 1000
                elif no == 4:
                    events.append(v)
            for ev in events:
                mid = offset = dur = 0
                stats = []
                for no, v in _fields(buf, *ev):
                    if no == 1:
                        mid = v
                    elif no == 2:
                        offset = _signed(v)
                    elif no == 3:
                        dur = _signed(v)
                    elif no == 4:
                        stats.append(v)
                if keep is not None and mid not in keep:
                    continue
                if with_stats:
                    out.append((mid, base + offset, dur, dict(
                        _stat(buf, v, self.stat_names) for v in stats)))
                else:
                    out.append((mid, base + offset, dur))
        return out


def planes(path: str) -> list:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = []
    for no, span in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name, lines, metas, stats = "", {}, [], []
        for no2, v in _fields(buf, *span):
            if no2 == 2:
                name = _text(buf, v)
            elif no2 == 3:
                lines.setdefault(_name_of(buf, v), []).append(v)
            elif no2 == 4:
                metas.append(v)
            elif no2 == 5:
                stats.append(v)
        event_meta = _id_map(buf, metas, lambda s: s)
        out.append(Plane(
            buf, name, lines,
            {k: _name_of(buf, s) for k, s in event_meta.items()},
            event_meta, _id_map(buf, stats, lambda s: _name_of(buf, s))))
    return out


# -- what the readers use ------------------------------------------------
@dataclasses.dataclass
class Trace:
    #: chip 0's operations inside the whole steps, in program order:
    #: ``(trace name, start_ps, duration_ps, op_name or None)``
    ops: list
    #: chip 0's whole step programs, ``(start_ps, end_ps)``
    steps: list
    #: the events of the Python threads' ``/host:CPU`` lines by name:
    #: ``{name: [(start_ps, duration_ps, line, stats), ...]}``
    host: dict
    #: what a reader has printed of this trace already (a partition is
    #: printed once, whichever metric's reader comes first)
    printed: set = dataclasses.field(default_factory=set)

    def first_time(self, what: str) -> bool:
        first = what not in self.printed
        self.printed.add(what)
        return first

    def busy(self):
        """Merged intervals in which an operation runs on chip 0."""
        return trace_reduce.merge([s, s + d] for _, s, d, _ in self.ops)


def step_programs(modules):
    """The whole step programs among ``(name, start, duration)`` module
    events, as ``trace_reduce.reduce`` picks them: the most frequent
    program, without a first one that is only its tail."""
    counts = {}
    for name, _, _ in modules:
        counts[name] = counts.get(name, 0) + 1
    if not counts:
        return []
    step_name = max(counts, key=counts.get)
    steps = sorted((s, s + d) for n, s, d in modules if n == step_name)
    typical = sorted(e - s for s, e in steps)[len(steps) // 2]
    if len(steps) > 2 and steps[0][1] - steps[0][0] < 0.9 * typical:
        steps = steps[1:]
    return steps


def _op_name(stats: dict):
    value = stats.get(OP_NAME_STAT)
    return (value.rpartition(":")[0] or value) if value else None


def python_line(name: str) -> bool:
    """The profiler names the line of a thread the runtime started
    ``<thread name>/<id>`` and that of a Python thread after the process
    (``python3``).  The program's annotations are made on Python
    threads; a runtime thread's line can hold millions of events (the
    host-side relayout of every image batch writes 70 MB a line)."""
    return "/" not in name


def read_trace(path: str, host_prefix_skipped: str = "$") -> Trace:
    """``host_prefix_skipped``: the Python tracer names its events
    ``$file:line function``; they are not the program's annotations."""
    ops, steps, host = [], [], {}
    every = planes(path)
    tpus = sorted((p for p in every if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if tpus:
        dev = tpus[0]
        steps = step_programs([(dev.event_names.get(m, ""), s, d)
                               for m, s, d in dev.events("XLA Modules")])
        if steps:
            lo, hi = steps[0][0], steps[-1][1]
            known = {}
            for mid, s, d in dev.events("XLA Ops"):
                if lo <= s and s + d <= hi:
                    if mid not in known:
                        known[mid] = (dev.event_names.get(mid, ""),
                                      _op_name(dev.meta_stats(mid)))
                    ops.append((known[mid][0], s, d, known[mid][1]))
    for plane in every:
        if plane.name != "/host:CPU":
            continue
        wanted = {m for m, n in plane.event_names.items()
                  if not n.startswith(host_prefix_skipped)}
        for line in filter(python_line, plane.lines):
            for mid, s, d, stats in plane.events(line, keep=wanted,
                                                 with_stats=True):
                host.setdefault(plane.event_names[mid], []).append(
                    (s, d, line, stats))
    return Trace(ops, steps, host)


def newest_trace_path():
    """The ``.xplane.pb`` of the newest traced run's directory under the
    temporary directory, or None."""
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         "cellbench_trace_*")),
                  key=os.path.getmtime)
    for trace_dir in reversed(dirs):
        try:
            return trace_reduce.find_xplane(trace_dir)
        except FileNotFoundError:
            continue
    return None


@functools.lru_cache(maxsize=2)
def _cached(path: str, mtime: float):
    return read_trace(path)


def same_run(tr: Trace, reduced: dict) -> bool:
    """Whether ``tr`` holds the step programs that the run's own
    reduction (``trace_reduce.reduce`` of the run's own directory:
    ``Context.trace``) counted, over the same window."""
    window = (tr.steps[-1][1] - tr.steps[0][0]) / 1e12 if tr.steps else 0.0
    return len(tr.steps) == reduced["steps"] \
        and abs(window - reduced["window_s"]) < 1e-6


def load(ctx=None, path: str = ""):
    """The run's trace, read once; None where there is none or it cannot
    be read.  ``ctx`` is the run's ``layers.Context``: the newest
    directory under the temporary directory is another run's where two
    traced cells share one, or a killed run left its own, and a trace
    that is not ``ctx.trace``'s reads as None."""
    try:
        path = path or newest_trace_path()
        if not path:
            return None
        tr = _cached(path, os.path.getmtime(path))
        if ctx is not None and not same_run(tr, ctx.trace):
            print(f"_xplane: {path} is not this run's trace "
                  f"({len(tr.steps)} step programs, the run's reduction "
                  f"counted {ctx.trace['steps']})")
            return None
        return tr
    except Exception as e:  # a reader never ends a traced run
        print(f"_xplane: trace not read ({type(e).__name__}: {e})")
        return None
