"""Median duration, in ms, of the program's own span ``span`` in the
traced part of the window: the annotation its telemetry writes on the
host's lines of the profiler trace (``observability.timeline``)."""

import statistics

from . import _xplane


def read(ctx, span):
    tr = _xplane.load(ctx)
    events = tr.host.get(span) if tr is not None else None
    if not events:
        return None
    return statistics.median(d for _, d, _, _ in events) / 1e9
