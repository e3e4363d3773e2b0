"""The program's process record (``observability.timeline``: the
once-only phases from the process's start, JAX's trace / lower / compile
events under them), as the readers of the set-up metrics ask for it.
They run in the program's process, so they ask the program itself."""

import sys


def record(ctx):
    """The record's snapshot (``start``, ``spans``, ``counters``, ...),
    or None: in a rehearsal (a CPU run's line holds no time), and in a
    program that keeps no record (or was never imported)."""
    if ctx.spec.rehearse:
        return None
    timeline = sys.modules.get("chainermn_tpu.observability.timeline")
    ask = getattr(timeline, "process_record", None)
    return ask() if ask is not None else None


def phases(rec, name):
    """The record's spans called ``name``, recompiles left out: those
    are no part of set-up."""
    return [e for e in rec["spans"]
            if e["name"] == name and not e["args"].get("recompile")]
