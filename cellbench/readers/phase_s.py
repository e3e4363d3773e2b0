"""Seconds of set-up inside the phase ``phase`` of the program's process
record: every occurrence of the phase, whole; or, with ``children``,
only the spans of those names directly under it (``jax.trace`` +
``jax.lower``: what Python tracing and lowering cost there;
``jax.compile``: compile, or load from the persistent cache).  None
where the phase did not run."""

from . import _process


def read(ctx, phase, children=None):
    rec = _process.record(ctx)
    found = _process.phases(rec, phase) if rec is not None else []
    if not found:
        return None
    if children is None:
        return sum(e["dur"] for e in found)
    under = {e["sid"] for e in found}
    return sum(e["dur"] for e in rec["spans"]
               if e["parent"] in under and e["name"] in children)
