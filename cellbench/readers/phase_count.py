"""How many spans called ``span`` (with ``cache``: only those whose
``cache`` argument reads so) the program's process record holds from the
process's start to the end of the last phase ``upto``: with
``jax.compile`` and ``step.first_call``, the programs a set-up compiled
or loaded up to its last step program, and how many of them the
persistent cache did not have.  None where ``upto`` did not run, or
where the record dropped spans (it is bounded)."""

from . import _process


def read(ctx, span, upto, cache=None):
    rec = _process.record(ctx)
    last = _process.phases(rec, upto) if rec is not None else []
    if not last or rec["dropped"]:
        return None
    end = max(e["t"] + e["dur"] for e in last)
    return sum(1 for e in rec["spans"]
               if e["name"] == span and e["t"] + e["dur"] <= end
               and cache in (None, e["args"].get("cache")))
