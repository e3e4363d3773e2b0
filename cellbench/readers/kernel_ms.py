"""Summed device time per step of the operations whose trace name
matches ``pattern``, in ms."""

import re


def read(ctx, pattern):
    rx, steps = re.compile(pattern), ctx.trace["steps"]
    hits = [sec for name, (sec, _) in ctx.trace["ops"].items()
            if rx.search(name)]
    if not hits or not steps:
        return None
    return sum(hits) / steps * 1e3
