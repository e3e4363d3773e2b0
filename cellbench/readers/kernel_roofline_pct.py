"""One flash kernel's share of its roofline: the least time the chip
could take for the calls of ``kernel`` the trace holds
(``flops.roofline_seconds`` of a call of that ``kind``, times the calls)
over the time they took.  The kernel is found by the name the program
gives its ``pallas_call`` (``name=``), which the trace's event name
starts with."""

import re

from .. import flops


def read(ctx, kernel, kind):
    rx = re.compile(r"^%?" + re.escape(kernel) + r"(\.\d+)?$")
    calls = [(seconds, count)
             for name, (seconds, count) in ctx.trace["ops"].items()
             if rx.match(name.partition(" = ")[0])]
    taken = sum(s for s, _ in calls)
    if not taken:
        return None
    sizes, traffic = ctx.spec.sizes, ctx.spec.traffic
    shape = dict(b=int(traffic["per_chip_batch"]), h=int(sizes["n_head"]),
                 s=int(traffic["seq_len"]),
                 dh=int(sizes["n_embd"]) // int(sizes["n_head"]))
    least, bound = flops.roofline_seconds(
        flops.flash_call_flops(kind, **shape),
        flops.flash_call_bytes(kind, **shape), ctx.peaks())
    least *= sum(c for _, c in calls)
    print(f"{kernel} roofline: bound by {bound}, least {least:.6g} s of "
          f"{taken:.6g} s taken")
    return 100.0 * least / taken
