"""The flash kernels' share of their roofline: the least time the chip
could take for the calls the trace holds (the larger of operations over
the bf16 peak and bytes over the HBM peak, call by call) over the time
they took.  ``_flash_forward`` is the forward call; of the
``_flash_backward`` calls the one with two results is dk/dv, the other
dq."""

from .. import flops


def _kind(name):
    head, _, rest = name.partition(" = ")
    if "_flash_forward" in head:
        return "fwd"
    if "_flash_backward" in head:
        return "dkv" if rest.startswith("(") else "dq"
    return None


def read(ctx):
    sizes, traffic = ctx.spec.sizes, ctx.spec.traffic
    shape = dict(b=int(traffic["per_chip_batch"]), h=int(sizes["n_head"]),
                 s=int(traffic["seq_len"]),
                 dh=int(sizes["n_embd"]) // int(sizes["n_head"]))
    calls = [(_kind(name), seconds, count)
             for name, (seconds, count) in ctx.trace["ops"].items()
             if _kind(name)]
    if not calls:
        return None
    peaks = ctx.peaks()
    least = taken = 0.0
    bounds = set()
    for kind, seconds, count in calls:
        t, bound = flops.roofline_seconds(
            flops.flash_call_flops(kind, **shape),
            flops.flash_call_bytes(kind, **shape), peaks)
        least += t * count
        taken += seconds
        bounds.add(bound)
    print(f"flash roofline: bound by {'/'.join(sorted(bounds))}, least "
          f"{least:.6g} s of {taken:.6g} s taken")
    return 100.0 * least / taken
