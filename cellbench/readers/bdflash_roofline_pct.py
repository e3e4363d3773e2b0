"""One block-causal flash kernel's share of its roofline: the least time
the chip could take for what the ``kind`` kernels of a layer compute
(``flops_sdar.bdflash_flops`` / ``bdflash_bytes``: the mask's live
pairs, whatever implements them) over the time the calls of ``kernel``
took.  A layer launches the kernel twice (clean queries, noised
queries), so two events are one layer's."""

import re

from .. import flops, flops_sdar


def read(ctx, kernel, kind):
    rx = re.compile(r"^%?" + re.escape(kernel) + r"(\.\d+)?$")
    calls = [(seconds, count)
             for name, (seconds, count) in ctx.trace["ops"].items()
             if rx.match(name.partition(" = ")[0])]
    taken = sum(s for s, _ in calls)
    if not taken:
        return None
    cfg, traffic = ctx.spec.sizes, ctx.spec.traffic
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    hq, dh = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    least, bound = flops.roofline_seconds(
        flops_sdar.bdflash_flops(kind, b, hq, s, dh,
                                 int(traffic["block_len"])),
        flops_sdar.bdflash_bytes(kind, b, hq,
                                 int(cfg["num_key_value_heads"]), s, dh),
        ctx.peaks())
    least *= sum(c for _, c in calls) / 2.0
    print(f"{kernel} roofline: bound by {bound}, least {least:.6g} s of "
          f"{taken:.6g} s taken")
    return 100.0 * least / taken
