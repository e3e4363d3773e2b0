"""The state-space scan's share of its roofline: the least time the chip
could take for the scan's forward and backward passes over all the
Mamba-2 layers of a step (``flops_granite.ssd_flops`` / ``ssd_bytes``:
the chunked algorithm's matmuls and least traffic, whatever implements
them), over the device time a step spends under the ``ssm_scan`` scope.
A forward pass computed again in the backward lowers the share."""

from .. import flops, flops_granite
from . import scope_ms


def read(ctx, scope="ssm_scan"):
    cfg = flops_granite.sizes_of(ctx.spec)
    if cfg is None:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    traffic = ctx.spec.traffic
    s, rows = int(traffic["seq_len"]), int(traffic["per_chip_batch"])
    least = 0.0
    for kind in ("fwd", "bwd"):
        seconds, bound = flops.roofline_seconds(
            flops_granite.ssd_flops(cfg, s, kind),
            flops_granite.ssd_bytes(cfg, s, kind), ctx.peaks())
        least += seconds * rows * cfg["layer_types"].count("mamba")
    print(f"{scope} roofline: backward bound by {bound}, least "
          f"{least * 1e3:.6g} ms of {taken_ms:.6g} ms a step")
    return 100.0 * least * 1e3 / taken_ms
