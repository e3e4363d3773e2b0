"""The delta-rule scan's share of its roofline: the least time the chip
could take for the scan's forward and backward passes over all the
Gated DeltaNet layers of a step (``flops_qwen3next.gdn_flops`` /
``gdn_bytes``: the chunked algorithm's matmuls, the solve counted as a
forward substitution, and least traffic, whatever implements them),
over the device time a step spends under the ``gdn_scan`` scope.  A
forward pass computed again in the backward lowers the share."""

from .. import flops, flops_qwen3next
from . import scope_ms


def read(ctx, scope="gdn_scan"):
    cfg = ctx.spec.sizes
    if "linear_num_value_heads" not in cfg:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    traffic = ctx.spec.traffic
    s, rows = int(traffic["seq_len"]), int(traffic["per_chip_batch"])
    layers = flops_qwen3next.layer_kinds(cfg).count("linear_attention")
    least = 0.0
    for kind in ("fwd", "bwd"):
        seconds, bound = flops.roofline_seconds(
            flops_qwen3next.gdn_flops(cfg, s, kind),
            flops_qwen3next.gdn_bytes(cfg, s, kind), ctx.peaks())
        least += seconds * rows * layers
    print(f"{scope} roofline: backward bound by {bound}, least "
          f"{least * 1e3:.6g} ms of {taken_ms:.6g} ms a step")
    return 100.0 * least * 1e3 / taken_ms
