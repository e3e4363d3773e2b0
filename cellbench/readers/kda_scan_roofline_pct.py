"""The channel-wise delta rule's scan's share of its roofline: the least
time the chip could take for the scan's forward and backward passes
over all the KDA layers of a step (``flops_kimilinear.kda_flops`` /
``kda_bytes``: the chunked algorithm's matmuls, the solve counted as a
forward substitution, and least traffic with ``g`` a float32 a key
channel, whatever implements them), over the device time a step spends
under the ``kda_scan`` scope.  A forward pass computed again in the
backward lowers the share."""

from .. import flops, flops_kimilinear
from . import scope_ms


def read(ctx, scope="kda_scan"):
    if "linear_attn_config" not in ctx.spec.config:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    cfg, traffic = flops_kimilinear.sizes_of(ctx.spec), ctx.spec.traffic
    s, rows = int(traffic["seq_len"]), int(traffic["per_chip_batch"])
    layers = sum(mixer == "kda"
                 for mixer, _ in flops_kimilinear.layer_kinds(cfg))
    least = 0.0
    for kind in ("fwd", "bwd"):
        seconds, bound = flops.roofline_seconds(
            flops_kimilinear.kda_flops(cfg, s, kind),
            flops_kimilinear.kda_bytes(cfg, s, kind), ctx.peaks())
        least += seconds * rows * layers
    print(f"{scope} roofline: backward bound by {bound}, least "
          f"{least * 1e3:.6g} ms of {taken_ms:.6g} ms a step")
    return 100.0 * least * 1e3 / taken_ms
