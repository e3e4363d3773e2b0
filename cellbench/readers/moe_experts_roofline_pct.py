"""The expert products' share of their roofline: the least time the chip
could take for the window's mean ``moe_rows_routed`` rows through three
products forward and six backward, with the held experts' weights read
once each way (``flops_sdar.expert_flops`` / ``expert_bytes``), over
the device time a step spends under the ``moe_experts`` scope.  Rows of
padding, or of a buffer larger than the routes, lower the share."""

from .. import flops, flops_sdar
from . import scope_ms


def read(ctx, scope="moe_experts"):
    if not ctx.telemetry or "counters" not in ctx.telemetry:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    cfg = ctx.spec.sizes
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    routed = float(ctx.telemetry["counters"]["moe_rows_routed"].mean())
    least, bound = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, d, f),
        flops_sdar.expert_bytes(routed, d, f, int(cfg["num_experts"]),
                                int(cfg["num_hidden_layers"])),
        ctx.peaks())
    print(f"{scope} roofline: bound by {bound}, least {least * 1e3:.6g} ms "
          f"of {taken_ms:.6g} ms a step for {routed:.1f} routed rows")
    return 100.0 * least * 1e3 / taken_ms
