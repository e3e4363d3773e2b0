"""The expert products' share of their roofline where the held experts
are counted under ``n_routed_experts``:
``readers/moe_experts_roofline_kimilinear.py``'s share, the held
experts' weights counted over the layers that have experts
(``flops_moonlight.expert_layers``)."""

from .. import flops, flops_moonlight, flops_sdar
from . import scope_ms


def read(ctx, scope="moe_experts"):
    cfg = ctx.spec.sizes
    if "n_routed_experts" not in cfg or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    routed = float(ctx.telemetry["counters"]["moe_rows_routed"].mean())
    least, bound = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, d, f),
        flops_sdar.expert_bytes(routed, d, f, int(cfg["n_routed_experts"]),
                                flops_moonlight.expert_layers(cfg)),
        ctx.peaks())
    print(f"{scope} roofline: bound by {bound}, least {least * 1e3:.6g} ms "
          f"of {taken_ms:.6g} ms a step for {routed:.1f} routed rows")
    return 100.0 * least * 1e3 / taken_ms
