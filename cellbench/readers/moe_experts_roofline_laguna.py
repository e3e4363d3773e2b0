"""The expert products' share of their roofline in the model of window
and full layers: ``readers/moe_experts_roofline_moonlight.py``'s share,
the held experts (``num_experts``) counted over the layers whose
``mlp_layer_types`` entry is ``sparse`` (four of the cell's five)."""

from .. import flops, flops_laguna, flops_sdar
from . import scope_ms


def read(ctx, scope="moe_experts"):
    if "sliding_window" not in ctx.spec.config or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    cfg = flops_laguna.sizes_of(ctx.spec)
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    routed = float(ctx.telemetry["counters"]["moe_rows_routed"].mean())
    least, bound = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, d, f),
        flops_sdar.expert_bytes(routed, d, f, int(cfg["num_experts"]),
                                flops_laguna.expert_layers(cfg)),
        ctx.peaks())
    print(f"{scope} roofline: bound by {bound}, least {least * 1e3:.6g} ms "
          f"of {taken_ms:.6g} ms a step for {routed:.1f} routed rows")
    return 100.0 * least * 1e3 / taken_ms
