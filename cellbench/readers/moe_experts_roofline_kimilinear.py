"""The expert products' share of their roofline in a model whose leading
layers are dense: ``readers/moe_experts_roofline_pct.py``'s share with
the held experts' weights counted over the layers that have experts
(``num_hidden_layers`` less ``first_k_dense_replace``)."""

from .. import flops, flops_sdar
from . import scope_ms


def read(ctx, scope="moe_experts"):
    cfg = ctx.spec.sizes
    if "first_k_dense_replace" not in cfg or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    taken_ms = scope_ms.read(ctx, scope)
    if not taken_ms:
        return None
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    routed = float(ctx.telemetry["counters"]["moe_rows_routed"].mean())
    least, bound = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, d, f),
        flops_sdar.expert_bytes(
            routed, d, f, int(cfg["num_experts"]),
            int(cfg["num_hidden_layers"])
            - int(cfg["first_k_dense_replace"])),
        ctx.peaks())
    print(f"{scope} roofline: bound by {bound}, least {least * 1e3:.6g} ms "
          f"of {taken_ms:.6g} ms a step for {routed:.1f} routed rows")
    return 100.0 * least * 1e3 / taken_ms
