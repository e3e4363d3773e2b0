"""Model FLOP/s utilisation of the SDAR-MoE block-diffusion step: the
FLOPs a step requires (``flops_sdar.step_model_flops``, the experts by
the window's mean ``moe_rows_routed``) times the steps a second
completed over the untraced part of the window, over the bf16 peak."""

from .. import flops_sdar


def read(ctx):
    if not ctx.telemetry or "counters" not in ctx.telemetry:
        return None
    cfg, traffic = ctx.spec.sizes, ctx.spec.traffic
    rows = int(traffic["per_chip_batch"])
    per_step = flops_sdar.step_model_flops(
        cfg, int(traffic["seq_len"]), rows, int(traffic["block_len"]),
        float(ctx.telemetry["counters"]["moe_rows_routed"].mean()))
    steps_per_s = ctx.untraced_rate_per_chip() / (
        ctx.samples_per_step / ctx.spec.chips)
    return 100.0 * per_step * steps_per_s / ctx.peaks()["bf16_flops_per_s"]
