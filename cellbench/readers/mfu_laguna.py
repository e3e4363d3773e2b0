"""Model FLOP/s utilisation of the step of the model of window and full
attention layers: the FLOPs a step requires
(``flops_laguna.step_model_flops``: no recomputation, a layer's
attention by its own mask's pairs and head count, the experts by the
window's mean ``moe_rows_routed``) times the steps a second completed
over the untraced part of the window, over the bf16 peak."""

from .. import flops_laguna


def read(ctx):
    traffic = ctx.spec.traffic
    if "sliding_window" not in ctx.spec.config or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    per_step = flops_laguna.step_model_flops(
        flops_laguna.sizes_of(ctx.spec), int(traffic["seq_len"]),
        int(traffic["per_chip_batch"]),
        float(ctx.telemetry["counters"]["moe_rows_routed"].mean()))
    steps_per_s = ctx.untraced_rate_per_chip() / (
        ctx.samples_per_step / ctx.spec.chips)
    return 100.0 * per_step * steps_per_s / ctx.peaks()["bf16_flops_per_s"]
