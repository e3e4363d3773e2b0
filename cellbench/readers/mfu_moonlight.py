"""Model FLOP/s utilisation of the latent-attention model's step with
sparse experts behind a leading dense layer: the FLOPs a step requires
(``flops_moonlight.step_model_flops``: no recomputation, attention's
causal half at its two widths in every layer, the experts by the
window's mean ``moe_rows_routed``) times the steps a second completed
over the untraced part of the window, over the bf16 peak."""

from .. import flops_moonlight


def read(ctx):
    traffic = ctx.spec.traffic
    if "n_routed_experts" not in ctx.spec.config or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    per_step = flops_moonlight.step_model_flops(
        flops_moonlight.sizes_of(ctx.spec), int(traffic["seq_len"]),
        int(traffic["per_chip_batch"]),
        float(ctx.telemetry["counters"]["moe_rows_routed"].mean()))
    steps_per_s = ctx.untraced_rate_per_chip() / (
        ctx.samples_per_step / ctx.spec.chips)
    return 100.0 * per_step * steps_per_s / ctx.peaks()["bf16_flops_per_s"]
