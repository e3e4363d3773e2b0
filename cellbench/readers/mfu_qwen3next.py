"""Model FLOP/s utilisation of the Gated DeltaNet / gated-attention
hybrid's step with sparse experts: the FLOPs a step requires
(``flops_qwen3next.step_model_flops``: no recomputation, attention's
causal half, the scan by its algorithm's matmuls, the experts by the
window's mean ``moe_rows_routed``) times the steps a second completed
over the untraced part of the window, over the bf16 peak."""

from .. import flops_qwen3next


def read(ctx):
    cfg, traffic = ctx.spec.sizes, ctx.spec.traffic
    if "linear_num_value_heads" not in cfg or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    per_step = flops_qwen3next.step_model_flops(
        cfg, int(traffic["seq_len"]), int(traffic["per_chip_batch"]),
        float(ctx.telemetry["counters"]["moe_rows_routed"].mean()))
    steps_per_s = ctx.untraced_rate_per_chip() / (
        ctx.samples_per_step / ctx.spec.chips)
    return 100.0 * per_step * steps_per_s / ctx.peaks()["bf16_flops_per_s"]
