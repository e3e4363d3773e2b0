"""Model FLOP/s utilisation of the Mamba-2 / attention hybrid's step:
the FLOPs a step requires (``flops_granite.step_model_flops``: no
recomputation, attention's causal half, the scan by its algorithm's
matmuls) times the steps a second completed over the untraced part of
the window, over the bf16 peak."""

from .. import flops_granite


def read(ctx):
    cfg = flops_granite.sizes_of(ctx.spec)
    if cfg is None:
        return None
    traffic = ctx.spec.traffic
    per_step = flops_granite.step_model_flops(
        cfg, int(traffic["seq_len"]), int(traffic["per_chip_batch"]))
    steps_per_s = ctx.untraced_rate_per_chip() / (
        ctx.samples_per_step / ctx.spec.chips)
    return 100.0 * per_step * steps_per_s / ctx.peaks()["bf16_flops_per_s"]
