"""Model FLOP/s utilisation of the LM step: the FLOPs the model requires
per token (no recomputation counted) times the tokens a second a chip
completed over the untraced part of the window, over the bf16 peak."""

from .. import flops


def read(ctx):
    sizes = ctx.spec.sizes
    per_token = flops.lm_model_flops_per_token(
        int(sizes["n_embd"]), int(sizes["n_layer"]),
        int(sizes["vocab_size"]), int(ctx.spec.traffic["seq_len"]),
        int(sizes["n_inner"]))
    return 100.0 * per_token * ctx.untraced_rate_per_chip() \
        / ctx.peaks()["bf16_flops_per_s"]
