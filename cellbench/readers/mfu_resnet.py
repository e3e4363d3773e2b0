"""Model FLOP/s utilisation of the ResNet-50 step: 6 x the forward MACs
of an image (from the layer shapes) times the images a second a chip
completed over the untraced part of the window, over the bf16 peak."""

from .. import flops


def read(ctx):
    sizes = ctx.spec.sizes
    per_image = flops.resnet50_model_flops_per_image(
        image_size=int(sizes["image_size"]),
        num_classes=int(sizes["num_classes"]),
        num_filters=int(sizes["num_filters"]))
    return 100.0 * per_image * ctx.untraced_rate_per_chip() \
        / ctx.peaks()["bf16_flops_per_s"]
