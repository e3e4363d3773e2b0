"""The causal kernels' share of their roofline in a model whose every
layer is latent attention (keys 192, values 128):
``readers/flash_roofline_kimilinear.py``'s share, launch by launch
against ``flops_moonlight.flash_call_flops`` / ``flash_call_bytes``, for
a configuration without that reader's ``linear_attn_config``."""

from .. import flops, flops_moonlight
from .flash_roofline_kimilinear import _KERNEL, _KINDS


def read(ctx):
    if "n_routed_experts" not in ctx.spec.config:
        return None
    cfg, traffic = flops_moonlight.sizes_of(ctx.spec), ctx.spec.traffic
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    least = taken = 0.0
    bounds = set()
    for name, (seconds, count) in ctx.trace["ops"].items():
        match = _KERNEL.match(name.partition(" = ")[0])
        if not match:
            continue
        kind = _KINDS[match.group(1)]
        t, bound = flops.roofline_seconds(
            flops_moonlight.flash_call_flops(cfg, kind, b, s),
            flops_moonlight.flash_call_bytes(cfg, kind, b, s), ctx.peaks())
        least += t * count
        taken += seconds
        bounds.add(bound)
    if not taken:
        return None
    print(f"flash roofline at 192 / 128: bound by "
          f"{'/'.join(sorted(bounds))}, least {least:.6g} s of "
          f"{taken:.6g} s taken")
    return 100.0 * least / taken
