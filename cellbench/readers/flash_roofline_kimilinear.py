"""The causal kernels' share of their roofline at a key width apart from
the value width (latent attention's 192 / 128): the least time the chip
could take for the launches the trace holds
(``flops_kimilinear.flash_call_flops`` / ``flash_call_bytes``: the
mask's live pairs at the published widths, launch by launch; channels a
kernel pads a key with are no work) over the time they took.
``_bdflash_forward`` is the forward launch, ``_bdflash_backward_dq`` and
``_bdflash_backward_dkdv`` the backward's two."""

import re

from .. import flops, flops_kimilinear

_KINDS = {"_bdflash_forward": "fwd", "_bdflash_backward_dq": "dq",
          "_bdflash_backward_dkdv": "dkv"}
_KERNEL = re.compile(r"^%?(" + "|".join(_KINDS) + r")(\.\d+)?$")


def read(ctx):
    if "linear_attn_config" not in ctx.spec.config:
        return None
    cfg, traffic = flops_kimilinear.sizes_of(ctx.spec), ctx.spec.traffic
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    least = taken = 0.0
    bounds = set()
    for name, (seconds, count) in ctx.trace["ops"].items():
        match = _KERNEL.match(name.partition(" = ")[0])
        if not match:
            continue
        kind = _KINDS[match.group(1)]
        t, bound = flops.roofline_seconds(
            flops_kimilinear.flash_call_flops(cfg, kind, b, s),
            flops_kimilinear.flash_call_bytes(cfg, kind, b, s), ctx.peaks())
        least += t * count
        taken += seconds
        bounds.add(bound)
    if not taken:
        return None
    print(f"flash roofline at 192 / 128: bound by "
          f"{'/'.join(sorted(bounds))}, least {least:.6g} s of "
          f"{taken:.6g} s taken")
    return 100.0 * least / taken
