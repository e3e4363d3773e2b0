"""The attention kernels' share of their roofline in a model of window
and full layers, one family a call: ``family="swa"`` the window
launches (``_swaflash_forward`` / ``_swaflash_backward_dq`` /
``_swaflash_backward_dkdv``), ``family="bd"`` the full layers'
(``_bdflash_*``).  Launch by launch: the least time for a launch of its
kind over the layers of that family (``flops_laguna.flash_call_flops``
by the mask's pairs, ``sum_i min(i + 1, window)`` a window head;
``flash_call_bytes``), over the time its events took.  ``kind``: one of
``fwd`` / ``dq`` / ``dkv`` alone, or all three together."""

import re

from .. import flops, flops_laguna

_KINDS = {"forward": "fwd", "backward_dq": "dq", "backward_dkdv": "dkv"}


def read(ctx, family, kind=None):
    if "sliding_window" not in ctx.spec.config:
        return None
    cfg, traffic = flops_laguna.sizes_of(ctx.spec), ctx.spec.traffic
    b, s = int(traffic["per_chip_batch"]), int(traffic["seq_len"])
    windowed = family == "swa"
    # the layers of this family share a head count and a mask
    layers = [(heads, flops_laguna.window_of(cfg, layer_type))
              for layer_type, _, heads in flops_laguna.layer_kinds(cfg)
              if (layer_type == flops_laguna.WINDOW_LAYER) == windowed]
    if not layers or len(set(layers)) != 1:
        return None
    heads, window = layers[0]
    kernel = re.compile(rf"^%?_{family}flash_({'|'.join(_KINDS)})(\.\d+)?$")
    least = taken = 0.0
    bounds = set()
    for name, (seconds, count) in ctx.trace["ops"].items():
        match = kernel.match(name.partition(" = ")[0])
        of = _KINDS[match.group(1)] if match else None
        if not match or kind not in (None, of):
            continue
        t, bound = flops.roofline_seconds(
            flops_laguna.flash_call_flops(cfg, of, b, s, heads, window),
            flops_laguna.flash_call_bytes(cfg, of, b, s, heads),
            ctx.peaks())
        least += t * count
        taken += seconds
        bounds.add(bound)
    if not taken:
        return None
    print(f"{family}flash {kind or 'all'} roofline at {heads} heads, window "
          f"{window}: bound by {'/'.join(sorted(bounds))}, least "
          f"{least:.6g} s of {taken:.6g} s taken")
    return 100.0 * least / taken
