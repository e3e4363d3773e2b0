#!/usr/bin/env python
"""Device time of each flash kernel, by compute tile, on the attached chip.

One call of ``_flash_forward`` + ``_flash_backward`` at the LM cells'
shape (``[b*h, s, d]`` = ``[48, 2048, 128]`` bf16, causal, default
blocks) is traced; the three ``pallas_call``s are found in the device
trace by their names and reported as the median device time of a call
and its share of the compute roofline (exact causal half, as
``cellbench/flops.py`` counts it).  Variants set the compute tile of the
forward and of the backward kernels (``whole`` = a tile as wide as the
block, i.e. none), so a row is one point of the sweep ``PERF.md``
(PR 28) records.  Chip only: it fails without a TPU.

Run:  python benchmarks/flash_kernel_bench.py [--seq 8192 --batch 1]
          [--variants shipped whole 512:512 256:256 512:128]
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
)

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.ops import pallas_attention as pa

PEAK_BF16 = {"TPU v5 lite": 197e12}
#: s x s x d matmuls a call runs (forward 2, dq 3, dk/dv 4)
MATMULS = {"_flash_forward": 2, "_flash_backward_dq": 3,
           "_flash_backward_dkdv": 4}


def kernel_us(fn, args, reps=10):
    """Median device microseconds of each flash kernel over ``reps``
    traced calls of ``fn`` (one warm call first)."""
    jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp(prefix="flash_kernel_bench_")
    with jax.profiler.trace(trace_dir):
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    times = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = e.name.lstrip("%").split(" = ")[0].split(".")[0]
                if name in MATMULS:
                    times.setdefault(name, []).append(e.duration_ns / 1e3)
    return {k: float(np.median(v)) for k, v in times.items()}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--variants", nargs="+",
                   default=["shipped", "whole", "512:512", "256:256"])
    a = p.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("flash_kernel_bench needs a TPU")
    peak = PEAK_BF16[device.device_kind]
    rng = np.random.RandomState(0)
    q, k, v, g = (
        jnp.asarray(rng.randn(a.batch, a.seq, a.heads, a.dim) * 0.5,
                    jnp.bfloat16) for _ in range(4))
    scale = a.dim ** -0.5
    print(json.dumps({
        "device": device.device_kind,
        "shape": [a.batch, a.seq, a.heads, a.dim],
        "bwd_1024_blocked_by_vmem": pa._bwd_compile_blocked(
            (q, k, v, q, jnp.zeros((a.batch * a.heads, a.seq)), g),
            True, scale, 1024, 1024),
        "census": pa.launch_census(a.seq, a.seq, a.dim),
    }), flush=True)

    def step(q, k, v, g):
        out, lse = pa._flash_forward(q, k, v, True, scale, None, None,
                                     False)
        return (out,) + pa._flash_backward(q, k, v, out, lse, g, True,
                                           scale, None, None, False)

    shipped = dict(pa._COMPUTE_TILE)
    for name in a.variants:
        if name == "shipped":
            tiles = shipped
        elif name == "whole":
            tiles = {"fwd": 1 << 30, "bwd": 1 << 30}
        else:
            fwd, bwd = (int(t) for t in name.split(":"))
            tiles = {"fwd": fwd, "bwd": bwd}
        pa._COMPUTE_TILE.update(tiles)  # read when the kernels trace
        jax.clear_caches()
        row = {"variant": name, "tiles": tiles}
        for kernel, us in kernel_us(jax.jit(step), (q, k, v, g)).items():
            least = (MATMULS[kernel] * 2.0 * a.batch * a.heads * a.seq ** 2
                     * a.dim / 2 / peak * 1e6)
            row[kernel] = {"us": round(us, 1),
                           "roofline_pct": round(100 * least / us, 2)}
        print(json.dumps(row), flush=True)
    pa._COMPUTE_TILE.update(shipped)


if __name__ == "__main__":
    main()
