#!/usr/bin/env python
"""Practical-peak calibration: a chained bf16 matmul loop.

MFU numbers divide by the DATASHEET bf16 peak (197 TFLOP/s on v5e).
This measures what a pure MXU workload actually sustains on this chip
(k-loop timing, noise-proof), giving the denominator its error bar:
conv-stack "inefficiency" claims are only meaningful relative to what
ANY program can reach here.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.utils.benchmarking import min_positive, protocol_fields

K = int(os.environ.get("PEAK_K", "30"))


def main(n=4096, chain=8):
    a = jnp.asarray(np.random.RandomState(0).randn(n, n), jnp.bfloat16)
    b = jnp.asarray(np.random.RandomState(1).randn(n, n), jnp.bfloat16)

    @jax.jit
    def steps(a, b, k):
        def body(i, carry):
            a, b = carry
            for _ in range(chain):
                a = (a @ b) * jnp.bfloat16(1e-3)  # keep values bounded
            return a, b

        out, _ = lax.fori_loop(0, k, body, (a, b))
        # scalar result: the readback that closes the timing must ship
        # bytes, not the 32 MB matrix (the transfer would swamp dt)
        return jnp.sum(out.astype(jnp.float32))

    def readback(x):
        return float(np.asarray(x).ravel()[0])

    readback(steps(a, b, 2))

    def timed(k):
        t0 = time.perf_counter()
        out = steps(a, b, k)
        readback(out)
        return time.perf_counter() - t0

    flops_per_iter = chain * 2 * n ** 3
    # min-of-N protocol (bench-wide since round 6): N paired k/2k
    # measurements, report the min, disclose the spread
    dts = []
    for _ in range(2):
        t1, t2 = timed(K), timed(2 * K)
        dts.append((t2 - t1) / K)
    dt = min_positive(dts)
    print(json.dumps({
        "n": n, "chain": chain,
        "iter_ms": round(dt * 1e3, 2),
        "tflops_per_sec": round(flops_per_iter / dt / 1e12, 1),
        "frac_of_197tf": round(flops_per_iter / dt / 197e12, 4),
        "samples_ms": [round(d * 1e3, 2) for d in dts],
        **protocol_fields(dts),
    }), flush=True)


if __name__ == "__main__":
    main()
