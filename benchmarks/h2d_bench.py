#!/usr/bin/env python
"""Host->device link measurement: bandwidth + RTT, and the implied
input-pipeline ceiling.

The `resnet50_native_input` bench config trails the synthetic-batch
config, and the gap is the input path.  This script measures the
host-to-device link directly:

  rtt_ms          scalar device_put -> readback round trips
  h2d_MBps        device_put of batch-sized arrays (bf16
                  128x224x224x3 = 36.75 MiB), each completed by a
                  jitted scalar readback (a full-array readback
                  would measure D2H too); paired k/2k timing
                  cancels the constant per-transfer round trip
  depth=2         two puts in flight (async dispatch) — what
                  prefetch_to_device actually achieves
  implied ceilings in images/sec for the ResNet batch shape

If the measured ceiling sits near the native-input bench number, the
config is link-bound as claimed; if it is far above, the loader or the
overlap scheduling is leaving throughput on the table.

Usage: python benchmarks/h2d_bench.py [--batch 128] [--image 224] [--k 12]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _scalar_probe():
    """Device-side scalar extraction: completion proof costing ~2 bytes
    of D2H instead of the whole buffer."""
    return jax.jit(lambda a: a.reshape(-1)[0].astype(jnp.float32))


def measure_rtt(dev, n=30):
    """Tiny-payload round trip: device_put + host readback."""
    x = np.float32(1.0)
    for _ in range(3):
        float(np.asarray(jax.device_put(x, dev)))
    t0 = time.perf_counter()
    for _ in range(n):
        float(np.asarray(jax.device_put(x, dev)))
    return (time.perf_counter() - t0) / n


def _put_all(dev, probe, arrs, depth):
    """Transfer every array, keeping ``depth`` in flight, each completed
    by a scalar readback; returns elapsed seconds."""
    in_flight = []
    t0 = time.perf_counter()
    for a in arrs:
        in_flight.append(jax.device_put(a, dev))
        while len(in_flight) >= depth:
            float(np.asarray(probe(in_flight.pop(0))))
    for x in in_flight:
        float(np.asarray(probe(x)))
    return time.perf_counter() - t0


def measure_h2d(dev, probe, arrs, depth):
    """Paired k/2k: (t_2k - t_k)/k per-transfer cost with constants
    cancelled; returns bytes/sec."""
    _put_all(dev, probe, arrs[:2], depth)  # warm path + compile probe
    t1 = _put_all(dev, probe, arrs, depth)
    t2 = _put_all(dev, probe, arrs + arrs, depth)
    per = (t2 - t1) / len(arrs)
    if per <= 0:
        per = t2 / (2 * len(arrs))
    return arrs[0].nbytes / per


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--k", type=int, default=12)
    args = ap.parse_args()

    dev = jax.devices()[0]
    import ml_dtypes

    rng = np.random.RandomState(0)
    # k distinct buffers so no caching layer can elide transfers.
    # TWO entropy tiers, in case the transport is entropy-sensitive;
    # the relevant ceiling for the input pipeline is the image-like
    # one: bf16 noise (incompressible) vs normalized-uint8 images (each
    # channel takes one of 256 discrete bf16 values, like the loader's
    # real output).
    arrs = [
        rng.randn(args.batch, args.image, args.image, 3)
        .astype(ml_dtypes.bfloat16)
        for _ in range(args.k)
    ]
    u8 = rng.randint(
        0, 256, size=(args.k, args.batch, args.image, args.image, 3)
    ).astype(np.float32)
    img_arrs = [
        ((u8[i] - 116.0) / 58.0).astype(ml_dtypes.bfloat16)
        for i in range(args.k)
    ]
    # The uint8 WIRE payload (NativeImageLoader wire="uint8"): raw crop
    # bytes — half of bf16's size AND maximally transport-compressible
    # (256 discrete byte values vs bf16's scattered bit patterns).
    # This row states the input ceiling the uint8-wire bench config is
    # entitled to claim.
    u8_arrs = [u8[i].astype(np.uint8) for i in range(args.k)]
    batch_bytes = arrs[0].nbytes
    u8_bytes = u8_arrs[0].nbytes
    probe = _scalar_probe()

    rtt = measure_rtt(dev)
    bw1 = measure_h2d(dev, probe, arrs, depth=1)
    bw2 = measure_h2d(dev, probe, arrs, depth=2)
    bw_img = measure_h2d(dev, probe, img_arrs, depth=2)
    bw_u8 = measure_h2d(dev, probe, u8_arrs, depth=2)

    def ceiling(bw, nbytes=None):
        # images/sec if the link were the only cost: one batch of bytes
        # per step (the per-dispatch RTT is cancelled by pairing, but a
        # real training loop pays it once per step, so add it back)
        t_batch = (nbytes or batch_bytes) / bw + rtt
        return args.batch / t_batch

    print(json.dumps({
        # each bandwidth figure is ONE paired k/2k transfer measurement
        # (constants cancelled, per-figure); no cross-repeat spread
        "n_measurements": 1,
        "device": str(getattr(dev, "device_kind", dev)),
        "batch_bytes_MiB": round(batch_bytes / 2**20, 2),
        "u8_batch_bytes_MiB": round(u8_bytes / 2**20, 2),
        "rtt_ms": round(rtt * 1e3, 3),
        "h2d_MBps_serial": round(bw1 / 1e6, 1),
        "h2d_MBps_depth2": round(bw2 / 1e6, 1),
        "h2d_MBps_imagelike_depth2": round(bw_img / 1e6, 1),
        "h2d_MBps_uint8_depth2": round(bw_u8 / 1e6, 1),
        "implied_ceiling_img_per_sec_serial": round(ceiling(bw1), 1),
        "implied_ceiling_img_per_sec_depth2": round(ceiling(bw2), 1),
        "implied_ceiling_img_per_sec_imagelike": round(
            ceiling(bw_img), 1
        ),
        "implied_ceiling_img_per_sec_uint8": round(
            ceiling(bw_u8, u8_bytes), 1
        ),
        "k": args.k,
    }), flush=True)


if __name__ == "__main__":
    main()
