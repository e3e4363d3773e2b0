#!/usr/bin/env python
"""ResNet-50 MFU ladder, noise-proof edition.

``resnet_mfu_hunt.py`` timed one dispatched step at a time, which
leaves per-dispatch host overhead in every sample.  Here k optimizer
steps run inside ONE jitted ``fori_loop`` — a single dispatch covers
seconds of device time, so the paired k/2k difference is dominated by
compute.  The loop bound is a traced argument: one executable
serves both k and 2k.

Variants are named on the command line (repeats allowed); each prints
one JSON line.  FLOPs are taken from the single-step program's XLA cost
analysis (the loop program's analysis does not multiply by the trip
count).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax

from chainermn_tpu.models import ResNet50
from chainermn_tpu.utils.benchmarking import protocol_fields
from chainermn_tpu.models.resnet import Bottleneck, ResNet

K = int(os.environ.get("HUNT_K", "40"))
PEAK = 197e12


def identity_norm(size, **kw):
    class _Id(nn.Module):
        @nn.compact
        def __call__(self, x, use_running_average=None):
            return x

    return _Id()


def _pinned_norm(size, kw, **pinned):
    """BatchNorm with this variant's dtype choice PINNED — the model's
    compute dtype offered through _bind_norm is discarded, so each rung
    measures exactly the configuration its name claims (the in-tree
    default_norm now resolves to bf16 for bf16 models)."""
    del size
    kw.pop("dtype", None)
    return nn.BatchNorm(
        use_running_average=kw.pop("use_running_average", None),
        momentum=0.9, epsilon=1e-5, **pinned, **kw,
    )


def fp32_norm(size, **kw):
    return _pinned_norm(size, kw, dtype=jnp.float32)


def bf16_norm(size, **kw):
    return _pinned_norm(size, kw, dtype=jnp.bfloat16)


def bf16_norm_bf16red(size, **kw):
    return _pinned_norm(size, kw, dtype=jnp.bfloat16,
                        force_float32_reductions=False)


def folded_norm(size, **kw):
    """MultiNodeBatchNormalization without a mesh axis: fp32 stats, the
    per-channel (inv*gamma, -mean*inv*gamma+beta) fold done in fp32,
    ONE bf16 multiply-add pass over the activation.  The full-bench A/B
    showed the sync-BN config (which uses this formulation) slightly
    beating flax BatchNorm — this rung isolates the formulation."""
    from chainermn_tpu.links.multi_node_batch_normalization import (
        MultiNodeBatchNormalization,
    )

    kw.pop("dtype", None)
    return MultiNodeBatchNormalization(
        size=size, axis_name=None, dtype=jnp.bfloat16, epsilon=1e-5,
        **kw,
    )


class S2DResNet(ResNet):
    """Stem consumes a 2x2 space-to-depth input (N, H/2, W/2, 12); the
    4x4 stride-1 conv with padding (2,1) is a reparametrization of the
    7x7 stride-2 conv (kernel zero-padded to 8x8, block-folded)."""

    @nn.compact
    def __call__(self, x):
        from chainermn_tpu.models.resnet import _bind_norm

        x = x.astype(self.dtype)
        x = nn.Conv(self.num_filters, (4, 4), strides=(1, 1),
                    padding=[(2, 1), (2, 1)], use_bias=False,
                    dtype=self.dtype, name="conv_init")(x)
        x = nn.relu(_bind_norm(self.norm, self.num_filters, self.train)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2 ** i, strides=strides,
                    norm=self.norm, dtype=self.dtype, train=self.train,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


def space_to_depth(x):
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // 2, w // 2, 4 * c)


def _readback(x):
    return float(np.asarray(x).ravel()[0])


def time_variant(name, model, batch, image=224, mutable_bn=True,
                 s2d=False):
    rng = jax.random.PRNGKey(0)
    shape = (1, image // 2, image // 2, 12) if s2d else (1, image, image, 3)
    variables = model.init(rng, jnp.zeros(shape, jnp.bfloat16))
    params = {"params": variables["params"],
              "batch_stats": variables.get("batch_stats", {})}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    x = np.random.RandomState(0).randn(batch, image, image, 3)
    x = jnp.asarray(x, jnp.bfloat16)
    if s2d:
        x = space_to_depth(x)
    y = jnp.asarray(
        np.random.RandomState(1).randint(0, 1000, (batch,)), jnp.int32
    )

    def loss_fn(p):
        kwargs = {"mutable": ["batch_stats"]} if mutable_bn else {}
        logits = model.apply(
            {"params": p["params"], "batch_stats": p["batch_stats"]},
            x, **kwargs,
        )
        if mutable_bn:
            logits, _ = logits
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    def one_step(p, o):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, loss

    @jax.jit
    def ksteps(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            return one_step(p, o)

        return lax.fori_loop(0, n, body, (p, o, jnp.float32(0)))

    # flops of ONE step from the unrolled single-step program
    flops = None
    try:
        single = jax.jit(one_step)
        an = single.lower(params, opt_state).compile().cost_analysis()
        if isinstance(an, (list, tuple)):
            an = an[0]
        flops = float(an.get("flops", 0.0)) or None
    except Exception:
        pass

    p, o, l = ksteps(params, opt_state, 2)  # compile + warm
    _readback(l)

    def timed(n):
        t0 = time.perf_counter()
        _, _, l = ksteps(params, opt_state, n)
        _readback(l)
        return time.perf_counter() - t0

    dts = []
    for _ in range(int(os.environ.get("HUNT_REPEATS", "2"))):
        t1 = timed(K)
        t2 = timed(2 * K)
        dts.append((t2 - t1) / K)
    dt = min(d for d in dts if d > 0) if any(d > 0 for d in dts) else dts[-1]
    out = {
        "variant": name,
        "batch": batch,
        "k": K,
        "step_time_ms": round(dt * 1e3, 2),
        "img_per_sec": round(batch / dt, 1),
        "samples": [round(d * 1e3, 2) for d in dts],
        **protocol_fields(dts),
    }
    if flops:
        out["tflops_per_step"] = round(flops / 1e12, 3)
        out["mfu"] = round(flops / dt / PEAK, 4)
    print(json.dumps(out), flush=True)


def _s2d(**kw):
    return S2DResNet(stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck,
                     train=True, **kw)


VARIANTS = {
    # "baseline" = the round-2 default (fp32 BN arithmetic), pinned
    # explicitly now that the in-tree default resolves to bf16 BN
    "baseline": lambda: time_variant(
        "baseline", ResNet50(train=True, norm=fp32_norm), 128),
    "default": lambda: time_variant("default", ResNet50(train=True), 128),
    "b256": lambda: time_variant(
        "b256", ResNet50(train=True, norm=fp32_norm), 256),
    "no_norm": lambda: time_variant(
        "no_norm", ResNet50(train=True, norm=identity_norm), 128,
        mutable_bn=False),
    "bn_bf16": lambda: time_variant(
        "bn_bf16", ResNet50(train=True, norm=bf16_norm), 128),
    "bn_bf16red": lambda: time_variant(
        "bn_bf16red", ResNet50(train=True, norm=bf16_norm_bf16red), 128),
    "folded": lambda: time_variant(
        "folded", ResNet50(train=True, norm=folded_norm), 128),
    "s2d_bn16": lambda: time_variant(
        "s2d_bn16", _s2d(norm=bf16_norm), 128, s2d=True),
    "s2d_bn16red": lambda: time_variant(
        "s2d_bn16red", _s2d(norm=bf16_norm_bf16red), 128, s2d=True),
    "s2d_only": lambda: time_variant("s2d_only", _s2d(), 128, s2d=True),
    "s2d_no_norm": lambda: time_variant(
        "s2d_no_norm", _s2d(norm=identity_norm), 128, mutable_bn=False,
        s2d=True),
}


def main():
    for name in (sys.argv[1:] or list(VARIANTS)):
        try:
            VARIANTS[name]()
        except Exception as e:
            print(json.dumps({"variant": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)


if __name__ == "__main__":
    main()
