"""ResNet family (ResNet-18/50/101), TPU-first.

Parity target: ``examples/imagenet/models/resnet50.py`` in the reference —
the headline data-parallel workload (BASELINE.md: images/sec/chip and
scaling efficiency are measured on ResNet-50).

TPU-native design choices:
* NHWC layout (XLA:TPU's native conv layout; NCHW would transpose on every
  conv) and bfloat16 compute with fp32 parameters; BatchNorm normalizes in
  the compute dtype (the round-3 MFU ablation's biggest lever: fp32 BN
  arithmetic cost 23% of the step) while statistics accumulate in fp32.
* A ``norm`` factory field so ``create_mnbn_model`` can swap BatchNorm for
  :class:`~chainermn_tpu.links.MultiNodeBatchNormalization` without
  touching model code.
* All convs lower to MXU-tiled ``lax.conv_general_dilated`` via flax; the
  stem + residual adds fuse into the surrounding convs under XLA.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn


def default_norm(size: int, **kw):
    """Plain BatchNorm factory.  ``size`` is the channel count (kept
    positional for MNBN-factory compatibility).

    ``dtype`` sets the *normalization arithmetic* dtype and defaults to
    fp32; models pass their compute dtype through ``_bind_norm``, so
    bf16 models normalize in bf16 (the ResNet-50 cell runs this way:
    ``PERF.md`` section 2 has what it costs against float32), while
    batch statistics still ACCUMULATE in fp32 (flax promotes
    half-precision reductions unless force_float32_reductions is
    disabled), so mean/var stay accurate over millions of elements."""
    del size
    return nn.BatchNorm(
        use_running_average=kw.pop("use_running_average", None),
        momentum=0.9, epsilon=1e-5,
        dtype=kw.pop("dtype", jnp.float32), **kw
    )



def _bind_norm(norm_factory: Callable, size: int, train: bool,
               dtype=None, **kw):
    """Instantiate a norm module and bind train/eval mode at call time
    (both flax BatchNorm and MultiNodeBatchNormalization accept
    ``use_running_average`` in ``__call__``).

    ``dtype`` is the model's compute dtype, offered to the factory as a
    *default* — only when its signature can accept it (a ``dtype``
    parameter or ``**kwargs``), and never overriding a dtype the factory
    or its creator pinned explicitly.  Factories written to the plain
    ``norm(size) -> Module`` contract keep working unchanged."""
    import inspect

    if dtype is not None and "dtype" not in kw:
        try:
            params = inspect.signature(norm_factory).parameters.values()
            accepts = any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                or p.name == "dtype"
                for p in params
            )
        except (TypeError, ValueError):
            accepts = False
        if accepts:
            kw["dtype"] = dtype
    m = norm_factory(size, **kw)
    try:
        accepts = "use_running_average" in inspect.signature(
            type(m).__call__
        ).parameters
    except (TypeError, ValueError):
        accepts = False
    if accepts:
        return lambda x: m(x, use_running_average=not train)
    return m


class Bottleneck(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    norm: Callable = default_norm
    dtype: Any = jnp.bfloat16
    train: bool = True

    @nn.compact
    def __call__(self, x):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        needs_proj = (
            x.shape[-1] != self.features * 4 or self.strides != (1, 1)
        )
        residual = x
        y = conv(self.features, (1, 1))(x)
        y = _bind_norm(self.norm, self.features, self.train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = conv(self.features, (3, 3), strides=self.strides, padding=[(1, 1), (1, 1)])(y)
        y = _bind_norm(self.norm, self.features, self.train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = conv(self.features * 4, (1, 1))(y)
        y = _bind_norm(self.norm, self.features * 4, self.train,
                       dtype=self.dtype,
                       scale_init=nn.initializers.zeros)(y)
        if needs_proj:
            residual = conv(self.features * 4, (1, 1), strides=self.strides)(x)
            residual = _bind_norm(self.norm, self.features * 4, self.train, dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class BasicBlock(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    norm: Callable = default_norm
    dtype: Any = jnp.bfloat16
    train: bool = True

    @nn.compact
    def __call__(self, x):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.features, (3, 3), strides=self.strides, padding=[(1, 1), (1, 1)])(x)
        y = _bind_norm(self.norm, self.features, self.train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = conv(self.features, (3, 3), padding=[(1, 1), (1, 1)])(y)
        y = _bind_norm(self.norm, self.features, self.train,
                       dtype=self.dtype,
                       scale_init=nn.initializers.zeros)(y)
        if x.shape[-1] != self.features or self.strides != (1, 1):
            residual = conv(self.features, (1, 1), strides=self.strides)(x)
            residual = _bind_norm(self.norm, self.features, self.train, dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: type = Bottleneck
    num_classes: int = 1000
    num_filters: int = 64
    norm: Callable = default_norm
    dtype: Any = jnp.bfloat16
    train: bool = True

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = nn.Conv(self.num_filters, (7, 7), strides=(2, 2),
                    padding=[(3, 3), (3, 3)], use_bias=False,
                    dtype=self.dtype, name="conv_init")(x)
        x = nn.relu(_bind_norm(self.norm, self.num_filters, self.train, dtype=self.dtype)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2**i, strides=strides, norm=self.norm,
                    dtype=self.dtype, train=self.train,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


def ResNet18(**kw) -> ResNet:
    return ResNet(stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock, **kw)


def ResNet50(**kw) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck, **kw)


def ResNet101(**kw) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck, **kw)
