#!/usr/bin/env python
"""Model-parallel MNIST: an MLP split across two chips.

Parity target: the reference's ``examples/mnist/train_mnist_model_parallel.py``
— ``MLP0`` (input half) on rank 0 and ``MLP1`` (output half) on rank 1,
composed with ``MultiNodeChainList``; activations cross the rank boundary
via ``functions.send``/``recv``.

TPU-native shape: one controller owns both stages; each stage's parameters
and optimizer state live on their own chip, the activation edge is an ICI
device-to-device copy, and backward chains the per-stage VJPs in reverse
(chainermn_tpu/link.py).

Run (any 2+ device setup; CPU works):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist/train_mnist_model_parallel.py --cpu-mesh
"""

import argparse
import os
import sys

try:  # installed package (pip install -e .)
    import chainermn_tpu  # noqa: F401
except ImportError:  # source checkout without installation
    sys.path.insert(
        0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    )

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

import chainermn_tpu as cmn
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.utils import get_mnist


class MLP0(nn.Module):
    """First half: runs on chip 0 (reference example's MLP0 on rank 0)."""

    n_units: int = 1000

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.n_units)(x))
        return nn.relu(nn.Dense(self.n_units)(x))


class MLP1(nn.Module):
    """Second half: runs on chip 1 and produces the logits."""

    n_out: int = 10

    @nn.compact
    def __call__(self, h):
        return nn.Dense(self.n_out)(h)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: model-parallel MNIST")
    p.add_argument("--batchsize", type=int, default=256)
    p.add_argument("--epoch", type=int, default=2)
    p.add_argument("--unit", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--n-train", type=int, default=8192)
    p.add_argument("--n-test", type=int, default=2048)
    p.add_argument("--cpu-mesh", action="store_true")
    args = p.parse_args(argv)

    cmn.global_except_hook.add_hook()

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
        devices = jax.devices("cpu")
    else:
        devices = jax.devices()
        from chainermn_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    if len(devices) < 2:
        print("model parallelism needs >= 2 devices; running both stages "
              "on one device", file=sys.stderr)
    comm = cmn.create_communicator("tpu", devices=devices[:2])

    train, test = get_mnist(n_train=args.n_train, n_test=args.n_test)
    # Model parallel: every "rank" sees the same batch (reference pairs
    # this example with create_multi_node_iterator); a single controller
    # already has exactly one batch stream, so a plain iterator suffices.
    train_it = SerialIterator(train, args.batchsize, shuffle=True, seed=1)

    model = cmn.MultiNodeChainList(comm)
    model.add_link(MLP0(args.unit), rank_in=None, rank_out=1)
    model.add_link(MLP1(10), rank_in=0, rank_out=None)

    x0, _ = train[0]
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(x0)[None])
    opt = model.optimizer(optax.sgd(args.lr))
    opt_state = opt.init(params)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = model.value_and_grad(loss_fn)

    it_count = 0
    for epoch in range(args.epoch):
        epoch_loss, n_batches = 0.0, 0
        while True:
            xs, ys = next(train_it)
            loss, grads = step(params, jnp.asarray(xs), jnp.asarray(ys))
            params, opt_state = opt.update(grads, opt_state, params)
            epoch_loss += float(loss)
            n_batches += 1
            it_count += 1
            if train_it.epoch > epoch:
                break
        # Eval: forward-only through both chips.
        xs = jnp.asarray(np.stack([t[0] for t in test]))
        ys = np.asarray([t[1] for t in test])
        logits = np.asarray(model(params, xs))
        acc = float((logits.argmax(-1) == ys).mean())
        print(f"epoch {epoch + 1}  iter {it_count}  "
              f"loss {epoch_loss / max(n_batches, 1):.4f}  "
              f"val/accuracy {acc:.4f}")

    return acc


if __name__ == "__main__":
    main()
