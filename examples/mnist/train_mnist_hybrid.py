#!/usr/bin/env python
"""Hybrid data x tensor parallel MNIST training on one 2-D mesh.

Parity target: the reference's dual-parallelism pattern — a 2-D process
grid of data-parallel groups x model-parallel groups composed with
``CommunicatorBase.split`` (SURVEY.md section 2, "Hybrid DP x MP";
dual-parallel MNIST example).

TPU-native shape: ONE ``('mn_data', 'mn_model')`` mesh.  The batch shards
over the data axis; the MLP's hidden layer is a Megatron column/row pair
sharded over the model axis (one psum per MLP block); gradient
collectives for both the sharded and the replicated parameters are
generated by vma-checked shard_map autodiff — there is no hand-written
sync anywhere.  ``comm.dp_groups()`` / ``comm.tp_groups()`` expose the
reference's split()-style sub-communicators over the same chips.

Run (8 virtual CPU devices -> 4-way DP x 2-way TP):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/mnist/train_mnist_hybrid.py --cpu-mesh --epoch 2
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
from chainermn_tpu.parallel import (
    ColumnParallelDense,
    RowParallelDense,
    megatron_param_specs,
)
from chainermn_tpu.utils import get_mnist


class HybridMLP(nn.Module):
    """784 -> hidden (column-sharded) -> relu -> 10 (row-sharded)."""

    n_units: int = 1024
    model_axis: str = "mn_model"

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = ColumnParallelDense(self.n_units, axis_name=self.model_axis)(x)
        x = jax.nn.relu(x)
        x = RowParallelDense(10, axis_name=self.model_axis)(x)
        return x


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: MNIST hybrid DP x TP"
    )
    p.add_argument("--tp", type=int, default=2,
                   help="model-axis width (chips per TP group)")
    p.add_argument("--batchsize", type=int, default=512)
    p.add_argument("--epoch", type=int, default=2)
    p.add_argument("--unit", type=int, default=1024)
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
    comm = cmn.create_communicator("hybrid", devices=devices,
                                   tp_size=args.tp)
    chief = comm.process_index == 0
    if chief:
        print(f"hybrid mesh: {dict(comm.mesh.shape)}  "
              f"(DP {comm.dp_size} x TP {comm.tp_size})")
        # the split()-composed view of the same topology (reference
        # pattern): one DP sub-communicator per model coordinate
        groups = comm.dp_groups()
        print(f"split-based DP groups: "
              f"{ {m: g.size for m, g in groups.items()} }")

    train, _ = get_mnist(n_train=args.n_train, n_test=args.n_test)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)

    # the batch shards over DP shards only (each TP group sees the whole
    # local batch), so round to a multiple of dp_size
    dp_shards = comm.dp_size // comm.process_count or 1
    batch = max(args.batchsize // comm.process_count // dp_shards * dp_shards,
                dp_shards)
    train_it = SerialIterator(train, batch, shuffle=True, seed=1)

    model = HybridMLP(n_units=args.unit)

    # TP param shapes depend on the model-axis size, so init inside a
    # one-off shard_map over the mesh and keep the global sharded arrays.
    from jax.sharding import PartitionSpec as P

    x0 = jnp.zeros((comm.dp_size, 28, 28))
    abstract = jax.eval_shape(
        jax.shard_map(
            lambda x: model.init(jax.random.PRNGKey(0), x),
            mesh=comm.mesh, in_specs=P("mn_data"), out_specs=P(),
            check_vma=False,
        ),
        x0,
    )
    specs = megatron_param_specs(abstract, model_axis="mn_model")
    params = jax.jit(
        jax.shard_map(
            lambda x: model.init(jax.random.PRNGKey(0), x),
            mesh=comm.mesh, in_specs=P("mn_data"), out_specs=specs,
            check_vma=False,
        ),
    )(x0)

    opt = cmn.create_multi_node_optimizer(optax.sgd(args.lr), comm)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = cmn.build_train_step(
        comm, loss_fn, opt,
        data_axes=comm.data_axis_names, param_specs=specs,
    )
    params, opt_state = step.place(params, opt.init(params))

    it = 0
    for epoch in range(args.epoch):
        losses = []
        epoch_now = train_it.epoch
        while train_it.epoch == epoch_now:
            xs, ys = train_it.next()
            bx = step.place_batch((jnp.asarray(xs), jnp.asarray(ys)))
            params, opt_state, metrics = step(params, opt_state, bx)
            losses.append(float(metrics["loss"]))
            it += 1
        if chief:
            print(f"epoch {epoch + 1}  iter {it}  "
                  f"loss {np.mean(losses):.4f}")

    if chief:
        col = params["params"]["ColumnParallelDense_0"]["kernel"]
        shards = {s.data.shape for s in col.addressable_shards}
        print(f"final loss {np.mean(losses):.4f}; column kernel global "
              f"{col.shape} stored as per-chip blocks {shards}")
    return float(np.mean(losses))


if __name__ == "__main__":
    main()
