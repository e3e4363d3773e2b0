#!/usr/bin/env python
"""Data-parallel MNIST training.

Parity target: the reference's ``examples/mnist/train_mnist.py`` (the
canonical ChainerMN data-parallel script: create_communicator ->
scatter_dataset -> multi-node optimizer -> Trainer with rank-0 reporting).

TPU-native shape: one controller drives all chips; the train step is a
single jitted SPMD program over the communicator's mesh; the "per-rank
shard" is the leading-axis shard of a global batch.

Run (defaults work anywhere, incl. CPU):
    python examples/mnist/train_mnist.py --communicator tpu --epoch 2
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

import chainermn_tpu as cmn
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.iterators.serial_iterator import EpochIterator
from chainermn_tpu.models import MLP
from chainermn_tpu.training import Trainer, Updater
from chainermn_tpu.training import extensions as T
from chainermn_tpu.extensions.evaluator import Evaluator
from chainermn_tpu.utils import get_mnist


def main(argv=None):
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: MNIST")
    p.add_argument("--communicator", default="tpu")
    p.add_argument("--batchsize", type=int, default=512,
                   help="global batch size (split over chips)")
    p.add_argument("--epoch", type=int, default=2)
    p.add_argument("--unit", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--n-train", type=int, default=8192)
    p.add_argument("--n-test", type=int, default=2048)
    p.add_argument("--cpu-mesh", action="store_true",
                   help="run on a virtual CPU device mesh (testing)")
    p.add_argument("--checkpoint", default=None,
                   help="enable checkpoint/resume under this name")
    args = p.parse_args(argv)

    cmn.global_except_hook.add_hook()

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
        devices = jax.devices("cpu")
        if len(devices) == 1:
            print(
                "note: one CPU device only; for an 8-device virtual mesh "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "before launching", file=sys.stderr,
            )
    else:
        devices = jax.devices()
        from chainermn_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    comm = cmn.create_communicator(args.communicator, devices=devices)
    chief = comm.process_index == 0
    if chief:
        print(f"communicator: {args.communicator}  {comm!r}")

    # Data: each process holds its shard (metadata-only scatter); the
    # per-process batch is this process's slice of the global batch.
    train, test = get_mnist(n_train=args.n_train, n_test=args.n_test)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(test, comm, shuffle=False, seed=0)

    # Per-process batch, rounded down to a multiple of the chip count so
    # every mesh size divides it (floored at one row per chip).
    batch_per_process = max(
        args.batchsize // comm.process_count // comm.size * comm.size,
        comm.size,
    )
    train_it = SerialIterator(train, batch_per_process, shuffle=True, seed=1)

    model = MLP(n_units=args.unit)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
    params = comm.bcast_data(params)  # initial weight sync (parity)

    opt = cmn.create_multi_node_optimizer(optax.sgd(args.lr), comm)
    opt_state = jax.device_put(
        opt.init(params), None
    )

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = cmn.build_train_step(comm, loss_fn, opt)
    params, opt_state = step.place(params, opt_state)

    updater = Updater(train_it, step, params, opt_state)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"))

    def eval_metric(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        acc = (jnp.argmax(logits, -1) == y).mean()
        return {"loss": loss, "accuracy": acc}

    evaluator = Evaluator(
        lambda: EpochIterator(test, batch_per_process, pad_to=comm.size),
        eval_metric, comm,
    )
    trainer.extend(cmn.create_multi_node_evaluator(evaluator, comm))

    log = T.LogReport(comm=comm)
    trainer.extend(T.Throughput(args.batchsize, comm=comm),
                   trigger=(1, "iteration"))
    trainer.extend(log, trigger=(1, "epoch"))
    trainer.extend(
        T.PrintReport(
            ["epoch", "iteration", "loss", "val/loss", "val/accuracy",
             "samples_per_sec"],
            log, comm=comm,
        ),
        trigger=(1, "epoch"),
    )
    if args.checkpoint:
        ckpt = cmn.create_multi_node_checkpointer(args.checkpoint, comm)
        trainer.extend(ckpt, trigger=(1, "epoch"))
        resumed = ckpt.restore_trainer(trainer)
        if resumed is not None and chief:
            print(f"resumed from iteration {resumed}")

    trainer.run()

    final = log.log[-1] if log.log else {}
    if chief:
        print("final:", {k: round(v, 4) for k, v in final.items()
                         if isinstance(v, float)})
    return final


if __name__ == "__main__":
    main()
