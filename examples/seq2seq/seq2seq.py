#!/usr/bin/env python
"""Data-parallel seq2seq training.

Parity target: the reference's ``examples/seq2seq/seq2seq.py`` (WMT En-Fr
encoder-decoder, data-parallel over ranks: scatter_dataset + multi-node
optimizer + multi-node evaluator reporting loss/perplexity).

TPU-native shape: static padded sequences, one jitted SPMD train step over
the communicator mesh; data is a synthetic translation corpus in this
zero-egress environment (see SyntheticTranslationDataset) — pass
``--vocab/--max-len`` to scale.

Run:
    python examples/seq2seq/seq2seq.py --communicator tpu --epoch 3
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
from chainermn_tpu.models.seq2seq import (
    Seq2Seq, seq2seq_loss, seq2seq_metrics, teacher_forcing, translate,
)
from chainermn_tpu.training import Trainer, Updater
from chainermn_tpu.training import extensions as T
from chainermn_tpu.extensions.evaluator import Evaluator
from chainermn_tpu.utils import SyntheticTranslationDataset


def main(argv=None):
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: seq2seq")
    p.add_argument("--communicator", default="tpu")
    p.add_argument("--batchsize", type=int, default=256,
                   help="global batch size (split over chips)")
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--unit", type=int, default=128)
    p.add_argument("--layer", type=int, default=2)
    p.add_argument("--vocab", type=int, default=32)
    p.add_argument("--max-len", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--n-train", type=int, default=4096)
    p.add_argument("--n-test", type=int, default=512)
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
    comm = cmn.create_communicator(args.communicator, devices=devices)
    chief = comm.process_index == 0
    if chief:
        print(f"communicator: {args.communicator}  {comm!r}")

    train = SyntheticTranslationDataset(
        args.n_train, vocab=args.vocab, max_len=args.max_len, seed=0
    )
    test = SyntheticTranslationDataset(
        args.n_test, vocab=args.vocab, max_len=args.max_len, seed=1
    )
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(test, comm, shuffle=False, seed=0)

    batch_per_process = max(
        args.batchsize // comm.process_count // comm.size * comm.size,
        comm.size,
    )
    train_it = SerialIterator(train, batch_per_process, shuffle=True, seed=1)

    model = Seq2Seq(n_source_vocab=args.vocab, n_target_vocab=args.vocab,
                    n_units=args.unit, n_layers=args.layer)
    xs0 = jnp.zeros((2, args.max_len), jnp.int32)
    ys0 = jnp.zeros((2, args.max_len + 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), xs0, ys0)
    params = comm.bcast_data(params)

    opt = cmn.create_multi_node_optimizer(optax.adam(args.lr), comm)

    def loss_fn(params, batch):
        xs, ys = batch
        ys_in, ys_out = teacher_forcing(ys)
        logits = model.apply(params, xs, ys_in)
        return seq2seq_loss(logits, ys_out)

    step = cmn.build_train_step(comm, loss_fn, opt)
    opt_state = opt.init(params)
    params, opt_state = step.place(params, opt_state)

    updater = Updater(train_it, step, params, opt_state)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"))

    def eval_metric(params, batch):
        xs, ys = batch
        ys_in, ys_out = teacher_forcing(ys)
        logits = model.apply(params, xs, ys_in)
        return seq2seq_metrics(logits, ys_out)

    evaluator = Evaluator(
        lambda: EpochIterator(test, batch_per_process, pad_to=comm.size),
        eval_metric, comm,
    )
    trainer.extend(cmn.create_multi_node_evaluator(evaluator, comm))

    log = T.LogReport(comm=comm)
    trainer.extend(log, trigger=(1, "epoch"))
    trainer.extend(
        T.PrintReport(
            ["epoch", "iteration", "loss", "val/loss", "val/perp",
             "val/accuracy"],
            log, comm=comm,
        ),
        trigger=(1, "epoch"),
    )
    trainer.run()

    # Qualitative check, reference-style: greedy-translate a few sources.
    params = updater.params
    if chief:
        xs = jnp.asarray(np.stack([test[i][0] for i in range(4)]))
        ys = translate(model, params, xs, max_length=args.max_len + 1)
        for s, t in zip(np.asarray(xs), ys):
            print("src:", s[s != 0].tolist(), "-> hyp:", t[t != 0].tolist())

    final = log.log[-1] if log.log else {}
    if chief:
        print("final:", {k: round(v, 4) for k, v in final.items()
                         if isinstance(v, float)})
    return final


if __name__ == "__main__":
    main()
