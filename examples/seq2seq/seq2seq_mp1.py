#!/usr/bin/env python
"""Model-parallel seq2seq: encoder and decoder on different chips.

Parity target: the reference's ``examples/seq2seq/seq2seq_mp1.py`` — the
encoder runs on rank 0 and the decoder on rank 1, connected through
``MultiNodeChainList`` + ``create_multi_node_n_step_rnn`` so the LSTM
hidden state streams between ranks; both ranks see the batch via
``create_multi_node_iterator``.

TPU-native shape: the two stages' parameters live on *different chips*;
the ``(h, c)`` hand-off is an ICI device-to-device edge inserted by
``MultiNodeChainList``; the decoder additionally consumes the target
tokens from the external input (``rank_in=[0, None]``), the
single-controller equivalent of every rank getting the batch from the
multi-node iterator.

Run (any >=2-device setup; CPU mesh for testing):
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python examples/seq2seq/seq2seq_mp1.py --cpu-mesh --epoch 3
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
from chainermn_tpu.link import MultiNodeChainList
from chainermn_tpu.models.seq2seq import (
    Decoder, Encoder, seq2seq_loss, seq2seq_metrics, teacher_forcing,
)
from chainermn_tpu.utils import SyntheticTranslationDataset


class EncoderStage(nn.Module):
    """Rank-0 component: source embedding + LSTM; emits the (h, c) state —
    the activation edge that streams to the decoder's chip (reference:
    the encoder half wrapped by ``create_multi_node_n_step_rnn`` with
    ``rank_out=1``)."""

    n_vocab: int
    n_units: int
    n_layers: int = 2

    @nn.compact
    def __call__(self, batch):
        xs, _ = batch
        state, _ = Encoder(self.n_vocab, self.n_units, self.n_layers,
                           name="encoder")(xs)
        return state


class DecoderStage(nn.Module):
    """Rank-1 component: consumes the streamed encoder state plus the
    target tokens from the external batch (``rank_in=[0, None]``)."""

    n_vocab: int
    n_units: int
    n_layers: int = 2

    @nn.compact
    def __call__(self, state, batch):
        _, ys_in = batch
        _, logits = Decoder(self.n_vocab, self.n_units, self.n_layers,
                            name="decoder")(state, ys_in)
        return logits


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: model-parallel seq2seq"
    )
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--unit", type=int, default=128)
    p.add_argument("--layer", type=int, default=2)
    p.add_argument("--vocab", type=int, default=32)
    p.add_argument("--max-len", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--n-train", type=int, default=2048)
    p.add_argument("--n-test", type=int, default=256)
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
        print("note: model-parallel example wants >=2 devices; running "
              "both stages on one device", file=sys.stderr)
    comm = cmn.create_communicator("naive", devices=devices[:2])
    print(f"model-parallel over {comm.size} device(s): {comm.devices}")

    train = SyntheticTranslationDataset(
        args.n_train, vocab=args.vocab, max_len=args.max_len, seed=0
    )
    test = SyntheticTranslationDataset(
        args.n_test, vocab=args.vocab, max_len=args.max_len, seed=1
    )

    # Model-parallel ranks all see the same batches (reference:
    # create_multi_node_iterator) — the dataset is NOT scattered.
    model = MultiNodeChainList(comm)
    model.add_link(
        EncoderStage(args.vocab, args.unit, args.layer),
        rank_in=None, rank_out=1, rank=0,
    )
    model.add_link(
        DecoderStage(args.vocab, args.unit, args.layer),
        rank_in=[0, None], rank_out=None, rank=1,
    )

    def batch_of(ds, idx):
        xs = jnp.asarray(np.stack([ds[i][0] for i in idx]))
        ys = jnp.asarray(np.stack([ds[i][1] for i in idx]))
        ys_in, ys_out = teacher_forcing(ys)
        return [xs, ys_in], ys_out

    x0, _ = batch_of(train, range(2))
    params = model.init(jax.random.PRNGKey(0), x0)

    opt = model.optimizer(optax.adam(args.lr))
    opt_state = opt.init(params)
    # Per-stage eager dispatch: each stage's params live on their OWN
    # chip (genuinely partitioned model memory), which plain jit cannot
    # take as one argument set — whole-step compilation of a chain
    # needs a mesh-based layout (that performance tier is
    # parallel.build_pipeline_train_step; see docs/model_parallel.md).
    step = model.value_and_grad(seq2seq_loss)

    rng = np.random.RandomState(1)
    n_iter = max(args.n_train // args.batchsize, 1)
    m = {}
    for epoch in range(args.epoch):
        order = rng.permutation(args.n_train)
        losses = []
        for it in range(n_iter):
            idx = order[it * args.batchsize:(it + 1) * args.batchsize]
            if len(idx) < args.batchsize:
                break  # drop-last keeps the traced shapes stable
            x, ys_out = batch_of(train, idx)
            loss, grads = step(params, x, ys_out)
            params, opt_state = opt.update(grads, opt_state, params)
            losses.append(float(loss))
        # Eval: forward on the test set.
        x, ys_out = batch_of(test, range(len(test)))
        logits = model(params, x)
        m = {k: float(v) for k, v in seq2seq_metrics(logits, ys_out).items()}
        print(f"epoch {epoch + 1}  train/loss {np.mean(losses):.4f}  "
              f"val/loss {m['loss']:.4f}  val/perp {m['perp']:.3f}  "
              f"val/acc {m['accuracy']:.3f}")
    return m


if __name__ == "__main__":
    main()
