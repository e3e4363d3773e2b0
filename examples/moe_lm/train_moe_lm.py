#!/usr/bin/env python
"""Composed-parallelism MoE language model training.

The capstone example: every parallelism family the framework offers in
ONE compiled train step on a ``mesh`` communicator's
``(mn_data, mn_seq, mn_model)`` mesh —

* data parallelism over ``mn_data`` (batch rows + gradient reduction),
* sequence parallelism over ``mn_seq`` (ring attention; the loss's
  next-token targets cross shard boundaries via ppermute),
* tensor parallelism over ``mn_model`` (Megatron column/row attention
  and MLP sharding),
* expert parallelism over ``mn_model`` (top-2 routed MoE layers with one
  all_to_all each way).

The reference's parallelism ceiling was DP plus hand-built model
parallelism over its collective functions (SURVEY.md section 2); this is
the composition those primitives point at.

Run on a virtual 8-chip mesh (2 data x 2 seq x 2 model):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/moe_lm/train_moe_lm.py --cpu-mesh --sp 2 --tp 2

On real hardware drop ``--cpu-mesh`` and size ``--sp/--tp`` to the slice.
"""

import argparse
import os
import sys
import time

try:  # installed package (pip install -e .)
    import chainermn_tpu  # noqa: F401
except ImportError:  # source checkout without installation
    sys.path.insert(
        0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    )


def synthetic_corpus(n_seqs, seq_len, vocab, seed=0):
    """Order-1 Markov token streams — structure a small LM can learn, so
    the loss falls well below log(vocab) within a few hundred steps."""
    import numpy as np

    rng = np.random.RandomState(seed)
    # sparse transition table: each token has 4 plausible successors
    succ = rng.randint(1, vocab, size=(vocab, 4))
    toks = np.zeros((n_seqs, seq_len), np.int32)
    toks[:, 0] = rng.randint(1, vocab, size=n_seqs)
    choice = rng.randint(0, 4, size=(n_seqs, seq_len))
    for t in range(1, seq_len):
        toks[:, t] = succ[toks[:, t - 1], choice[:, t]]
    return toks


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: composed-parallelism MoE LM"
    )
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel width (mn_seq axis)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor/expert-parallel width (mn_model axis)")
    p.add_argument("--batchsize", type=int, default=None,
                   help="global batch rows (default: 2 per data shard)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-experts", type=int, default=4)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--aux-coef", type=float, default=1e-2)
    p.add_argument("--report-every", type=int, default=20)
    p.add_argument("--generate", type=int, default=0,
                   help="tokens to sample after training via the dense "
                        "single-device twin (0 disables)")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="shard the embedding table + tied head over the "
                        "model axis (Megatron vocab parallelism)")
    p.add_argument("--native-loader", action="store_true",
                   help="assemble token batches with the C++ worker-"
                        "thread loader (GIL-free, deterministic)")
    p.add_argument("--cpu-mesh", action="store_true",
                   help="run on a virtual CPU device mesh (testing)")
    args = p.parse_args(argv)

    import chainermn_tpu as cmn

    cmn.global_except_hook.add_hook()

    import jax

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
        devices = jax.devices("cpu")
    else:
        devices = jax.devices()
        from chainermn_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        moe_lm_loss,
        moe_param_specs,
    )
    from chainermn_tpu.parallel import sharded_init

    comm = cmn.create_communicator(
        "mesh", devices=devices, sp_size=args.sp, tp_size=args.tp
    )
    chief = comm.process_index == 0
    if chief:
        print(f"mesh: dp={comm.dp_size} x sp={comm.sp_size} x "
              f"tp={comm.tp_size}  {comm!r}")

    batch = args.batchsize or 2 * comm.dp_size
    model = MoeTransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, n_experts=args.n_experts, moe_every=2,
        k=2, capacity_factor=1.25, max_len=args.seq_len,
        seq_axis="mn_seq", tp_axis="mn_model", expert_axis="mn_model",
        vocab_parallel=args.vocab_parallel,
        aux_stat_axes=("mn_data", "mn_seq", "mn_model"),
    )

    corpus = synthetic_corpus(
        max(batch * 8, 64), args.seq_len, args.vocab, seed=0
    )
    sample = jnp.asarray(corpus[:batch])
    params, specs = sharded_init(
        lambda t: model.init(jax.random.PRNGKey(0), t),
        comm.mesh, (P("mn_data", "mn_seq"),), moe_param_specs, sample,
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    if chief:
        print(f"params: {n_params / 1e6:.2f} M  "
              f"(expert blocks sharded over mn_model)")

    opt = cmn.create_multi_node_optimizer(
        optax.adamw(args.lr, weight_decay=0.01), comm
    )

    def loss_fn(p, b):
        return moe_lm_loss(
            model.apply(p, b), b, seq_axis="mn_seq",
            model_axis="mn_model", aux_coef=args.aux_coef,
            vocab_parallel=args.vocab_parallel,
        )

    step = cmn.build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
    )
    params, opt_state = step.place(params, opt.init(params))

    loader = None
    if args.native_loader:
        from chainermn_tpu.utils.native_loader import NativeTokenLoader

        loader = NativeTokenLoader(
            corpus.reshape(-1), batch, args.seq_len, n_threads=4, seed=1
        )
        if chief:
            print("input: native C++ token loader "
                  f"({loader.batches_per_epoch} batches/epoch)")

    rng = np.random.RandomState(1)
    t0, tokens_done, last_loss = time.perf_counter(), 0, float("nan")
    for it in range(1, args.steps + 1):
        if loader is not None:
            # __next__ copies out of the ring slot before releasing it —
            # required here because place_batch's device transfer is
            # async and must not race a worker refilling the slot
            toks = step.place_batch(jnp.asarray(next(loader)))
        else:
            rows = rng.randint(0, corpus.shape[0], size=batch)
            toks = step.place_batch(jnp.asarray(corpus[rows]))
        params, opt_state, metrics = step(params, opt_state, toks)
        tokens_done += batch * args.seq_len
        if it % args.report_every == 0 or it == args.steps:
            last_loss = float(metrics["loss"])  # forces completion
            dt = time.perf_counter() - t0
            if chief:
                print(f"step {it:5d}  loss {last_loss:.4f}  "
                      f"{tokens_done / dt:,.0f} tok/s")
            t0, tokens_done = time.perf_counter(), 0
    if loader is not None:
        loader.close()
    if chief:
        print(f"final: loss={last_loss:.4f} "
              f"(uniform would be {np.log(args.vocab):.3f}; the Markov "
              "corpus floor is log 4 = 1.386)")

    if args.generate > 0:
        # Sample from the SAME sharded parameter tree: sequence
        # parallelism is training-only, so the generation twin drops
        # seq_axis but KEEPS the tensor/expert (and vocab) sharding —
        # generate() runs the whole KV-cache loop in one shard_map over
        # the mesh (head-sharded caches, expert all_to_all per step,
        # routing at the per-call no-drop capacity bound; with
        # --vocab-parallel only the frontier logits row is all-gathered
        # per decoded token).
        from chainermn_tpu.models.transformer import generate

        gen_model = MoeTransformerLM(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers,
            n_experts=args.n_experts, moe_every=2, k=2,
            capacity_factor=1.25, max_len=args.seq_len,
            tp_axis="mn_model", expert_axis="mn_model",
            vocab_parallel=args.vocab_parallel,
        )
        prompt = jnp.asarray(corpus[:2, :8])
        out = np.asarray(generate(
            gen_model, params, prompt, args.generate,
            comm=comm, param_specs=specs,
        ))
        if chief:
            tier = "vp+tp/ep" if args.vocab_parallel else "tp/ep"
            print(f"sampled ({tier}-sharded MoE KV-cache decode): "
                  f"{out[0].tolist()}")
    return last_loss


if __name__ == "__main__":
    main()
