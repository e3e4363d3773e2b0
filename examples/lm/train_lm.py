#!/usr/bin/env python
"""Decoder-only language model: train, then sample.

The dense/TP/SP TransformerLM family's CLI surface (the MoE composition
lives in ``examples/moe_lm/``).  Three ways to run the same model:

* dense (default): one chip or pure data parallelism,
* ``--sp N``: sequence parallelism — ring (or ``--sp-impl ulysses``)
  attention over the ``mn_seq`` axis, loss targets crossing shard
  boundaries via ppermute,
* ``--tp N``: Megatron tensor parallelism over ``mn_model`` (column/row
  attention + MLP sharding).

After training it SAMPLES from the model: dense and TP models generate
natively (TP decode runs the whole loop in one shard_map with
head-sharded KV caches); an SP-trained model is re-materialized as its
dense twin (identical parameter tree for ``seq_axis=None``) first —
the training-only nature of sequence sharding is the point being
demonstrated.

Virtual-mesh smoke run (2 data x 2 seq x 2 model):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/lm/train_lm.py --cpu-mesh --sp 2 --tp 2

On one real TPU chip, flash attention kicks in automatically for long
sequences: ``python examples/lm/train_lm.py --seq-len 2048 --flash``.

The block's options (``models.transformer.BlockOptions``) have flags of
their own: ``--rmsnorm``, grouped-query heads, ``--no-positions``, a
``--gated-mlp``, the stream's multipliers, ``--layer-types`` with
``mamba`` entries for Mamba-2 layers (``--ssm-*`` size their mixer),
``--remat-blocks`` and ``--chunked-ce``.  A Mamba-2 / attention hybrid
on the CPU:

    python examples/lm/train_lm.py --cpu-mesh --rmsnorm --gated-mlp \
      --no-positions --n-kv-heads 2 --layer-types mamba,mamba,attention \
      --ssm-heads 8 --ssm-head-dim 32 --ssm-state 32 --ssm-chunk 64
"""

import argparse
import dataclasses
import os
import sys
import time

try:  # installed package (pip install -e .)
    import chainermn_tpu  # noqa: F401
except ImportError:  # source checkout without installation
    sys.path.insert(
        0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    )

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "moe_lm"))
from train_moe_lm import synthetic_corpus  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: decoder-only LM + sampling"
    )
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel width (mn_seq axis)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel width (mn_model axis)")
    p.add_argument("--sp-impl", choices=("ring", "ulysses"),
                   default="ring")
    p.add_argument("--batchsize", type=int, default=None,
                   help="global batch rows (default: 2 per data shard)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--d-ff", type=int, default=None,
                   help="MLP width (default 4 x d-model)")
    # the block's options: models.transformer.BlockOptions
    p.add_argument("--rmsnorm", action="store_true",
                   help="RMSNorm (gain only) instead of LayerNorm")
    p.add_argument("--norm-eps", type=float, default=1e-6)
    p.add_argument("--n-kv-heads", type=int, default=None,
                   help="key/value heads (grouped-query attention)")
    p.add_argument("--attention-scale", type=float, default=None,
                   help="factor on q k^T where not head width ** -0.5")
    p.add_argument("--no-positions", action="store_true",
                   help="no position table and no positions in attention")
    p.add_argument("--gated-mlp", action="store_true",
                   help="W_out(SiLU(g) * u), [g | u] = W_in x, no biases")
    p.add_argument("--layer-types", default=None,
                   help="comma-separated kinds of sequence mixer "
                        "(models.transformer.LAYER_KINDS), a layer each, "
                        "repeated over the depth")
    p.add_argument("--ssm-heads", type=int, default=0,
                   help="heads of a Mamba-2 mixer")
    p.add_argument("--ssm-head-dim", type=int, default=64)
    p.add_argument("--ssm-state", type=int, default=128)
    p.add_argument("--ssm-conv", type=int, default=4)
    p.add_argument("--ssm-chunk", type=int, default=256)
    p.add_argument("--gdn-key-heads", type=int, default=0,
                   help="key heads of a Gated DeltaNet mixer")
    p.add_argument("--gdn-value-heads", type=int, default=0)
    p.add_argument("--gdn-key-dim", type=int, default=128)
    p.add_argument("--gdn-value-dim", type=int, default=128)
    p.add_argument("--gdn-conv", type=int, default=4)
    p.add_argument("--gdn-chunk", type=int, default=64)
    p.add_argument("--embedding-multiplier", type=float, default=1.0)
    p.add_argument("--residual-multiplier", type=float, default=1.0)
    p.add_argument("--logits-scaling", type=float, default=1.0)
    p.add_argument("--remat-blocks", action="store_true",
                   help="compute each block's forward again in the "
                        "backward pass.  Kept: the blocks' inputs and, "
                        "as far as the device's memory goes (its "
                        "reported limit less parameters and optimizer "
                        "state less a reserve: "
                        "models.transformer.remat_budget; nothing off "
                        "the TPU), first the attention kernels' "
                        "result (attn_out, with --flash on the general "
                        "path: the backward then runs no forward launch "
                        "again), then the in_proj results of the gated "
                        "MLP (mlp_in) and of the Mamba-2 mixer (ssm_in)")
    p.add_argument("--chunked-ce", type=int, default=0, metavar="CHUNKS",
                   help="head + cross-entropy over this many vocabulary "
                        "chunks (ops.chunked_lm_loss): the logits are "
                        "never whole; dense model, --sp 1 --tp 1")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--report-every", type=int, default=20)
    p.add_argument("--grad-wire", action="store_true",
                   help="data-parallel only (--sp 1 --tp 1): build the "
                        "step without param_specs, so that "
                        "create_multi_node_optimizer's own wire ships "
                        "the gradients (small leaves in buckets, and on "
                        "a multi-chip TPU mesh large ones in place) "
                        "instead of autodiff's all-reduce a leaf")
    p.add_argument("--flash", action="store_true",
                   help="use the Pallas flash-attention kernel (TPU)")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="shard the embedding + tied head over the TP "
                        "axis (train with vp_lm_loss; sampling gathers "
                        "only the frontier logits row per token); "
                        "requires --tp > 1")
    p.add_argument("--generate", type=int, default=32,
                   help="tokens to sample after training (0 disables)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--serve", type=int, default=0,
                   help="after training, serve N greedy-decode requests "
                        "through the continuous-batching engine "
                        "(chainermn_tpu.serving; 0 disables)")
    p.add_argument("--serve-capacity", type=int, default=4,
                   help="decode slots for --serve (padded slot model)")
    p.add_argument("--serve-tokens", type=int, default=16,
                   help="max new tokens per served request")
    p.add_argument("--cpu-mesh", action="store_true",
                   help="run on a virtual CPU device mesh (testing)")
    args = p.parse_args(argv)
    if args.grad_wire and (args.sp != 1 or args.tp != 1
                           or args.vocab_parallel):
        p.error("--grad-wire is the data-parallel step's: --sp 1 --tp 1 "
                "and a dense vocabulary (sharded parameters need "
                "param_specs)")

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

    from chainermn_tpu.models.transformer import (
        LAYER_KINDS,
        BlockOptions,
        TransformerLM,
        generate,
        lm_loss,
        remat_budget,
        remat_kept,
        sp_lm_loss,
        vp_lm_loss,
    )
    from chainermn_tpu.parallel import megatron_param_specs, sharded_init

    comm = cmn.create_communicator(
        "mesh", devices=devices, sp_size=args.sp, tp_size=args.tp
    )
    chief = comm.process_index == 0
    if chief:
        print(f"mesh: dp={comm.dp_size} x sp={comm.sp_size} x "
              f"tp={comm.tp_size}  {comm!r}")

    if args.layer_types and set(args.layer_types.split(",")) \
            - set(LAYER_KINDS):
        p.error(f"--layer-types holds {', '.join(LAYER_KINDS)}")
    options = BlockOptions(
        norm="rmsnorm" if args.rmsnorm else "layernorm",
        norm_eps=args.norm_eps, n_kv_heads=args.n_kv_heads,
        attention_scale=args.attention_scale,
        no_positions=args.no_positions, gated_mlp=args.gated_mlp,
        layer_types=tuple(args.layer_types.split(","))
        if args.layer_types else None,
        ssm_heads=args.ssm_heads, ssm_head_dim=args.ssm_head_dim,
        ssm_state=args.ssm_state, ssm_conv=args.ssm_conv,
        ssm_chunk=args.ssm_chunk,
        gdn_key_heads=args.gdn_key_heads,
        gdn_value_heads=args.gdn_value_heads,
        gdn_key_dim=args.gdn_key_dim, gdn_value_dim=args.gdn_value_dim,
        gdn_conv=args.gdn_conv, gdn_chunk=args.gdn_chunk,
        embedding_multiplier=args.embedding_multiplier,
        residual_multiplier=args.residual_multiplier,
        logits_scaling=args.logits_scaling,
        remat_blocks=args.remat_blocks,
    )
    # the general attention path takes the kernels by option, the fused
    # qkv path as a function
    attention_fn = None
    if args.flash and options.general_attention:
        options = dataclasses.replace(options, use_flash=True)
    elif args.flash:
        from chainermn_tpu.ops.pallas_attention import flash_attention_fn

        attention_fn = flash_attention_fn()
    # only the fused-qkv block of GPT-2 has a KV-cache decode form
    cached_decode = not (options.general_attention or options.layer_types)

    def make_model(seq_axis, tp_axis, deterministic=False,
                   options=options):
        return TransformerLM(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
            max_len=args.seq_len, dropout_rate=args.dropout,
            deterministic=deterministic, seq_axis=seq_axis,
            tp_axis=tp_axis, sp_impl=args.sp_impl,
            vocab_parallel=args.vocab_parallel,
            attention_fn=attention_fn, options=options,
        )

    seq_axis = "mn_seq" if args.sp > 1 else None
    tp_axis = "mn_model" if args.tp > 1 else None
    if args.vocab_parallel and tp_axis is None:
        p.error("--vocab-parallel requires --tp > 1")
    if args.chunked_ce and (seq_axis or tp_axis):
        p.error("--chunked-ce is the dense model's: --sp 1 --tp 1")
    model = make_model(seq_axis, tp_axis)

    batch = args.batchsize or 2 * comm.dp_size
    corpus = synthetic_corpus(
        max(batch * 8, 64), args.seq_len, args.vocab, seed=0
    )
    sample = jnp.asarray(corpus[:batch])
    specs_fn = lambda tree: megatron_param_specs(
        tree, model_axis="mn_model"
    )
    params, specs = sharded_init(
        lambda t: model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)}, t),
        comm.mesh, (P("mn_data", "mn_seq"),), specs_fn, sample,
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    if chief:
        print(f"params: {n_params / 1e6:.2f} M")

    opt = cmn.create_multi_node_optimizer(
        optax.adamw(args.lr, weight_decay=0.01), comm
    )
    opt_state = opt.init(params)
    if options.remat_blocks:
        # what the blocks keep besides their inputs, as far as the memory
        # left beside the state goes (the parameter tree is the same
        # under any plan: the step is traced once, with this one)
        tokens = batch // comm.dp_size * args.seq_len // comm.sp_size
        widths = options.remat_widths(args.d_ff or 4 * args.d_model,
                                      args.n_heads, d_model=args.d_model)
        options = dataclasses.replace(
            options, remat_budget_bytes=remat_budget(
                comm.mesh.local_devices[0], (params, opt_state), tokens,
                widths))
        model = make_model(seq_axis, tp_axis, options=options)
        kept, kept_bytes = remat_kept(
            model.remat_plan(tokens), tokens, widths)
        if kept:
            cmn.observability.phase_attributes(
                "setup.build_step",
                **{"remat.kept": kept, "remat.kept_bytes": kept_bytes})

    def main_loss(p, b):
        if args.chunked_ce:
            # the tied head a vocabulary chunk at a time: the (b, s, V)
            # logits never materialize
            from chainermn_tpu.ops import chunked_lm_loss

            return chunked_lm_loss(model, p, b, args.chunked_ce)
        logits = model.apply(
            p, b, rngs={"dropout": jax.random.PRNGKey(0)}
        )
        if args.vocab_parallel:
            # vocab-sharded logits: softmax statistics assembled with
            # collectives, the full-vocab row never materializes (the
            # psums also make the loss mn_model-invariant)
            return vp_lm_loss(logits, b, tp_axis, seq_axis=seq_axis)
        if seq_axis is not None:
            return sp_lm_loss(logits, b, seq_axis)
        return lm_loss(logits, b)

    def loss_fn(p, b):
        main = main_loss(p, b)
        # Certify replication to vma-checked autodiff over every mesh
        # axis the loss wasn't reduced over: unused (size-1) axes still
        # shard the batch spec, so vma tracks them as varying — the
        # pmean over a size-1 axis is a free identity.
        certify = []
        if seq_axis is None:
            certify.append(comm.seq_axis_name)
        if tp_axis is None:
            certify.append(comm.model_axis_name)
        elif not args.vocab_parallel:
            certify.append(tp_axis)
        from chainermn_tpu.functions import collectives as cc

        for ax in certify:
            main = cc.pmean(main, ax)
        return main

    # --grad-wire: the parameters replicated and the optimizer's own
    # exchange of the gradients (its wire: buckets of the small leaves,
    # the large ones in place on a multi-chip TPU mesh); otherwise the
    # hybrid body, whose reductions are autodiff's, one a leaf
    step = cmn.build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=None if args.grad_wire else specs,
        batch_specs=P("mn_data", "mn_seq"),
    )
    params, opt_state = step.place(params, opt_state)

    rng = np.random.RandomState(1)
    t0, tokens_done, last_loss = time.perf_counter(), 0, float("nan")
    losses = []  # every reported loss, in step order
    for it in range(1, args.steps + 1):
        rows = rng.randint(0, corpus.shape[0], size=batch)
        toks = step.place_batch(jnp.asarray(corpus[rows]))
        params, opt_state, metrics = step(params, opt_state, toks)
        tokens_done += batch * args.seq_len
        if it % args.report_every == 0 or it == args.steps:
            last_loss = float(metrics["loss"])  # forces completion
            losses.append(last_loss)
            dt = time.perf_counter() - t0
            if chief:
                print(f"step {it:5d}  loss {last_loss:.4f}  "
                      f"{tokens_done / dt:,.0f} tok/s")
            t0, tokens_done = time.perf_counter(), 0
    if chief:
        print(f"final: loss={last_loss:.4f} "
              f"(uniform {np.log(args.vocab):.3f}, corpus floor 1.386)")
        print(cmn.observability.setup_line())

    if args.generate > 0:
        # Sampling: SP is training-only — materialize the dense twin
        # (identical param tree for seq_axis=None); TP generates
        # natively under its mesh.
        # (the block-causal kernels want whole blocks of positions: a
        # prompt's odd lengths go through the dense masked softmax)
        gen_model = make_model(
            None, tp_axis, deterministic=True,
            options=dataclasses.replace(options, use_flash=False,
                                        remat_blocks=False))
        prompt = jnp.asarray(corpus[:2, :8])
        kw = {}
        if tp_axis is not None:
            kw = dict(comm=comm, param_specs=specs)
        out = generate(
            gen_model, params, prompt, args.generate,
            temperature=args.temperature,
            rng=jax.random.PRNGKey(7),
            use_cache=None if cached_decode else False, **kw,
        )
        out = np.asarray(out)
        if chief:
            tier = (
                "vocab-parallel" if args.vocab_parallel
                else "tp-sharded" if tp_axis is not None
                else "dense"
            ) + (" KV-cache" if cached_decode else " recompute")
            print(f"sampled ({tier} decode): "
                  f"{out[0].tolist()}")

    served = None
    if args.serve > 0:
        # Serving tier: greedy decode over the trained checkpoint
        # through the continuous-batching engine (paged KV cache,
        # padded slot model).  SP is training-only — the dense twin
        # serves; TP serves natively under its mesh.
        if args.vocab_parallel:
            p.error("--serve does not support --vocab-parallel yet "
                    "(serve the dense-head twin)")
        if not cached_decode:
            p.error("--serve decodes through the KV cache, which only "
                    "the GPT-2 block has")
        from chainermn_tpu.serving.batcher import (
            ContinuousBatcher,
            Request,
        )
        from chainermn_tpu.serving.decode import DecodeEngine

        serve_model = make_model(None, tp_axis, deterministic=True)
        kw = {}
        if tp_axis is not None:
            kw = dict(comm=comm, param_specs=specs)
        engine = DecodeEngine(
            serve_model, params, capacity=args.serve_capacity, **kw
        )
        batcher = ContinuousBatcher(engine)
        rng_req = np.random.RandomState(11)
        requests = [
            Request(
                corpus[rng_req.randint(corpus.shape[0]),
                       : int(rng_req.randint(4, 12))].tolist(),
                args.serve_tokens,
            )
            for _ in range(args.serve)
        ]
        t0 = time.perf_counter()
        results = batcher.serve(requests)
        dt = time.perf_counter() - t0
        report = batcher.latency_report()
        if chief:
            for r in results[: min(3, len(results))]:
                print(f"  {r.id}: {r.output}")
            lat = report.get("serving.token_latency", {})
            print(
                f"served {report['done']} requests "
                f"({report['tokens_generated']} tokens, "
                f"{report['tokens_generated'] / dt:,.0f} tok/s, "
                f"token p50 {lat.get('p50_ms', float('nan')):.2f} ms "
                f"p99 {lat.get('p99_ms', float('nan')):.2f} ms, "
                f"failed {report['failed']})"
            )
        served = {"model": serve_model, "requests": requests,
                  "results": results, "report": report}
    return {"loss": last_loss, "losses": losses, "comm": comm,
            "model": model, "specs": specs, "step": step,
            "params": params, "opt_state": opt_state, "batch": toks,
            "served": served}


if __name__ == "__main__":
    main()
