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

The block's options make the same script train another family: with
``--rope-theta`` the model takes the general block (``--rmsnorm``,
``--n-kv-heads``, ``--head-dim``, ``--qk-norm``, ``--untied-head``,
SiLU-gated experts routed without drops with ``--dropless``), and
``--block-diffusion B`` trains it on the block-diffusion objective: a
batch is the tree ``(tokens, mask, weights)``, the clean and the noised
copy of every sequence go through every layer under block-causal masks.
One chip's share of SDAR-30B-A3B-Chat (experts 0..15 of 128, an eighth
of the vocabulary; ``cellbench/configs/sdar-30b-a3b.json``):

    python examples/moe_lm/train_moe_lm.py --d-model 2048 --n-heads 32 \
      --n-kv-heads 4 --head-dim 128 --d-ff 768 --n-experts 128 --top-k 8 \
      --held 0,16 --moe-every 1 --n-layers 4 --vocab 18992 --seq-len 8192 \
      --batchsize 1 --rope-theta 1e6 --rmsnorm --qk-norm --untied-head \
      --dropless --block-diffusion 4 --flash --lr 1e-5 --aux-coef 1e-3

``--layer-types`` gives each layer's sequence mixer its kind
(``linear_attention``: Gated DeltaNet, sized by ``--gdn-*``),
``--shared-d-ff`` every expert layer a gated shared expert,
``--attn-output-gate`` / ``--rotary-fraction`` / ``--zero-centered-norm``
the attention and norms of Qwen3-Next, ``--remat-blocks`` per-block
recomputation and ``--chunked-ce`` the head a vocabulary chunk at a
time.  One chip's share of Qwen3-Next-80B-A3B-Instruct (experts 0..31
of 512, an eighth of the vocabulary, one period of the layer pattern;
``cellbench/configs/qwen3-next-80b-a3b.json``):

    python examples/moe_lm/train_moe_lm.py --d-model 2048 --n-heads 16 \
      --n-kv-heads 2 --head-dim 256 --d-ff 512 --shared-d-ff 512 \
      --n-experts 512 --top-k 10 --held 0,32 --moe-every 1 --n-layers 4 \
      --layer-types linear_attention,linear_attention,linear_attention,attention \
      --gdn-key-heads 16 --gdn-value-heads 32 --vocab 18992 \
      --seq-len 8192 --batchsize 2 --rope-theta 1e7 --rotary-fraction 0.25 \
      --rmsnorm --zero-centered-norm --qk-norm --attn-output-gate \
      --untied-head --dropless --flash --remat-blocks --chunked-ce 8 \
      --lr 1e-5 --aux-coef 1e-3

``--no-positions`` takes the general block without any position;
``--layer-types`` also knows ``kda`` (a delta rule with a decay a key
channel, sized by ``--gdn-*``) and ``latent_attention`` (keys and values
expanded from one compression, ``--latent-*``); ``--first-dense`` makes
the leading layers' MLPs dense (``--gated-mlp``, ``--dense-d-ff``);
``--router-score sigmoid``, ``--router-bias``, ``--routed-scale`` and
``--shared-ungated`` are the router's and the shared expert's other
forms.  One chip's share of Kimi-Linear-48B-A3B-Instruct (experts 0..7
of 256, an eighth of the vocabulary, the leading dense layer and the
four that follow; ``cellbench/configs/kimi-linear-48b-a3b.json``):

    python examples/moe_lm/train_moe_lm.py --d-model 2304 --n-heads 32 \
      --no-positions --latent-kv-rank 512 --d-ff 1024 --shared-d-ff 1024 \
      --shared-ungated --n-experts 256 --top-k 8 --held 0,8 --moe-every 1 \
      --first-dense 1 --dense-d-ff 9216 --gated-mlp --n-layers 5 \
      --layer-types kda,kda,kda,latent_attention --gdn-value-heads 32 \
      --router-score sigmoid --router-bias --routed-scale 2.446 \
      --vocab 20480 --seq-len 8192 --batchsize 2 --rmsnorm --norm-eps 1e-5 \
      --untied-head --dropless --flash --remat-blocks --chunked-ce 8 \
      --lr 1e-5 --aux-coef 1e-3

With ``--rope-theta`` a ``latent_attention`` layer rotates the channels
all heads share (the family's decoupled rotation), and ``--seq-aux``
counts the load-balancing loss a sequence.  One chip's share of
Moonlight-16B-A3B (experts 0..7 of 64, an eighth of the vocabulary, the
leading dense layer and the five that follow;
``cellbench/configs/moonlight-16b-a3b.json``):

    python examples/moe_lm/train_moe_lm.py --d-model 2048 --n-heads 16 \
      --rope-theta 50000 --layer-types latent_attention \
      --latent-kv-rank 512 --d-ff 1408 --shared-d-ff 2816 \
      --shared-ungated --n-experts 64 --top-k 6 --held 0,8 --moe-every 1 \
      --first-dense 1 --dense-d-ff 11264 --gated-mlp --n-layers 6 \
      --router-score sigmoid --router-bias --routed-scale 2.446 --seq-aux \
      --vocab 20480 --seq-len 8192 --batchsize 2 --rmsnorm --norm-eps 1e-5 \
      --untied-head --dropless --flash --remat-blocks --chunked-ce 8 \
      --lr 1e-5 --aux-coef 1e-3

``window_attention`` layers see the last ``--window`` keys, with
``--window-heads`` query heads and a rotation of their own
(``--window-rope-theta``, ``--window-rotary-fraction``); ``--rope-yarn``
scales the ``attention`` layers' rotation and ``--head-gate`` gates
every head's output.  One chip's share of Laguna-S-2.1 (experts 0..7 of
256, an eighth of the vocabulary, the leading dense layer and one
period of three window layers and a full one;
``cellbench/configs/laguna-s-2.1.json``):

    python examples/moe_lm/train_moe_lm.py --d-model 3072 --n-heads 48 \
      --n-kv-heads 8 --head-dim 128 --head-gate --rope-theta 500000 \
      --rotary-fraction 0.5 --rope-yarn 128,8192,32,1,1.4852030263919618 \
      --layer-types attention,window_attention,window_attention,window_attention \
      --window 512 --window-heads 72 --window-rope-theta 10000 \
      --window-rotary-fraction 1 --d-ff 1024 --shared-d-ff 1024 \
      --n-experts 256 --top-k 10 --held 0,8 --moe-every 1 \
      --routed-scale 2.5 --first-dense 1 --dense-d-ff 12288 --gated-mlp \
      --n-layers 5 --vocab 12544 --seq-len 8192 --batchsize 1 --rmsnorm \
      --untied-head --dropless --flash --remat-blocks --chunked-ce 8 \
      --lr 1e-5 --aux-coef 1e-3
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


def block_diffusion_batch(tokens, block, mask_id, rng, t_min=1e-3):
    """One block-diffusion draw over ``tokens (rows, s)``: a noise level
    ``t_b ~ U(t_min, 1]`` a block of ``block`` positions, each of its
    tokens masked with probability ``t_b``.  Returns the batch tree
    ``(tokens, mask, weights)``, ``weights = 1 / t_b`` on the masked
    positions and 0 elsewhere."""
    import numpy as np

    rows, s = tokens.shape
    t = 1.0 - rng.uniform(0.0, 1.0 - t_min, size=(rows, s // block))
    t = np.repeat(t, block, axis=1)
    mask = (rng.uniform(size=(rows, s)) < t) & (tokens != mask_id)
    weights = np.where(mask, 1.0 / t, 0.0).astype(np.float32)
    return tokens.astype(np.int32), mask, weights


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
    g = p.add_argument_group(
        "the block's options (models.transformer.BlockOptions)")
    g.add_argument("--rope-theta", type=float, default=None,
                   help="rotary positions of this base: the general "
                        "block, no position table (needs --sp 1); in a "
                        "latent_attention layer the rotation of the "
                        "--latent-shared-dim channels all heads share")
    g.add_argument("--no-positions", action="store_true",
                   help="no position anywhere: the general block, no "
                        "position table, no rotation, a latent_attention "
                        "layer's shared channels unrotated (needs --sp 1)")
    g.add_argument("--rmsnorm", action="store_true")
    g.add_argument("--n-kv-heads", type=int, default=None,
                   help="key/value heads shared by groups of query heads")
    g.add_argument("--head-dim", type=int, default=None)
    g.add_argument("--qk-norm", action="store_true",
                   help="RMSNorm over each head of q and k")
    g.add_argument("--untied-head", action="store_true")
    g.add_argument("--flash", action="store_true",
                   help="the Pallas attention kernels (TPU)")
    g.add_argument("--d-ff", type=int, default=None,
                   help="width of the MLP / of one expert")
    g.add_argument("--top-k", type=int, default=2)
    g.add_argument("--moe-every", type=int, default=2)
    g.add_argument("--dropless", action="store_true",
                   help="SiLU-gated experts routed without drops")
    g.add_argument("--held", default=None, metavar="FIRST,COUNT",
                   help="the experts this chip holds of --n-experts "
                        "(one chip's share of a layer; --tp 1)")
    g.add_argument("--return-routes", action="store_true",
                   help="hand every step's routing decisions back in "
                        "metrics['aux']['routes'] (layers, tokens, k)")
    g.add_argument("--block-diffusion", type=int, default=0, metavar="B",
                   help="train on the block-diffusion objective with "
                        "blocks of B positions (mask id: --vocab - 1)")
    g.add_argument("--norm-eps", type=float, default=1e-6)
    g.add_argument("--zero-centered-norm", action="store_true",
                   help="RMSNorm gains 1 + w, w initialised 0")
    g.add_argument("--rotary-fraction", type=float, default=1.0,
                   help="the leading share of a head that is rotated")
    g.add_argument("--attn-output-gate", action="store_true",
                   help="q_proj twice as wide: a sigmoid gate a head on "
                        "what the output projection reads")
    g.add_argument("--head-gate", action="store_true",
                   help="a sigmoid gate a head (g_proj (d, heads)) on the "
                        "head's whole output")
    g.add_argument("--rope-yarn", default=None,
                   metavar="FACTOR,LENGTH[,FAST,SLOW[,ATTENTION_FACTOR]]",
                   help="YaRN on the attention layers' rotation "
                        "(models.transformer.YarnScaling)")
    g.add_argument("--window", type=int, default=0,
                   help="keys a window_attention layer's query sees, its "
                        "own among them")
    g.add_argument("--window-heads", type=int, default=0,
                   help="query heads of the window_attention layers "
                        "(default: --n-heads)")
    g.add_argument("--window-rope-theta", type=float, default=None,
                   help="base of the window_attention layers' rotation "
                        "(default: --rope-theta)")
    g.add_argument("--window-rotary-fraction", type=float, default=None,
                   help="the leading share of a head a window_attention "
                        "layer rotates (default: --rotary-fraction)")
    g.add_argument("--layer-types", default=None,
                   help="comma-separated kinds of sequence mixer, a "
                        "layer each, repeated over the depth")
    g.add_argument("--gdn-key-heads", type=int, default=0,
                   help="key heads of a Gated DeltaNet mixer")
    g.add_argument("--gdn-value-heads", type=int, default=0)
    g.add_argument("--gdn-key-dim", type=int, default=128)
    g.add_argument("--gdn-value-dim", type=int, default=128)
    g.add_argument("--gdn-conv", type=int, default=4)
    g.add_argument("--gdn-chunk", type=int, default=64)
    g.add_argument("--latent-kv-rank", type=int, default=0,
                   help="latent attention: channels of the compression "
                        "keys and values are expanded from")
    g.add_argument("--latent-nope-dim", type=int, default=128,
                   help="a head's own channels of a query and a key")
    g.add_argument("--latent-shared-dim", type=int, default=64,
                   help="a key's channels that all heads share")
    g.add_argument("--latent-value-dim", type=int, default=128)
    g.add_argument("--shared-d-ff", type=int, default=0,
                   help="width of the gated shared expert beside the "
                        "routed ones (--dropless)")
    g.add_argument("--shared-ungated", action="store_true",
                   help="the shared expert without its sigmoid gate")
    g.add_argument("--router-score", default="softmax",
                   choices=("softmax", "sigmoid"),
                   help="the router's scores (--dropless)")
    g.add_argument("--router-bias", action="store_true",
                   help="a bias an expert on the choice of the top k "
                        "alone: outside the weights, no gradient, left "
                        "alone by the optimizer")
    g.add_argument("--routed-scale", type=float, default=1.0,
                   help="factor on the renormalised routed weights")
    g.add_argument("--seq-aux", action="store_true",
                   help="the load-balancing loss counted a sequence "
                        "and averaged over the sequences, not over all "
                        "rows of the batch (--dropless)")
    g.add_argument("--first-dense", type=int, default=0, metavar="K",
                   help="the first K layers' MLPs are dense")
    g.add_argument("--dense-d-ff", type=int, default=None,
                   help="width of a dense layer's MLP where it is not "
                        "--d-ff")
    g.add_argument("--gated-mlp", action="store_true",
                   help="a dense layer's MLP is W_out(SiLU(g) * u)")
    g.add_argument("--remat-blocks", action="store_true",
                   help="compute each block's forward again in the "
                        "backward pass; kept besides the blocks' inputs, "
                        "as far as the device's memory goes "
                        "(models.transformer.remat_budget): first the "
                        "attention kernels' result (attn_out, with "
                        "--flash: the backward then runs no forward "
                        "launch again), then the dense MLP's and the "
                        "mixers' in-projections' results, last the "
                        "delta rule's kernels' (scan_out) "
                        "(models.transformer.REMAT_NAMES)")
    g.add_argument("--chunked-ce", type=int, default=0, metavar="CHUNKS",
                   help="head + cross-entropy over this many vocabulary "
                        "chunks: the logits are never whole")
    args = p.parse_args(argv)
    general = args.rope_theta is not None or args.no_positions
    if args.rope_theta is not None and args.no_positions:
        p.error("--rope-theta or --no-positions")
    if not general and (args.rmsnorm or args.n_kv_heads or args.head_dim
                        or args.qk_norm or args.untied_head or args.flash
                        or args.dropless or args.held
                        or args.return_routes or args.block_diffusion
                        or args.zero_centered_norm or args.attn_output_gate
                        or args.rotary_fraction != 1.0 or args.layer_types
                        or args.shared_d_ff or args.remat_blocks
                        or args.chunked_ce or args.latent_kv_rank
                        or args.shared_ungated or args.router_bias
                        or args.router_score != "softmax"
                        or args.routed_scale != 1.0 or args.seq_aux
                        or args.first_dense
                        or args.dense_d_ff or args.gated_mlp
                        or args.head_gate or args.rope_yarn or args.window
                        or args.window_heads or args.window_rope_theta
                        or args.window_rotary_fraction is not None):
        p.error("the block's options come with --rope-theta or "
                "--no-positions")
    if args.chunked_ce and args.block_diffusion:
        p.error("--chunked-ce is the next-token loss's")
    if general and (args.sp != 1 or args.generate or args.vocab_parallel):
        p.error("--rope-theta / --no-positions need --sp 1, --generate 0 "
                "and a dense vocabulary: the general block has no "
                "sequence-parallel, decode or vocab-parallel path yet")
    if args.held and (args.tp != 1 or not args.dropless):
        p.error("--held is one chip's share: --tp 1 and --dropless")

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

    from chainermn_tpu.functions import collectives as cc
    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        ROUTES,
        MoeTransformerLM,
        RouterOptions,
        moe_lm_loss,
        moe_param_specs,
    )
    from chainermn_tpu.models.transformer import (
        HEAD_CE_SCOPE,
        LAYER_KINDS,
        BlockOptions,
        YarnScaling,
        block_diffusion_loss,
        model_remat_widths,
        noised_copy,
        remat_budget,
        remat_kept,
    )
    from chainermn_tpu.parallel import sharded_init

    layer_types = tuple(args.layer_types.split(",")) \
        if args.layer_types else None
    if set(layer_types or ()) - set(LAYER_KINDS):
        p.error(f"--layer-types holds {', '.join(LAYER_KINDS)}")
    if ("window_attention" in (layer_types or ())) != bool(args.window):
        p.error("--window comes with window_attention layers, and they "
                "with it")
    yarn = None
    if args.rope_yarn:
        factor, length, *rest = (float(x) for x in args.rope_yarn.split(","))
        yarn = YarnScaling(factor, int(length), *rest)

    comm = cmn.create_communicator(
        "mesh", devices=devices, sp_size=args.sp, tp_size=args.tp
    )
    chief = comm.process_index == 0
    if chief:
        print(f"mesh: dp={comm.dp_size} x sp={comm.sp_size} x "
              f"tp={comm.tp_size}  {comm!r}")

    batch = args.batchsize or 2 * comm.dp_size
    sizes = dict(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, n_experts=args.n_experts, d_ff=args.d_ff,
        moe_every=args.moe_every, k=args.top_k, capacity_factor=1.25,
        max_len=args.seq_len, vocab_parallel=args.vocab_parallel,
        # every axis that splits tokens (the general block's expert
        # layer sees all of its data shard's tokens on every chip)
        aux_stat_axes=("mn_data", "mn_seq") + (() if general
                                                else ("mn_model",)),
    )
    mask_id = args.vocab - 1
    options = BlockOptions(
        norm="rmsnorm" if args.rmsnorm else "layernorm",
        norm_eps=args.norm_eps,
        n_kv_heads=args.n_kv_heads, head_dim=args.head_dim,
        rope_theta=args.rope_theta, qk_norm=args.qk_norm,
        block_diffusion=args.block_diffusion, use_flash=args.flash,
        rotary_fraction=args.rotary_fraction,
        attn_output_gate=args.attn_output_gate,
        head_gate=args.head_gate, rope_yarn=yarn, window=args.window,
        window_heads=args.window_heads,
        window_rope_theta=args.window_rope_theta,
        window_rotary_fraction=args.window_rotary_fraction,
        zero_centered_norm=args.zero_centered_norm,
        layer_types=layer_types,
        gdn_key_heads=args.gdn_key_heads,
        gdn_value_heads=args.gdn_value_heads,
        gdn_key_dim=args.gdn_key_dim, gdn_value_dim=args.gdn_value_dim,
        gdn_conv=args.gdn_conv, gdn_chunk=args.gdn_chunk,
        latent_kv_rank=args.latent_kv_rank,
        latent_nope_dim=args.latent_nope_dim,
        latent_shared_dim=args.latent_shared_dim,
        latent_value_dim=args.latent_value_dim,
        gated_mlp=args.gated_mlp, no_positions=args.no_positions,
        remat_blocks=args.remat_blocks,
    )

    def make_general(options):
        # the mixers whole on every chip over its own sequences, the
        # experts over the model axis (or this chip's share of them)
        return MoeTransformerLM(
            **sizes, expert_axis="mn_model" if args.tp > 1 else None,
            options=options,
            routing="dropless" if args.dropless else "capacity",
            held=tuple(int(x) for x in args.held.split(","))
            if args.held else None,
            shared_d_ff=args.shared_d_ff,
            router_options=RouterOptions(
                score=args.router_score, selection_bias=args.router_bias,
                routed_scale=args.routed_scale,
                shared_gated=not args.shared_ungated,
                seq_aux=args.seq_aux),
            first_dense=args.first_dense, dense_d_ff=args.dense_d_ff,
            tie_head=not args.untied_head,
            return_hidden=bool(args.block_diffusion or args.chunked_ce),
        )

    if general:
        model = make_general(options)
    else:
        model = MoeTransformerLM(
            **sizes, seq_axis="mn_seq", tp_axis="mn_model",
            expert_axis="mn_model",
        )

    corpus = synthetic_corpus(
        max(batch * 8, 64), args.seq_len, args.vocab, seed=0
    )
    if args.block_diffusion:
        corpus = np.minimum(corpus, mask_id - 1)  # the mask id is no token
        sample = jnp.concatenate([jnp.asarray(corpus[:batch])] * 2, axis=1)
    else:
        sample = jnp.asarray(corpus[:batch])
    params, specs = sharded_init(
        # the counters a dropless layer sows are no parameters
        lambda t: {"params": model.init(jax.random.PRNGKey(0), t)["params"]},
        comm.mesh, (P("mn_data", "mn_seq"),), moe_param_specs, sample,
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    if chief:
        print(f"params: {n_params / 1e6:.2f} M  "
              f"(expert blocks sharded over mn_model)")

    # the selection bias is no weight: it has no gradient (Adam then
    # leaves it where it is) and is kept out of the decay
    decayed = (lambda tree: jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key != "router_bias", tree)) \
        if args.router_bias else None
    opt = cmn.create_multi_node_optimizer(
        optax.adamw(args.lr, weight_decay=0.01, mask=decayed), comm
    )
    opt_state = opt.init(params)
    if options.remat_blocks:
        # what the blocks keep besides their inputs, as far as the memory
        # left beside the state goes (the parameter tree is the same
        # under any plan: the step is traced once, with this one)
        tokens = batch // comm.dp_size * args.seq_len
        widths = model_remat_widths(model)
        model = make_general(dataclasses.replace(
            options, remat_budget_bytes=remat_budget(
                comm.mesh.local_devices[0], (params, opt_state), tokens,
                widths)))
        kept, kept_bytes = remat_kept(
            model.remat_plan(tokens), tokens, widths)
        if kept:
            cmn.observability.phase_attributes(
                "setup.build_step",
                **{"remat.kept": kept, "remat.kept_bytes": kept_bytes})

    def loss_fn(p, b):
        return moe_lm_loss(
            model.apply(p, b), b, seq_axis="mn_seq",
            model_axis="mn_model", aux_coef=args.aux_coef,
            vocab_parallel=args.vocab_parallel,
        )

    def general_loss_fn(p, b):
        """The general block's loss and its expert layers' counters
        (summed over layers and chips)."""
        tokens = b[0] if args.block_diffusion else b
        inputs = noised_copy(tokens, b[1], mask_id) \
            if args.block_diffusion else tokens
        (out, aux), sown = model.apply(p, inputs,
                                       mutable=[COUNTERS, ROUTES])
        if args.block_diffusion:
            head = p["params"]["lm_head"] if args.untied_head \
                else p["params"]["embed"]["embedding"]
            main = block_diffusion_loss(out, head, tokens, b[2])
            total = main + args.aux_coef * aux
        elif args.chunked_ce:
            from chainermn_tpu.ops.chunked_ce import (
                chunked_softmax_cross_entropy,
            )

            head = p["params"]["lm_head"] if args.untied_head \
                else p["params"]["embed"]["embedding"]
            with jax.named_scope(HEAD_CE_SCOPE):
                main = chunked_softmax_cross_entropy(
                    out[:, :-1].reshape(-1, out.shape[-1]), head,
                    tokens[:, 1:].reshape(-1), args.chunked_ce).mean()
            total = main + args.aux_coef * aux
        else:
            total = moe_lm_loss((out, aux), tokens, aux_coef=args.aux_coef)
        for axis in ("mn_seq", "mn_model"):  # certify: see moe_lm_loss
            total = cc.pmean(total, axis)
        counters = {}
        for path, values in jax.tree_util.tree_leaves_with_path(
                sown.get(COUNTERS, {})):
            name = path[-2].key
            # over the chips whose counts differ: data shards, and the
            # model axis where the experts lie over it
            axes = tuple(a for a in comm.axis_names
                         if a in jax.typeof(values).vma)
            counters[name] = counters.get(name, 0) + (
                cc.psum(values, axes) if axes else values)
        if args.return_routes:
            # every layer's (tokens, k) choices, the data shards' tokens
            # one after another as the batch's rows are
            routes = jnp.stack(jax.tree_util.tree_leaves(sown[ROUTES]))
            for a in comm.axis_names:
                if a in jax.typeof(routes).vma:
                    # an all-gather the type system knows to be the
                    # same on every chip: each lays its rows into zeros
                    # at its own place, and the sum is the whole
                    n, t = jax.lax.axis_size(a), routes.shape[1]
                    whole = jnp.zeros((routes.shape[0], n * t,
                                       routes.shape[2]), routes.dtype)
                    routes = cc.psum(jax.lax.dynamic_update_slice(
                        whole, routes, (0, jax.lax.axis_index(a) * t, 0)),
                        a)
            counters["routes"] = routes
        return total, counters

    step = cmn.build_train_step(
        comm, general_loss_fn if general else loss_fn, opt,
        data_axes=comm.data_axis_names, param_specs=specs,
        batch_specs=P("mn_data", "mn_seq"), has_aux=general,
    )
    params, opt_state = step.place(params, opt_state)

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
            toks = corpus[rows]
            if args.block_diffusion:
                toks = block_diffusion_batch(
                    toks, args.block_diffusion, mask_id, rng)
            toks = step.place_batch(jax.tree_util.tree_map(jnp.asarray,
                                                           toks))
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
        print(cmn.observability.setup_line())

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
    return {"loss": last_loss, "comm": comm, "model": model,
            "specs": specs, "step": step, "params": params,
            "opt_state": opt_state, "batch": toks, "metrics": metrics}


if __name__ == "__main__":
    main()
