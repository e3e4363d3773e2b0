#!/usr/bin/env python
"""Where the dp4 cell's gradient all-reduces sit in the compiled step.

Compiles the train step of ``cgpt590m_dp4_s2048`` (Cerebras-GPT-590M
through ``build_train_step(param_specs=...)`` with the flash kernels, as
``examples/lm/train_lm.py --flash`` builds it at ``--sp 1 --tp 1``)
ahead of time for a *described* ``v5e:2x2`` and prints

* the entry computation's schedule, a character an event (``S`` / ``D``
  an asynchronous collective's start / done, ``R`` a synchronous
  collective, ``m`` a matmul fusion, ``k`` a kernel, ``.`` any other
  fusion; a step of an asynchronous collective reads ``m`` fused onto a
  matmul, ``e`` onto elementwise compute, ``s`` alone), and
* the census of the gradient reductions: synchronous against
  asynchronous, and the asynchronous ones with compute between start
  and done (``analysis.hlo.collective_schedule``; the same reading
  ``step.collective_schedule`` gives on the chip), beside the program's
  argument, temporary and code sizes (``memory_analysis()``: what a
  warm start loads is the code).

No chip: nothing runs and nothing is timed.  A compile that passes is
not a chip run; what the chip makes of a schedule is ``PERF.md``'s to
say.  The 18-layer step takes 40-130 s to compile here, ``--layers 3``
about 25 s.

Run:  python benchmarks/collective_schedule_aot.py [--layers 3]
          [--chips 1] [--options '{"xla_...": "true"}'] [--hlo out.txt]
          [--grad-wire [--packed]]

``--options`` replaces the option set of the step builder's rule
(``{}``: the program without the rule), to read a candidate before a
chip is asked for.

``--grad-wire`` compiles ``cgpt590m_dpwire4_s2048``'s step instead (no
``param_specs``: ``create_multi_node_optimizer``'s own exchange) and
prints how the wire splits the gradients (leaves in place, buckets
packed, bytes of each: ``optimizers._split_wire``) beside the
schedule's counts.  Its four forms are a command each:

    --grad-wire --packed                  A  every leaf packed, no options
                                             (the wire before PR 51)
    --grad-wire --packed --options rule   B  packed, the rule's options
    --grad-wire --options '{}'            C  large leaves in place, none
    --grad-wire                           D  in place + options (the step)
"""

import argparse
import contextlib
import json
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
)

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


@contextlib.contextmanager
def one_process():
    """Described devices have no backend to ask for the process count."""
    with mock.patch.object(jax, "process_count", lambda backend=None: 1), \
            mock.patch.object(jax, "process_index", lambda backend=None: 0):
        yield


def _abstract_arguments(mesh, opt, params, specs, tokens, batch_spec):
    """``(params, opt_state, batch)`` as shapes with their shardings on
    ``mesh``: what a step over described devices is lowered with."""
    def placed(tree, spec_tree):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, spec_tree)

    opt_state = jax.eval_shape(opt.init, params)
    state_specs = optax.tree_map_params(
        opt, lambda _leaf, spec: spec, opt_state, specs,
        transform_non_params=lambda _leaf: P())
    return (placed(params, specs), placed(opt_state, state_specs),
            placed(tokens, batch_spec))


def build_lm_step(devices, *, n_layers=18, d_model=1536, n_heads=12,
                  vocab=50257, seq_len=2048, per_chip_batch=4, d_ff=None,
                  options=None, chunked_ce=0, lr=1e-3, grad_wire=False):
    """The LM cells' step over ``devices`` (described or attached) and
    its abstract arguments ``(params, opt_state, batch)``, shardings on.
    ``grad_wire``: the example's ``--grad-wire`` step (no
    ``param_specs``: the optimizer's own wire ships the gradients).
    ``options`` (a ``BlockOptions``), ``d_ff`` and ``chunked_ce`` build
    the example's model under its block flags instead of the GPT-2
    block with the flash kernels as ``attention_fn``."""
    import chainermn_tpu as cmn
    from chainermn_tpu.functions import collectives as cc
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        TransformerLM,
        lm_loss,
    )
    from chainermn_tpu.ops import chunked_lm_loss
    from chainermn_tpu.ops.pallas_attention import flash_attention_fn
    from chainermn_tpu.parallel import megatron_param_specs

    comm = cmn.create_communicator(
        "mesh", devices=list(devices), sp_size=1, tp_size=1)
    mesh = comm.mesh
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, max_len=seq_len, dropout_rate=0.0, d_ff=d_ff,
        # interpret=False: the host's backend is the CPU, the target is not
        attention_fn=None if options else flash_attention_fn(
            interpret=False),
        options=options or BlockOptions(),
    )
    batch_spec = P("mn_data", "mn_seq")
    rows = per_chip_batch * len(devices)
    tokens = jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)
    params = jax.eval_shape(
        jax.shard_map(
            lambda t: model.init({"params": jax.random.PRNGKey(0),
                                  "dropout": jax.random.PRNGKey(1)}, t),
            mesh=mesh, in_specs=(batch_spec,), out_specs=P(),
            check_vma=False),
        tokens)
    specs = megatron_param_specs(params, model_axis="mn_model")
    opt = cmn.create_multi_node_optimizer(
        optax.adamw(lr, weight_decay=0.01), comm)

    def loss_fn(p, b):
        loss = chunked_lm_loss(model, p, b, chunked_ce) if chunked_ce \
            else lm_loss(
                model.apply(p, b, rngs={"dropout": jax.random.PRNGKey(0)}),
                b)
        for axis in (comm.seq_axis_name, comm.model_axis_name):
            loss = cc.pmean(loss, axis)  # width 1: certifies replication
        return loss

    step = cmn.build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=None if grad_wire else specs, batch_specs=batch_spec)

    return step, _abstract_arguments(mesh, opt, params, specs, tokens,
                                     batch_spec)


def build_moe_lm_step(devices, *, options, vocab, d_model, n_heads,
                      n_layers, d_ff, n_experts, top_k, held, shared_d_ff,
                      seq_len, per_chip_batch, chunked_ce, lr=1e-5,
                      aux_coef=1e-3, **model_fields):
    """The step ``examples/moe_lm/train_moe_lm.py`` builds on its general
    path at ``--sp 1 --tp 1`` (``--dropless --untied-head --moe-every 1
    --chunked-ce N``: the next-token loss a vocabulary chunk at a time,
    the expert layers' counters as ``aux``) over ``devices`` (described
    or attached), and its abstract arguments ``(params, opt_state,
    batch)``, shardings on.  ``options``: the model's ``BlockOptions``;
    ``model_fields``: further fields of ``MoeTransformerLM``
    (``router_options``, ``first_dense``, ``dense_d_ff``)."""
    import chainermn_tpu as cmn
    from chainermn_tpu.functions import collectives as cc
    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        MoeTransformerLM,
        moe_param_specs,
    )
    from chainermn_tpu.models.transformer import HEAD_CE_SCOPE
    from chainermn_tpu.ops.chunked_ce import chunked_softmax_cross_entropy

    comm = cmn.create_communicator(
        "mesh", devices=list(devices), sp_size=1, tp_size=1)
    mesh = comm.mesh
    model = MoeTransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, n_experts=n_experts, d_ff=d_ff, moe_every=1,
        k=top_k, max_len=seq_len, aux_stat_axes=("mn_data", "mn_seq"),
        options=options, routing="dropless", held=held,
        shared_d_ff=shared_d_ff, tie_head=False, return_hidden=True,
        **model_fields)
    batch_spec = P("mn_data", "mn_seq")
    tokens = jax.ShapeDtypeStruct(
        (per_chip_batch * len(devices), seq_len), jnp.int32)
    params = jax.eval_shape(
        jax.shard_map(
            lambda t: {"params": model.init(jax.random.PRNGKey(0),
                                            t)["params"]},
            mesh=mesh, in_specs=(batch_spec,), out_specs=P(),
            check_vma=False),
        tokens)
    specs = moe_param_specs(params)
    # the example's: a selection bias is kept out of the decay
    decayed = (lambda tree: jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key != "router_bias", tree)) \
        if model.router_options.selection_bias else None
    opt = cmn.create_multi_node_optimizer(
        optax.adamw(lr, weight_decay=0.01, mask=decayed), comm)

    def loss_fn(p, b):
        (hidden, aux), sown = model.apply(p, b, mutable=[COUNTERS])
        with jax.named_scope(HEAD_CE_SCOPE):
            main = chunked_softmax_cross_entropy(
                hidden[:, :-1].reshape(-1, d_model), p["params"]["lm_head"],
                b[:, 1:].reshape(-1), chunked_ce).mean()
        total = main + aux_coef * aux
        for axis in (comm.seq_axis_name, comm.model_axis_name):
            total = cc.pmean(total, axis)  # width 1: certifies replication
        counters = {}
        for path, values in jax.tree_util.tree_leaves_with_path(
                sown.get(COUNTERS, {})):
            axes = tuple(a for a in comm.axis_names
                         if a in jax.typeof(values).vma)
            counters[path[-2].key] = counters.get(path[-2].key, 0) + (
                cc.psum(values, axes) if axes else values)
        return total, counters

    step = cmn.build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=specs, batch_specs=batch_spec, has_aux=True)

    return step, _abstract_arguments(mesh, opt, params, specs, tokens,
                                     batch_spec)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--layers", type=int, default=18)
    p.add_argument("--chips", type=int, default=4, choices=(1, 2, 4))
    p.add_argument("--options", default=None,
                   type=lambda s: s if s == "rule" else json.loads(s),
                   help="JSON object: replaces the builder's option set "
                        "('rule': the builder's own, with --packed)")
    p.add_argument("--grad-wire", action="store_true",
                   help="the example's --grad-wire step: the wire's "
                        "collectives in place of autodiff's psum a leaf")
    p.add_argument("--packed", action="store_true",
                   help="with --grad-wire: every leaf packed into the "
                        "plan's buckets, whatever the mesh (and the "
                        "step compiled with --options all the same)")
    p.add_argument("--hlo", default="", help="write the program text here")
    args = p.parse_args(argv)

    from jax.experimental import topologies

    from chainermn_tpu import optimizers
    from chainermn_tpu.analysis.hlo import (
        WEIGHT_GRADIENT_BYTES,
        collective_schedule,
    )

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    rule = dict(optimizers._ASYNC_GRAD_REDUCE_OPTIONS)
    if args.options == "rule":
        args.options = rule
    split_wire = optimizers._split_wire
    with contextlib.ExitStack() as patches:
        patches.enter_context(one_process())
        if args.options is not None:
            patches.enter_context(mock.patch.object(
                optimizers, "_ASYNC_GRAD_REDUCE_OPTIONS", args.options))
        if args.packed:
            patches.enter_context(mock.patch.object(
                optimizers, "_split_wire",
                lambda *a, **kw: split_wire(*a, **{**kw,
                                                   "in_place": False})))
        step, abstract = build_lm_step(
            topo.devices[:args.chips], n_layers=args.layers,
            grad_wire=args.grad_wire)
        t0 = time.perf_counter()
        lowered = step.get_jitted(*abstract[:2]).lower(*abstract)
        # a packed wire is handed no options by the builder: the form
        # under them (B) is compiled with them here
        compiled = lowered.compile(
            compiler_options=args.options if args.packed else None)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    schedule = collective_schedule(text)
    memory = compiled.memory_analysis()
    print(schedule.condensed)
    for op in schedule.ops:
        if op.nbytes >= WEIGHT_GRADIENT_BYTES:
            print(f"  {op.cls:18s} {op.nbytes / 1e6:8.1f} MB  "
                  f"{'async' if op.asynchronous else 'sync ':5s} "
                  f"compute inside {op.compute_inside:2d}  "
                  f"{(op.op_name or '')[-64:]}")
    split = {}
    if args.grad_wire:
        from chainermn_tpu.observability import process_record

        split = [e["args"] for e in process_record()["spans"]
                 if e["name"] == "setup.build_step"][-1]
    print(json.dumps({
        "layers": args.layers, "chips": args.chips, **split,
        "compiled_with": "the builder's options" if not args.packed
        else sorted(args.options or ()),
        "compile_s": round(time.perf_counter() - t0, 1),
        "argument_gb": memory.argument_size_in_bytes / 1e9,
        "temp_gb": memory.temp_size_in_bytes / 1e9,
        "code_mb": memory.generated_code_size_in_bytes / 1e6,
        "census_weight_gradients": schedule.census(WEIGHT_GRADIENT_BYTES),
        "census_all": schedule.census(),
    }))


if __name__ == "__main__":
    main()
