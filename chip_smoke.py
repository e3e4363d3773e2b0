#!/usr/bin/env python
"""Proof that the main path starts and computes correctly on the TPU.

Run from the repo root on a machine with the chip:

    python chip_smoke.py            # one chip: phases resnet50, lm,
                                    # block_diffusion
    python chip_smoke.py --chips 4  # four chips: phases sp_tp, dp only

Every phase calls an example's ``main([...])`` in THIS process (one
process per chip: nothing here starts a child), at the full width of
the model, checks what comes out and prints one JSON line.  A failed
check raises, so the script exits non-zero at the first failing phase.
Without a TPU it exits non-zero before any phase; it has no CPU mode.
The last line of stdout is ``{"ok": true, "device": {...}}``.

Seconds are split into ``compile_s`` — time inside the backend compiler
or loading from the persistent compilation cache
(``chainermn_tpu.utils.compile_cache``) — and ``run_s``, the rest of the
phase's wall clock (data generation, tracing, execution, checks).
"""

import argparse
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# ResNet-50 at the bench headline width: 224 px, 1000 classes, batch 128.
RESNET_ARGV = [
    "--arch", "resnet50", "--communicator", "hierarchical",
    "--image-size", "224", "--num-classes", "1000", "--batchsize", "128",
    "--n-train", "512", "--n-val", "128", "--epoch", "1",
]
# The widest dense LM the repo has: 8 layers x 1024, 8 heads of 128,
# vocab 32768, seq 2048, batch 8.
LM_WIDTH_ARGV = [
    "--flash", "--seq-len", "2048", "--vocab", "32768", "--d-model", "1024",
    "--n-layers", "8", "--n-heads", "8", "--batchsize", "8",
    "--report-every", "1",
]
LM_ARGV = LM_WIDTH_ARGV + [
    "--steps", "4", "--generate", "8", "--serve", "4",
    "--serve-capacity", "4", "--serve-tokens", "16",
]
LM_SP_TP_ARGV = LM_WIDTH_ARGV + [
    "--sp", "2", "--tp", "2", "--steps", "2", "--generate", "0",
]
LM_DP_ARGV = LM_WIDTH_ARGV + ["--steps", "2", "--generate", "0"]
#: of the LM step's weight-gradient all-reduce bytes over four chips, the
#: share that is asynchronous with compute inside: the ahead-of-time
#: compile at LM_WIDTH_ARGV reads 34 of 34, all of the bytes (the cell's
#: 18-layer step 74 of 74), the embedding's float32 sum among them; with
#: the k-loop fusions left out of the option set it read 17 of 34,
#: 0.2195 (PR 31).  Set from the ahead-of-time reading (PR 33)
LM_DP_OVERLAPPED_SHARE = 0.99


def check(cond, msg):
    """A phase check that ``python -O`` cannot remove."""
    if not cond:
        raise AssertionError(msg)


def load_example(rel_path):
    """Import ``examples/<rel_path>`` as a module, the way a user's
    ``python examples/...`` would find it."""
    path = os.path.join(HERE, "examples", rel_path)
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


class PhaseClock:
    """Wall clock of a phase, with the backend-compile share split off
    (JAX reports every compile, and every persistent-cache load, as a
    ``backend_compile_duration`` event)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self._t0 = time.perf_counter()
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, **_):
        if event == self._EVENT:
            self.compile_s += duration

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)

    def seconds(self):
        total = time.perf_counter() - self._t0
        return {"compile_s": round(self.compile_s, 3),
                "run_s": round(total - self.compile_s, 3)}


def report(phase, device, clock, **fields):
    print(json.dumps({"phase": phase, **clock.seconds(), **fields,
                      "device_kind": device["kind"],
                      "device_count": device["count"]}), flush=True)


def lowered_step_text(out):
    """StableHLO of the train step an example's ``main`` returned, for
    the arguments it last ran with."""
    jitted = out["step"].get_jitted(out["params"], out["opt_state"])
    return jitted.lower(
        out["params"], out["opt_state"], out["batch"]).as_text()


def all_finite(values):
    return all(math.isfinite(float(v)) for v in values)


def max_rel_err(got, ref):
    """max|got - ref| over max|ref|, in float32 on the host."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# bf16 has 8 bits of mantissa; the repo's kernel tests hold bf16 results
# to 2e-2 (tests/test_pallas_kernels.py::test_bf16_inputs)
BF16_TOL = 2e-2


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------
def phase_resnet50(device, argv=RESNET_ARGV):
    train_imagenet = load_example("imagenet/train_imagenet.py")
    with PhaseClock() as clock:
        out = train_imagenet.main(argv)
        final = out["final"]
        check(out["comm"].devices[0].platform == device["platform"],
              f"communicator is on {out['comm'].devices[0].platform}")
        check(len(out["losses"]) >= 3, f"only {len(out['losses'])} steps")
        check(all_finite(out["losses"]), f"loss not finite: {out['losses']}")
        val = {k: final[k] for k in ("val/loss", "val/accuracy")}
        check(all_finite(val.values()), f"validation not finite: {val}")
        report("resnet50", device, clock, steps=len(out["losses"]),
               losses=out["losses"], **val)


def _fwd_bwd(attend, q, k, v, g):
    """``(out, dq, dk, dv)`` of ``attend`` under the cotangent ``g``."""
    import jax
    import jax.numpy as jnp

    def run(q, k, v, g):
        def f(q, k, v):
            out = attend(q, k, v)
            return (out.astype(jnp.float32) * g).sum(), out

        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    return jax.jit(run)(q, k, v, g)


def _flash_vs_dense(b, s, h, d):
    """One flash forward+backward at the train step's attention shape
    against ``ops.attention``'s dense core, both on the chip."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.attention import multi_head_attention
    from chainermn_tpu.ops.pallas_attention import flash_attention

    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
                  for key in (kq, kk, kv, kg))

    flash = _fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), q, k, v, g)
    dense = _fwd_bwd(lambda q, k, v: multi_head_attention(
        q, k, v, causal=True), q, k, v, g)
    errs = {name: max_rel_err(a, r)
            for name, a, r in zip(("out", "dq", "dk", "dv"), flash, dense)}
    check(max(errs.values()) <= BF16_TOL,
          f"flash vs dense beyond bf16 tolerance {BF16_TOL}: {errs}")
    return errs


def _decode_flash_vs_dense(served, params, capacity, n_tokens):
    """The served requests again, on two engines that differ only in the
    decode attend: the dense gather and the paged Pallas kernel.  Both
    are fed the dense engine's greedy tokens, so every step compares
    logits for the same cache contents."""
    import numpy as np

    from chainermn_tpu.serving.decode import DecodeEngine

    prompts = [r.prompt for r in served["requests"]]
    check(len(prompts) <= capacity, "more requests than decode slots")
    engines = {
        impl: DecodeEngine(served["model"], params, capacity=capacity,
                           attention_impl=impl)
        for impl in ("dense", "flash")
    }
    tokens = np.zeros((capacity,), np.int32)
    worst = 0.0
    for slot, prompt in enumerate(prompts):
        rows = {}
        for impl, eng in engines.items():
            got = eng.admit(len(prompt) + n_tokens)
            check(got == slot, f"{impl} engine admitted slot {got}")
            rows[impl] = eng.prefill(slot, prompt)
        worst = max(worst, max_rel_err(rows["flash"], rows["dense"]))
        tokens[slot] = int(np.argmax(rows["dense"]))
    active = list(range(len(prompts)))
    for _ in range(n_tokens - 1):
        logits = {impl: eng.decode_step(tokens)[active]
                  for impl, eng in engines.items()}
        check(np.isfinite(logits["flash"]).all(), "flash decode not finite")
        worst = max(worst, max_rel_err(logits["flash"], logits["dense"]))
        tokens[active] = np.argmax(logits["dense"], axis=-1)
    check(worst <= BF16_TOL,
          f"flash decode logits off dense by {worst} > {BF16_TOL}")
    return worst


def phase_lm(device, argv=LM_ARGV):
    from chainermn_tpu.ops import pallas_attention as pa
    from chainermn_tpu.resilience import log as rlog

    train_lm = load_example("lm/train_lm.py")
    args = dict(zip(argv, argv[1:]))
    b, s, h = (int(args[k]) for k in ("--batchsize", "--seq-len",
                                      "--n-heads"))
    d = int(args["--d-model"]) // h
    check(not pa._should_interpret(None),
          "the flash kernels would run interpreted here")
    events = rlog.ResilienceLog()
    rlog.attach(events)
    try:
        with PhaseClock() as clock:
            out = train_lm.main(argv)
            losses = out["losses"]
            check(all_finite(losses), f"loss not finite: {losses}")
            check(losses[-1] < losses[0], f"loss did not fall: {losses}")
            served = out["served"]
            done = [r.state for r in served["results"]]
            check(done == ["done"] * len(served["requests"]),
                  f"requests not done: {done}")
            n_kernels = lowered_step_text(out).count("tpu_custom_call")
            # per layer: flash forward, dq, dk/dv
            check(n_kernels >= 3,
                  f"{n_kernels} tpu_custom_call in the lowered train step")
            flash_err = _flash_vs_dense(b, s, h, d)
            decode_err = _decode_flash_vs_dense(
                served, out["params"], int(args["--serve-capacity"]),
                int(args["--serve-tokens"]),
            )
            retries = events.events("kernel_retry")
            check(not retries,
                  f"backward blocks shrank at default geometry: {retries}")
            census = pa.launch_census(s, s, d, causal=True)
            geometry = {
                kind: [-(-s // c["n_q_blocks"]), -(-s // c["n_k_blocks"])]
                for kind, c in census.items()
            }
            report("lm", device, clock, losses=losses,
                   requests_done=len(done),
                   tokens_generated=served["report"]["tokens_generated"],
                   tpu_custom_calls=n_kernels, flash_blocks=geometry,
                   flash_vs_dense_max_rel_err=flash_err,
                   decode_flash_vs_dense_max_rel_err=decode_err,
                   kernel_retries=len(retries))
    finally:
        rlog.detach(events)


# ----------------------------------------------------------------------
# four chips: the cross-chip paths and what they are compared with
# ----------------------------------------------------------------------
def _resnet_setup(train_imagenet, comm, args, *, mnbn, overlap="none"):
    """What ``train_imagenet.main`` builds, from the same library calls,
    on ``comm``: (step, params, opt_state, batch iterator)."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.links import create_mnbn_model
    from chainermn_tpu.utils import SyntheticImageDataset

    size, classes, batch = (int(args[k]) for k in (
        "--image-size", "--num-classes", "--batchsize"))
    train = SyntheticImageDataset(
        int(args["--n-train"]), shape=(size, size, 3),
        n_classes=min(classes, 64), seed=0)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    it = train_imagenet._RngBatchIterator(
        SerialIterator(train, batch, shuffle=True, seed=1),
        n_local_shards=comm.size, shard_base=0, n_global_shards=comm.size)
    model = train_imagenet.make_model(args["--arch"], classes, train=True)
    if mnbn:
        model = create_mnbn_model(model, comm)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, size, size, 3), jnp.bfloat16))
    params = {"params": variables["params"],
              "batch_stats": variables.get("batch_stats", {})}
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, overlap=overlap)
    step = cmn.build_train_step(
        comm, train_imagenet.make_loss_fn(
            model, lambda x: x.astype(jnp.bfloat16)),
        opt, has_aux=True,
        merge_aux=lambda p, aux: {**p, "batch_stats": aux})
    params, opt_state = step.place(params, opt.init(params))
    return step, params, opt_state, it


def _run_steps(step, params, opt_state, batches):
    losses = []
    for batch in batches:
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(metrics["loss"])
    return [float(l) for l in losses]


def _bytes_in_use(devices):
    return {d.id: d.memory_stats()["bytes_in_use"] for d in devices}


def _check_placement(devices, trainer, step, sample_batch, rows_per_chip):
    """Params, optimizer state and batch really are on every chip."""
    import jax

    from chainermn_tpu.communicators._topology import Topology

    topo = Topology.create(devices)
    check(topo.inter_size == 1 and set(topo.intra_sizes) == {len(devices)},
          f"topology inter={topo.inter_size} intra={topo.intra_sizes}")
    for name, tree in (("params", trainer.updater.params),
                       ("opt_state", trainer.updater.opt_state)):
        for leaf in jax.tree_util.tree_leaves(tree):
            on = {s.device for s in leaf.addressable_shards}
            check(on == set(devices),
                  f"a {name} leaf lives on {len(on)} of {len(devices)} chips")
    x = step.place_batch(sample_batch)[0]
    shard_rows = sorted((s.device.id, s.data.shape[0])
                        for s in x.addressable_shards)
    check([r for _, r in shard_rows] == [rows_per_chip] * len(devices)
          and len({i for i, _ in shard_rows}) == len(devices),
          f"batch shards (device id, rows): {shard_rows}")
    in_use = _bytes_in_use(devices)
    # ResNet-50's parameters and momentum alone are ~200 MB on each chip
    check(min(in_use.values()) > 100e6, f"bytes_in_use per chip: {in_use}")
    return {
        "coords": [list(getattr(d, "coords", ())) for d in topo.devices],
        "slice_index": [getattr(d, "slice_index", None)
                        for d in topo.devices],
        "inter_size": topo.inter_size, "intra_size": topo.intra_sizes[0],
        "batch_shard_rows": [r for _, r in shard_rows],
        "bytes_in_use": in_use,
    }


def phase_dp(device, argv=RESNET_ARGV):
    import jax
    import numpy as np

    import chainermn_tpu as cmn
    from chainermn_tpu.analysis import assert_census_agreement, check_overlap
    from chainermn_tpu.analysis.hlo import hlo_census
    from chainermn_tpu.comm_wire.planner import plan_of_tree

    train_imagenet = load_example("imagenet/train_imagenet.py")
    args = dict(zip(argv, argv[1:]))
    devices = jax.devices()
    name = args["--communicator"]
    with PhaseClock() as clock:
        # the example, sync-BN, over every chip
        out = train_imagenet.main(argv + ["--mnbn"])
        losses_n = out["losses"]
        check(all_finite(losses_n), f"loss not finite: {losses_n}")
        check(set(out["comm"].devices) == set(devices),
              "the example's communicator does not span every chip")

        # the same global batches and seed on one chip
        one = cmn.create_communicator(name, devices=devices[:1])
        step1, p1, o1, it1 = _resnet_setup(
            train_imagenet, one, args, mnbn=True)
        batches = [next(it1) for _ in losses_n]
        x, y, _ = batches[0]
        placement = _check_placement(
            devices, out["trainer"], out["step"],
            (x, y, np.arange(len(devices), dtype=np.int32)),
            int(args["--batchsize"]) // len(devices))
        del out
        losses_1 = _run_steps(step1, p1, o1, batches)
        first = abs(losses_n[0] - losses_1[0]) / abs(losses_1[0])
        check(first <= BF16_TOL,
              f"first-step loss {losses_n[0]} on {len(devices)} chips vs "
              f"{losses_1[0]} on one")
        del step1, p1, o1, it1, batches

        # bucket overlap against the synchronous bucketed step
        comm = cmn.create_communicator(name, devices=devices)
        runs, census = {}, {}
        for mode in ("none", "bucket"):
            step, p, o, it = _resnet_setup(
                train_imagenet, comm, args, mnbn=False, overlap=mode)
            batches = [step.place_batch(next(it)) for _ in range(4)]
            jitted = step.get_jitted(p, o)
            plan = plan_of_tree(p)
            n_stats = len(jax.tree_util.tree_leaves(p["batch_stats"]))
            trace = step.collective_trace(p, o, batches[0])
            lowered = jitted.lower(p, o, batches[0])
            assert_census_agreement(trace, lowered.as_text())
            census[mode] = {
                "traced": trace.census(),
                "compiled": hlo_census(lowered.compile().as_text()),
            }
            if mode == "bucket":
                late = check_overlap(
                    jitted.scheduled_jaxpr(p, o, batches[0]), plan)
                check(late == [], f"overlap ordering: {late}")
            runs[mode] = _run_steps(step, p, o, batches)
            del step, p, o, it, batches, jitted, lowered
        # the gradient wire: the plan's buckets plus the loss pmean.  The
        # BN running statistics (has_aux) are mean-reduced leaf by leaf
        # beside it, one more all-reduce each.
        traced = census["none"]["traced"].get("all_reduce")
        check(traced == plan.n_buckets + 1 + n_stats,
              f"{traced} all-reduces traced; the bucket plan promises "
              f"{plan.n_buckets} + 1 loss, plus {n_stats} BN statistics")
        check(census["bucket"]["traced"] == census["none"]["traced"],
              f"overlap changed the census: {census}")
        check(1 <= census["none"]["compiled"].get("all_reduce", 0) <= traced,
              f"compiled census {census['none']['compiled']}")
        check(runs["none"] == runs["bucket"],
              f"overlap='bucket' losses {runs['bucket']} differ from the "
              f"synchronous {runs['none']}")
        lm = _lm_dp_schedule(devices)
        report("dp", device, clock, communicator=name, placement=placement,
               losses_n_chips=losses_n, losses_one_chip=losses_1,
               first_step_rel_diff=first, n_buckets=plan.n_buckets,
               bn_statistic_leaves=n_stats,
               all_reduce_census=census, overlap_losses=runs["bucket"],
               sync_losses=runs["none"], **lm)


def _lm_dp_schedule(devices, argv=LM_DP_ARGV):
    """The LM's ``param_specs`` step, data-parallel over every chip: its
    gradient all-reduces are autodiff's, one a leaf, and the step builder
    compiles every one asynchronous, riding a weight-gradient matmul or
    another leaf's AdamW update.  Read from the program that ran."""
    from chainermn_tpu.analysis.hlo import WEIGHT_GRADIENT_BYTES

    out = load_example("lm/train_lm.py").main(argv)
    losses = out["losses"]
    check(all_finite(losses) and losses[-1] < losses[0],
          f"LM loss over {len(devices)} chips: {losses}")
    check(out["comm"].dp_size == len(devices),
          f"the LM's mesh is dp={out['comm'].dp_size}")
    schedule = out["step"].collective_schedule(
        out["params"], out["opt_state"], out["batch"])
    grads = schedule.census(min_bytes=WEIGHT_GRADIENT_BYTES)
    check(grads["n_async"] == grads["n_overlapped"]
          and grads["overlapped_bytes_share"] >= LM_DP_OVERLAPPED_SHARE,
          f"weight-gradient all-reduces asynchronous with compute inside: "
          f"under {LM_DP_OVERLAPPED_SHARE} of the bytes, or one without: "
          f"{grads}\n{schedule.condensed}")
    return {"lm_dp_losses": losses,
            "lm_dp_weight_gradient_reductions": grads,
            "lm_dp_schedule": schedule.condensed}


def phase_sp_tp(device, argv=LM_SP_TP_ARGV):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.models.transformer import lm_loss
    from chainermn_tpu.parallel import sharded_init

    train_lm = load_example("lm/train_lm.py")
    args = dict(zip(argv, argv[1:]))
    batch, seq, vocab = (int(args[k]) for k in (
        "--batchsize", "--seq-len", "--vocab"))
    with PhaseClock() as clock:
        out = train_lm.main(argv)
        losses = out["losses"]
        check(all_finite(losses), f"loss not finite: {losses}")
        comm, model, specs = out["comm"], out["model"], out["specs"]
        check((comm.sp_size, comm.tp_size) == (2, 2),
              f"mesh is sp={comm.sp_size} tp={comm.tp_size}")
        check("tpu_custom_call" in lowered_step_text(out),
              "ring attention did not take its flash tier")
        del out

        # the example's initial parameters and first batch again
        corpus = train_lm.synthetic_corpus(
            max(batch * 8, 64), seq, vocab, seed=0)
        params0, _ = sharded_init(
            lambda t: model.init(
                {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)}, t),
            comm.mesh, (P("mn_data", "mn_seq"),), lambda tree: specs,
            jnp.asarray(corpus[:batch]),
        )
        rows = np.random.RandomState(1).randint(
            0, corpus.shape[0], size=batch)

        # the dense twin on one chip: no sequence axis, dense attention,
        # the tensor axis at width 1 (same parameter tree)
        one = cmn.create_communicator(
            "mesh", devices=jax.devices()[:1], sp_size=1, tp_size=1)
        twin = model.clone(seq_axis=None, attention_fn=None)
        params_one = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(
                np.asarray(x), NamedSharding(one.mesh, s)),
            params0, specs)
        twin_loss = jax.jit(jax.shard_map(
            lambda p, t: lm_loss(twin.apply(p, t), t),
            mesh=one.mesh, in_specs=(specs, P("mn_data", "mn_seq")),
            out_specs=P(), check_vma=False,
        ))
        ref = float(twin_loss(
            params_one,
            jax.device_put(corpus[rows],
                           NamedSharding(one.mesh, P("mn_data", "mn_seq"))),
        ))
        diff = abs(losses[0] - ref) / abs(ref)
        check(diff <= BF16_TOL,
              f"first-step loss {losses[0]} under sp=2 x tp=2 vs {ref} for "
              "the dense twin on one chip")
        report("sp_tp", device, clock, mesh="dp1 x sp2 x tp2",
               losses=losses, dense_twin_first_loss=ref,
               first_step_rel_diff=diff)


def phase_block_diffusion(device, b=1, s=8192, hq=32, hkv=4, d=128,
                          block=4):
    """The block-causal grouped-query kernels at the shape of
    ``sdar30b_train_bd4_s8192`` (one sequence as clean + noised copy)
    against the dense form, forward and all three gradients."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.pallas_attention import (
        block_diffusion_attention,
        block_diffusion_attention_dense,
    )

    with PhaseClock() as clock:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, g = (jax.random.normal(key, (b, 2 * s, hq, d), jnp.bfloat16)
                for key in keys[:2])
        k, v = (jax.random.normal(key, (b, 2 * s, hkv, d), jnp.bfloat16)
                for key in keys[2:])

        kernels = _fwd_bwd(lambda q, k, v: block_diffusion_attention(
            q, k, v, block, interpret=False), q, k, v, g)
        dense = _fwd_bwd(lambda q, k, v: block_diffusion_attention_dense(
            q, k, v, block, query_rows=512), q, k, v, g)
        errs = {name: max_rel_err(a, r) for name, a, r in zip(
            ("out", "dq", "dk", "dv"), kernels, dense)}
        check(max(errs.values()) <= BF16_TOL,
              f"block-diffusion kernels vs dense beyond bf16 tolerance "
              f"{BF16_TOL}: {errs}")
        report("block_diffusion", device, clock, shape=[b, 2 * s, hq, hkv, d],
               block=block, kernels_vs_dense_max_rel_err=errs)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the cross-chip paths (dp, sp_tp) and what "
                        "they are compared with")
    chips = p.parse_args(argv).chips

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {device}")
    if device["count"] != chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX found {device}")

    from chainermn_tpu.utils.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    for phase in ((phase_resnet50, phase_lm, phase_block_diffusion)
                  if chips == 1 else (phase_sp_tp, phase_dp)):
        phase(device)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
