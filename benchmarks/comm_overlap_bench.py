#!/usr/bin/env python
"""Exposed-communication + double-buffering A/Bs, measured.

How much of the gradient exchange is *exposed*, and what the
double-buffering knob buys, via the reference's DummyCommunicator
methodology (SURVEY.md section 5.1): run the same training config with
and without the exchange, subtract.  A pre-cell script: no number of
its is on the ledger; cell B1 (``ROADMAP.md`` Reach B1, the bucketed
wire on four chips) decides what of it becomes a cell.

Variants (each prints one JSON line; k steps in ONE jitted fori_loop):

Three rungs per config:
  *_sync   build_train_step over the real communicator (psum in program)
  *_dummy  build_train_step over DummyCommunicator — the IDENTICAL
           compiled program minus the gradient exchange, so
           (sync - dummy)/sync is the exposed-communication share with
           everything else held equal
  *_bare   a bare jitted optax step, no communicator machinery at all

real-chip tier (default; 1-device mesh — the psum degenerates, so
sync-vs-dummy bounds the single-chip machinery+collective cost):
    resnet_{sync,dummy,bare}        ResNet-50 b128 224^2, sgd+momentum
    lm_{sync,dummy,bare}            TransformerLM 8L/1024d b8 s2048, adamw

virtual-mesh tier (--cpu-mesh; 8 virtual devices — the psum REALLY
crosses ranks; CPU-confounded in that all 8 share host cores, so the
exposed share here is a *pessimistic upper bound*: there is zero spare
bandwidth to hide anything):
    mesh_{sync,dummy}               MLP-1000 b2048-global
    mesh_db_on / mesh_db_off        same config, double_buffering A/B
    mesh_resnet_{sync,dummy,db_on,db_off}
                                    ResNet-18 32^2 b128-global (conv mix)

overlap_* rungs (ISSUE 8): the bucket-granularity overlap A/B —
``overlap_off``/``overlap_on`` (MLP), ``overlap_resnet_off/on``
(ResNet-18 conv mix), ``overlap_int8_on`` (compressed wire under the
schedule).  Both legs run the bit-identical program; only the issue
order of the bucket psums moves, so the ratio isolates pure
scheduling.  On the CPU mesh the collectives share the host's cores
with compute, so the A/B here bounds machinery cost — the ICI win
needs the TPU capture.

wire_flat / wire_hier / wire_hier_int8 rungs (ISSUE 11): the multi-hop
schedule A/B on ONE hierarchical mesh (CPU tier: 2 synthetic slices of
4 via CHAINERMN_TPU_FAKE_SLICE_SIZE).  wire_flat is the single-psum
baseline, wire_hier the full-precision rs→ar→ag triple, wire_hier_int8
the int8+EF inter hop.  Every row carries the schedule/codec
fingerprint (``wire_schedules`` census + ``wire_plan_hash``) so a
capture pins WHICH program it measured.

wire_tuned_* rungs (ISSUE 12): the measured-feedback autotune A/B —
``wire_tuned_base`` (fixed 4 MiB/6-slot constants) vs ``wire_tuned``
(BandwidthProfile -> trace-driven bucket sizing + profile-driven
schedule choice), on the flat CPU mesh and
(``wire_tuned_hier_base``/``wire_tuned_hier``) the synthetic 2-slice
hierarchical mesh.  The tuned legs prefer a PINNED profile
(``CHAINERMN_TPU_WIRE_PROFILE`` whose mesh signature matches — a
stable hash, so captures stay comparable) and calibrate in-process only
without one (a fresh hash every capture: a disclosed retune).  Tuned
rows carry ``profile_hash`` /
``tuned_bucket_bytes`` / ``tuned_max_buckets`` /
``predicted_sync_ms`` beside the plan fingerprints.

telemetry_overhead (ISSUE 10): the observability layer's enabled-vs-
disabled A/B on the host-driven Updater path (span sites live on the
host; the fori_loop harness would measure nothing), min-of-N fields
sourced from the shared ``observability.metrics.Histogram``.

Usage:
    python benchmarks/comm_overlap_bench.py                  # real chip
    python benchmarks/comm_overlap_bench.py --cpu-mesh       # 8 virt dev
    python benchmarks/comm_overlap_bench.py resnet_sync resnet_nosync
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--cpu-mesh" in sys.argv:
    sys.argv.remove("--cpu-mesh")
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    CPU_MESH = True
else:
    CPU_MESH = False

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from chainermn_tpu.utils.benchmarking import time_kloop

K = int(os.environ.get("HUNT_K", "8" if CPU_MESH else "40"))
REPEATS = int(os.environ.get("HUNT_REPEATS", "2"))


def _time_kloop(ksteps, params, opt_state):
    return time_kloop(
        lambda n: ksteps(params, opt_state, n)[2], K, REPEATS
    )


def _emit(name, dt, dts, batch, **extra):
    pos = [d for d in dts if d > 0]
    rec = {
        "variant": name,
        "step_time_ms": round(dt * 1e3, 3),
        "samples_ms": [round(d * 1e3, 3) for d in dts],
        # bench-wide min-of-N disclosure (the protocol every timed row
        # carries): how many paired measurements, how far apart
        "n_measurements": len(dts),
        "k": K,
        "global_batch": batch,
    }
    if len(pos) >= 2:
        rec["spread_max_over_min"] = round(max(pos) / min(pos), 3)
    rec.update(extra)
    print(json.dumps(rec), flush=True)


def _pinned_profile(mesh):
    """The committed-beside-the-capture BandwidthProfile named by
    ``CHAINERMN_TPU_WIRE_PROFILE``, or ``None`` when the tuned rung
    should calibrate in-process.  A pinned path that no longer resolves
    would otherwise silently demote every capture to in-process
    calibration — fresh hash each run, so tuned rows read as RETUNED
    forever — so a MISSING file is disclosed on stderr (rows go to
    stdout).  A mesh-signature mismatch stays silent by design: one
    pinned file can only match one rung's mesh, and the other rungs
    falling back fresh is the documented normal capture shape."""
    from chainermn_tpu.comm_wire.autotune import (
        PROFILE_ENV, BandwidthProfile,
    )

    pinned = os.environ.get(PROFILE_ENV)
    if not pinned:
        return None
    if not os.path.exists(pinned):
        print(
            f"comm_overlap_bench: {PROFILE_ENV}={pinned!r} does not "
            "exist — falling back to in-process calibration (tuned "
            "rows get a fresh profile_hash and read as retuned, not "
            "comparable with a pinned capture)",
            file=sys.stderr,
        )
        return None
    cand = BandwidthProfile.load(pinned)
    return cand if cand.matches_mesh(mesh) else None


def _run_sync(name, model_ctor, batch_fn, loss_of, tx, *,
              double_buffering=False, comm_name="tpu", wire="auto",
              overlap="none", profile=None, tune_self=False, **extra):
    """Multi-node tier: build_train_step over the communicator's mesh —
    grad psum + update in one program (k of them in one fori_loop).
    ``wire`` selects the gradient wire (per_leaf / auto-bucketed /
    codec name / WireConfig) — the wire_* rung axis.  ``overlap``
    selects the bucket-granularity overlap engine — the overlap_*
    rung axis (bit-identical program, reordered so each bucket's psum
    issues under the remaining backward).  ``profile`` (ISSUE 12)
    feeds the measured-feedback autotuner — the sentinel
    ``"calibrate"`` runs a short in-process calibration sweep on the
    rung's own communicator (sizes via ``HUNT_CAL_SIZES``, bytes,
    comma-separated); ``tune_self=True`` additionally traces the
    step once and rebuilds the optimizer with ``tune_trace=`` so the
    bucket sizing comes from the tuner, not the constants — the
    wire_tuned_* rung axis."""
    import chainermn_tpu as cmn

    comm = cmn.create_communicator(comm_name)
    if profile == "calibrate":
        from chainermn_tpu.comm_wire.autotune import calibrate

        # a PINNED profile (the env path, committed beside the capture)
        # takes precedence when it matches this rung's mesh: its hash
        # is then stable across captures, so tuned rows stay
        # comparable.  Only without one does the rung calibrate
        # in-process — a fresh hash every capture, a disclosed retune.
        profile = _pinned_profile(comm.mesh)
        if profile is None:
            sizes = tuple(int(s) for s in os.environ.get(
                "HUNT_CAL_SIZES", "16384,262144,1048576"
            ).split(","))
            profile = calibrate(comm, sizes=sizes, repeats=1,
                                label=f"bench:{name}")
    model = model_ctor()
    x, y, init_arg = batch_fn(comm)
    params0 = comm.bcast_data(model.init(jax.random.PRNGKey(0), init_arg))

    def build(tune_trace=None):
        opt = cmn.create_multi_node_optimizer(
            tx, comm, double_buffering=double_buffering, wire=wire,
            overlap=overlap, profile=profile, tune_trace=tune_trace,
        )
        step = cmn.build_train_step(
            comm, lambda p, b: loss_of(model, p, b), opt, donate=False
        )
        return opt, step

    opt, step = build()
    params, opt_state = step.place(params0, opt.init(params0))
    bx = jax.device_put(x, step.batch_sharding)
    by = jax.device_put(y, step.batch_sharding)
    if tune_self:
        # the tuned leg: trace the baseline-built step (free — nothing
        # runs), hand the trace's cost records + the profile to the
        # factory, rebuild.  The rebuilt plan is what the fingerprint
        # fields below disclose.
        tr = step.collective_trace(params, opt_state, (bx, by))
        opt, step = build(tr)
        params, opt_state = step.place(params0, opt.init(params0))
        # what the measured model PREDICTS for the tuned program's
        # reductions — held beside the measured step time on the row,
        # so a capture shows prediction quality, not just the verdict
        from chainermn_tpu.comm_wire.autotune import predict_sync_time

        tuned_tr = step.collective_trace(params, opt_state, (bx, by))
        pred = predict_sync_time(tuned_tr.records, profile)
        if pred is not None:
            extra.setdefault("predicted_sync_ms", round(pred * 1e3, 4))
    inner = step.get_jitted(params, opt_state)

    @jax.jit
    def ksteps(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            p, o, m = inner(p, o, (bx, by))
            return p, o, m["loss"]

        return lax.fori_loop(0, n, body, (p, o, jnp.float32(0)))

    extra = dict(extra)
    extra.setdefault("overlap", getattr(opt, "overlap", "none"))
    if getattr(opt, "wire", None) is not None:
        # schedule-aware fingerprint (ISSUE 11): the per-bucket
        # schedule census + agreed plan hash identify WHAT program a
        # wire_* row measured, so a capture where the planner silently
        # collapsed hier to flat reads as a config change, not noise.
        # opt.wire_plan folds the profile in (ISSUE 12), so the hash
        # here IS the one plan_agreement would exchange.
        wplan = opt.wire_plan(params)
        plan = wplan.plan
        extra.setdefault("wire_codec", opt.wire.codec)
        extra.setdefault("wire_buckets", plan.n_buckets)
        extra.setdefault("wire_n_leaves", plan.n_leaves)
        extra.setdefault("wire_schedules", wplan.schedule_census())
        extra.setdefault("wire_plan_hash", wplan.plan_hash()[:12])
        extra.setdefault("mesh_shape", dict(comm.mesh.shape))
        if getattr(opt, "profile", None) is not None:
            # tuned-row provenance (ISSUE 12): the profile content
            # hash makes a retune read as a DISCLOSED config change,
            # and the tuned knobs show what the tuner actually chose
            extra.setdefault("profile_hash",
                             opt.profile.profile_hash()[:12])
            extra.setdefault("tuned_bucket_bytes", opt.wire.bucket_bytes)
            extra.setdefault("tuned_max_buckets", opt.wire.max_buckets)
    else:
        extra.setdefault("wire_codec", "per_leaf")
        extra.setdefault(
            "wire_n_leaves",
            len(jax.tree_util.tree_leaves(params)),
        )
    dt, dts = _time_kloop(ksteps, params, opt_state)
    _emit(name, dt, dts, int(x.shape[0]), **extra)


def _run_telemetry_overhead(model_ctor, batch_fn, loss_of, tx):
    """ISSUE 10 rung: the telemetry overhead A/B on the HOST-DRIVEN
    step path (Updater.update's span sites — a compiled k-in-fori_loop
    harness would measure nothing: the instrumentation is host-side).
    Emits ``telemetry_overhead_off`` / ``_on`` rows timed by the SAME
    ``time_steps`` min-of-N protocol as every other rung — the raw
    samples it now returns land in an ``observability.metrics.Histogram``
    whose ``protocol_fields()`` produce the row's disclosure (one
    source for the reported number, the spread, and the telemetry
    histogram).  Plus a ``telemetry_overhead`` ratio row (on/off;
    ~1.0 = the contract's enabled-path cost is in the noise — the
    DISABLED-path ≤1 % contract is pinned separately by
    tests/test_observability.py)."""
    import itertools

    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.training.trainer import Updater
    from chainermn_tpu.utils.benchmarking import time_steps

    comm = cmn.create_communicator("tpu")
    model = model_ctor()
    x, y, init_arg = batch_fn(comm)
    params = comm.bcast_data(model.init(jax.random.PRNGKey(0), init_arg))
    opt = cmn.create_multi_node_optimizer(tx, comm)
    step = cmn.build_train_step(
        comm, lambda p, b: loss_of(model, p, b), opt, donate=False
    )
    p0, o0 = step.place(params, opt.init(params))
    batch = (
        jax.device_put(x, step.batch_sharding),
        jax.device_put(y, step.batch_sharding),
    )
    steps_per = max(K // 2, 2)
    results = {}
    for mode in ("off", "on"):
        upd = Updater(itertools.cycle([batch]), step, p0, o0)

        def run():
            upd.update()
            return upd.last_metrics["loss"]

        # the "off" leg must actually be off (a CHAINERMN_TPU_TELEMETRY
        # env activation would otherwise record through it, collapsing
        # the A/B to ~1.0), and teardown restores whatever was active
        # before instead of clobbering it for later rungs
        prev = obs.active()
        tel = obs.Telemetry(label="bench") if mode == "on" else None
        obs.install(tel)
        try:
            dt, dts = time_steps(run, steps_per, warmup=1,
                                 repeats=REPEATS)
        finally:
            obs.install(prev)
        hist = obs.Histogram(f"telemetry_overhead_{mode}")
        hist.extend(dts)
        results[mode] = dt
        rec = {
            "variant": f"telemetry_overhead_{mode}",
            "step_time_ms": round(dt * 1e3, 3),
            "samples_ms": [round(d * 1e3, 3) for d in dts],
            "k": steps_per,
            "global_batch": int(x.shape[0]),
            "telemetry": mode,
            # min-of-N disclosure from the telemetry Histogram — the
            # one shared protocol source (ISSUE 10 satellite)
            **hist.protocol_fields(),
        }
        if tel is not None:
            rec["spans_recorded"] = len(tel.timeline)
        print(json.dumps(rec), flush=True)
    if results["off"] > 0:
        print(json.dumps({
            "variant": "telemetry_overhead",
            "overhead_ratio": round(results["on"] / results["off"], 4),
            "n_measurements": 2 * REPEATS,
        }), flush=True)


def _run_bare(name, model_ctor, batch_fn, loss_of, tx):
    """Machinery rung: identical loss/optimizer arithmetic, NO
    communicator machinery at all — a bare jitted optax step on one
    shard's worth of batch.  sync - bare = shard_map + multi-node
    optimizer overhead (+ the exchange, where one exists)."""
    import chainermn_tpu as cmn

    comm = cmn.create_communicator("tpu")  # only for shard sizing
    model = model_ctor()
    x, y, init_arg = batch_fn(comm)
    shard = x.shape[0] // comm.size
    x, y = x[:shard], y[:shard]
    params = model.init(jax.random.PRNGKey(0), init_arg)
    opt_state = tx.init(params)

    def one_step(p, o):
        loss, grads = jax.value_and_grad(
            lambda p: loss_of(model, p, (x, y))
        )(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    @jax.jit
    def ksteps(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            return one_step(p, o)

        return lax.fori_loop(0, n, body, (p, o, jnp.float32(0)))

    dt, dts = _time_kloop(ksteps, params, opt_state)
    _emit(name, dt, dts, shard)


# ---- model/config builders ------------------------------------------


def _image_loss(model, p, b):
    x, y = b
    logits, _ = model.apply(
        {"params": p["params"], "batch_stats": p.get("batch_stats", {})},
        x, mutable=["batch_stats"],
    )
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _image_loss_plain(model, p, b):
    x, y = b
    logits, _ = model.apply(p, x, mutable=["batch_stats"])
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _resnet50_cfg():
    from chainermn_tpu.models import ResNet50

    def batch(comm):
        b = 128 * comm.size
        x = jnp.asarray(
            np.random.RandomState(0).randn(b, 224, 224, 3), jnp.bfloat16
        )
        y = jnp.asarray(
            np.random.RandomState(1).randint(0, 1000, (b,)), jnp.int32
        )
        return x, y, jnp.zeros((1, 224, 224, 3), jnp.bfloat16)

    return (lambda: ResNet50(train=True), batch,
            optax.sgd(0.1, momentum=0.9))


def _lm_cfg():
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss
    from chainermn_tpu.ops.pallas_attention import flash_attention_fn

    seq, vocab = 2048, 32768

    def ctor():
        return TransformerLM(
            vocab_size=vocab, d_model=1024, n_heads=8, n_layers=8,
            max_len=seq, attention_fn=flash_attention_fn(),
        )

    def batch(comm):
        b = 8 * comm.size
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, vocab, (b, seq)), jnp.int32
        )
        return toks, toks, jnp.zeros((1, seq), jnp.int32)

    def loss_of(model, p, b):
        return lm_loss(model.apply(p, b[0]), b[0])

    return ctor, batch, loss_of, optax.adamw(3e-4, weight_decay=0.01)


def _mlp_cfg():
    from chainermn_tpu.models import MLP

    units = int(os.environ.get("HUNT_MLP_UNITS", "1000"))
    b_per = int(os.environ.get("HUNT_MLP_BATCH", "256"))

    def ctor():
        return MLP(n_units=units, dtype=jnp.bfloat16)

    def batch(comm):
        b = b_per * comm.size
        x = jnp.asarray(
            np.random.RandomState(0).rand(b, 28, 28), jnp.float32
        )
        y = jnp.asarray(
            np.random.RandomState(1).randint(0, 10, (b,)), jnp.int32
        )
        return x, y, jnp.zeros((1, 28, 28))

    def loss_of(model, p, b):
        logits = model.apply(p, b[0])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b[1]
        ).mean()

    return ctor, batch, loss_of, optax.sgd(0.05)


def _resnet18_cfg():
    from chainermn_tpu.models import ResNet18

    def batch(comm):
        b = 16 * comm.size
        x = jnp.asarray(
            np.random.RandomState(0).randn(b, 32, 32, 3), jnp.bfloat16
        )
        y = jnp.asarray(
            np.random.RandomState(1).randint(0, 10, (b,)), jnp.int32
        )
        return x, y, jnp.zeros((1, 32, 32, 3), jnp.bfloat16)

    return (lambda: ResNet18(num_classes=10, train=True), batch,
            optax.sgd(0.1, momentum=0.9))


def _variants():
    rn_ctor, rn_batch, rn_tx = _resnet50_cfg()
    lm_ctor, lm_batch, lm_loss_of, lm_tx = _lm_cfg()
    ml_ctor, ml_batch, ml_loss_of, ml_tx = _mlp_cfg()
    r18_ctor, r18_batch, r18_tx = _resnet18_cfg()
    variants = {
        # real-chip tier.  *_dummy = DummyCommunicator at the compiled
        # tier: the identical program minus the gradient exchange —
        # (sync - dummy)/sync is the exposed-communication share.
        # *_bare = no communicator machinery at all.
        "resnet_sync": lambda: _run_sync(
            "resnet_sync", rn_ctor, rn_batch, _image_loss, rn_tx),
        "resnet_dummy": lambda: _run_sync(
            "resnet_dummy", rn_ctor, rn_batch, _image_loss, rn_tx,
            comm_name="dummy"),
        "resnet_bare": lambda: _run_bare(
            "resnet_bare", rn_ctor, rn_batch, _image_loss_plain, rn_tx),
        "lm_sync": lambda: _run_sync(
            "lm_sync", lm_ctor, lm_batch, lm_loss_of, lm_tx),
        "lm_dummy": lambda: _run_sync(
            "lm_dummy", lm_ctor, lm_batch, lm_loss_of, lm_tx,
            comm_name="dummy"),
        "lm_bare": lambda: _run_bare(
            "lm_bare", lm_ctor, lm_batch, lm_loss_of, lm_tx),
        # virtual-mesh tier (run with --cpu-mesh): the psum crosses ranks
        "mesh_sync": lambda: _run_sync(
            "mesh_sync", ml_ctor, ml_batch, ml_loss_of, ml_tx),
        "mesh_dummy": lambda: _run_sync(
            "mesh_dummy", ml_ctor, ml_batch, ml_loss_of, ml_tx,
            comm_name="dummy"),
        "mesh_db_on": lambda: _run_sync(
            "mesh_db_on", ml_ctor, ml_batch, ml_loss_of, ml_tx,
            double_buffering=True),
        "mesh_db_off": lambda: _run_sync(
            "mesh_db_off", ml_ctor, ml_batch, ml_loss_of, ml_tx),
        "mesh_resnet_sync": lambda: _run_sync(
            "mesh_resnet_sync", r18_ctor, r18_batch, _image_loss, r18_tx),
        "mesh_resnet_dummy": lambda: _run_sync(
            "mesh_resnet_dummy", r18_ctor, r18_batch, _image_loss, r18_tx,
            comm_name="dummy"),
        "mesh_resnet_db_on": lambda: _run_sync(
            "mesh_resnet_db_on", r18_ctor, r18_batch, _image_loss, r18_tx,
            double_buffering=True),
        "mesh_resnet_db_off": lambda: _run_sync(
            "mesh_resnet_db_off", r18_ctor, r18_batch, _image_loss,
            r18_tx),
        # communicator-variant A/B on identical grad-sync work: gives
        # `two_dimensional` its first perf presence (VERDICT r3 #7) and
        # validates each factorization's collective sequence end-to-end
        "mesh_comm_flat": lambda: _run_sync(
            "mesh_comm_flat", ml_ctor, ml_batch, ml_loss_of, ml_tx,
            comm_name="flat"),
        "mesh_comm_hierarchical": lambda: _run_sync(
            "mesh_comm_hierarchical", ml_ctor, ml_batch, ml_loss_of,
            ml_tx, comm_name="hierarchical"),
        "mesh_comm_two_dimensional": lambda: _run_sync(
            "mesh_comm_two_dimensional", ml_ctor, ml_batch, ml_loss_of,
            ml_tx, comm_name="two_dimensional"),
    }
    # wire_* rungs: the gradient-wire A/B ladder (per-leaf vs bucketed
    # vs bucketed+int8, sync/dummy pairs so exposed-comm share divides
    # into launch-count savings vs byte savings; db on/off rides the
    # bucketed path).  Runs on the CPU mesh (--cpu-mesh) in CI and on
    # chip for driver captures.
    from chainermn_tpu.comm_wire import WireConfig

    int8_ef = WireConfig(codec="int8", error_feedback=True)
    for rung, kw in {
        "wire_perleaf_sync": dict(wire="per_leaf"),
        "wire_perleaf_dummy": dict(wire="per_leaf", comm_name="dummy"),
        "wire_bucketed_sync": dict(wire="auto"),
        "wire_bucketed_dummy": dict(wire="auto", comm_name="dummy"),
        "wire_int8_sync": dict(wire=int8_ef),
        "wire_int8_dummy": dict(wire=int8_ef, comm_name="dummy"),
        # overlap_* rungs (ISSUE 8): the bucket-granularity overlap
        # A/B.  overlap_off IS wire_bucketed_sync's program (identical
        # config) but keeps its own rung name so the off/on pair reads
        # as one A/B and survives rung-list edits together.
        "overlap_off": dict(wire="auto", overlap="none"),
        "overlap_on": dict(wire="auto", overlap="bucket"),
        "overlap_int8_on": dict(wire=int8_ef, overlap="bucket"),
    }.items():
        variants[rung] = (
            lambda rung=rung, kw=kw: _run_sync(
                rung, ml_ctor, ml_batch, ml_loss_of, ml_tx, **kw
            )
        )
    # wire_flat / wire_hier / wire_hier_int8 rungs (ISSUE 11): the
    # multi-hop schedule A/B on the SAME hierarchical mesh.  On the CPU
    # mesh the 8 virtual devices are grouped into 2 synthetic slices of
    # 4 (CHAINERMN_TPU_FAKE_SLICE_SIZE — devices with a real
    # slice_index are never regrouped) so the ('mn_inter', 'mn_intra')
    # pair genuinely factorizes; on chip the rungs run on the real
    # slice topology.  Schedules are EXPLICIT per rung (not "auto") so
    # each row's fingerprint pins what program was measured; the CPU
    # A/B bounds scheduling machinery cost — the DCN-byte win needs the
    # TPU capture.
    hier_wire = WireConfig(schedule="hier_rs_ag")
    hier_int8 = WireConfig(codec="int8", error_feedback=True,
                           schedule="hier_rs_ag")

    def _run_hier_rung(rung, kw):
        prev = os.environ.get("CHAINERMN_TPU_FAKE_SLICE_SIZE")
        if CPU_MESH:
            os.environ["CHAINERMN_TPU_FAKE_SLICE_SIZE"] = "4"
        try:
            _run_sync(rung, ml_ctor, ml_batch, ml_loss_of, ml_tx, **kw)
        finally:
            if CPU_MESH:
                if prev is None:
                    os.environ.pop("CHAINERMN_TPU_FAKE_SLICE_SIZE", None)
                else:
                    os.environ["CHAINERMN_TPU_FAKE_SLICE_SIZE"] = prev

    for rung, kw in {
        "wire_flat": dict(wire=WireConfig(schedule="flat"),
                          comm_name="hierarchical"),
        "wire_hier": dict(wire=hier_wire, comm_name="hierarchical"),
        "wire_hier_int8": dict(wire=hier_int8,
                               comm_name="hierarchical"),
    }.items():
        variants[rung] = (
            lambda rung=rung, kw=kw: _run_hier_rung(rung, kw)
        )
    # wire_tuned_* rungs (ISSUE 12): the measured-feedback autotune
    # A/B.  *_base is the fixed-constant wire (identical machinery to
    # wire_bucketed_sync but its own rung name so the off/on pair reads
    # as one A/B and survives rung-list edits together); the tuned leg
    # calibrates a BandwidthProfile on the rung's own mesh, traces the
    # step, and rebuilds with profile+tune_trace — bucket sizing and
    # flat-vs-hier both measured.  Runs on the flat 8-dev CPU mesh AND
    # the CHAINERMN_TPU_FAKE_SLICE_SIZE hierarchical mesh (2 synthetic
    # slices of 4); every tuned row carries profile_hash /
    # wire_plan_hash / wire_schedules provenance.  On the CPU mesh the
    # profile measures dispatch latency, not interconnect — the A/B
    # bounds tuning machinery cost; the real curves need the TPU
    # capture.
    for rung, kw in {
        "wire_tuned_base": dict(wire="auto"),
        "wire_tuned": dict(wire="auto", profile="calibrate",
                           tune_self=True),
    }.items():
        variants[rung] = (
            lambda rung=rung, kw=kw: _run_sync(
                rung, ml_ctor, ml_batch, ml_loss_of, ml_tx, **kw
            )
        )
    for rung, kw in {
        "wire_tuned_hier_base": dict(wire="auto",
                                     comm_name="hierarchical"),
        "wire_tuned_hier": dict(wire="auto", comm_name="hierarchical",
                                profile="calibrate", tune_self=True),
    }.items():
        variants[rung] = (
            lambda rung=rung, kw=kw: _run_hier_rung(rung, kw)
        )
    # telemetry overhead A/B (ISSUE 10): host-driven step path,
    # enabled vs disabled, min-of-N fields from the shared Histogram
    variants["telemetry_overhead"] = lambda: _run_telemetry_overhead(
        ml_ctor, ml_batch, ml_loss_of, ml_tx
    )
    # the conv-mix overlap A/B (ResNet-18 on the virtual mesh): multi-
    # bucket plan over a real backward chain
    for rung, kw in {
        "overlap_resnet_off": dict(wire="auto", overlap="none"),
        "overlap_resnet_on": dict(wire="auto", overlap="bucket"),
    }.items():
        variants[rung] = (
            lambda rung=rung, kw=kw: _run_sync(
                rung, r18_ctor, r18_batch, _image_loss, r18_tx, **kw
            )
        )
    return variants


def main():
    variants = _variants()
    default = (
        ["mesh_sync", "mesh_dummy", "mesh_db_off", "mesh_db_on",
         "mesh_resnet_sync", "mesh_resnet_dummy", "mesh_resnet_db_off",
         "mesh_resnet_db_on",
         "wire_perleaf_sync", "wire_perleaf_dummy", "wire_bucketed_sync",
         "wire_bucketed_dummy", "wire_int8_sync", "wire_int8_dummy",
         "wire_flat", "wire_hier", "wire_hier_int8",
         "wire_tuned_base", "wire_tuned",
         "wire_tuned_hier_base", "wire_tuned_hier",
         "overlap_off", "overlap_on", "overlap_int8_on",
         "overlap_resnet_off", "overlap_resnet_on",
         "telemetry_overhead"]
        if CPU_MESH else
        ["resnet_sync", "resnet_dummy", "resnet_bare", "lm_sync",
         "lm_dummy", "lm_bare"]
    )
    for name in (sys.argv[1:] or default):
        try:
            variants[name]()
        except Exception as e:
            print(json.dumps({"variant": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)


if __name__ == "__main__":
    main()
