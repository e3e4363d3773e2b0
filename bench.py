#!/usr/bin/env python
"""Benchmark harness: the five BASELINE.md configs, with MFU.

Prints one JSON line per config as it completes, with the HEADLINE line
(ResNet-50 data-parallel, the BASELINE.json primary metric) printed
LAST:

  {"metric": "resnet50_train_images_per_sec_per_chip", "value": ...,
   "unit": "images/sec/chip", "vs_baseline": ..., "step_time_ms": ...,
   "model_tflops_per_step": ..., "mfu": ..., "configs": {...}}

Configs (BASELINE.json):
  1. MNIST MLP data-parallel, flat communicator
  2. ResNet-50 ImageNet data-parallel, hierarchical communicator  [headline]
     (+ a native-C++-input-pipeline variant when a compiler is present)
  3. VGG16 with double-buffering ON vs OFF (the A/B is the point)
  4. ResNet-50 with MultiNodeBatchNormalization (sync-BN over ICI)
  5. seq2seq model-parallel (MultiNodeChainList encoder|decoder)

`vs_baseline` divides by the ChainerMN-era ~125 img/s/chip figure
(BASELINE.md; 1024xP100, 2017 — the only published reference number).
MFU is the auditable calibration: XLA's own per-step FLOP count divided
by (step time x detected chip peak).

Env knobs: BENCH_STEPS (k of the k-in-one-dispatch loop) / BENCH_BATCH
/ BENCH_IMAGE / BENCH_BURN_S / BENCH_ONLY=name,.. /
BENCH_SMOKE=1 (tiny shapes, CPU-friendly smoke run).
"""

import json
import os
import sys

try:  # installed package (pip install -e .)
    import chainermn_tpu  # noqa: F401
except ImportError:  # source checkout: repo root = this file's directory
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CHAINERMN_RESNET50_IMG_PER_SEC_PER_CHIP = 125.0

# Peak bf16 dense FLOP/s per chip by device kind (public figures).
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))


def _env(name, default):
    return int(os.environ.get(name, default))


def _peak_flops(device):
    kind = device.device_kind
    for k, v in _PEAK_BF16.items():
        if kind.startswith(k):
            return v
    raise ValueError(
        f"no peak FLOP/s known for device kind {kind!r}: add it to "
        "_PEAK_BF16 with its source"
    )


def _flops_of(jitted, *args):
    """XLA's own FLOP estimate for one step (honest, auditable)."""
    try:
        analysis = jitted.lower(*args).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        return float(analysis.get("flops", 0.0)) or None
    except Exception:
        return None


def _flash_attn_tflops(batch, heads, seq, dh, n_layers, causal=True):
    """Analytic attention-matmul FLOPs for one TRAINING step — the term
    XLA's cost analysis cannot see (it treats ``pallas_call`` as a
    black box, so every flash config's XLA count omits the attention
    matmuls entirely; at seq 8192 that is the dominant FLOP term).

    Formula (stated so the number is auditable):
      forward  = QK^T + PV            = 2 matmuls = 4*b*h*s^2*dh FLOPs
      backward = recomputed QK^T + dV/dP/dQ/dK    = 5 matmuls = 2.5x fwd
      training total = 3.5x fwd       = 14*b*h*s^2*dh
      causal: the kernel skips dead blocks        -> halve
    per layer; multiplied by ``n_layers``.
    """
    per_layer = 14.0 * batch * heads * seq * seq * dh
    if causal:
        per_layer /= 2
    return per_layer * n_layers / 1e12


def _fingerprint(**kw):
    """Self-describing config string attached to every bench record so
    cross-round trend lines can't silently compare different models
    (round 2->3 the LM silently went 16h/dh64 -> 8h/dh128)."""
    return "|".join(f"{k}={kw[k]}" for k in sorted(kw))


from chainermn_tpu.utils.benchmarking import (  # noqa: E402
    force_completion as _force,
    protocol_fields as _spread_fields,
    time_kloop as _time_kloop,
    time_steps as _time_steps_raw,
)

# Device burn-in before every timed config: ~12 s of device activity
# ahead of the first timed executable of a fresh process (see
# utils/benchmarking.time_steps).  BENCH_BURN_S=0 to disable.
_BURN_S = float(os.environ.get("BENCH_BURN_S", "0" if SMOKE else "12"))


# (no _time_steps burn-in wrapper anymore: every live call site invokes
# _time_steps_raw directly with its own burn policy — the native-input
# row burns only its first pass, the seq2seq eager illustration
# deliberately never burns)


def _burned_kloop(run_k, k, repeats=2):
    """Burn-in + paired-k/2k timing of a k-steps-in-one-dispatch
    callable; returns ``(seconds_per_step, samples)`` — the per-repeat
    samples feed every row's min-of-N spread record (round 6: the
    native-input row's ``n_measurements``/``spread_max_over_min``
    protocol extended to ALL rows, VERDICT r5 #1).  The burn loop's
    first call absorbs compilation, then ``_BURN_S`` of device activity
    runs before timing."""
    if _BURN_S > 0:
        import time as _t

        _force(run_k(2))  # compile
        t_end = _t.perf_counter() + _BURN_S
        while _t.perf_counter() < t_end:
            _force(run_k(max(k // 2, 1)))
    return _time_kloop(run_k, k, repeats)


# _spread_fields is utils.benchmarking.protocol_fields (imported above):
# the min-of-N disclosure — n_measurements + spread_max_over_min — is
# ONE protocol defined in one place, shared with every benchmarks/
# script and enforced by analysis.lint's untimed-row rule.


def _copy_spread(dst, src, suffix=""):
    """Propagate one sub-record's spread disclosure into a config row
    (one implementation so no row can silently drop a field; ``suffix``
    distinguishes multi-leg rows like the vgg on/off A/B)."""
    if "n_measurements" in src and "n_measurements" not in dst:
        dst["n_measurements"] = src["n_measurements"]
    if "spread_max_over_min" in src:
        dst["spread_max_over_min" + suffix] = src["spread_max_over_min"]


def _ab_disclosure(rec, leg_a, leg_b, suffix_a, suffix_b):
    """Two-leg A/B row disclosure: total samples across both legs, the
    row spread is the WORSE leg's (the ratio is only as trustworthy as
    its noisier side), then the per-leg fields, suffixed."""
    rec["n_measurements"] = (leg_a.get("n_measurements", 0)
                             + leg_b.get("n_measurements", 0))
    spreads = [r["spread_max_over_min"] for r in (leg_a, leg_b)
               if "spread_max_over_min" in r]
    if spreads:
        rec["spread_max_over_min"] = max(spreads)
    _copy_spread(rec, leg_a, suffix_a)
    _copy_spread(rec, leg_b, suffix_b)


def _kloop_step_time(step, params, opt_state, batch, k, repeats=2):
    """``(seconds_per_step, samples)`` with k steps inside ONE jitted
    fori_loop.

    A single dispatch covering k steps keeps per-dispatch host overhead
    out of the per-step figure, which matters most for sub-ms configs.
    The step must be built with ``donate=False`` (the loop re-enters
    with the same buffers)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if getattr(step, "donate", False):
        raise ValueError(
            "_kloop_step_time requires a step built with donate=False: "
            "the k-loop re-enters with the same buffers, and a donated "
            "step consumes params/opt_state on the warm call"
        )
    inner = step.get_jitted(params, opt_state)

    @jax.jit
    def ksteps(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            p, o, m = inner(p, o, batch)
            return p, o, m["loss"]

        return lax.fori_loop(0, n, body, (p, o, jnp.float32(0)))

    return _burned_kloop(
        lambda n: ksteps(params, opt_state, n)[2], k, repeats
    )


def _train_setup(comm, model, image, batch, n_classes, mutable_bn,
                 double_buffering=False, wire="auto", overlap="none"):
    """Shared scaffolding: params, step fn, a resident synthetic batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn

    rng = jax.random.PRNGKey(0)
    variables = model.init(
        rng, jnp.zeros((1, image, image, 3), jnp.bfloat16)
    )
    params = {"params": variables["params"],
              "batch_stats": variables.get("batch_stats", {})}
    params = comm.bcast_data(params)
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm,
        double_buffering=double_buffering, wire=wire, overlap=overlap,
    )

    def loss_fn(p, b):
        x, y = b
        kwargs = {"mutable": ["batch_stats"]} if mutable_bn else {}
        logits = model.apply(
            {"params": p["params"], "batch_stats": p["batch_stats"]},
            x, rngs={"dropout": jax.random.PRNGKey(7)}, **kwargs,
        )
        if mutable_bn:
            logits, _ = logits
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))
    x = jnp.asarray(
        np.random.RandomState(0).randn(batch, image, image, 3), jnp.bfloat16
    )
    y = jnp.asarray(
        np.random.RandomState(1).randint(0, n_classes, (batch,)), jnp.int32
    )
    bx = jax.device_put(x, step.batch_sharding)
    by = jax.device_put(y, step.batch_sharding)

    jitted = step.get_jitted(params, opt_state)
    return step, jitted, (params, opt_state, (bx, by))


def bench_image_model(comm, model, *, image, batch, n_classes=1000,
                      mutable_bn=True, steps=None,
                      double_buffering=False, wire="auto",
                      overlap="none"):
    steps = steps or _env("BENCH_STEPS", 4 if SMOKE else 20)
    step, jitted, args = _train_setup(
        comm, model, image, batch, n_classes, mutable_bn,
        double_buffering=double_buffering, wire=wire, overlap=overlap,
    )
    params, opt_state, batch_dev = args
    step_time, samples = _kloop_step_time(
        step, params, opt_state, batch_dev, steps
    )
    flops = _flops_of(jitted, *args)
    peak = None if SMOKE else _peak_flops(comm.devices[0])
    out = {
        "images_per_sec": batch / step_time,
        "images_per_sec_per_chip": batch / step_time / comm.size,
        "step_time_ms": step_time * 1e3,
        **_spread_fields(samples),
    }
    if flops:
        out["model_tflops_per_step"] = flops / 1e12
        if peak:
            out["mfu"] = flops / step_time / (peak * comm.size)
    return out


def config_mnist_flat():
    import jax.numpy as jnp

    import chainermn_tpu as cmn
    from chainermn_tpu.models import MLP

    comm = cmn.create_communicator("flat")
    batch = _env("BENCH_MNIST_BATCH", 64 if SMOKE else 2048) * comm.size
    steps = _env("BENCH_STEPS", 4 if SMOKE else 30)

    import jax
    import numpy as np
    import optax

    model = MLP(n_units=1000, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
    params = comm.bcast_data(params)
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)

    def loss_fn(p, b):
        x, y = b
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))
    x = jnp.asarray(
        np.random.RandomState(0).rand(batch, 28, 28), jnp.float32
    )
    y = jnp.asarray(
        np.random.RandomState(1).randint(0, 10, (batch,)), jnp.int32
    )
    bx = jax.device_put(x, step.batch_sharding)
    by = jax.device_put(y, step.batch_sharding)

    # Sub-ms steps need a BIG k so one dispatch covers the measurement
    # (driver captures ranged 1M-7M samples/s under per-dispatch noise;
    # the k-loop measures 14.9M +-0.2%).
    k = steps * (10 if SMOKE else 100)
    step_time, samples = _kloop_step_time(
        step, params, opt_state, (bx, by), k
    )
    return {
        "metric": "mnist_mlp_flat_samples_per_sec_per_chip",
        "value": round(batch / step_time / comm.size, 2),
        "unit": "samples/sec/chip",
        "step_time_ms": round(step_time * 1e3, 3),
        "communicator": "flat",
        "k_loop": k,
        **_spread_fields(samples),
        "config_fingerprint": _fingerprint(
            arch="mlp1000", b=batch, dtype="bf16"
        ),
    }


def config_resnet50_hierarchical():
    import chainermn_tpu as cmn
    from chainermn_tpu.models import ResNet50, ResNet18

    comm = cmn.create_communicator("hierarchical")
    image = _env("BENCH_IMAGE", 64 if SMOKE else 224)
    batch = _env("BENCH_BATCH", 8 if SMOKE else 128) * comm.size
    model_cls = ResNet18 if SMOKE else ResNet50
    model = model_cls(num_classes=1000, train=True)
    r = bench_image_model(comm, model, image=image, batch=batch)
    per_chip = r["images_per_sec_per_chip"]
    out = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            per_chip / CHAINERMN_RESNET50_IMG_PER_SEC_PER_CHIP, 3
        ),
        "step_time_ms": round(r["step_time_ms"], 2),
        "batch": batch,
        "communicator": "hierarchical",
        "config_fingerprint": _fingerprint(
            arch=model_cls.__name__, b=batch, img=image, bn="bf16"
        ),
    }
    _copy_spread(out, r)
    if "model_tflops_per_step" in r:
        out["model_tflops_per_step"] = round(r["model_tflops_per_step"], 2)
    if "mfu" in r:
        out["mfu"] = round(r["mfu"], 4)
    return out


def _uint8_link_ceiling(dev, batch, image, k=8):
    """SAME-RUN uint8 link-ceiling probe (VERDICT r5 #7): measure the
    H2D bandwidth of exactly the wire payload the native-input config
    ships (a batch of image-size uint8 crops) at the same transport
    instant as the end-to-end number.  The r5 record compared its
    end-to-end draw against a ceiling measured hours earlier on a link
    that drifts 2-6x across a day; recording
    ``fraction_of_link_ceiling`` from a same-run probe removes that
    confound from the committed capture."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    try:
        import h2d_bench
    finally:
        sys.path.pop(0)
    import numpy as np

    rng = np.random.RandomState(0)
    arrs = [
        rng.randint(0, 256, size=(batch, image, image, 3)).astype(np.uint8)
        for _ in range(k)
    ]
    probe = h2d_bench._scalar_probe()
    rtt = h2d_bench.measure_rtt(dev)
    bw = h2d_bench.measure_h2d(dev, probe, arrs, depth=2)
    t_batch = arrs[0].nbytes / bw + rtt
    # component fields merged (**link) into the native-input row, which
    # carries the row-level n_measurements/spread disclosure itself
    # mnlint: allow(untimed-row)
    return {
        "link_uint8_MBps": round(bw / 1e6, 1),
        "link_rtt_ms": round(rtt * 1e3, 2),
        "link_ceiling_img_per_sec_uint8": round(batch / t_batch, 1),
    }


def config_resnet50_native_input():
    """Config 2 variant: the C++ input pipeline feeds real host batches
    (crop/flip off the GIL) instead of a resident device batch — the
    end-to-end number including input.

    uint8 over the wire: the loader ships raw uint8 crops — 1/2 of
    bf16's bytes (benchmarks/h2d_bench.py's uint8 row states the
    host-to-device ceiling) — and mean/std/bf16-cast runs INSIDE the
    jitted step (device_normalize fuses into the first conv).  Timing
    is min-of-N (N=3) with the spread reported."""
    from chainermn_tpu.utils.native_loader import (
        NativeImageLoader,
        device_normalize,
        native_available,
    )

    if not native_available():
        return {"metric": "resnet50_native_input", "skipped": "no g++"}

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import ResNet50, ResNet18

    comm = cmn.create_communicator("hierarchical")
    image = _env("BENCH_IMAGE", 64 if SMOKE else 224)
    batch = _env("BENCH_BATCH", 8 if SMOKE else 128) * comm.size
    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)
    n_data = max(batch * 2, 512 if SMOKE else 2048)

    rng = np.random.RandomState(0)
    images = rng.randint(
        0, 256, size=(n_data, image + 8, image + 8, 3), dtype=np.uint8
    )
    labels = rng.randint(0, 1000, size=(n_data,)).astype(np.int32)
    mean, std = (123.7, 116.3, 103.5), (58.4, 57.1, 57.4)
    loader = NativeImageLoader(
        images, labels, batch, crop=(image, image), n_threads=8,
        seed=0, shuffle=True, train=True, mean=mean, std=std,
        wire="uint8",
    )

    model_cls = ResNet18 if SMOKE else ResNet50
    model = model_cls(num_classes=1000, train=True)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3), jnp.bfloat16)
    )
    params = {"params": variables["params"],
              "batch_stats": variables.get("batch_stats", {})}
    params = comm.bcast_data(params)
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.1, momentum=0.9), comm)

    def loss_fn(p, b):
        x_u8, y = b
        x = device_normalize(x_u8, mean, std, dtype=jnp.bfloat16)
        logits, _ = model.apply(
            {"params": p["params"], "batch_stats": p["batch_stats"]},
            x, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    from chainermn_tpu.iterators import prefetch_to_device

    step = cmn.build_train_step(comm, loss_fn, opt)
    params, opt_state = step.place(params, opt.init(params))
    state = {"p": params, "o": opt_state}

    def host_batches():
        while True:
            slot, xv, yv = loader.acquire()
            try:
                # plain copies detach from the zero-copy slot; the wire
                # stays uint8 (half of bf16's bytes, no host-side cast)
                yield (np.array(xv), np.array(yv))
            finally:
                loader.release(slot)

    # double-buffered H2D: batch i+1's device_put is dispatched while
    # step i computes (async dispatch), hiding transfer behind compute
    it = prefetch_to_device(host_batches(), step.place_batch, depth=2)

    def run():
        state["p"], state["o"], m = step(state["p"], state["o"], next(it))
        return m["loss"]

    # min-of-N: first pass carries the burn-in, the rest re-measure the
    # same resident pipeline; the best pass is the number (transport
    # noise only ADDS time) and the spread is reported alongside.
    n_meas = _env("BENCH_NATIVE_REPEATS", 1 if SMOKE else 3)
    dts = []
    try:
        for i in range(n_meas):
            dt_i, _ = _time_steps_raw(
                run, steps, warmup=1, burn_seconds=_BURN_S if i == 0 else 0,
            )
            dts.append(dt_i)
    finally:
        it.close()  # retire the generator's held slot before the loader
        loader.close()
    dt = min(dts)
    # same-run link-ceiling probe; its failure must not kill the row.
    # GLOBAL batch rate vs GLOBAL-batch ceiling (the probe ships the
    # whole batch over the one host link, so the per-chip rate would
    # understate the fraction by comm.size on multi-chip hosts)
    link = {}
    try:
        link = _uint8_link_ceiling(comm.devices[0], batch, image)
        ceiling = link["link_ceiling_img_per_sec_uint8"]
        if ceiling > 0:
            link["fraction_of_link_ceiling"] = round(
                (batch / dt) / ceiling, 3
            )
    except Exception as e:
        link = {"link_ceiling_error": f"{type(e).__name__}: {e}"}
    return {
        "metric": "resnet50_native_input_images_per_sec_per_chip",
        "value": round(batch / dt / comm.size, 2),
        **link,
        "unit": "images/sec/chip (incl. C++ input pipeline, uint8 wire, "
                "double-buffered H2D; min of N)",
        "step_time_ms": round(dt * 1e3, 2),
        "n_measurements": n_meas,
        "spread_max_over_min": round(max(dts) / min(dts), 2),
        "all_images_per_sec_per_chip": [
            round(batch / d / comm.size, 1) for d in dts
        ],
        "config_fingerprint": _fingerprint(
            arch=model_cls.__name__, b=batch, img=image,
            loader="native_cpp", wire="uint8", prefetch=2,
        ),
        "note": (
            "end to end including input (host loader and "
            "host-to-device copy) — see docs/performance.md "
            "'Native-input pipeline'"
        ),
    }


def config_vgg16_overlap():
    """Bucket-granularity overlap A/B on VGG (ISSUE 8): the SAME VGG16
    tier timed with the synchronous bucketed wire vs the overlap-
    scheduled program (each bucket's psum issued under the remaining
    backward segments).  This rung REPLACES ``vgg16_db`` — the ROADMAP
    decision rule ("overlap >=1.05x on VGG/ResNet or double-buffering
    retires from bench", executed this round — docs/performance.md
    "Double-buffering: retired from the bench") ended double
    buffering's three captures at ~0.97x; the optimizer class and its
    tests remain.  Both legs are bit-identical programs (same buckets,
    codec, reduction order), so the ratio isolates pure scheduling."""
    import chainermn_tpu as cmn
    from chainermn_tpu.comm_wire import plan_of_tree
    from chainermn_tpu.models import VGG16

    image = _env("BENCH_IMAGE", 64 if SMOKE else 224)
    batch = _env("BENCH_VGG_BATCH", 4 if SMOKE else 64)
    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)
    out = {}
    for mode in ("none", "bucket"):
        comm = cmn.create_communicator("tpu")
        model = VGG16(num_classes=1000, train=True)
        r = bench_image_model(
            comm, model, image=image, batch=batch * comm.size,
            steps=steps, overlap=mode,
        )
        out["on" if mode == "bucket" else "off"] = r
    on, off = out["on"], out["off"]
    import jax

    model = VGG16(num_classes=1000, train=True)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, image, image, 3), jax.numpy.bfloat16),
    )
    plan = plan_of_tree(variables)
    rec = {
        "metric": "vgg16_overlap_speedup",
        "value": round(
            on["images_per_sec_per_chip"] / off["images_per_sec_per_chip"],
            3,
        ),
        "unit": "x (bucket overlap ON / OFF; >=1.05x is the gate)",
        "images_per_sec_per_chip_off": round(
            off["images_per_sec_per_chip"], 2
        ),
        "images_per_sec_per_chip_on": round(
            on["images_per_sec_per_chip"], 2
        ),
        "step_time_ms_off": round(off["step_time_ms"], 2),
        "step_time_ms_on": round(on["step_time_ms"], 2),
        "mfu_off": round(off.get("mfu", 0.0), 4) or None,
        "wire_buckets": plan.n_buckets,
        "config_fingerprint": _fingerprint(
            arch="VGG16", b_per_chip=batch, img=image,
            codec="none", buckets=plan.n_buckets, overlap="bucket",
        ),
    }
    _ab_disclosure(rec, off, on, "_off", "_on")
    return rec


def config_grad_wire():
    """Flat-wire gradient-sync A/B (ISSUE 4): the SAME ResNet tier
    timed with the legacy per-leaf psum storm vs the bucketed fused
    wire — the launch-count half of the wire win, on-chip.  The byte
    half (int8) and the sync/dummy split live in
    ``benchmarks/comm_overlap_bench.py``'s ``wire_*`` rungs; this row
    is the driver-captured headline ratio, fingerprinted with the codec
    and bucket count so cross-round trend lines can't silently compare
    different plans."""
    import jax

    import chainermn_tpu as cmn
    from chainermn_tpu.comm_wire import plan_of_tree
    from chainermn_tpu.models import ResNet50, ResNet18

    image = _env("BENCH_IMAGE", 64 if SMOKE else 224)
    batch = _env("BENCH_BATCH", 8 if SMOKE else 128)
    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)
    model_cls = ResNet18 if SMOKE else ResNet50
    out = {}
    for wire in ("per_leaf", "auto"):
        comm = cmn.create_communicator("tpu")
        model = model_cls(num_classes=1000, train=True)
        out[wire] = bench_image_model(
            comm, model, image=image, batch=batch * comm.size,
            steps=steps, wire=wire,
        )
    leaf, bucketed = out["per_leaf"], out["auto"]
    # the plan the "auto" leg compiled — a pure function of shapes, so
    # eval_shape (abstract init, zero device work) is all it needs
    model = model_cls(num_classes=1000, train=True)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, image, image, 3), jax.numpy.float32),
    )
    plan = plan_of_tree(variables)
    rec = {
        "metric": "grad_wire_bucketed_speedup",
        "value": round(
            leaf["step_time_ms"] / bucketed["step_time_ms"], 3
        ),
        "unit": "x (per-leaf step time / bucketed step time)",
        "step_time_ms_per_leaf": round(leaf["step_time_ms"], 2),
        "step_time_ms_bucketed": round(bucketed["step_time_ms"], 2),
        "wire_buckets": plan.n_buckets,
        "wire_n_leaves": plan.n_leaves,
        "config_fingerprint": _fingerprint(
            arch=model_cls.__name__, b_per_chip=batch, img=image,
            codec="none", buckets=plan.n_buckets,
        ),
    }
    _ab_disclosure(rec, leaf, bucketed, "_per_leaf", "_bucketed")
    return rec


def config_resnet50_mnbn():
    import jax.numpy as jnp

    import chainermn_tpu as cmn
    from chainermn_tpu.links.create_mnbn_model import mnbn_factory
    from chainermn_tpu.models import ResNet50, ResNet18

    comm = cmn.create_communicator("tpu")
    image = _env("BENCH_IMAGE", 64 if SMOKE else 224)
    batch = _env("BENCH_BATCH", 8 if SMOKE else 128) * comm.size
    model_cls = ResNet18 if SMOKE else ResNet50
    model = model_cls(
        num_classes=1000, train=True, norm=mnbn_factory(comm),
        dtype=jnp.bfloat16,
    )
    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)
    r = bench_image_model(
        comm, model, image=image, batch=batch, steps=steps,
    )
    out = {
        "metric": "resnet50_mnbn_images_per_sec_per_chip",
        "value": round(r["images_per_sec_per_chip"], 2),
        "unit": "images/sec/chip (sync-BN over ICI)",
        "step_time_ms": round(r["step_time_ms"], 2),
        "config_fingerprint": _fingerprint(
            arch=model_cls.__name__, b=batch, img=image, bn="mnbn_bf16"
        ),
    }
    _copy_spread(out, r)
    if "mfu" in r:
        out["mfu"] = round(r["mfu"], 4)
    return out


def _bench_lm(model, loss_fn, comm, *, batch, seq, vocab,
              with_flops=False, attn_tflops=None):
    """Shared LM-config scaffold: init + broadcast, adamw multi-node
    step, resident token batch, honest paired-run timing.  Returns
    (tokens_per_sec_per_chip, step_time_s, flops_dict).

    ``attn_tflops``: analytic flash-attention FLOPs (TF) to add on top
    of the XLA count (which can't see inside pallas_call); when given,
    the headline ``mfu`` includes it and the XLA-only figure is kept as
    ``mfu_xla_counted``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn

    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)
    toks0 = jnp.zeros((1, seq), jnp.int32)
    params = comm.bcast_data(model.init(jax.random.PRNGKey(0), toks0))
    opt = cmn.create_multi_node_optimizer(
        optax.adamw(3e-4, weight_decay=0.01), comm
    )
    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (batch, seq)), jnp.int32
    )
    bt = jax.device_put(toks, step.batch_sharding)
    step_time, samples = _kloop_step_time(step, params, opt_state, bt,
                                          steps)
    extra = _spread_fields(samples)
    if with_flops:
        flops = _flops_of(
            step.get_jitted(params, opt_state), params, opt_state, bt
        )
        peak = None if SMOKE else _peak_flops(comm.devices[0])
        if flops:
            total = flops + (attn_tflops or 0.0) * 1e12
            extra["model_tflops_per_step"] = round(total / 1e12, 2)
            if attn_tflops:
                extra["attn_tflops_analytic"] = round(attn_tflops, 2)
                extra["tflops_xla_counted"] = round(flops / 1e12, 2)
            if peak:
                extra["mfu"] = round(
                    total / step_time / (peak * comm.size), 4
                )
                if attn_tflops:
                    extra["mfu_xla_counted"] = round(
                        flops / step_time / (peak * comm.size), 4
                    )
    tps = batch * seq / step_time / comm.size
    return tps, step_time, extra


def _lm_dims():
    vocab = 2048 if SMOKE else 32768
    d_model = 128 if SMOKE else 1024
    n_layers = 2 if SMOKE else 8
    return vocab, d_model, n_layers


def _lm_heads(d_model):
    """Head width 128 = the MXU lane dimension: dh=64 leaves half the
    lanes idle in the flash kernel's QK/PV matmuls — measured 40%
    slower end-to-end (benchmarks/transformer_mfu.py heads8 rung)."""
    return max(d_model // 128, 1)


def config_transformer_lm():
    """Beyond the reference's workloads: decoder-only LM with the Pallas
    flash-attention kernel — the matmul-heavy config where MFU should
    approach the chip's practical ceiling."""
    import chainermn_tpu as cmn
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss
    from chainermn_tpu.ops.pallas_attention import flash_attention_fn

    comm = cmn.create_communicator("tpu")
    vocab, d_model, n_layers = _lm_dims()
    seq = 128 if SMOKE else 2048
    batch = _env("BENCH_LM_BATCH", 2 if SMOKE else 8) * comm.size
    heads = _lm_heads(d_model)
    # Split fwd/bwd flash geometry (round-5 sweep, confirmed twice in
    # swapped order): fwd 1024x2048 + bwd 1024x1024 measures 120.3/
    # 120.9 ms vs 123.2/123.4 shared — +2% at seq 2048 (the backward's
    # scoped-VMEM limit does not bind the forward).  seq 8192 prefers
    # shared 1024x1024 (its config below keeps it).
    fbq, fbk, bbq, bbk = 1024, 2048, 1024, 1024
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=n_layers, max_len=seq,
        attention_fn=None if SMOKE else flash_attention_fn(
            block_q=fbq, block_k=fbk,
            bwd_block_q=bbq, bwd_block_k=bbk,
        ),
    )
    attn = None if SMOKE else _flash_attn_tflops(
        batch, heads, seq, d_model // heads, n_layers
    )
    tps, step_time, extra = _bench_lm(
        model, lambda p, b: lm_loss(model.apply(p, b), b), comm,
        batch=batch, seq=seq, vocab=vocab, with_flops=True,
        attn_tflops=attn,
    )
    return {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip (flash attention, bf16)",
        "step_time_ms": round(step_time * 1e3, 2),
        "seq_len": seq,
        "d_model": d_model,
        "n_layers": n_layers,
        "n_heads": model.n_heads,
        "config_fingerprint": _fingerprint(
            arch="dense_lm", b=batch, s=seq, d=d_model, L=n_layers,
            h=heads, v=vocab,
            # derived from the SAME variables passed to the kernel so a
            # retune cannot silently desynchronize the recorded geometry
            # ("split" = the round-6 diagonal-split taxonomy)
            attn=(f"flash_split_f{fbq}x{fbk}_b{bbq}x{bbk}"
                  if not SMOKE else "xla"),
        ),
        **extra,
    }


def _long_seq_lm_config(*, seq, smoke_seq, batch_env, batch_default):
    """Shared body of the long-sequence LM tiers (seq 8192 / 16384):
    identical model, 1024x1024 flash blocks (the r4/r5 sweeps' choice
    at both lengths), analytic attention FLOPs and fingerprint — only
    the length, batch knob and metric strings differ, so a fix to one
    tier cannot miss the other."""
    import chainermn_tpu as cmn
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss
    from chainermn_tpu.ops.pallas_attention import flash_attention_fn

    comm = cmn.create_communicator("tpu")
    vocab, d_model, n_layers = _lm_dims()
    s = smoke_seq if SMOKE else seq
    batch = _env(batch_env, batch_default) * comm.size
    heads = _lm_heads(d_model)
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=n_layers, max_len=s,
        attention_fn=None if SMOKE else flash_attention_fn(
            block_q=1024, block_k=1024
        ),
    )
    attn = None if SMOKE else _flash_attn_tflops(
        batch, heads, s, d_model // heads, n_layers
    )
    tps, step_time, extra = _bench_lm(
        model, lambda p, b: lm_loss(model.apply(p, b), b), comm,
        batch=batch, seq=s, vocab=vocab, with_flops=True,
        attn_tflops=attn,
    )
    return {
        "metric": f"transformer_lm_seq{seq}_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": f"tokens/sec/chip (flash attention, bf16, seq {seq})",
        "step_time_ms": round(step_time * 1e3, 2),
        "seq_len": s,
        "config_fingerprint": _fingerprint(
            arch="dense_lm", b=batch, s=s, d=d_model, L=n_layers,
            h=heads, v=vocab,
            attn="flash_split_1024x1024" if not SMOKE else "xla",
        ),
        **extra,
    }


def config_transformer_lm_long():
    """Long-context tier: seq 8192 where XLA's fused attention OOMs on
    this chip — the flash kernel is what makes the config exist at all
    (docs/performance.md).  Batch 2 with 1024x1024 flash blocks: the
    round-4 sweep (benchmarks/longseq_tune.py) measured 94.3k tok/s
    (MFU 0.61) there vs 67.8k at the round-3 defaults (b1, 256x512
    blocks, which were tuned at seq 2048); 1024x2048 blocks exceed the
    16 MB scoped-vmem limit and b4 OOMs HBM."""
    return _long_seq_lm_config(seq=8192, smoke_seq=256,
                               batch_env="BENCH_LM_LONG_BATCH",
                               batch_default=2)


def config_transformer_lm_xl():
    """seq-16384 tier, promoted to a first-class fingerprinted config
    (VERDICT r5 #4: the 61.3k tok/s round-5 result lived only in the
    perf doc's prose — a regression there was invisible to the bench).
    Batch 1, 1024x1024 flash blocks (the r5 sweep's choice at this
    length); attention is ~72% of the analytic FLOPs here, and under
    the diagonal-split kernel 120 of 136 live blocks per program run
    the unmasked fast branch (block_census) — the config where the
    split's win is largest."""
    return _long_seq_lm_config(seq=16384, smoke_seq=512,
                               batch_env="BENCH_LM_XL_BATCH",
                               batch_default=1)


def config_moe_lm():
    """MoE tier: GShard-style top-2 routed experts every other block
    (models/moe_transformer.py) — measures the routing + expert-compute
    machinery; on one chip the expert exchange degenerates (the EP
    all_to_all path is exercised by tests and dryrun_multichip)."""
    import chainermn_tpu as cmn
    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        moe_lm_loss,
    )
    from chainermn_tpu.ops.pallas_attention import flash_attention_fn

    comm = cmn.create_communicator("tpu")
    vocab, d_model, n_layers = _lm_dims()
    n_experts = 4 if SMOKE else 8
    seq = 128 if SMOKE else 2048
    # batch 4/chip: the round-5 sweep measured 86.0k tok/s vs 80.6k at
    # b2 and 80.9k at b8 (b8 posts the highest MFU, 0.564, but pays
    # ~13% more routed-capacity FLOPs per token — tokens/s is the
    # user-facing number, so b4 is the default)
    batch = _env("BENCH_MOE_BATCH", 2 if SMOKE else 4) * comm.size
    heads = _lm_heads(d_model)
    model = MoeTransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=n_layers, n_experts=n_experts, moe_every=2, k=2,
        max_len=seq,
        dispatch_impl=os.environ.get("BENCH_MOE_DISPATCH", "auto"),
        attention_fn=None if SMOKE else flash_attention_fn(),
    )
    attn = None if SMOKE else _flash_attn_tflops(
        batch, heads, seq, d_model // heads, n_layers
    )
    tps, step_time, extra = _bench_lm(
        model,
        lambda p, b: moe_lm_loss(model.apply(p, b), b, aux_coef=1e-2),
        comm, batch=batch, seq=seq, vocab=vocab, with_flops=True,
        attn_tflops=attn,
    )
    return {
        "metric": "moe_lm_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip (top-2 MoE every other block)",
        "step_time_ms": round(step_time * 1e3, 2),
        "n_experts": n_experts,
        "config_fingerprint": _fingerprint(
            arch="moe_lm", b=batch, s=seq, d=d_model, L=n_layers,
            h=heads, v=vocab, E=n_experts, k=2, every=2,
            attn="flash_split" if not SMOKE else "xla",
        ),
        **extra,
    }


def config_seq2seq_mp():
    """Seq2seq model-parallel — re-expressed honestly (VERDICT r4 #4).

    Three measurements, each named for what it is:
    1. the one-chip WHOLE-STEP-JITTED chain (both stages share the only
       chip — a dispatch-cost number, so NO MFU field: the placement is
       degenerate and an MFU would imply a model-parallel efficiency
       this config cannot measure);
    2. the chain's native eager per-stage dispatch (the reference's
       fill-drain ergonomics) — the cost whole-step jit removes;
    3. the same enc|dec split through the REAL pipeline tier
       (parallel.build_pipeline_train_step, 2 stages, GPipe) in a CPU
       virtual-mesh subprocess — a structure/convergence record (twin
       equality is pinned by tests/test_parallel.py), not a TPU number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.link import MultiNodeChainList

    comm = cmn.create_communicator("tpu")
    vocab = 1024 if SMOKE else 8192
    units = 128 if SMOKE else 512
    seqlen = 16 if SMOKE else 40
    batch = _env("BENCH_SEQ_BATCH", 8 if SMOKE else 64)
    steps = _env("BENCH_STEPS", 3 if SMOKE else 10)

    # encoder on rank 0 / decoder on rank min(1, size-1): the reference's
    # seq2seq_mp1 split (both land on the same chip in a 1-chip world).
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", "seq2seq"),
    )
    from seq2seq_mp1 import DecoderStage, EncoderStage

    model = MultiNodeChainList(comm)
    dec_rank = min(1, comm.size - 1)
    model.add_link(EncoderStage(vocab, units, 2), rank_in=None,
                   rank_out=dec_rank, rank=0)
    model.add_link(DecoderStage(vocab, units, 2), rank_in=[0, None],
                   rank_out=None, rank=dec_rank)

    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(1, vocab, (batch, seqlen)), jnp.int32)
    tgt = jnp.asarray(rng.randint(1, vocab, (batch, seqlen)), jnp.int32)

    params = model.init(jax.random.PRNGKey(0), (src, tgt))

    def loss_fn(logits, tgt):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tgt[:, 1:]
        ).mean()

    vag = model.value_and_grad(loss_fn)
    opt = model.optimizer(optax.adam(1e-3))
    state = opt.init(params)

    # One compiled program for the whole multi-stage step: the chain's
    # stage-by-stage dispatch (its eager ergonomics) would otherwise pay
    # one host round-trip per op, which a high-latency link amplifies.
    import jax as _jax

    @_jax.jit
    def whole_step(params, state):
        loss, grads = vag(params, (src, tgt), tgt)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    # k whole-steps in one dispatch (same noise-proofing as the other
    # configs; this config's ~5 ms steps drowned in dispatch noise —
    # r03/r04 captures differed 35%)
    @_jax.jit
    def ksteps(p, s, n):
        def body(i, carry):
            p, s, _ = carry
            return whole_step(p, s)

        return _jax.lax.fori_loop(
            0, n, body, (p, s, jnp.float32(0))
        )

    k = steps * (2 if SMOKE else 10)
    step_time, kloop_samples = _burned_kloop(
        lambda n: ksteps(params, state, n)[2], k
    )
    tokens = batch * seqlen * 2  # enc + dec

    # 2. eager per-stage dispatch (the chain's ergonomic tier): each
    # stage + the optimizer dispatches separately, paying the link RTT
    # per dispatch — the cost the whole-step jit removes.  Few steps,
    # no burn: this is an illustration of dispatch overhead (+-20 % is
    # fine), not a throughput claim.
    def eager_run():
        nonlocal params, state
        loss, grads = vag(params, (src, tgt), tgt)
        params, state = opt.update(grads, state, params)
        return loss

    eager_dt, _ = _time_steps_raw(eager_run, 2 if SMOKE else 3, warmup=1)

    # 3. the REAL pipeline: enc|dec through build_pipeline_train_step
    # on a CPU virtual mesh in a subprocess (it must never touch the
    # TPU this process holds: JAX_PLATFORMS=cpu in its environment, and
    # the script forces the cpu platform before any backend query).
    pipeline_rec = None
    if not SMOKE:
        import subprocess

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # append, not clobber: the operator's XLA_FLAGS may be load-
        # bearing for their XLA install
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2"
        ).strip()
        try:
            r = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "benchmarks", "pipeline_seq2seq.py"),
                 "--steps", "8", "--batch", str(batch),
                 "--unit", str(units), "--seqlen", str(seqlen),
                 "--vocab", str(vocab)],
                capture_output=True, text=True, timeout=600, env=env,
            )
            lines = r.stdout.strip().splitlines()
            if r.returncode != 0 or not lines:
                pipeline_rec = {
                    "error": f"exit {r.returncode}: "
                             f"{(r.stderr or r.stdout)[-300:]}"
                }
            else:
                pipeline_rec = json.loads(lines[-1])
        except Exception as e:
            pipeline_rec = {"error": f"{type(e).__name__}: {e}"}

    out = {
        "metric": "seq2seq_mp_tokens_per_sec_per_chip",
        "value": round(tokens / step_time / comm.size, 1),
        "unit": "tokens/sec/chip (enc|dec chain, WHOLE step jitted, "
                "both stages on the ONE chip - a dispatch-cost "
                "measurement, not a pipeline)",
        "step_time_ms": round(step_time * 1e3, 2),
        **_spread_fields(kloop_samples),
        "eager_per_stage_step_ms": round(eager_dt * 1e3, 1),
        "eager_vs_jit_dispatch_cost_x": round(eager_dt / step_time, 1),
        "pipeline_2stage_virtual_mesh": pipeline_rec,
        "n_chips": comm.size,
        "config_fingerprint": _fingerprint(
            arch="seq2seq_gru2", b=batch, s=seqlen, units=units, v=vocab
        ),
    }
    return out


def main():
    from chainermn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    headline = None
    extras = {}
    secondary = [
        ("mnist", config_mnist_flat),
        ("vgg16_overlap", config_vgg16_overlap),
        ("grad_wire", config_grad_wire),
        ("resnet50_mnbn", config_resnet50_mnbn),
        ("transformer_lm", config_transformer_lm),
        ("transformer_lm_long", config_transformer_lm_long),
        ("transformer_lm_xl", config_transformer_lm_xl),
        ("moe_lm", config_moe_lm),
        ("seq2seq_mp", config_seq2seq_mp),
        ("resnet50_native_input", config_resnet50_native_input),
    ]
    only = os.environ.get("BENCH_ONLY")  # comma-separated config names
    if only:
        names = {n.strip() for n in only.split(",")}
        secondary = [(n, f) for n, f in secondary if n in names]
        if "resnet50" not in names and "headline" not in names:
            secondary_only = True
        else:
            secondary_only = False
    else:
        secondary_only = False
    try:
        try:
            if not secondary_only:
                headline = config_resnet50_hierarchical()
        except Exception as e:  # secondaries must still run
            headline = {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": None,
                "unit": "images/sec/chip",
                "vs_baseline": None,
                "error": f"{type(e).__name__}: {e}",
            }
        for name, fn in secondary:
            try:
                r = fn()
            except Exception as e:  # keep the harness alive per config
                r = {"metric": name, "error": f"{type(e).__name__}: {e}"}
            extras[name] = r
            print(json.dumps(r), flush=True)
    finally:
        if headline is None:
            headline = {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": None,
                "unit": "images/sec/chip",
                "vs_baseline": None,
                "error": (
                    "headline filtered out by BENCH_ONLY" if only
                    else "headline config failed"
                ),
            }
        # Full record -> file (the driver's capture keeps only the LAST
        # ~2000 chars of stdout: round 3's final line embedded the whole
        # configs dict, blew that budget, and the driver recorded
        # parsed=null.  The final printed line now stays compact —
        # value+MFU per config — so it always survives the tail window.)
        full = dict(headline)
        full["configs"] = {
            k: {kk: vv for kk, vv in v.items() if kk != "configs"}
            for k, v in extras.items()
        }
        if not only:  # a filtered run must not clobber the full capture
            try:
                with open(
                    os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "bench_out.json"), "w"
                ) as f:
                    json.dump(full, f, indent=1)
            except OSError:
                pass
        # compact VIEW of rows already captured (with their protocol
        # fields) in bench_out.json — not a measurement row
        headline["summary"] = {  # mnlint: allow(untimed-row)
            k: {
                "v": v.get("value"),
                "mfu": v.get("mfu"),
                "mfu_x": v.get("mfu_xla_counted"),
                "ms": v.get("step_time_ms"),
                "u": v.get("unit"),
            }
            for k, v in extras.items()
        }
        line = json.dumps(headline)
        if len(line) > 1900:  # driver keeps only the last ~2000 chars
            for s in headline["summary"].values():
                s.pop("u", None)
            line = json.dumps(headline)
        print(line, flush=True)
    failed = [k for k, v in {"headline": headline, **extras}.items()
              if "error" in v]
    if failed and not (secondary_only and failed == ["headline"]):
        # every config's record is printed above; a failure still fails
        # the run
        sys.exit(f"bench: failed configs: {', '.join(failed)}")


if __name__ == "__main__":
    main()
