#!/usr/bin/env python
"""Serving-tier decode throughput: tokens/sec/chip, batch 1 vs saturated.

Two rungs over the continuous-batching engine (ISSUE 13):

  decode_bs1        capacity 1, one request — the latency-bound floor
                    (every decoded token pays the full step dispatch +
                    the TP collectives; "Understanding and Improving
                    Communication Performance in Multi-node LLM
                    Inference" (PAPERS.md): decode is collective-
                    latency-bound, so this rung moves with launch
                    latency, not bandwidth).
  decode_saturated  capacity C, 2C queued requests — continuous
                    batching keeps every slot busy; throughput per chip
                    is the capacity-bound ceiling the batcher exists
                    to reach.

Two A/B pairs over the same substrate (ISSUE 17):

  decode_prefix_shared / decode_prefix_cold
                    2C requests sharing a one-page system prefix
                    (~66% prompt overlap), served with copy-on-write
                    prefix sharing ON vs OFF.  The shared row carries
                    ``pages_saved`` (peak distinct-pages delta vs the
                    cold serve) and ``prefix_hits`` — outputs are
                    bit-identical by contract, so the fingerprints are
                    the win, the tokens/sec the cost of earning it.
  decode_spec_k4 / decode_spec_off
                    speculative decode (half-width 1-layer draft
                    proposes 4, target verifies in one batched step)
                    vs plain decode on identical requests.  The rung
                    reports ``acceptance_rate`` — with the bench's
                    RANDOM weights the draft rarely matches, so this
                    pair prices the speculative MACHINERY at its
                    acceptance floor; an on-chip run with a trained
                    draft re-reads the same row at a real acceptance
                    (the verify program's collective census rides the
                    row, pinned by ``spec_verify_step``).

A third A/B pair over one MIXED stream (ISSUE 18):

  decode_disagg_on / decode_disagg_off
                    2C requests alternating long (3-page) and short
                    (half-page) prompts — the mixed load where one
                    prefill steals decode iterations from every
                    in-flight request.  The off leg serves unified;
                    the on leg splits into a prefill pool (publishes
                    codec-packed KV handoffs through the journal) and
                    a decode pool (ingests them).  Rows carry the
                    handoff codec + exact wire bytes + handoff count,
                    and TTFT p50/p99 split into queue/prefill
                    components — the headline is whether
                    disaggregation moved queue time or prefill time
                    at unchanged (bit-identical) outputs.
                    HUNT_HANDOFF_CODEC selects the wire (default
                    bf16 — lossless on the bf16 cache).

Protocol: the serving loop is HOST-driven (admission, argmax, page
bookkeeping between compiled steps), so each rung times paired
k / 2k-token serves and reports the min positive paired difference —
prefill and compile cost cancel in the difference exactly like the
k-loop harness's paired dispatches.  Every row carries the min-of-N
disclosure plus the serving fingerprints: the decode program's
authored collective census and trace hash (what the ``decode_step``
budget pin enforces), capacity/page geometry, and the batcher's
p50/p99 token latency.

``tokens_per_sec_per_chip`` is HIGHER-better.  A pre-cell script: no
number of its is on the ledger; cell B2 (``ROADMAP.md`` Reach B2, the
first serving cell) decides what of it becomes a cell.

Usage:
    python benchmarks/decode_bench.py                  # real chip
    python benchmarks/decode_bench.py --cpu-mesh       # 8 virt devices
    python benchmarks/decode_bench.py decode_bs1
Env: HUNT_DECODE_TOKENS (k, default 32), HUNT_DECODE_CAPACITY (8),
HUNT_SERVE_DMODEL/LAYERS/HEADS/VOCAB/PROMPT for the model fixture.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--cpu-mesh" in sys.argv:
    sys.argv.remove("--cpu-mesh")
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.utils.benchmarking import min_positive, protocol_fields

K = int(os.environ.get("HUNT_DECODE_TOKENS", "32"))
REPEATS = int(os.environ.get("HUNT_REPEATS", "2"))
CAPACITY = int(os.environ.get("HUNT_DECODE_CAPACITY", "8"))
D_MODEL = int(os.environ.get("HUNT_SERVE_DMODEL", "256"))
LAYERS = int(os.environ.get("HUNT_SERVE_LAYERS", "4"))
HEADS = int(os.environ.get("HUNT_SERVE_HEADS", "8"))
VOCAB = int(os.environ.get("HUNT_SERVE_VOCAB", "512"))
PROMPT = int(os.environ.get("HUNT_SERVE_PROMPT", "16"))
PAGE = int(os.environ.get("HUNT_SERVE_PAGE", "16"))


def _fixture():
    from chainermn_tpu.models.transformer import TransformerLM

    max_len = PROMPT + 2 * K + PAGE
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=HEADS,
        n_layers=LAYERS, max_len=max_len,
    )
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, 8), jnp.int32),
    )
    return model, params


def _engine(model, params, capacity):
    from chainermn_tpu.serving.decode import DecodeEngine

    return DecodeEngine(model, params, capacity=capacity,
                        page_size=PAGE)


def _serve_tokens(model, params, capacity, n_requests, max_new):
    """One timed leg: a fresh engine+batcher serves ``n_requests`` of
    ``max_new`` tokens each; returns (wall_seconds, tokens, report)."""
    from chainermn_tpu.serving.batcher import ContinuousBatcher, Request

    eng = _engine(model, params, capacity)
    b = ContinuousBatcher(eng)
    rng = np.random.RandomState(0)
    reqs = [
        Request(rng.randint(0, VOCAB, PROMPT).tolist(), max_new)
        for _ in range(n_requests)
    ]
    t0 = time.monotonic()
    b.serve(reqs)
    dt = time.monotonic() - t0
    assert b.latency_report()["failed"] == 0
    return dt, b.tokens_generated, b.latency_report()


def _fingerprints(model, params, capacity):
    """The plan/budget fingerprint fields every decode row carries: the
    authored collective census + trace hash of the decode program (the
    ``decode_step`` pin's subject) — a capture where the program grew a
    collective reads as a config change, not noise."""
    from chainermn_tpu.analysis import budget_for

    eng = _engine(model, params, capacity)
    tr = eng.collective_trace("decode")
    census = tr.census()
    ceiling = budget_for("decode_step")
    within = all(census.get(c, 0) <= n for c, n in ceiling.items())
    return {
        "decode_census": census,
        "decode_trace_hash": tr.trace_hash()[:12],
        "budget": "decode_step",
        "budget_within": bool(within),
        "capacity": capacity,
        "page_size": PAGE,
        "prompt_len": PROMPT,
        "model": f"lm{LAYERS}x{D_MODEL}",
    }


def _overlap_requests(n_requests, max_new):
    """2C requests over a ONE-PAGE shared system prefix plus a
    half-page unique tail (~66% prompt overlap, page-aligned so the
    prefix index can alias it)."""
    from chainermn_tpu.serving.batcher import Request

    rng = np.random.RandomState(0)
    sys_prefix = rng.randint(0, VOCAB, PAGE).tolist()
    return [
        Request(
            sys_prefix + rng.randint(0, VOCAB, PAGE // 2).tolist(),
            max_new,
        )
        for _ in range(n_requests)
    ]


def _serve_overlap(model, params, capacity, n_requests, max_new, share):
    """Timed leg over the shared-prefix request mix; additionally
    tracks the peak DISTINCT page count (what sharing shrinks)."""
    from chainermn_tpu.serving.batcher import ContinuousBatcher

    eng = _engine(model, params, capacity)
    b = ContinuousBatcher(eng, share_prefixes=share)
    for r in _overlap_requests(n_requests, max_new):
        b.submit(r)
    peak = 0
    t0 = time.monotonic()
    while b.step():
        peak = max(peak, eng.cache.used_pages)
    dt = time.monotonic() - t0
    rep = b.latency_report()
    assert rep["failed"] == 0
    return dt, b.tokens_generated, rep, peak


def _disagg_fixture():
    """The mixed-stream fixture: max_len sized for the LONG prompts
    (3 pages) plus the 2k generation leg."""
    from chainermn_tpu.models.transformer import TransformerLM

    max_len = 3 * PAGE + 2 * K + PAGE
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=HEADS,
        n_layers=LAYERS, max_len=max_len,
    )
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, 8), jnp.int32),
    )
    return model, params


def _mixed_requests(n_requests, max_new):
    """The one mixed stream both disagg legs serve: alternating 3-page
    long prompts and half-page short ones, fixed seed."""
    from chainermn_tpu.serving.batcher import Request

    rng = np.random.RandomState(4)
    long_len, short_len = 3 * PAGE, max(2, PAGE // 2)
    return [
        Request(
            rng.randint(0, VOCAB,
                        long_len if i % 2 == 0 else short_len).tolist(),
            max_new, id=f"mix{i}",
        )
        for i in range(n_requests)
    ]


def _serve_mixed_unified(model, params, capacity, n_requests, max_new):
    """The off leg: one unified batcher serves the mixed stream."""
    from chainermn_tpu.serving.batcher import ContinuousBatcher

    eng = _engine(model, params, capacity)
    b = ContinuousBatcher(eng)
    t0 = time.monotonic()
    b.serve(_mixed_requests(n_requests, max_new))
    dt = time.monotonic() - t0
    rep = b.latency_report()
    assert rep["failed"] == 0
    return dt, b.tokens_generated, rep


def _serve_mixed_disagg(model, params, capacity, n_requests, max_new,
                        codec):
    """The on leg: prefill pool publishes handoffs through a journal,
    decode pool ingests — same stream, bit-identical outputs for
    lossless codecs (pinned in tests; this leg prices it)."""
    import tempfile

    from chainermn_tpu.serving import (
        DisaggDecodeReplica, PrefillReplica, RequestJournal,
    )

    with tempfile.TemporaryDirectory() as td:
        journal = RequestJournal(td)
        journal.submit_all(_mixed_requests(n_requests, max_new))
        pr = PrefillReplica(
            _engine(model, params, capacity), journal, codec=codec
        )
        dr = DisaggDecodeReplica(
            _engine(model, params, capacity), journal,
            handoff_timeout_s=600.0,
        )
        t0 = time.monotonic()
        pr.serve()
        dr.serve(until_complete=n_requests, timeout_s=600.0)
        dt = time.monotonic() - t0
        rep = dr.batcher.latency_report()
        assert rep["failed"] == 0
        assert dr.local_prefills == 0  # every request rode a handoff
        return dt, dr.batcher.tokens_generated, rep, pr.wire_bytes, \
            pr.published


def _run_disagg_rung(name, on):
    model, params = _disagg_fixture()
    capacity, n_requests = CAPACITY, 2 * CAPACITY
    codec = os.environ.get("HUNT_HANDOFF_CODEC", "bf16")
    samples, reports = [], []
    extra = {"disagg": bool(on),
             "handoff_codec": codec if on else None}
    for _ in range(max(REPEATS, 1)):
        if on:
            t1, n1, _, _, _ = _serve_mixed_disagg(
                model, params, capacity, n_requests, K, codec
            )
            t2, n2, rep2, wire2, pubs2 = _serve_mixed_disagg(
                model, params, capacity, n_requests, 2 * K, codec
            )
            extra["handoff_bytes"] = wire2
            extra["n_handoffs"] = pubs2
        else:
            t1, n1, _ = _serve_mixed_unified(
                model, params, capacity, n_requests, K
            )
            t2, n2, rep2 = _serve_mixed_unified(
                model, params, capacity, n_requests, 2 * K
            )
        samples.append(t2 - t1)
        reports.append((n2 - n1, rep2))
    # TTFT and its queue/prefill split: WHICH term disaggregation
    # moved is the pair's entire story
    for key, label in (("serving.ttft", "ttft"),
                       ("serving.ttft.queue", "ttft_queue"),
                       ("serving.ttft.prefill", "ttft_prefill"),
                       ("serving.ingest_latency", "ingest")):
        h = reports[-1][1].get(key)
        if h:
            extra[f"{label}_p50_ms"] = h["p50_ms"]
            extra[f"{label}_p99_ms"] = h["p99_ms"]
    fp = _fingerprints(model, params, capacity)
    # the prefill program's census rides too — the prefill_step pin's
    # subject is what a prefill POOL runs all day
    from chainermn_tpu.analysis import budget_for

    eng = _engine(model, params, capacity)
    tr = eng.collective_trace("prefill", bucket=PAGE)
    census = tr.census()
    ceiling = budget_for("prefill_step")
    fp.update({
        "prefill_census": census,
        "prefill_budget": "prefill_step",
        "prefill_budget_within": all(
            census.get(c, 0) <= n for c, n in ceiling.items()
        ),
    })
    _emit_row(name, samples, reports, fp, extra)


def _draft_fixture():
    from chainermn_tpu.models.transformer import TransformerLM

    d_model = max(16, D_MODEL // 2)
    heads = max(1, HEADS // 2)
    model = TransformerLM(
        vocab_size=VOCAB, d_model=d_model, n_heads=heads,
        n_layers=1, max_len=PROMPT + 2 * K + PAGE,
    )
    params = model.init(
        {"params": jax.random.PRNGKey(2),
         "dropout": jax.random.PRNGKey(3)},
        jnp.zeros((1, 8), jnp.int32),
    )
    return model, params


def _serve_spec(model, params, draft, dparams, capacity, n_requests,
                max_new, k):
    """Timed leg: the speculative batcher over the same request stream
    as :func:`_serve_tokens` (identical outputs by contract)."""
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.decode import DecodeEngine
    from chainermn_tpu.serving.speculative import SpeculativeBatcher

    eng = _engine(model, params, capacity)
    dr = DecodeEngine(
        draft, dparams, capacity=capacity, page_size=PAGE,
        pages_per_slot=eng.pages_per_slot,
        num_pages=eng.cache.num_pages,
    )
    b = SpeculativeBatcher(eng, dr, k=k)
    rng = np.random.RandomState(0)
    reqs = [
        Request(rng.randint(0, VOCAB, PROMPT).tolist(), max_new)
        for _ in range(n_requests)
    ]
    t0 = time.monotonic()
    b.serve(reqs)
    dt = time.monotonic() - t0
    rep = b.latency_report()
    assert rep["failed"] == 0
    return dt, b.tokens_generated, rep


def _emit_row(name, samples, reports, fingerprints, extra=None):
    """The shared row shape: min-positive paired difference, noise-
    floor null disclosure, protocol fields, serving fingerprints."""
    dt = min_positive(samples)
    tokens = reports[0][0]
    n_chips = len(jax.devices())
    rep = reports[-1][1]
    # every paired difference non-positive = the serve wall is inside
    # host jitter (noise floor).  A negative tokens/sec is nonsense
    # and a committed one would mislead forever: report a DISCLOSED
    # null instead.
    value = round(tokens / dt / n_chips, 3) if dt > 0 else None
    row = {
        "metric": f"{name}_tokens_per_sec_per_chip",
        "value": value,
        "noise_floor": dt <= 0,
        "unit": "tokens_per_sec_per_chip",
        "tokens_per_leg": tokens,
        "n_chips": n_chips,
        "samples_s": [round(s, 4) for s in samples],
        **protocol_fields(samples),
        **fingerprints,
    }
    if extra:
        row.update(extra)
    lat = rep.get("serving.token_latency")
    if lat:
        row["token_latency_p50_ms"] = lat["p50_ms"]
        row["token_latency_p99_ms"] = lat["p99_ms"]
    print(json.dumps(row), flush=True)


def _run_rung(name, capacity, n_requests):
    model, params = _fixture()
    samples, reports = [], []
    for _ in range(max(REPEATS, 1)):
        t1, n1, _ = _serve_tokens(model, params, capacity, n_requests, K)
        t2, n2, rep2 = _serve_tokens(
            model, params, capacity, n_requests, 2 * K
        )
        samples.append(t2 - t1)           # seconds for n2 - n1 tokens
        reports.append((n2 - n1, rep2))
    _emit_row(name, samples, reports,
              _fingerprints(model, params, capacity))


def _run_prefix_rung(name, share):
    model, params = _fixture()
    capacity, n_requests = CAPACITY, 2 * CAPACITY
    samples, reports, peaks = [], [], []
    for _ in range(max(REPEATS, 1)):
        t1, n1, _, _ = _serve_overlap(
            model, params, capacity, n_requests, K, share
        )
        t2, n2, rep2, peak2 = _serve_overlap(
            model, params, capacity, n_requests, 2 * K, share
        )
        samples.append(t2 - t1)
        reports.append((n2 - n1, rep2))
        peaks.append(peak2)
    extra = {
        "share_prefixes": share,
        "peak_used_pages": max(peaks),
        "prefix_hits": reports[-1][1].get("prefix_hits", 0),
        "prefix_tokens_shared":
            reports[-1][1].get("prefix_tokens_shared", 0),
    }
    if share:
        # the acceptance-criterion fingerprint: distinct pages saved
        # vs an identical cold serve (outputs bit-identical; pinned
        # by tests, disclosed here)
        _, _, _, cold_peak = _serve_overlap(
            model, params, capacity, n_requests, 2 * K, False
        )
        extra["pages_saved"] = cold_peak - max(peaks)
    _emit_row(name, samples, reports,
              _fingerprints(model, params, capacity), extra)


def _spec_fingerprints(model, params, capacity, k):
    """The verify program's authored census — the subject of the
    ``spec_verify_step`` pin — alongside the decode fingerprints."""
    from chainermn_tpu.analysis import budget_for

    fp = _fingerprints(model, params, capacity)
    eng = _engine(model, params, capacity)
    tr = eng.collective_trace("verify", bucket=k)
    census = tr.census()
    ceiling = budget_for("spec_verify_step")
    within = all(census.get(c, 0) <= n for c, n in ceiling.items())
    fp.update({
        "verify_census": census,
        "verify_trace_hash": tr.trace_hash()[:12],
        "spec_budget": "spec_verify_step",
        "spec_budget_within": bool(within),
    })
    return fp


def _run_spec_rung(name, k):
    model, params = _fixture()
    capacity, n_requests = CAPACITY, 2 * CAPACITY
    if k == 0:
        _run_rung(name, capacity, n_requests)
        return
    draft, dparams = _draft_fixture()
    samples, reports = [], []
    for _ in range(max(REPEATS, 1)):
        t1, n1, _ = _serve_spec(
            model, params, draft, dparams, capacity, n_requests, K, k
        )
        t2, n2, rep2 = _serve_spec(
            model, params, draft, dparams, capacity, n_requests,
            2 * K, k
        )
        samples.append(t2 - t1)
        reports.append((n2 - n1, rep2))
    spec = reports[-1][1].get("speculative", {})
    extra = {
        "spec_k": k,
        "acceptance_rate": spec.get("acceptance_rate", 0.0),
        "verify_steps": spec.get("verify_steps", 0),
        "draft_model": f"lm1x{max(16, D_MODEL // 2)}",
    }
    _emit_row(name, samples, reports,
              _spec_fingerprints(model, params, capacity, k), extra)


def main():
    rungs = {
        "decode_bs1": lambda: _run_rung("decode_bs1", 1, 1),
        "decode_saturated": lambda: _run_rung(
            "decode_saturated", CAPACITY, 2 * CAPACITY
        ),
        "decode_prefix_shared": lambda: _run_prefix_rung(
            "decode_prefix_shared", True
        ),
        "decode_prefix_cold": lambda: _run_prefix_rung(
            "decode_prefix_cold", False
        ),
        "decode_spec_k4": lambda: _run_spec_rung("decode_spec_k4", 4),
        "decode_spec_off": lambda: _run_spec_rung("decode_spec_off", 0),
        "decode_disagg_on": lambda: _run_disagg_rung(
            "decode_disagg_on", True
        ),
        "decode_disagg_off": lambda: _run_disagg_rung(
            "decode_disagg_off", False
        ),
    }
    for name in (sys.argv[1:] or list(rungs)):
        try:
            rungs[name]()
        except Exception as e:
            print(json.dumps({"metric": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)


if __name__ == "__main__":
    main()
