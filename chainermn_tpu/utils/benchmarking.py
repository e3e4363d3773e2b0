"""Measurement helpers for the scripts under ``benchmarks/``,
``observability/metrics.py`` and ``comm_wire/autotune.py``.

They force completion with a host *value readback* (it needs the bytes,
so it cannot return before the work is done) and time paired k/2k runs
whose difference cancels the readback round-trip and any constant
per-call overhead.
"""

from __future__ import annotations

import time

import numpy as np


def force_completion(x) -> float:
    """Block until ``x`` is computed by reading one element back."""
    return float(np.asarray(x).ravel()[0])


def time_steps(run_fn, steps: int, warmup: int = 1,
               burn_seconds: float = 0.0, repeats: int = 1):
    """Seconds per step of ``run_fn`` via paired k / 2k timed runs.

    Returns ``(dt, samples)``: the reported seconds-per-step under the
    min-of-N protocol (smallest positive paired difference; the long
    run's average as the noise-floor fallback) AND the raw per-repeat
    paired-difference samples — callers attach the samples to an
    ``observability.metrics.Histogram`` / ``protocol_fields`` so the
    reported number and its spread disclosure come from one source
    (ISSUE 10 satellite: the helper used to discard them, leaving each
    bench rung to re-measure for its spread).

    ``run_fn()`` must return an array whose value depends on the step's
    full computation (chain steps through a carried state so the final
    readback transitively waits on every one).  At least one warmup call
    always runs — it absorbs compilation and produces the value the
    pre-timing readback synchronizes on.

    ``burn_seconds``: keep the device busy with ``run_fn`` for at least
    this long before timing, so that the first executable measured in
    a fresh process is not timed cold; benchmark entry points pass
    ~10 s here.  The burn runs once, before the first repeat.
    """
    steps = max(int(steps), 1)
    out = None
    for _ in range(max(int(warmup), 1)):
        out = run_fn()
    force_completion(out)
    if burn_seconds > 0:
        t_end = time.perf_counter() + burn_seconds
        while time.perf_counter() < t_end:
            out = run_fn()
            force_completion(out)

    def timed(k):
        t0 = time.perf_counter()
        for _ in range(k):
            out = run_fn()
        force_completion(out)
        return time.perf_counter() - t0

    dts = []
    t2_last = None
    for _ in range(max(int(repeats), 1)):
        t1 = timed(steps)
        t2 = timed(2 * steps)
        dts.append((t2 - t1) / steps)
        t2_last = t2
    dt = min_positive(dts)
    if dt <= 0:  # noise floor: fall back to the long run's average
        dt = t2_last / (2 * steps)
    return dt, dts


def protocol_fields(samples) -> dict:
    """The min-of-N disclosure every timed bench row carries
    (``analysis.lint``'s ``untimed-row`` rule enforces its presence):
    ``n_measurements`` = how many paired measurements produced the
    reported number, ``spread_max_over_min`` = how far apart the
    positive ones landed (omitted honestly when fewer than 2 samples
    are positive — fabricating a spread from noise-floor readings would
    overstate confidence).  ``samples`` is in any unit; the spread is
    unit-free."""
    samples = list(samples)
    out = {"n_measurements": len(samples)}
    pos = [s for s in samples if s > 0]
    if len(pos) >= 2:
        out["spread_max_over_min"] = round(max(pos) / min(pos), 3)
    return out


def min_positive(samples):
    """The reported number under the min-of-N protocol: the smallest
    POSITIVE sample (noise only adds time, so min bounds from above);
    when every paired difference landed non-positive (noise floor) the
    last sample is the honest fallback.  Companion of
    :func:`protocol_fields` — the selection and the disclosure are one
    protocol, defined in one place."""
    samples = list(samples)
    pos = [s for s in samples if s > 0]
    return min(pos) if pos else samples[-1]


def time_kloop(run_k, k: int, repeats: int = 2):
    """Seconds per step for a k-steps-in-ONE-dispatch harness.

    ``run_k(n)`` must execute n steps inside a single device dispatch
    (e.g. a jitted ``fori_loop`` with a traced trip count) and return an
    array depending on every step.  Times paired k / 2k dispatches and
    returns ``(dt, samples)`` where dt is the min positive paired
    difference — per-dispatch link noise that plagues step-at-a-time
    timing cancels because one dispatch covers seconds of device time
    (shared here so the benchmark scripts can't drift apart).  Falls
    back to the long run's
    average when every paired difference is non-positive (noise floor).
    """
    force_completion(run_k(2))  # compile + warm
    dts = []
    t2k_last = None
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        force_completion(run_k(k))
        t1 = time.perf_counter()
        force_completion(run_k(2 * k))
        t2 = time.perf_counter()
        dts.append(((t2 - t1) - (t1 - t0)) / k)
        t2k_last = t2 - t1
    positive = [d for d in dts if d > 0]
    dt = min(positive) if positive else t2k_last / (2 * k)
    return dt, dts
