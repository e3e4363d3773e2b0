"""The timed window: one step in flight, a host-clock stamp at every
step's completion, and a count of the compiles that happen meanwhile."""

from __future__ import annotations

import math
import statistics
import time

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads: JAX reports
    both under one event) while it is entered."""

    def __enter__(self):
        from jax import monitoring

        self.count, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)


def run_window(dispatch, seconds: float, *, clock=time.perf_counter,
               on_stamp=None):
    """Drive ``dispatch() -> loss`` for ``seconds`` with exactly one
    step in flight: dispatch step i+1, then wait for step i's loss and
    stamp the clock.  Returns ``(t0, stamps, losses)``: ``t0`` is the
    completion of the step before the first counted one (the next step
    is already in flight then), so ``stamps[k] - t0`` covers exactly
    ``k + 1`` steps.  The step in flight when the window closes is
    waited for and not counted."""
    prev = dispatch()
    pending = dispatch()
    float(prev)
    t0 = clock()
    stamps, losses = [], []
    while True:
        nxt = dispatch()
        losses.append(float(pending))  # waits for the step to complete
        now = clock()
        stamps.append(now)
        if on_stamp is not None:
            on_stamp(len(stamps), now - t0)
        pending = nxt
        if now - t0 >= seconds:
            break
    float(pending)
    return t0, stamps, losses


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def step_times_ms(t0: float, stamps):
    """The time between consecutive step completions, in ms: one value
    for every step of the window."""
    edges = [t0] + list(stamps)
    return [(b - a) * 1e3 for a, b in zip(edges, edges[1:])]


def summarize(t0, stamps, losses) -> dict:
    times = step_times_ms(t0, stamps)
    median = statistics.median(times)
    late = [t - median for t in times if t > 1.5 * median]
    return {
        "steps": len(stamps),
        "failed": sum(1 for l in losses if not math.isfinite(l)),
        "elapsed_s": stamps[-1] - t0,
        "step_ms_p90": percentile(times, 90.0),
        "step_ms_median": median,
        "step_ms_max": max(times),
        # steps that took over 1.5 medians, and the time they lost: what
        # the rate sees of a stall that the 90th percentile does not
        "late_steps": len(late),
        "late_ms": sum(late),
    }
