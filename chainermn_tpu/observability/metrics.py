"""Metrics registry: histograms of span durations.

The runtime half of the repo's measurement story.  The static analyzer
(``analysis``) prices every collective before it runs; these metrics
record what actually happened — step times, data-wait vs compute
splits, per-bucket wire latencies — in a process-local registry the
:class:`~chainermn_tpu.observability.report.MetricsReport` extension
aggregates across ranks.

Design mirrors the fault injector's activation pattern
(``resilience.fault_injection``): the registry only exists inside an
active :class:`~chainermn_tpu.observability.timeline.Telemetry`, and
every instrumented site's disabled fast path is a single ``is None``
check in ``observability.timeline.span`` — no counter, no dict lookup,
no allocation (the ≤1 % overhead contract, pinned by
``tests/test_observability.py``).

``Histogram`` is also the bench tier's sample carrier: its
:meth:`Histogram.protocol_fields` defers to
``utils.benchmarking.protocol_fields``, so ``spread_max_over_min`` in a
bench row and in a telemetry report are computed by the SAME code from
the SAME samples (the ``time_steps`` satellite of ISSUE 10).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class Histogram:
    """Append-only sample list with the percentile/spread queries the
    cross-rank report needs.

    Samples are kept raw (not pre-bucketed): step counts are small
    (thousands per run), the report windows consume them incrementally,
    and raw samples are what the min-of-N protocol helpers operate on.
    """

    __slots__ = ("name", "_values")

    def __init__(self, name: str = ""):
        self.name = name
        self._values: List[float] = []

    def observe(self, v: float) -> None:
        self._values.append(float(v))

    def extend(self, values) -> None:
        for v in values:
            self.observe(v)

    @property
    def values(self) -> List[float]:
        return list(self._values)

    @property
    def last(self) -> Optional[float]:
        """Most recent sample without copying the list (the per-step
        derived-metric path reads this every iteration)."""
        return self._values[-1] if self._values else None

    def tail(self, start: int) -> List[float]:
        """Samples from index ``start`` on, copying only the tail —
        the report windows consume these incrementally, and copying
        the full history per report would be quadratic over a long
        run."""
        return list(self._values[start:])

    @property
    def count(self) -> int:
        return len(self._values)

    @property
    def sum(self) -> float:
        return float(np.sum(self._values)) if self._values else 0.0

    @property
    def mean(self) -> float:
        return float(np.mean(self._values)) if self._values else 0.0

    @property
    def max(self) -> float:
        return float(np.max(self._values)) if self._values else 0.0

    def percentile(self, p: float) -> float:
        if not self._values:
            return 0.0
        return float(np.percentile(np.asarray(self._values), p))

    def protocol_fields(self) -> dict:
        """The min-of-N disclosure (``n_measurements`` /
        ``spread_max_over_min``) computed by the ONE shared helper —
        ``utils.benchmarking.protocol_fields`` — so bench rows and
        telemetry reports can never disagree about what a spread is."""
        from ..utils.benchmarking import protocol_fields

        return protocol_fields(self._values)

    @property
    def spread_max_over_min(self) -> Optional[float]:
        return self.protocol_fields().get("spread_max_over_min")

    def __len__(self):
        return len(self._values)

    def __repr__(self):
        return f"<Histogram {self.name} n={len(self._values)}>"


class MetricsRegistry:
    """Get-or-create registry of named metrics.

    Instrumented sites never construct metrics directly — they ask the
    registry, which creates on first use, so a site and its reader
    cannot disagree about a metric's identity.
    """

    def __init__(self):
        self._histograms: Dict[str, Histogram] = {}

    def histogram(self, name: str) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            h = self._histograms[name] = Histogram(name)
        return h

    def has_histogram(self, name: str) -> bool:
        return name in self._histograms

    def snapshot(self) -> dict:
        """JSON-serializable view of everything recorded so far."""
        return {
            "histograms": {
                k: {
                    "count": h.count,
                    "mean": h.mean,
                    "p50": h.percentile(50),
                    "p99": h.percentile(99),
                    "max": h.max,
                }
                for k, h in self._histograms.items()
            },
        }
