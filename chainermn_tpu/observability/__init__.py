"""Runtime telemetry: span timeline, metrics, cross-rank attribution.

The measurement layer closing the loop the static analyzer opened: the
repo can account for every collective before it runs (``analysis``'s
``CollectiveTrace`` with ``bytes_on_wire``, shardlint attribution, HBM
pins) — this package records what actually happened at runtime and
joins the two.

* :mod:`.metrics` — histograms in a get-or-create registry;
  ``Histogram`` shares the min-of-N protocol helpers with
  ``utils.benchmarking`` so bench rows and telemetry reports compute
  spreads identically.
* :mod:`.timeline` — nestable ``span()`` context managers on the
  monotonic clock, exportable as Chrome-trace/Perfetto JSON and JSONL;
  ``ResilienceLog`` events merge into the same stream.  Activation
  mirrors the fault injector (``is None`` fast path when disabled,
  ``CHAINERMN_TPU_TELEMETRY`` env activation for spawned workers).
  Its process record (``phase()``, ``process_record()``) holds the
  once-only set-up phases from the process's start, JAX's trace /
  lower / compile events under them and a step's recompiles, telemetry
  on or off.
* :mod:`.attribute` — the static-vs-measured join:
  :func:`attribute(timeline, trace)` matches measured collective spans
  to ``CollectiveRecord``\\ s and prices achieved bytes/sec against the
  ring-model ``bytes_on_wire``; :func:`measured_issue_report` is the
  runtime analogue of ``analysis.check_overlap``'s issue ``delay``.
* :mod:`.report` — :class:`MetricsReport`, the trainer extension that
  allgathers per-process phase summaries (lockstep-retried), reports
  cross-rank p50/p99, and flags stragglers.

See docs/observability.md for the span taxonomy, viewing instructions,
and the overhead contract (disabled path ≤1 % of a CPU-mesh step,
pinned by ``tests/test_observability.py``).
"""

from .metrics import (  # noqa: F401
    Histogram,
    MetricsRegistry,
)
from .timeline import (  # noqa: F401
    ENV_TELEMETRY,
    NULL_SPAN,
    ProcessRecord,
    Telemetry,
    Timeline,
    active,
    install,
    instant,
    observe,
    phase,
    phase_attributes,
    process_record,
    setup_line,
    span,
)
from .attribute import (  # noqa: F401
    Attribution,
    AttributionReport,
    MeasuredIssue,
    SPAN_CLASS,
    attribute,
    measured_issue_report,
)
from .report import (  # noqa: F401
    DEFAULT_PHASES,
    STRAGGLER_PHASES,
    MetricsReport,
)
