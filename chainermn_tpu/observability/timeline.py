"""Span timeline: nestable wall-time spans, exportable as Chrome trace.

The runtime counterpart of ``analysis.trace``: where the static trace
records the *program's* ordered collectives, the timeline records when
each instrumented phase of the *host loop* actually ran — per rank, on
the monotonic clock, with nesting — so a slow step can be localized to
a straggler rank, a stalled input pipeline, or a bucket psum that
failed to hide under backward (exactly the question PAPERS.md's
multi-node inference study answers with latency attribution, not byte
counts).

Activation follows the fault injector's pattern
(``resilience.fault_injection``): a module-global ``_ACTIVE``
:class:`Telemetry` that is ``None`` unless a context manager /
``install()`` / the ``CHAINERMN_TPU_TELEMETRY`` env var enabled it, and
the instrumented sites' disabled fast path is one ``is None`` check
returning a stateless null context manager (overhead contract:
disabled-path cost ≤1 % of a CPU-mesh step, pinned by
``tests/test_observability.py``).

Every active span is also a ``jax.profiler.TraceAnnotation`` of the
same name on its thread's line of the profiler's host plane, so one
``jax.profiler`` trace shows the host's phases over the device's
operations on one clock; ``step`` / ``update`` are the
``StepTraceAnnotation`` ("train", ``step_num``) xprof's step view wants.

Span taxonomy (see docs/observability.md for the full table)::

    step                 one trainer iteration (update + extensions)
    update               Updater.update (incl. injected-fault sites)
    data.wait            blocking on next(iterator)
    feed.collate/place   device prefetcher: next(host iterator), the
                         device_put enqueue (both under data.wait)
    feed.h2d             enqueue -> placed batch ready (its own thread)
    compute.dispatch     batch placement + compiled-step dispatch
    collective.<name>    eager-tier collective (allreduce, psum buckets)
    wire.pack/ship/reduce  bucket pipeline phases (host-staged tier)
    obj_store.send/recv/exchange   control-plane transport
    checkpoint.save/resume/agreement/reshard

Observer effect, disclosed: with telemetry active, the eager tier's
per-bucket collective spans force completion (``block_until_ready``)
so a span is a *latency*, not a dispatch time — the measured run
serializes bucket dispatch where the unobserved run pipelines it.  The
disabled path is byte-identical to pre-telemetry behavior.

``ResilienceLog`` events (which carry monotonic timestamps since
ISSUE 10's satellite fix) merge into the same stream via
:meth:`Timeline.merge_resilience`, so one exported timeline shows
spans, faults, retries, and restarts in context; ``Trainer.run`` merges
its own log automatically when telemetry is active.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class Telemetry:
    """One activation's worth of state: a metrics registry + a timeline
    that feeds span durations into it (every closed span observes its
    duration into ``registry.histogram(span_name)``)."""

    def __init__(self, label: str = "telemetry"):
        from .metrics import MetricsRegistry

        self.label = label
        self.registry = MetricsRegistry()
        self.timeline = Timeline(label=label, registry=self.registry)


class _NullSpan:
    """The disabled path's context manager: stateless singleton, no
    clock reads, no allocation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


#: argument that makes a span a *step*: the outermost such span of a
#: thread enters the profiler as ``StepTraceAnnotation(STEP_NAME,
#: step_num=...)`` (the marker xprof builds its step view from), with
#: the span's own name under ``span``; every other span is a
#: ``TraceAnnotation`` of its name
STEP_ARG = "step_num"
STEP_NAME = "train"

_profiler = None  # jax.profiler, imported at the first active span


class _SpanCM:
    """Context manager recording one span on enter/exit — on the
    timeline's monotonic clock, and as an annotation on the calling
    thread's line of the JAX profiler's ``/host:CPU`` plane, beside the
    device planes and on their clock (a few hundred ns while no
    ``jax.profiler`` trace is being taken)."""

    __slots__ = ("_tl", "name", "args", "_t0", "_id", "_parent", "_ann",
                 "_step")

    def __init__(self, tl: "Timeline", name: str, args: dict):
        self._tl = tl
        self.name = name
        self.args = args

    def set(self, **args) -> None:
        """Attach/overwrite span args mid-span (e.g. payload bytes
        known only after serialization).  The profiler's annotation
        keeps the args the span was opened with."""
        self.args.update(args)

    def __enter__(self):
        global _profiler
        tl = self._tl
        stack = tl._stack()
        self._parent = stack[-1] if stack else 0
        self._id = next(tl._ids)
        stack.append(self._id)
        if _profiler is None:
            import jax.profiler as _profiler
        local = tl._local
        self._step = STEP_ARG in self.args \
            and not getattr(local, "in_step", False)
        if self._step:
            local.in_step = True
            self._ann = _profiler.StepTraceAnnotation(
                STEP_NAME, span=self.name, **self.args)
        else:
            self._ann = _profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        tl = self._tl
        if self._step:
            tl._local.in_step = False
        stack = tl._stack()
        if stack and stack[-1] == self._id:
            stack.pop()
        tl._append({
            "type": "span",
            "name": self.name,
            "t": self._t0,
            "dur": t1 - self._t0,
            "sid": self._id,
            "parent": self._parent,
            "tid": tl._tid(),
            "args": self.args,
        })
        if tl._registry is not None:
            tl._registry.histogram(self.name).observe(t1 - self._t0)
        return False


class Timeline:
    """Append-only event stream (spans + instants), thread-safe.

    Times are ``time.monotonic()`` seconds; exports are relative to the
    timeline's construction instant (``t0``), in microseconds for the
    Chrome trace.  A wall-clock anchor (``wall0``) rides along so
    cross-rank timelines can be aligned approximately.
    """

    def __init__(self, label: str = "timeline", registry=None):
        self.label = label
        self.t0 = time.monotonic()
        self.wall0 = time.time()
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._registry = registry
        self._ids = itertools.count(1)
        self._tids: Dict[int, int] = {}
        # id -> the event OBJECT: holding the reference is load-bearing
        # (a bare id() set would let freed events recycle addresses and
        # silently drop later logs' events from the merge)
        self._merged: Dict[int, object] = {}

    # -- recording -----------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, **args) -> _SpanCM:
        return _SpanCM(self, name, args)

    def instant(self, name: str, t: Optional[float] = None, **args) -> None:
        """A zero-duration marker (fault fired, straggler flagged).
        ``t`` overrides the timestamp (monotonic seconds) — how merged
        resilience events keep their original positions."""
        self._append({
            "type": "instant",
            "name": name,
            "t": time.monotonic() if t is None else float(t),
            "tid": self._tid(),
            "args": args,
        })

    def merge_resilience(self, log) -> int:
        """Fold a ``ResilienceLog``'s events into this timeline as
        ``resilience.<kind>`` instants at their recorded monotonic
        timestamps.  Idempotent per event *object* (``emit`` appends the
        same event object to every attached sink, so merging both a
        trainer log and a standalone sink cannot duplicate); events
        predating the monotonic-timestamp fields are skipped.  Returns
        the number of events merged."""
        n = 0
        for ev in log:
            if id(ev) in self._merged:
                continue
            self._merged[id(ev)] = ev
            mono = getattr(ev, "monotonic", None)
            if mono is None:
                continue
            args = {"site": ev.site}
            for k, v in ev.info.items():
                args[k] = v if isinstance(
                    v, (int, float, str, bool, type(None))
                ) else repr(v)
            # the RECORDING rank, under its own key: an event's info
            # may legitimately carry a "process" that names the
            # SUBJECT (the straggler emit does), and the recorder
            # stamp must not be overwritten by it
            proc = getattr(ev, "process", None)
            if proc is not None:
                args["recorded_by"] = proc
            self.instant(f"resilience.{ev.kind}", t=mono, **args)
            n += 1
        return n

    # -- queries -------------------------------------------------------
    def events(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            evs = list(self._events)
        evs.sort(key=lambda e: e["t"])
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        return evs

    def spans(self, name: Optional[str] = None) -> List[dict]:
        return [e for e in self.events(name) if e["type"] == "span"]

    def __len__(self):
        with self._lock:
            return len(self._events)

    # -- export --------------------------------------------------------
    @property
    def process(self) -> int:
        from ..resilience.log import process_index

        return process_index()

    def chrome_trace(self) -> dict:
        """The Chrome-trace/Perfetto JSON object (``chrome://tracing``,
        https://ui.perfetto.dev — load the file directly)."""
        pid = self.process
        out = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": f"{self.label} (process {pid})"},
        }]
        for e in self.events():
            ts = (e["t"] - self.t0) * 1e6
            if e["type"] == "span":
                out.append({
                    "name": e["name"], "cat": "span", "ph": "X",
                    "ts": ts, "dur": e["dur"] * 1e6,
                    "pid": pid, "tid": e["tid"], "args": e["args"],
                })
            else:
                out.append({
                    "name": e["name"], "cat": "event", "ph": "i",
                    "ts": ts, "s": "p", "pid": pid, "tid": e["tid"],
                    "args": e["args"],
                })
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {"label": self.label, "wall0": self.wall0},
        }

    def to_chrome_trace(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f, default=str)
        return path

    def to_jsonl(self, path: str, *, meta: bool = False) -> str:
        """One JSON object per event, sorted by time, timestamps
        relative to ``t0`` in seconds — the grep/diff-friendly export
        the mp scenarios consume.

        ``meta=True`` prepends one ``{"type": "meta", ...}`` row
        carrying the wall-clock anchor (``wall0``, captured at the same
        instant as ``t0``): cross-process readers (the fleet tier's
        merged report) recover each event's approximate wall time as
        ``wall0 + t``, which is what lets N processes' exports land on
        one ordered timeline."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        pid = self.process
        with open(path, "w", encoding="utf-8") as f:
            if meta:
                f.write(json.dumps({
                    "type": "meta", "name": "timeline.meta", "t": 0.0,
                    "process": pid, "tid": 0,
                    "args": {"wall0": self.wall0, "label": self.label},
                }) + "\n")
            for e in self.events():
                row = {
                    "type": e["type"],
                    "name": e["name"],
                    "t": round(e["t"] - self.t0, 9),
                    "process": pid,
                    "tid": e["tid"],
                    "args": e["args"],
                }
                if e["type"] == "span":
                    row["dur"] = round(e["dur"], 9)
                f.write(json.dumps(row, default=str) + "\n")
        return path


# ----------------------------------------------------------------------
# activation (the fault injector's pattern)
# ----------------------------------------------------------------------
ENV_TELEMETRY = "CHAINERMN_TPU_TELEMETRY"

_ACTIVE: Optional[Telemetry] = None


def active() -> Optional[Telemetry]:
    return _ACTIVE


def install(telemetry: Optional[Telemetry]) -> None:
    """Set (or clear, with ``None``) the process-global telemetry."""
    global _ACTIVE
    _ACTIVE = telemetry


def span(name: str, **args):
    """Hot-path hook at every instrumented site.

    The disabled fast path is this one ``is None`` check returning the
    stateless :data:`NULL_SPAN` — no clock read, no allocation beyond
    the kwargs dict."""
    t = _ACTIVE
    if t is None:
        return NULL_SPAN
    return t.timeline.span(name, **args)


def instant(name: str, **args) -> None:
    t = _ACTIVE
    if t is not None:
        t.timeline.instant(name, **args)


class observe:
    """Context manager: activate a :class:`Telemetry` for a ``with``
    block (nesting restores the previous one on exit)::

        with observability.observe() as tel:
            trainer.run()
        tel.timeline.to_chrome_trace("trace.json")
    """

    def __init__(self, telemetry: Optional[Telemetry] = None):
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._prev: Optional[Telemetry] = None

    def __enter__(self) -> Telemetry:
        self._prev = _ACTIVE
        install(self.telemetry)
        return self.telemetry

    def __exit__(self, *exc):
        install(self._prev)
        return False


def _from_env() -> None:
    """Activate from ``CHAINERMN_TPU_TELEMETRY`` (any non-empty value
    other than "0") — how spawned multi-process workers get telemetry
    without an object reference, mirroring ``CHAINERMN_TPU_FAULTS``."""
    raw = os.environ.get(ENV_TELEMETRY)
    if raw and raw != "0":
        install(Telemetry(label=f"env:{raw}"))


_from_env()
