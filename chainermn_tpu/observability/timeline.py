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
    feed.collate         device prefetcher: next(host iterator), under
                         data.wait
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

The process record (:class:`ProcessRecord`, one a process:
:data:`PROCESS`) holds what runs once a process and never on the step
path, whether or not telemetry is on: the set-up *phases*, JAX's own
trace / lower / compile events as their children, and the recompiles of
a step.  Its zero is the process's start as the OS has it, so the
interpreter's start and every import before the program's first line
are a phase too.  ``Timeline.chrome_trace()`` shows its spans at their
own times, so one Chrome trace holds set-up and steps; a phase that runs
under a ``jax.profiler`` trace is a ``TraceAnnotation`` like any span::

    setup                  root: process start -> end of the last phase
    setup.before_program   process start -> first line of this package
    setup.import           ``import chainermn_tpu``
    setup.communicator     create_communicator
    setup.init_params      parallel.sharded_init (or the caller's
                           ``with observability.phase(...)``)
    setup.optimizer        create_multi_node_optimizer, opt.init
    setup.build_step       build_train_step
    setup.place_state      step.place
    step.first_call        a call of a step object during which JAX
                           compiled or loaded its program (fun_name,
                           ordinal, cache); ``step.trace`` where a step
                           was traced and nothing was compiled (the
                           divergence guard, ``jax.eval_shape``)
    jax.trace/lower/compile  JAX's own stages (``jax.monitoring``), each
                           under the phase or stage open on its thread;
                           the traces of inner jits inside a stage are a
                           count and a sum on it (nested_traces,
                           nested_s), not spans
    step.recompile         instant: a step object compiled a program
                           after its first (fun_name, ordinal, seconds,
                           cache): in the record always, in the timeline
                           while telemetry is on

    counters, in total and by phase: compile.programs,
    compile.cache_hits, compile.cache_misses, compile.cache_load_s

Overhead of the record: a phase is two clock reads and one list append,
once a process; the JAX listeners fire at trace, lower and compile
events only, which a cached step never reaches.  The step path gains no
span and no clock read from it.

``ResilienceLog`` events (which carry monotonic timestamps since
ISSUE 10's satellite fix) merge into the same stream via
:meth:`Timeline.merge_resilience`, so one exported timeline shows
spans, faults, retries, and restarts in context; ``Trainer.run`` merges
its own log automatically when telemetry is active.
"""

from __future__ import annotations

import collections
import functools
import itertools
import json
import os
import sys
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

    Times are ``time.monotonic()`` seconds; the JSONL export is relative
    to the timeline's construction instant (``t0``), the Chrome trace to
    the process's start (it holds the process record's set-up phases
    too), in microseconds.  A wall-clock anchor (``wall0``) rides along
    so cross-rank timelines can be aligned approximately.
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

    def _tid(self, ident: Optional[int] = None) -> int:
        if ident is None:
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
        # the process record's spans at their own times: time zero is
        # the process's start, so set-up and steps are one trace
        zero = min(self.t0, PROCESS.start)
        for e in PROCESS.snapshot()["spans"]:
            out.append({
                "name": e["name"], "cat": "process", "ph": "X",
                "ts": (e["t"] - zero) * 1e6, "dur": e["dur"] * 1e6,
                "pid": pid, "tid": self._tid(e["ident"]),
                "args": e["args"],
            })
        for e in self.events():
            ts = (e["t"] - zero) * 1e6
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
            # wall0: the wall clock at time zero
            "otherData": {"label": self.label,
                          "wall0": self.wall0 - (self.t0 - zero)},
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
# the process record: what runs once a process, recorded always
# ----------------------------------------------------------------------
ROOT_PHASE = "setup"
_ROOT_ID = -1  # the record's ids are negative: no Timeline sid is

#: JAX's own stages (the ``jax.monitoring`` events of
#: ``dispatch.log_elapsed_time``) and the spans they become
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
#: the persistent cache's verdict on the compile in progress
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COUNTERS = ("compile.programs", "compile.cache_hits",
            "compile.cache_misses", "compile.cache_load_s")


def _process_start():
    """The process's start on ``time.monotonic()`` as the OS has it
    (``/proc/self/stat``'s start time against the boot clock, to a
    clock tick), and where it came from: ``"os"``, or ``"import"`` (now)
    on a system without ``/proc``."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command, field 2, may hold spaces and parentheses
            after_comm = f.read().rsplit(b")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return now, "import"
    return now - max(age, 0.0), "os"


class _Phase:
    """Context manager of one phase of a :class:`ProcessRecord`."""

    __slots__ = ("_rec", "name", "args", "_t0", "id", "_parent", "_ann")

    def __init__(self, rec: "ProcessRecord", name: str, args: dict,
                 t0: Optional[float] = None):
        self._rec, self.name, self.args, self._t0 = rec, name, args, t0

    def __enter__(self):
        rec = self._rec
        local = rec._thread()
        rec._end_step_trace(local)
        with rec._lock:
            self.args.update(rec._attributes.pop(self.name, {}))
        self._parent = local.phases[-1].id if local.phases else _ROOT_ID
        self.id = -next(rec._ids)
        local.phases.append(self)
        # jax.profiler is loaded with jax; before that no trace is on
        prof = sys.modules.get("jax.profiler")
        self._ann = None
        if prof is not None:
            self._ann = prof.TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        if self._t0 is None:
            self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec = self._rec
        local = rec._thread()
        rec._end_step_trace(local)
        if local.phases and local.phases[-1] is self:
            local.phases.pop()
        rec._append(self.name, self._t0, t1, self.id, self._parent,
                    self.args)
        return False


class ProcessRecord:
    """Once-only phases from the process's start, JAX's trace / lower /
    compile events as their children, compile counters and the
    recompiles of a step: recorded always, bounded, on the timeline's
    clock (``time.monotonic()``; ``start`` is the process's own start).

    Spans are dicts like a :class:`Timeline`'s (``name``, ``t``,
    ``dur``, ``sid``, ``parent``, ``args``) with the recording thread's
    ``ident``; the root is :data:`ROOT_PHASE`, which ends where the last
    phase under it ended."""

    #: spans kept; past it the counters still count and ``dropped`` says
    #: how many spans were not kept
    MAX_SPANS = 16384
    MAX_RECOMPILES = 256

    def __init__(self, start: Optional[float] = None):
        if start is None:
            self.start, self.origin = _process_start()
        else:
            self.start, self.origin = float(start), "given"
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(2)
        self._spans: List[dict] = []
        self.root = {"type": "span", "name": ROOT_PHASE, "t": self.start,
                     "dur": 0.0, "sid": _ROOT_ID, "parent": 0,
                     "ident": threading.main_thread().ident, "args": {}}
        self.recompiles: collections.deque = collections.deque(
            maxlen=self.MAX_RECOMPILES)
        self.counters = dict.fromkeys(COUNTERS, 0)
        self.by_phase: Dict[str, dict] = {}
        self.dropped = 0
        self._listening = False
        # attributes waiting for the next phase of a name
        self._attributes: Dict[str, dict] = {}

    # -- recording -----------------------------------------------------
    def _thread(self):
        local = self._local
        if not hasattr(local, "phases"):
            local.phases = []   # open phases, innermost last
            local.stages = []   # open JAX stages: [id, start, traces
            #                     nested in it, their seconds]
            local.step = None   # the step call being traced, if any
            local.cache = None  # the cache's word on the compile open
            local.load_s = 0.0
        return local

    def _append(self, name, t0, t1, sid, parent, args) -> None:
        with self._lock:
            # the root ends with the last phase under it: not with a
            # JAX stage outside every phase, nor with a recompile
            if parent == _ROOT_ID and not name.startswith("jax.") \
                    and not args.get("recompile"):
                self.root["dur"] = max(self.root["dur"], t1 - self.start)
            if len(self._spans) >= self.MAX_SPANS:
                self.dropped += 1
                return
            self._spans.append({
                "type": "span", "name": name, "t": t0, "dur": t1 - t0,
                "sid": sid, "parent": parent,
                "ident": threading.get_ident(), "args": args})

    def _count(self, local, counter: str, by=1) -> None:
        if local.step is not None:
            phase = "step.first_call"
        else:
            phase = local.phases[-1].name if local.phases else ROOT_PHASE
        with self._lock:
            self.counters[counter] += by
            per = self.by_phase.setdefault(phase,
                                           dict.fromkeys(COUNTERS, 0))
            per[counter] += by

    def phase(self, name: str, *, t0: Optional[float] = None,
              **args) -> _Phase:
        """``with record.phase(name, **args):`` records one phase under
        the phase open on this thread (else under the root)."""
        return _Phase(self, name, args, t0)

    def phase_attributes(self, name: str, **args) -> None:
        """Attributes for the next phase called ``name``, from a caller
        that knows them and does not open the phase itself (the example
        that knows what its model keeps, for ``build_train_step``'s
        ``setup.build_step``)."""
        with self._lock:
            self._attributes.setdefault(name, {}).update(args)

    def open_phase_attributes(self) -> dict:
        """The attributes of the innermost phase open on this thread
        (the root's outside every phase), as the dict its span keeps:
        what the caller writes there shows in the record, also after
        the phase has closed (a step builder learns its gradients'
        shapes at the step's first call)."""
        local = self._thread()
        return local.phases[-1].args if local.phases else self.root["args"]

    # -- the program's first lines -------------------------------------
    def program_starts(self, t: float) -> _Phase:
        """``t``: the first line of the package's ``__init__``.  Records
        ``setup.before_program`` up to it and opens ``setup.import``
        there; the caller closes that at its last line."""
        self._append("setup.before_program", self.start, t,
                     -next(self._ids), _ROOT_ID, {"origin": self.origin})
        return self.phase("setup.import", t0=t).__enter__()

    # -- JAX's stages and the cache's events ---------------------------
    def listen(self) -> None:
        """Register the ``jax.monitoring`` listeners, once."""
        if self._listening:
            return
        self._listening = True
        from jax import monitoring

        monitoring.register_scalar_listener(self._stage_starts)
        monitoring.register_event_time_span_listener(self._stage_ends)
        monitoring.register_event_listener(self._cache_event)
        monitoring.register_event_duration_secs_listener(self._cache_load)

    def _stage_starts(self, event, value, fun_name="", **_):
        if event in _JAX_STAGES:
            local = self._thread()
            step = local.step
            if step is not None and _JAX_STAGES[event] == "jax.trace" \
                    and fun_name != step["args"]["fun_name"]:
                # another program's trace begins: the step traced
                # before it was not compiled (a trace of the step
                # itself goes on with it: JAX may not run its body
                # again after the divergence guard's walk)
                self._end_step_trace(local)
            local.stages.append(
                [-next(self._ids), time.monotonic(), 0, 0.0])

    def _cache_event(self, event, **_):
        verdict = _CACHE_EVENTS.get(event)
        if verdict is not None:
            local = self._thread()
            local.cache = verdict
            self._count(local, "compile.cache_hits" if verdict == "hit"
                        else "compile.cache_misses")

    def _cache_load(self, event, duration, **_):
        if event == _CACHE_LOAD_EVENT:
            local = self._thread()
            local.load_s = duration
            self._count(local, "compile.cache_load_s", duration)

    def _stage_ends(self, event, start, end, fun_name="", **_):
        name = _JAX_STAGES.get(event)
        if name is None:
            return
        now = time.monotonic()
        local = self._thread()
        # a listener registered inside a stage sees its end only
        sid, _, nested, nested_s = local.stages.pop() if local.stages \
            else (-next(self._ids), now, 0, 0.0)
        t0 = now - (end - start)  # JAX stamps the wall clock: keep ours
        if local.stages and name == "jax.trace":
            # the trace of an inner jit inside another program's stage:
            # thousands a step, a count and a sum on the stage they are
            # in and no spans of their own
            outer = local.stages[-1]
            outer[2] += 1 + nested
            outer[3] += now - t0
            return
        if local.stages:
            parent = local.stages[-1][0]
        elif local.step is not None:
            parent = local.step["sid"]
            local.step["end"] = now
        else:
            parent = local.phases[-1].id if local.phases else _ROOT_ID
        args = {"fun_name": fun_name}
        if nested:
            args.update(nested_traces=nested, nested_s=nested_s)
        if name == "jax.compile":
            self._count(local, "compile.programs")
            args["cache"] = local.cache or "uncached"
            if local.load_s:
                args["load_s"] = local.load_s
            local.cache, local.load_s = None, 0.0
        self._append(name, t0, now, sid, parent, args)
        if name == "jax.compile" and local.step is not None \
                and not local.stages:
            if local.step["args"]["fun_name"] in fun_name:
                self._end_step_call(local, fun_name, now - t0,
                                    args["cache"])
            else:  # another program's compile: the step's never came
                self._end_step_trace(local)

    # -- a step object's slow path -------------------------------------
    def step_traced(self, fun_name: str, ordinal: int,
                    programs: list) -> None:
        """Called by a step object from inside the trace of its program,
        which is the slow path and nothing else: opens the step call
        that the compile after this trace closes as ``step.first_call``.
        ``ordinal``: the call's number among the step object's calls;
        ``programs``: the step object's one-element count of the
        programs compiled for it so far, raised here."""
        local = self._thread()
        self._end_step_trace(local, force=True)
        t0 = local.stages[0][1] if local.stages else time.monotonic()
        local.step = {
            "sid": -next(self._ids), "t": t0, "end": t0,
            "parent": local.phases[-1].id if local.phases else _ROOT_ID,
            "args": {"fun_name": fun_name, "ordinal": ordinal},
            "programs": programs}

    def _end_step_trace(self, local, force: bool = False) -> None:
        """A step that was traced and not compiled after (the divergence
        guard's walk, ``jax.eval_shape``, an ahead-of-time ``lower``)
        ends with the last stage under it, as ``step.trace``."""
        step = local.step
        if step is None or (local.stages and not force):
            return
        local.step = None
        self._append("step.trace", step["t"], step["end"], step["sid"],
                     step["parent"], step["args"])

    def _end_step_call(self, local, fun_name, seconds, cache) -> None:
        step, local.step = local.step, None
        # the program's name as the device trace has it (jit__step)
        args = dict(step["args"], fun_name=fun_name, cache=cache)
        recompile = step["programs"][0] > 0
        step["programs"][0] += 1
        now = time.monotonic()
        if recompile:
            args["recompile"] = True
            note = {"fun_name": fun_name, "ordinal": args["ordinal"],
                    "seconds": seconds, "cache": cache}
            self.recompiles.append({"type": "instant",
                                    "name": "step.recompile", "t": now,
                                    "ident": threading.get_ident(),
                                    "args": note})
            instant("step.recompile", t=now, **note)
            prof = sys.modules.get("jax.profiler")
            if prof is not None:
                # while a profile is taken: its name beside the idle
                # gap it caused, on the thread that compiled
                with prof.TraceAnnotation("step.recompile", **note):
                    pass
        self._append("step.first_call", step["t"], now, step["sid"],
                     step["parent"], args)

    # -- queries -------------------------------------------------------
    def snapshot(self) -> dict:
        """What is recorded so far: ``start`` and its ``origin``,
        ``spans`` (the root first; a step call still open is not among
        them, its children are), ``recompiles``, the ``counters`` in
        total and ``by_phase``, and ``dropped``."""
        with self._lock:
            return {
                "start": self.start, "origin": self.origin,
                "spans": [dict(self.root)] + list(self._spans),
                "recompiles": list(self.recompiles),
                "counters": dict(self.counters),
                "by_phase": {k: dict(v)
                             for k, v in self.by_phase.items()},
                "dropped": self.dropped,
            }


#: this process's record
PROCESS = ProcessRecord()


def phase(name: str, **args) -> _Phase:
    """``with phase("setup.init_params"):`` records a once-only phase in
    the process record, telemetry on or off.  Not for the step path: a
    phase reads the clock twice and appends to a bounded list."""
    return PROCESS.phase(name, **args)


def phase_attributes(name: str, **args) -> None:
    """:meth:`ProcessRecord.phase_attributes` of this process's record:
    ``args`` become attributes of the next phase called ``name``."""
    PROCESS.phase_attributes(name, **args)


def open_phase_attributes() -> dict:
    """:meth:`ProcessRecord.open_phase_attributes` of this process's
    record: the open phase's attributes, to write into now or later."""
    return PROCESS.open_phase_attributes()


def phased(name: str):
    """Decorator form of :func:`phase`: every call of the function is
    one phase ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def in_phase(*args, **kwargs):
            with PROCESS.phase(name):
                return fn(*args, **kwargs)

        return in_phase

    return wrap


def process_record() -> dict:
    """The process record's :meth:`ProcessRecord.snapshot`: how a
    running job, a benchmark's reader or a test asks where set-up went
    and which step recompiled."""
    return PROCESS.snapshot()


def setup_line() -> str:
    """Where set-up went so far, as one line for a job's log: seconds
    by phase (every occurrence summed), the step calls' part in JAX's
    stages, and the compile counters."""
    rec = PROCESS.snapshot()
    by_name: Dict[str, float] = {}
    calls = set()
    said = []  # the set-up phases' attributes, e.g. remat.kept
    for e in rec["spans"][1:]:
        if e["name"].startswith(("setup.", "step.")) \
                and not e["args"].get("recompile"):
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
            if e["name"] == "step.first_call":
                calls.add(e["sid"])
            elif e["name"].startswith("setup.") and e["args"]:
                said.append(e["name"].split(".", 1)[1] + ": " + ", ".join(
                    f"{k} {v}" for k, v in e["args"].items()))
    stages = {"jax.trace": 0.0, "jax.lower": 0.0, "jax.compile": 0.0}
    for e in rec["spans"]:
        if e["parent"] in calls and e["name"] in stages:
            stages[e["name"]] += e["dur"]
    phases = ", ".join(f"{name.split('.', 1)[1]} {seconds:.2f}"
                       for name, seconds in by_name.items())
    c = rec["counters"]
    return (
        f"set-up {rec['spans'][0]['dur']:.2f} s from the process's "
        f"start: {phases} (step calls: trace "
        f"{stages['jax.trace']:.2f}, lower {stages['jax.lower']:.2f}, "
        f"compile or load {stages['jax.compile']:.2f}); "
        f"{c['compile.programs']} programs, {c['compile.cache_hits']} "
        f"from the cache in {c['compile.cache_load_s']:.2f} s, "
        f"{c['compile.cache_misses']} written to it, "
        f"{len(rec['recompiles'])} recompiles of a step"
        + "".join(f"; {note}" for note in said))


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
