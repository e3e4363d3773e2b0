"""Cross-rank metric aggregation + straggler detection.

:class:`MetricsReport` is a trainer extension that periodically
allgathers each process's per-phase timing summaries over the obj
store (riding the SAME lockstep retry as ``plan_agreement`` /
``newest_common_step`` — a transient fault or torn payload during the
exchange is observed and retried by every process together), computes
p50/p99 across the pooled samples, and flags processes whose mean step
time exceeds the cross-rank spread: the straggler question the
per-rank timeline alone cannot answer.

Each report appends one JSONL row per phase to ``out/filename``
(chief-only), keyed for a direction-aware diff
(``phase.<name>.p50_ms`` etc., unit ms, lower-is-better), and each
flagged process is emitted as a ``straggler`` resilience event — so it
lands both on ``trainer.resilience_log`` and, merged, in the exported
timeline next to the faults and retries that may explain it.

Single-controller worlds have one host clock, so the "per-rank"
summaries collapse to one process's view; the cross-rank machinery
becomes interesting (and is mp-tested, scenario ``telemetry``) in real
multi-process worlds.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import timeline as _tl

# phases summarized by default — the Trainer/Updater span taxonomy
# plus the derived rank-local ``update.host`` (update minus children)
DEFAULT_PHASES = (
    "step", "update", "data.wait", "compute.dispatch", "update.host",
)

# phases the straggler detector tries, in order of rank-locality:
# lockstep SPMD equalizes wall-clock step time (healthy ranks block in
# the collective waiting for the slow one), so the convicting evidence
# is host time the rank spent on ITSELF (update.host), then a stalled
# input pipeline (data.wait); bare step time is the last resort for
# non-lockstep setups
STRAGGLER_PHASES = ("update.host", "data.wait", "step")


class MetricsReport:
    """Trainer extension: cross-rank phase summaries + stragglers.

    Straggler rule: a process is flagged when, for some phase in
    ``straggler_phases`` (rank-local first — see
    :data:`STRAGGLER_PHASES`), its mean exceeds ``straggler_factor *``
    the leave-one-out median (the median of the OTHER processes'
    means — in a 2-rank world a straggler inflates the whole-world
    median enough to hide behind it) AND the phase is material: at
    least ``min_step_fraction`` of that process's mean step time
    (sub-millisecond bookkeeping phases have huge ratios and no
    meaning; with no recorded ``step`` baseline a non-step phase is
    never convicted — a zero floor would re-admit exactly that
    noise).  Needs >= 2 processes; a world of one has no one to
    straggle behind.

    If no telemetry is active when the trainer initializes extensions,
    the report enables one for the run (and disables it in
    ``finalize``) — attaching the extension IS opting into measurement.

    **Post-resume warmup skip**: the first report window after a
    restart (an ``elastic_restart`` on the trainer log at initialize,
    or a mid-run auto-resume ``restart``) is compile-dominated — the
    resized/restored world retraces, so every rank's step mean inflates
    and the materiality floor happens to mask real stragglers.  Rather
    than leaning on that coincidence, ``warmup_windows`` (default 1)
    windows after a resume are excluded from conviction BY CONTRACT:
    rows still aggregate, but the detector emits a
    ``straggler_warmup_skip`` event instead of convicting.  Fresh runs
    (no resume) skip nothing.
    """

    priority = 120
    trigger = (1, "epoch")
    name = "metrics_report"

    def __init__(self, comm=None, trigger=(1, "epoch"),
                 phases: Sequence[str] = DEFAULT_PHASES,
                 straggler_factor: float = 1.5,
                 straggler_phases: Sequence[str] = STRAGGLER_PHASES,
                 min_step_fraction: float = 0.05,
                 filename: Optional[str] = "metrics.jsonl",
                 out: str = "result",
                 warmup_windows: int = 1):
        if straggler_factor <= 1.0:
            raise ValueError(
                f"straggler_factor must be > 1, got {straggler_factor}"
            )
        if warmup_windows < 0:
            raise ValueError(
                f"warmup_windows must be >= 0, got {warmup_windows}"
            )
        self._comm = comm
        self.trigger = trigger
        self._phases = tuple(phases)
        self._factor = float(straggler_factor)
        self._straggler_phases = tuple(straggler_phases)
        self._min_step_fraction = float(min_step_fraction)
        self._filename = filename
        self._out = out
        self._warmup_windows = int(warmup_windows)
        self._warmup_left = 0
        self._restarts_seen = 0
        self._consumed: Dict[str, int] = {}
        self._own_telemetry = None
        self.last_report: Optional[dict] = None
        self.straggler_processes: List[int] = []

    # -- extension protocol --------------------------------------------
    def initialize(self, trainer) -> None:
        if _tl.active() is None:
            self._own_telemetry = _tl.Telemetry(label="metrics_report")
            _tl.install(self._own_telemetry)
        # post-resume warmup: a trainer that already carries a restart
        # record (run_elastic logs elastic_restart BEFORE run) starts
        # with its first warmup_windows report windows conviction-free
        log = getattr(trainer, "resilience_log", None)
        if log is not None:
            self._restarts_seen = len(log.events("restart"))
            if log.events("elastic_restart") or self._restarts_seen:
                self._warmup_left = self._warmup_windows

    def finalize(self, trainer=None) -> None:
        if self._own_telemetry is not None and \
                _tl.active() is self._own_telemetry:
            _tl.install(None)
        self._own_telemetry = None

    # -- summaries -----------------------------------------------------
    def _local_summary(self) -> dict:
        """This process's NEW samples per phase since the last report
        (incremental windows: every report summarizes its own interval,
        so a straggler phase cannot be averaged away by earlier healthy
        intervals)."""
        t = _tl.active()
        phases: Dict[str, list] = {}
        if t is not None:
            for ph in self._phases:
                if not t.registry.has_histogram(ph):
                    continue
                start = self._consumed.get(ph, 0)
                new = t.registry.histogram(ph).tail(start)
                self._consumed[ph] = start + len(new)
                if new:
                    phases[ph] = [float(v) for v in new]
        proc = 0
        if self._comm is not None:
            proc = int(self._comm.process_index)
        return {"process": proc, "phases": phases}

    def _exchange(self, local: dict) -> List[dict]:
        if self._comm is None:
            return [local]
        # single-process worlds still exchange (a cheap in-memory
        # allgather) so the dedupe-by-process and lockstep-retry paths
        # are exercised by every tier, not just the mp one
        from ..resilience.retry import lockstep_allgather

        return lockstep_allgather(
            self._comm, local, site="metrics_report.exchange"
        )

    def __call__(self, trainer) -> None:
        if _tl.active() is None:
            return
        with _tl.span("metrics_report"):
            # the window cursors advance inside _local_summary; a
            # failed (retry-exhausted) exchange must roll them back or
            # the NEXT report silently omits the very interval that
            # contained the faults
            consumed_before = dict(self._consumed)
            local = self._local_summary()
            try:
                summaries = self._exchange(local)
            except Exception:
                self._consumed = consumed_before
                raise
        # one summary per process (a single-controller obj store
        # returns size copies of the one local payload)
        by_proc: Dict[int, dict] = {}
        for s in summaries:
            if isinstance(s, dict) and "process" in s:
                by_proc.setdefault(int(s["process"]), s)
        # per-process phase means, computed ONCE and shared by the row
        # aggregation and the straggler detector
        means_map = {
            ph: self._phase_means(by_proc, ph)
            for ph in dict.fromkeys(
                tuple(self._phases) + tuple(self._straggler_phases)
                + ("step",)
            )
        }
        rows = self._aggregate(by_proc, trainer.iteration, means_map)
        # a mid-run auto-resume (restart) re-arms the warmup skip: the
        # rolled-back world re-dispatches (and possibly re-compiles)
        # exactly like a fresh resume
        log = getattr(trainer, "resilience_log", None)
        if log is not None:
            n_restarts = len(log.events("restart"))
            if n_restarts > self._restarts_seen:
                self._restarts_seen = n_restarts
                self._warmup_left = max(
                    self._warmup_left, self._warmup_windows
                )
        if self._warmup_left > 0:
            self._warmup_left -= 1
            self.straggler_processes = []
            from ..resilience.log import emit

            emit(
                "straggler_warmup_skip", "metrics_report",
                iteration=trainer.iteration,
                windows_left=self._warmup_left,
            )
        else:
            self._flag_stragglers(by_proc, trainer, means_map)
        self.last_report = {
            "iteration": trainer.iteration,
            "rows": rows,
            "stragglers": list(self.straggler_processes),
        }
        trainer.observation["stragglers"] = list(
            self.straggler_processes
        )
        self._write(rows)

    # -- consumers -----------------------------------------------------
    def process_means(self, phase: str = "step") -> Dict[int, float]:
        """Per-process mean SECONDS for ``phase`` from the last report
        window (empty before the first window, or when the phase went
        unrecorded).  The capacity layer's probation rule compares a
        candidate host's probe-window step mean against the world's
        medians through this accessor — the same numbers the straggler
        detector convicts on, read back out of the aggregated rows."""
        rep = self.last_report
        if not rep:
            return {}
        for row in rep.get("rows") or []:
            if row.get("phase") == phase:
                return {
                    int(p): float(m) / 1e3
                    for p, m in (row.get("process_mean_ms") or {}).items()
                }
        return {}

    # -- aggregation ---------------------------------------------------
    def _aggregate(self, by_proc: Dict[int, dict], iteration: int,
                   means_map: Optional[Dict[str, Dict[int, float]]]
                   = None) -> List[dict]:
        rows: List[dict] = []
        for ph in self._phases:
            pooled: List[float] = []
            proc_means = (
                means_map[ph] if means_map is not None
                else self._phase_means(by_proc, ph)
            )
            for _, s in sorted(by_proc.items()):
                vals = (s.get("phases") or {}).get(ph) or []
                pooled.extend(float(v) for v in vals)
            if not pooled:
                continue
            arr = np.asarray(pooled)
            row = {
                "phase": ph,
                "iteration": int(iteration),
                "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 4),
                "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 4),
                "mean_ms": round(float(arr.mean()) * 1e3, 4),
                "max_ms": round(float(arr.max()) * 1e3, 4),
                "n_measurements": int(arr.size),
                "process_mean_ms": {
                    str(p): round(m * 1e3, 4)
                    for p, m in proc_means.items()
                },
            }
            means = list(proc_means.values())
            if len(means) >= 2 and min(means) > 0:
                row["spread_max_over_min"] = round(
                    max(means) / min(means), 3
                )
            rows.append(row)
        return rows

    @staticmethod
    def _phase_means(by_proc: Dict[int, dict],
                     ph: str) -> Dict[int, float]:
        means = {}
        for proc, s in by_proc.items():
            vals = (s.get("phases") or {}).get(ph) or []
            if vals:
                means[proc] = float(np.mean(vals))
        return means

    def _flag_stragglers(self, by_proc: Dict[int, dict], trainer,
                         means_map: Optional[
                             Dict[str, Dict[int, float]]] = None
                         ) -> None:
        from ..resilience.log import emit

        self.straggler_processes = []
        if len(by_proc) < 2:
            return
        if means_map is not None:
            step_means = means_map.get("step", {})
            means_by_phase = {
                ph: means_map[ph] for ph in self._straggler_phases
            }
        else:  # standalone use (unit tests): compute locally
            step_means = self._phase_means(by_proc, "step")
            means_by_phase = {
                ph: self._phase_means(by_proc, ph)
                for ph in self._straggler_phases
            }
        for proc in sorted(by_proc):
            for ph in self._straggler_phases:
                means = means_by_phase[ph]
                if len(means) != len(by_proc):
                    continue  # phase not recorded by every process
                m = means[proc]
                # leave-one-out median: in small worlds (2 ranks!) a
                # straggler inflates the whole-world median enough to
                # hide itself behind it — the healthy baseline is the
                # OTHER ranks' median
                others = [v for p, v in means.items() if p != proc]
                med = float(np.median(others))
                if med <= 0:
                    continue
                if ph != "step":
                    # materiality floor: a rank-local phase must be a
                    # real share of this rank's step before its ratio
                    # convicts — and WITHOUT a step baseline the check
                    # refuses to convict (floor=0 would re-admit the
                    # microsecond-bookkeeping false positives the
                    # floor exists to prevent)
                    if proc not in step_means:
                        continue
                    floor = self._min_step_fraction * step_means[proc]
                    if m <= floor:
                        continue
                if m > self._factor * med:
                    self.straggler_processes.append(proc)
                    emit(
                        "straggler", "metrics_report",
                        process=proc,
                        phase=ph,
                        mean_ms=round(m * 1e3, 4),
                        median_ms=round(med * 1e3, 4),
                        ratio=round(m / med, 3),
                        iteration=trainer.iteration,
                    )
                    break

    # -- output --------------------------------------------------------
    def _write(self, rows: List[dict]) -> None:
        if not self._filename or not rows:
            return
        if self._comm is not None and self._comm.process_index != 0:
            return
        os.makedirs(self._out, exist_ok=True)
        path = os.path.join(self._out, self._filename)
        with open(path, "a", encoding="utf-8") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
