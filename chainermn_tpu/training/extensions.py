"""Standard trainer extensions: logging / reporting / throughput.

The reference relied on Chainer's ``LogReport``/``PrintReport``/
``ProgressBar`` with the documented convention that only rank 0 attaches
them (SURVEY.md section 5.5).  Here the equivalents are first-class, and the
rank-0 convention is built in: pass ``comm`` and each extension silences
itself on non-zero processes automatically.

``Throughput`` is the distributed-specific addition: it reports
samples/sec (global and per-chip) — the metric family the ImageNet example
printed and BASELINE.md targets.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, Optional, Sequence

import numpy as np


def _is_chief(comm) -> bool:
    return comm is None or comm.process_index == 0


class LogReport:
    """Accumulates observations; writes a JSON log (rank 0 only)."""

    priority = 150
    trigger = (1, "epoch")
    name = "log_report"

    def __init__(self, comm=None, filename: Optional[str] = "log.json",
                 out: str = "result", trigger=(1, "epoch")):
        self._comm = comm
        self._filename = filename
        self._out = out
        self.trigger = trigger
        self.log: list = []
        self._pending: Dict[str, list] = {}

    def observe(self, observation: Dict[str, Any]) -> None:
        for k, v in observation.items():
            try:
                self._pending.setdefault(k, []).append(float(v))
            except (TypeError, ValueError):
                pass

    def __call__(self, trainer):
        self.observe(trainer.observation)
        entry = {
            "iteration": trainer.iteration,
            "epoch": trainer.epoch,
            "elapsed_time": trainer.elapsed_time,
        }
        for k, vals in self._pending.items():
            entry[k] = float(np.mean(vals))
        self._pending.clear()
        self.log.append(entry)
        if self._filename and _is_chief(self._comm):
            os.makedirs(self._out, exist_ok=True)
            with open(os.path.join(self._out, self._filename), "w") as f:
                json.dump(self.log, f, indent=1)


class PrintReport:
    """Prints selected log entries as a table (rank 0 only)."""

    priority = 140
    trigger = (1, "epoch")
    name = "print_report"

    def __init__(self, entries: Sequence[str], log_report: LogReport,
                 comm=None, stream=None):
        self._entries = list(entries)
        self._log_report = log_report
        self._comm = comm
        self._stream = stream or sys.stdout
        self._header_printed = False

    def __call__(self, trainer):
        if not _is_chief(self._comm):
            return
        if not self._log_report.log:
            return
        if not self._header_printed:
            self._stream.write(
                "  ".join(f"{e:>14s}" for e in self._entries) + "\n"
            )
            self._header_printed = True
        last = self._log_report.log[-1]
        cells = []
        for e in self._entries:
            v = last.get(e)
            cells.append(
                f"{v:14.6g}" if isinstance(v, (int, float)) else f"{'':>14s}"
            )
        self._stream.write("  ".join(cells) + "\n")
        self._stream.flush()


class ProgressBar:
    """Lightweight iteration progress line (rank 0 only)."""

    priority = 130
    trigger = (1, "iteration")
    name = "progress_bar"

    def __init__(self, comm=None, update_interval: int = 50, stream=None):
        self._comm = comm
        self._interval = update_interval
        self._stream = stream or sys.stdout

    def __call__(self, trainer):
        if not _is_chief(self._comm):
            return
        if trainer.iteration % self._interval:
            return
        t = trainer.elapsed_time
        ips = trainer.iteration / t if t > 0 else 0.0
        self._stream.write(
            f"\riter {trainer.iteration}  epoch {trainer.epoch}  "
            f"{ips:.2f} it/s"
        )
        self._stream.flush()


class Profile:
    """Capture a ``jax.profiler`` trace over a window of iterations.

    SURVEY.md section 5.1: the reference shipped no in-package profiler
    (users fell back to Chainer hooks + nvprof); the TPU rebuild makes
    step-window tracing a first-class trainer extension.  The trace
    covers updates ``[start, stop)`` and lands in ``logdir`` in the
    TensorBoard profile-plugin format.  Extensions only run *between*
    updates, so the earliest capturable update is 2 (any ``start <= 2``
    opens the trace at the same point, after update 1):

        trainer.extend(T.Profile(start=10, stop=13, comm=comm))
        ...
        tensorboard --logdir profile/   # -> Profile tab: timeline,
                                        #    op stats, memory viewer

    Only the chief process traces by default (every process writes its
    own device's timeline under multi-controller when
    ``all_processes=True``).  Running the same job on the ``dummy``
    communicator gives the communication overhead by subtraction.
    """

    priority = 170  # before Throughput so the trace brackets real work
    trigger = (1, "iteration")
    name = "profile"

    def __init__(self, start: int = 10, stop: int = 13,
                 logdir: str = "profile", comm=None,
                 all_processes: bool = False):
        if stop <= start:
            raise ValueError(f"need start < stop, got [{start}, {stop})")
        self._start = start
        self._stop = stop
        self._logdir = logdir
        self._comm = comm
        self._all = all_processes
        self._active = False
        self.done = False

    def _should_trace(self) -> bool:
        return self._all or _is_chief(self._comm)

    def __call__(self, trainer):
        import jax

        if self.done or not self._should_trace():
            return
        # Extensions run AFTER the update increments trainer.iteration,
        # so to trace updates [start, stop) the trace must open once
        # update (start-1) has completed and close once update (stop-1)
        # has.  (The first traceable update is 2: the extension's first
        # chance to open the trace is after update 1.)
        if not self._active and trainer.iteration >= self._start - 1:
            jax.profiler.start_trace(self._logdir)
            self._active = True
        elif self._active and trainer.iteration >= self._stop - 1:
            # make async dispatches land inside the trace window
            for v in trainer.observation.values():
                try:
                    jax.block_until_ready(v)
                except Exception:
                    pass
            jax.profiler.stop_trace()
            self._active = False
            self.done = True

    def finalize(self, trainer=None):
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self.done = True


class Throughput:
    """Reports global and per-chip samples/sec into the observation."""

    priority = 160
    trigger = (1, "iteration")
    name = "throughput"

    def __init__(self, batch_size_global: int, comm=None, warmup: int = 2):
        self._bs = batch_size_global
        self._comm = comm
        self._warmup = warmup
        self._t0 = None
        self._count = 0

    def __call__(self, trainer):
        self._count += 1
        if self._count == self._warmup:
            self._t0 = time.monotonic()
            self._n0 = self._count
            return
        if self._t0 is None:
            return
        dt = time.monotonic() - self._t0
        n = self._count - self._n0
        if dt <= 0 or n <= 0:
            return
        sps = n * self._bs / dt
        trainer.observation["samples_per_sec"] = sps
        if self._comm is not None and self._comm.size:
            trainer.observation["samples_per_sec_per_chip"] = (
                sps / self._comm.size
            )
