"""FleetWorld: supervised launch of simulated 16-64-rank worlds.

Every multi-process scenario before this tier spawned 2 processes from
a test file; the fleet tier makes the launcher a *subsystem*: process
supervision with per-process output capture, shared-filesystem scratch,
the env wiring that delivers a :class:`~chainermn_tpu.fleet.schedule.
FaultSchedule` and the fault injector's per-process targeting index
into workers it cannot reach by object reference, and a bounded
wall-clock budget whose overrun tears the whole world down LOUDLY
(every process killed, every tail quoted) instead of letting a wedged
collective eat a CI job's full timeout.

The worlds are gloo-CPU ``jax.distributed`` processes (virtual CPU
devices standing in for per-host chips — the same substrate as the
2-proc mp tier, at production shape).  One core machine note: the
workers timeshare, so budgets are wall-clock generous; the budget is a
deadlock detector, not a performance assertion.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

from .schedule import ENV_SLICE, FaultSchedule

_FLEET_DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT_WORKER = os.path.join(_FLEET_DIR, "worker.py")
_REPO_ROOT = os.path.dirname(os.path.dirname(_FLEET_DIR))

# the injector's targeting index — must be set before any worker import
# can activate the env-spec injector (fault_injection._from_env)
ENV_PROCESS_INDEX = "CHAINERMN_TPU_FAULT_PROCESS_INDEX"


class FleetBudgetError(RuntimeError):
    """The world outlived its wall-clock budget and was torn down."""


# expect_exit sentinel for a preemption wave's SURVIVORS: the process
# must have finished its paperwork (printed RESULT), but its exit may
# be a clean 0 OR a runtime reap (negative: killed by signal) — when
# the wave's victims die, the coordination service's error propagation
# hard-aborts surviving peers, racing their exit.  Scenarios therefore
# publish results BEFORE the wave point and the launcher accepts either
# ending, exactly like a real preemption where survivors are reaped
# with the job.  A POSITIVE non-matching exit (a Python failure) still
# fails the world.
REAPED = "reaped"


class FleetProcResult(NamedTuple):
    process: int
    returncode: Optional[int]  # None: killed by the budget teardown
    output: str

    @property
    def payload(self) -> Optional[dict]:
        """The worker's ``RESULT <json>`` line, parsed (last one wins),
        or None when the process printed none (died, or by design)."""
        line = None
        for l in self.output.splitlines():
            if l.startswith("RESULT "):
                line = l
        if line is None:
            return None
        try:
            return json.loads(line[len("RESULT "):])
        except ValueError:
            return None

    def tail(self, n: int = 2000) -> str:
        return self.output[-n:]


class FleetResult:
    """One launched world's outcome: per-process results + helpers."""

    def __init__(self, label: str, scenario: str,
                 procs: List[FleetProcResult], elapsed_s: float,
                 budget_s: float):
        self.label = label
        self.scenario = scenario
        self.procs = procs
        self.elapsed_s = elapsed_s
        self.budget_s = budget_s

    def payloads(self) -> Dict[int, dict]:
        """process index -> RESULT payload, for processes that printed
        one."""
        return {p.process: p.payload for p in self.procs
                if p.payload is not None}

    def assert_ok(self, expect_exit: Optional[Dict[int, object]] = None
                  ) -> Dict[int, dict]:
        """Every process exited with its expected code (default 0;
        ``expect_exit`` overrides per process — how a preemption wave's
        victims assert their injected exit codes, and
        :data:`REAPED` marks its survivors), and every expected-0 or
        REAPED process printed a RESULT payload.  Returns the
        payloads."""
        expect_exit = expect_exit or {}
        problems = []
        for p in self.procs:
            want = expect_exit.get(p.process, 0)
            if want == REAPED:
                # paperwork done + (clean exit | runtime reap)
                if p.payload is None:
                    problems.append(
                        f"[{self.label}/{self.scenario}] process "
                        f"{p.process} (wave survivor) printed no RESULT "
                        f"before the reap\n--- tail ---\n{p.tail()}"
                    )
                elif p.returncode is not None and p.returncode > 0:
                    problems.append(
                        f"[{self.label}/{self.scenario}] process "
                        f"{p.process} (wave survivor) exited "
                        f"{p.returncode} — a failure, not a reap\n"
                        f"--- tail ---\n{p.tail()}"
                    )
                continue
            if p.returncode != want:
                problems.append(
                    f"[{self.label}/{self.scenario}] process {p.process} "
                    f"exited {p.returncode}, expected {want}\n"
                    f"--- tail ---\n{p.tail()}"
                )
            elif want == 0 and p.payload is None:
                problems.append(
                    f"[{self.label}/{self.scenario}] process {p.process} "
                    f"printed no RESULT\n--- tail ---\n{p.tail()}"
                )
        if problems:
            raise AssertionError("\n\n".join(problems))
        return self.payloads()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class FleetWorld:
    """Launch ``n_procs`` workers over a shared scratch, under a budget.

    ``schedule``: a :class:`FaultSchedule` rendered into each worker's
    env.  ``local_devices``: virtual CPU devices per process.
    ``budget_s``: hard wall-clock bound for the whole world — overrun
    kills every process and raises :class:`FleetBudgetError` quoting
    the schedule and every process's output tail.
    """

    def __init__(self, n_procs: int, scratch: str, *,
                 local_devices: int = 1, budget_s: float = 300.0,
                 schedule: Optional[FaultSchedule] = None,
                 extra_env: Optional[Dict[str, str]] = None,
                 label: str = "fleet",
                 worker: str = DEFAULT_WORKER):
        if n_procs < 1:
            raise ValueError(f"n_procs must be >= 1, got {n_procs}")
        self.n_procs = int(n_procs)
        self.scratch = str(scratch)
        self.local_devices = int(local_devices)
        self.budget_s = float(budget_s)
        self.schedule = schedule
        self.extra_env = dict(extra_env or {})
        self.label = label
        self.worker = worker
        os.makedirs(self.scratch, exist_ok=True)

    # -- env wiring -----------------------------------------------------
    def env_for(self, process_index: int) -> Dict[str, str]:
        """The spawned worker's environment: CPU-mesh substrate
        (JAX_PLATFORMS pinned to cpu — a fleet world never takes the
        chip, which belongs to one process at a time), the
        repo on PYTHONPATH, the fault injector's targeting index, and
        the schedule's rendered specs."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={self.local_devices}"
        )
        env["PYTHONPATH"] = (
            _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        )
        env[ENV_PROCESS_INDEX] = str(process_index)
        if self.schedule is not None:
            env.update(self.schedule.env())
            if self.schedule.slice_size and self.local_devices != 1:
                # unit reconciliation: the schedule's slice_size counts
                # PROCESSES, but Topology.create's fake-slice grouping
                # counts DEVICE positions — with L local devices per
                # process the topology slice must span
                # slice_size * L device positions to group exactly the
                # processes the schedule's slice_loss will kill
                env[ENV_SLICE] = str(
                    self.schedule.slice_size * self.local_devices
                )
        env.update(self.extra_env)
        return env

    # -- launch ---------------------------------------------------------
    def start(self, scenario: str, args: Optional[dict] = None
              ) -> "FleetWorld":
        """Spawn the world WITHOUT blocking and return ``self``.

        The async half of :meth:`launch` — how a driver runs several
        worlds concurrently (the scale-up scenario: an N-proc training
        world plus 1-proc probe worlds publishing presence manifests
        into the same scratch).  Collect with :meth:`wait`; the budget
        clock starts here."""
        if getattr(self, "_pending", None) is not None:
            raise RuntimeError(
                f"fleet world '{self.label}' already started — wait() "
                "first"
            )
        port = _free_port()
        args_json = json.dumps(args or {})
        outs: list = []
        procs: list = []
        t0 = time.monotonic()
        try:
            for i in range(self.n_procs):
                out = open(os.path.join(
                    self.scratch, f"{self.label}_p{i}.out"), "w+b")
                outs.append(out)
                procs.append(subprocess.Popen(
                    [sys.executable, self.worker, scenario, str(port),
                     str(i), str(self.n_procs), self.scratch,
                     self.label, args_json],
                    env=self.env_for(i), stdout=out,
                    stderr=subprocess.STDOUT,
                ))
        except BaseException:
            # never leave a half-launched world running; close the
            # output file a failed Popen orphaned
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for out in outs:
                out.close()
            raise
        self._pending = (scenario, outs, procs, t0)
        return self

    def running(self) -> bool:
        """True while any started process is still alive."""
        if getattr(self, "_pending", None) is None:
            return False
        return any(p.poll() is None for p in self._pending[2])

    def wait(self, *, expect_exit: Optional[Dict[int, object]] = None
             ) -> FleetResult:
        """Block until the started world exits (or its budget — counted
        from :meth:`start` — expires), collect outputs, and return the
        :class:`FleetResult`."""
        if getattr(self, "_pending", None) is None:
            raise RuntimeError(
                f"fleet world '{self.label}' was never started"
            )
        scenario, outs, procs, t0 = self._pending
        self._pending = None
        try:
            deadline = t0 + self.budget_s
            pending = set(range(self.n_procs))
            while pending and time.monotonic() < deadline:
                for i in list(pending):
                    if procs[i].poll() is not None:
                        pending.discard(i)
                if pending:
                    time.sleep(0.05)
            if pending:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
                raise FleetBudgetError(self._overrun_report(
                    scenario, outs, procs, time.monotonic() - t0,
                    sorted(pending),
                ))
        finally:
            # safety net for exceptional exits (interrupt): never
            # leave the world running
            for p in procs:
                if p.poll() is None:
                    p.kill()
            results = []
            for i, (p, out) in enumerate(zip(procs, outs)):
                out.flush()
                out.seek(0)
                text = out.read().decode("utf-8", "replace")
                out.close()
                results.append(FleetProcResult(i, p.poll(), text))
        result = FleetResult(self.label, scenario, results,
                             time.monotonic() - t0, self.budget_s)
        if expect_exit is not None:
            result.assert_ok(expect_exit)
        return result

    def launch(self, scenario: str, args: Optional[dict] = None,
               *, expect_exit: Optional[Dict[int, object]] = None
               ) -> FleetResult:
        """Spawn the world, wait under the budget, return the result.

        ``args`` is delivered to every worker as a JSON argv (the
        scenario's parameter block).  ``expect_exit`` forwards to
        :meth:`FleetResult.assert_ok` when given; without it the caller
        asserts explicitly.  Equivalent to ``start(...)`` + ``wait()``.
        """
        self.start(scenario, args)
        return self.wait(expect_exit=expect_exit)

    def _overrun_report(self, scenario: str, outs, procs,
                        elapsed: float, stuck: Sequence[int]) -> str:
        lines = [
            f"fleet world '{self.label}' scenario '{scenario}' "
            f"({self.n_procs} procs) exceeded its {self.budget_s:.0f}s "
            f"wall-clock budget (ran {elapsed:.1f}s); processes "
            f"{list(stuck)} never exited — world torn down.",
        ]
        if self.schedule is not None:
            lines.append(self.schedule.describe())
        for i, out in enumerate(outs):
            try:
                out.flush()
                out.seek(0)
                tail = out.read().decode("utf-8", "replace")[-1500:]
            except Exception:
                tail = "<unreadable>"
            rc = procs[i].poll()
            lines.append(f"--- process {i} (rc={rc}) tail ---\n{tail}")
        return "\n".join(lines)
