"""Training loop: Updater + Trainer.

The reference has no trainer of its own — ChainerMN plugs into Chainer's
``Trainer``/``StandardUpdater`` (SURVEY.md section 3.2: ``trainer.run() ->
StandardUpdater.update_core -> optimizer.update``).  A standalone framework
needs the loop itself, so this module provides a minimal functional
equivalent: the Updater owns (params, opt_state, step_fn); the Trainer owns
the iteration/epoch bookkeeping, extensions, and reporting.

TPU-native properties: the per-iteration work is ONE jitted SPMD step (built
by ``optimizers.build_train_step``); the loop never blocks on device results
unless an extension asks for them (async dispatch keeps the TPU busy while
the host prepares the next batch).
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, Optional

import jax

from .triggers import get_trigger
from ..observability import timeline as _obs
from ..resilience import fault_injection as _fi
from ..resilience import log as _rlog
from ..resilience.errors import (
    ResilienceError,
    RestartBudgetExceededError,
    StepDivergedError,
)


class Updater:
    """Owns the train state and applies one compiled step per iteration."""

    def __init__(self, iterator, step_fn: Callable, params, opt_state,
                 *, batch_sharding=None):
        self.iterator = iterator
        self.step_fn = step_fn
        self.params = params
        self.opt_state = opt_state
        self._explicit_sharding = batch_sharding is not None
        self.batch_sharding = batch_sharding or getattr(
            step_fn, "batch_sharding", None
        )
        self.last_metrics: Dict[str, Any] = {}
        #: updates made by this object (the profiler's step number)
        self.iteration = 0

    @property
    def epoch(self) -> int:
        return getattr(self.iterator, "epoch", 0)

    @property
    def epoch_detail(self) -> float:
        return getattr(self.iterator, "epoch_detail", 0.0)

    def update(self) -> None:
        # telemetry spans ("update" > "data.wait"/"compute.dispatch"):
        # the data-wait-vs-compute split of the step taxonomy; disabled
        # path is one `is None` check per span (docs/observability.md)
        # "update" carries step_num: the outermost such span is the
        # profiler's StepTraceAnnotation (Trainer.run's "step" when it
        # drives the loop, this one when update() is called directly)
        with _obs.span("update", step_num=self.iteration):
            # resilience site: a deterministic mid-run failure point for
            # exercising auto-resume (no-op — one None check — when no
            # injector is active)
            _fi.fire("trainer.update")
            with _obs.span("data.wait"):
                batch = next(self.iterator)
            with _obs.span("compute.dispatch"):
                place_batch = getattr(self.step_fn, "place_batch", None)
                # build_train_step exposes its own placement predicate;
                # a batch already laid out per the step's sharding
                # (prefetch_to_device output) must NOT be re-placed —
                # in multi-process runs
                # make_array_from_process_local_data on a
                # non-fully-addressable global array crashes.  An
                # explicit batch_sharding always goes through
                # device_put (a no-op when already right).
                is_placed = getattr(self.step_fn, "is_placed", None)
                if place_batch is not None and not self._explicit_sharding:
                    if not (is_placed is not None and is_placed(batch)):
                        batch = place_batch(batch)
                elif self.batch_sharding is not None:
                    batch = jax.device_put(batch, self.batch_sharding)
                self.params, self.opt_state, self.last_metrics = \
                    self.step_fn(self.params, self.opt_state, batch)
        self.iteration += 1
        self._observe_host_time()

    @staticmethod
    def _observe_host_time() -> None:
        """Derived rank-LOCAL metric: ``update.host`` = update minus
        its data.wait/compute.dispatch children — host time this rank
        spent NEITHER waiting for data NOR dispatching (injected
        faults, GC, host contention).  The straggler detector keys on
        it because lockstep SPMD *equalizes* wall-clock step time
        across ranks (healthy ranks block in the collective waiting
        for the slow one), so only rank-local phases can convict."""
        tel = _obs.active()
        if tel is None:
            return
        reg = tel.registry
        u = reg.histogram("update").last
        d = reg.histogram("data.wait").last
        c = reg.histogram("compute.dispatch").last
        if u is None or d is None or c is None:
            return
        reg.histogram("update.host").observe(max(u - d - c, 0.0))


class _ExtensionEntry:
    def __init__(self, ext, trigger, priority: int, name: str):
        self.ext = ext
        self.trigger = get_trigger(trigger)
        self.priority = priority
        self.name = name


class Trainer:
    """Runs the updater until a stop condition, firing extensions.

    Stop condition mirrors Chainer: ``stop_trigger=(n, 'epoch'|'iteration')``.
    Extension protocol: a callable ``ext(trainer)``; optional attributes
    ``trigger`` (default each epoch), ``priority``, ``initialize(trainer)``,
    ``finalize(trainer)``.
    """

    def __init__(self, updater: Updater, stop_trigger=(1, "epoch"),
                 out: str = "result"):
        self.updater = updater
        self.stop_n, self.stop_unit = stop_trigger
        self.out = out
        self.iteration = 0
        self.observation: Dict[str, Any] = {}
        self._extensions: list[_ExtensionEntry] = []
        self._start_time: Optional[float] = None
        # Structured record of every injected/observed fault, retry,
        # skipped step, and restart during run() — the assertion surface
        # for tests and reporting extensions.
        from ..resilience.log import ResilienceLog

        self.resilience_log = ResilienceLog()
        self.restarts = 0
        self._pending_guard = None  # deferred grads_finite read

    # -- extension management -----------------------------------------
    def extend(self, ext, trigger=None, priority: Optional[int] = None,
               name: Optional[str] = None):
        trigger = trigger if trigger is not None else getattr(
            ext, "trigger", (1, "epoch")
        )
        priority = priority if priority is not None else getattr(
            ext, "priority", 100
        )
        name = name or getattr(ext, "name", None) or type(ext).__name__
        self._extensions.append(_ExtensionEntry(ext, trigger, priority, name))
        return self

    def get_extension(self, name: str):
        for e in self._extensions:
            if e.name == name:
                return e.ext
        raise KeyError(name)

    # -- loop ----------------------------------------------------------
    @property
    def epoch(self) -> int:
        return self.updater.epoch

    @property
    def elapsed_time(self) -> float:
        now = time.monotonic()
        return now - (self._start_time or now)

    def _stop(self) -> bool:
        if self.stop_unit == "iteration":
            return self.iteration >= self.stop_n
        return self.updater.epoch >= self.stop_n

    def _check_step_guard(self) -> None:
        """Host side of the non-finite-step guard: the compiled step
        already skipped (or applied, under ``warn``) the update in
        cross-rank agreement; here the policy's host effect happens —
        record the event, warn, or abort.

        The flag is read one iteration LATE: materializing iteration
        i's ``grads_finite`` would otherwise block the host on step i
        every time, serializing the async-dispatch pipeline.  Deferring
        the read until after step i+1 is dispatched keeps the overlap;
        by then step i has (almost always) completed, so ``float()``
        returns without waiting.  The pending flag is flushed at loop
        end (``_flush_step_guard``), so no event is ever lost."""
        policy = getattr(self.updater.step_fn, "nonfinite_policy", None)
        if policy is None:
            return
        flag = (self.updater.last_metrics or {}).get("grads_finite")
        prev, self._pending_guard = (
            self._pending_guard,
            None if flag is None else (self.iteration, flag, policy),
        )
        if prev is not None:
            self._consume_guard(prev)

    def _flush_step_guard(self) -> None:
        prev, self._pending_guard = self._pending_guard, None
        if prev is not None:
            self._consume_guard(prev)

    def _consume_guard(self, pending) -> None:
        iteration, flag, policy = pending
        if float(flag) > 0.0:
            return
        self.resilience_log.record(
            "nonfinite_step", "trainer.update",
            iteration=iteration, policy=policy,
        )
        if policy == "abort":
            raise StepDivergedError(
                f"non-finite gradients at iteration {iteration} "
                "(policy 'abort'); all ranks agreed via the compiled "
                "pmin flag, so the abort is collective-safe",
                site="trainer.update",
            )
        if policy == "warn":
            warnings.warn(
                f"non-finite gradients at iteration {iteration} "
                "applied under policy 'warn'"
            )

    def _find_checkpointer(self):
        for e in self._extensions:
            if hasattr(e.ext, "restore_trainer"):
                return e.ext
        return None

    def _find_adaptive(self):
        from ..resilience.adaptive import AdaptiveExecution

        for e in self._extensions:
            if isinstance(e.ext, AdaptiveExecution):
                return e.ext
        return None

    def _auto_resume(self, error: ResilienceError) -> None:
        """Roll back to the newest common checkpoint (params, opt_state,
        iteration, iterator position).  Without a checkpointer extension
        the in-flight state is still consistent (the step is functional:
        an aborted update left params untouched), so training simply
        continues from the current iteration."""
        ckpt = self._find_checkpointer()
        step = ckpt.restore_trainer(self) if ckpt is not None else None
        self.resilience_log.record(
            "restart", error.site,
            restored_step=step, restarts=self.restarts,
            error=f"{type(error).__name__}: {error}",
        )

    def run(self, max_restarts: int = 0, adapt=None) -> None:
        """Run to the stop trigger.

        ``max_restarts``: auto-resume budget.  A *recoverable*
        :class:`ResilienceError` escaping an update (exhausted obj-store
        retries, an injected transient fault, a corrupted control-plane
        payload) rolls the trainer back to the newest common checkpoint
        (see :meth:`_auto_resume`) and continues, up to this many times;
        the budget and every restart are recorded on
        ``self.resilience_log``.  Exhaustion raises
        :class:`RestartBudgetExceededError` with the last failure
        chained; non-recoverable errors propagate immediately.

        ``adapt``: a :class:`~chainermn_tpu.resilience.adaptive.
        AdaptPolicy` (or ``AdaptiveExecution``) making this a
        straggler-adaptive run: the policy consumes the attached
        ``MetricsReport``'s convictions and rebalances/demotes per its
        hysteresis (docs/resilience.md "Self-healing runtime").  A
        demotion raises :class:`~chainermn_tpu.resilience.errors.
        DemotionRequiredError` on every rank together — recovery is the
        elastic N−1 restart, not an in-place resume.  With a
        :class:`~chainermn_tpu.resilience.adaptive.CapacityWatcher`
        attached (``adapt=AdaptiveExecution(policy, comm=...,
        watcher=..., hosts=[...])``), healed hosts publishing presence
        manifests are held under weight-0 probation and an agreed
        promotion raises :class:`~chainermn_tpu.resilience.errors.
        PromotionRequiredError` the same collective way — recovery is
        the elastic N+k restart from the decision snapshot
        (docs/resilience.md "Scale-up and re-admission").
        """
        if adapt is not None and self._find_adaptive() is None:
            from ..resilience.adaptive import (
                AdaptiveExecution,
                AdaptPolicy,
            )

            ext = (adapt if isinstance(adapt, AdaptiveExecution)
                   else AdaptiveExecution(adapt)
                   if isinstance(adapt, AdaptPolicy)
                   else None)
            if ext is None:
                raise TypeError(
                    f"adapt= wants an AdaptPolicy or AdaptiveExecution, "
                    f"got {type(adapt).__name__}"
                )
            self.extend(ext)
        self._start_time = time.monotonic()
        _rlog.attach(self.resilience_log)
        try:
            for e in self._extensions:
                init = getattr(e.ext, "initialize", None)
                if init:
                    init(self)
            exts = sorted(self._extensions, key=lambda e: -e.priority)
            self.restarts = 0
            while not self._stop():
                try:
                    # "step" span: one trainer iteration — update AND
                    # its extensions (a checkpoint stall is step time
                    # the operator pays; the sub-spans split it)
                    with _obs.span("step", step_num=self.iteration):
                        self.updater.update()
                        self.iteration += 1
                        self.observation = {
                            k: v
                            for k, v in (
                                self.updater.last_metrics or {}
                            ).items()
                        }
                        self._check_step_guard()
                        # extensions run INSIDE the recovery scope: a
                        # transient failure during e.g. the
                        # checkpointer's collective save is as
                        # recoverable as one during the update itself
                        for e in exts:
                            if e.trigger(self):
                                e.ext(self)
                except ResilienceError as err:
                    if not err.recoverable:
                        raise
                    if self.restarts >= max_restarts:
                        if self.restarts == 0:
                            # auto-resume never engaged (max_restarts=0):
                            # propagate the original, still-recoverable
                            # error unchanged so outer layers can apply
                            # their own policy to the true taxonomy
                            raise
                        raise RestartBudgetExceededError(
                            f"giving up after {self.restarts} restart(s) "
                            f"(max_restarts={max_restarts}); last failure: "
                            f"{type(err).__name__}: {err}",
                            site=err.site,
                            attempts=err.attempts,
                        ) from err
                    self.restarts += 1
                    # the restored state invalidates any deferred
                    # grads_finite read from the rolled-back step
                    self._pending_guard = None
                    self._auto_resume(err)
            self._flush_step_guard()
        finally:
            try:
                # finalize runs on error exits too: the async
                # checkpointer must drain its in-flight save (a
                # truncated snapshot outlives the exception) and a
                # MetricsReport that installed its own process-global
                # telemetry must uninstall it (leaking it would keep
                # recording — and serializing the observed wire — for
                # every later run in the process).  Each finalize is
                # isolated: one raising must neither mask the run's
                # own exception nor skip the remaining extensions'
                # cleanup.
                errs = []
                for e in self._extensions:
                    fin = getattr(e.ext, "finalize", None)
                    if fin:
                        try:
                            fin(self)
                        except Exception as fe:  # noqa: BLE001
                            errs.append((e.name, fe))
                            self.resilience_log.record(
                                "finalize_error", "trainer.run",
                                extension=e.name,
                                error=f"{type(fe).__name__}: {fe}",
                            )
                import sys as _sys

                if errs and _sys.exc_info()[0] is None:
                    # clean run: a finalize failure must not vanish
                    raise errs[0][1]
                # erroring run: the run's own exception wins; the
                # finalize failures are on the resilience log (and,
                # merged, in the timeline)
            finally:
                # one merged stream: the run's faults/retries/restarts
                # land in the active timeline at their recorded
                # monotonic positions (idempotent — emit shares event
                # objects, so an additional explicit merge cannot
                # duplicate)
                tel = _obs.active()
                if tel is not None:
                    tel.timeline.merge_resilience(self.resilience_log)
                _rlog.detach(self.resilience_log)

    # -- elastic restart mode (resilience.elastic) ---------------------
    @classmethod
    def run_elastic(cls, build, *, communicator_name: str = "tpu",
                    devices=None, max_restarts: int = 0,
                    comm_kwargs: Optional[Dict[str, Any]] = None,
                    peer_store=None) -> "Trainer":
        """Elastic restart: re-form the world from the surviving ranks,
        rebuild the trainer in it, resume THROUGH the checkpoint
        resharder, and run.

        ``build(comm) -> Trainer`` constructs the new world's trainer
        (model, optimizer, compiled step, iterators, extensions —
        including a checkpointer pointed at the shared snapshot root).
        The newest common checkpoint is restored via
        ``restore_trainer``: a world-size mismatch in its manifest
        routes the state through ``resilience.elastic.reshard_state``
        (ZeRO blocks re-partitioned bit-identically, per-rank residuals
        dropped, iterator cursors rescaled).  The agreement stack
        re-arms by construction — the fresh optimizer's ``init``
        re-exchanges the wire ``plan_hash`` and the fresh compiled
        step's first multi-process dispatch re-runs ``trace_agreement``
        for the NEW program (both are keyed per program variant; see
        ``elastic.reestablish_agreements`` to force them explicitly).
        Returns the trainer after ``run(max_restarts=...)``.

        The path is direction-agnostic: the same resharder serves a
        world that SHRANK (preemption, demotion) and one that GREW (a
        promoted host joining after probation — the ``N+k`` restart a
        :class:`~chainermn_tpu.resilience.errors.
        PromotionRequiredError` asks for; growth floors the iterator
        cursor, re-visiting a sample rather than skipping one).

        ``peer_store``: a :class:`~chainermn_tpu.resilience.peer_ckpt.
        PeerCheckpointStore` adds the in-memory tier to step election —
        the store rebinds its ring to the re-formed world (dropping
        orphaned replicas), the peer and FS tiers each vote their
        newest common step, and the PEER tier is preferred when its
        step is at least as new (RAM restore, no FS read).  A broken
        ring or an older peer step falls back to the FS cold tier; the
        recorded ``elastic_restart`` event carries ``tier`` so the
        fleet report prices which path recovery took.
        """
        from ..resilience import elastic as _elastic

        comm = _elastic.reform_world(
            communicator_name, devices=devices, **(comm_kwargs or {})
        )
        trainer = build(comm)
        ckpt = trainer._find_checkpointer()
        restored = None
        tier = None
        if peer_store is not None:
            peer_store.rebind(comm)
            peer_step = peer_store.newest_common_step()
            fs_step = (ckpt.newest_common_step()
                       if ckpt is not None else None)
            if peer_step is not None and (
                fs_step is None or peer_step >= fs_step
            ):
                restored = peer_store.restore_trainer(trainer)
                if restored is not None:
                    tier = "peer"
        if restored is None and ckpt is not None:
            restored = ckpt.restore_trainer(trainer)
            if restored is not None:
                tier = "fs"
        resized = (peer_store.last_resize
                   if tier == "peer" and peer_store is not None
                   else getattr(ckpt, "last_resize", None))
        trainer.resilience_log.record(
            "elastic_restart", "trainer.run_elastic",
            restored_step=restored, world=comm.size,
            resized=resized, tier=tier,
        )
        trainer.run(max_restarts=max_restarts)
        return trainer

    # -- state (for checkpointing) -------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        out = {
            "iteration": self.iteration,
            "iterator": self.updater.iterator.serialize()
            if hasattr(self.updater.iterator, "serialize") else None,
        }
        adaptive = self._find_adaptive()
        if adaptive is not None:
            # one JSON-string leaf: scalar-shaped, so it survives the
            # elastic resharder verbatim across any N→M (the POLICY
            # decides what a world change resets — its per-process
            # maps — at the first observe() in the new world)
            import json as _json

            out["adaptive"] = _json.dumps(adaptive.policy.state_dict())
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.iteration = state["iteration"]
        if state.get("iterator") and hasattr(self.updater.iterator, "restore"):
            self.updater.iterator.restore(state["iterator"])
        adaptive = self._find_adaptive()
        raw = state.get("adaptive")
        if adaptive is not None and raw is not None:
            import json as _json

            try:
                doc = _json.loads(str(raw))
                if not isinstance(doc, dict):
                    raise TypeError(
                        f"adaptive state decoded to "
                        f"{type(doc).__name__}, not an object"
                    )
                adaptive.policy.load_state_dict(doc)
            except (ValueError, TypeError, KeyError,
                    AttributeError) as e:
                warnings.warn(
                    f"could not restore adaptive policy state "
                    f"({type(e).__name__}: {e}); hysteresis starts fresh"
                )
