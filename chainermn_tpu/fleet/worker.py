"""Fleet worker: one process of a simulated 16-64-rank world.

Spawned by :class:`~chainermn_tpu.fleet.world.FleetWorld` (never by
hand):

    python -m chainermn_tpu.fleet.worker <scenario> <port> <pid> \
        <nproc> <scratch> <label> <args_json>

Each worker initializes ``jax.distributed`` against the local
coordinator on a gloo CPU backend, installs telemetry plus the
streaming resilience sink (so a process killed by a ``die`` fault still
leaves its events on disk), runs one scenario, exports its timeline
with the wall-clock anchor, and prints ``RESULT <json>``.

Scenarios are the fleet tier's reusable building blocks — the
elasticity-chain leg (:func:`scenario_chain_leg`), the fleet-shaped
serving churn (:func:`scenario_serving_wave` /
:func:`scenario_serving_resume`), and the world-formation rendezvous —
driven by tests and ``benchmarks/fleet_chaos_bench.py`` alike.
"""

from __future__ import annotations

import json
import os
import sys
import time

_CTX: dict = {}


def _lockstep_allgather(comm, payload, site: str = "fleet.rendezvous"):
    """The agreement-shaped exchange (``resilience.retry.
    lockstep_allgather``): a torn payload or transient fault fails —
    and re-exchanges — on all ranks together, exactly like
    ``plan_agreement`` / ``newest_common_step``."""
    from chainermn_tpu.resilience.retry import lockstep_allgather

    return lockstep_allgather(comm, payload, site=site)


def _export_artifacts() -> None:
    """Flush this worker's post-mortem artifacts (idempotent)."""
    tel = _CTX.get("telemetry")
    if tel is not None:
        tel.timeline.to_jsonl(_CTX["trace_path"], meta=True)
    rec = _CTX.get("protocol")
    if rec is not None:
        rec.to_jsonl(_CTX["protocol_path"])


def finish_and_exit(out: dict, code: int = 0,
                    linger_s: float = 0.0) -> None:
    """Survivor epilogue for wave scenarios: export artifacts and print
    the RESULT payload FIRST (the runtime's peer-death propagation may
    reap this process at any moment once victims die — paperwork before
    linger), then optionally linger (keeping the coordinator alive for
    late victims), then ``os._exit`` — a graceful interpreter exit
    would hang in ``jax.distributed`` teardown waiting for the wave's
    victims, exactly like a real preemption (recovery happens at
    restart, the next leg)."""
    _export_artifacts()
    print("RESULT " + json.dumps(out or {}), flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    if linger_s > 0:
        time.sleep(linger_s)
    os._exit(code)


# ----------------------------------------------------------------------
def scenario_rendezvous(pid, nproc, scratch, label, args):
    """World formation at fleet width: create the communicator, run one
    lockstep agreement exchange (the schedule may tear it — the retry
    is the point), and report the injector's observations."""
    import chainermn_tpu as cmn
    from chainermn_tpu.resilience import fault_injection as fi

    comm = cmn.create_communicator(args.get("comm", "tpu"))
    assert comm.process_count == nproc, (comm.process_count, nproc)
    got = _lockstep_allgather(comm, pid, site="fleet.rendezvous")
    assert got == list(range(nproc)), got
    inj = fi.active()
    counts = dict(inj.log.counts) if inj is not None else {}
    desc = comm.world_descriptor()
    return {
        "size": comm.size,
        "world": desc["world_size"],
        "mesh_axes": desc["mesh_axes"],
        "faults": counts.get("fault_injected", 0),
    }


def scenario_sleep(pid, nproc, scratch, label, args):
    """Wedge on purpose — the budget-teardown test's subject."""
    time.sleep(float(args.get("sleep_s", 3600)))
    return {}


# ----------------------------------------------------------------------
def _chain_pieces(comm, scratch, lr, mom, dim):
    """One elasticity-chain leg's training pieces: a ZeRO (sgd+momentum)
    world — momentum state genuinely blocked over the ranks, the state
    that must reshard N→M — over a loss whose gradient is world-size
    independent.

    Every process feeds the SAME two local rows {0, 1}: the per-chip
    batch mean is 0.5 at any world size, so the gradient is elementwise
    ``w - 0.5`` on every leg of any chain and the single-world numpy
    oracle (:func:`~chainermn_tpu.fleet.chain.momentum_oracle`) prices
    the whole trajectory with no replay.
    """
    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.optimizers import build_train_step

    def loss_fn(params, batch):
        return 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(lr, momentum=mom), comm, zero_redundancy=True
    )
    step = build_train_step(comm, loss_fn, opt, donate=False)
    ckpt = cmn.create_multi_node_checkpointer(
        "chain", comm, path=os.path.join(scratch, "chain_ckpt")
    )
    rows = [np.zeros((dim,), np.float32), np.ones((dim,), np.float32)]
    return opt, step, ckpt, rows


def scenario_chain_leg(pid, nproc, scratch, label, args):
    """One leg of an elasticity chain (driven by
    :class:`~chainermn_tpu.fleet.chain.ElasticityChain`).

    Wave leg (``wave_at`` set — chain-initial): rendezvous (the
    schedule may tear the exchange → lockstep retry), then train and
    collectively snapshot steps ``1..wave_at-1``, each step checked
    against the oracle; then fire the wave site — the schedule's
    victims die there, the survivors linger (so every victim's exit
    lands while the coordinator still serves) and are reaped with the
    job, exactly like a real preemption wave.

    Resume leg: ``Trainer.run_elastic`` re-forms the world, restores
    THROUGH the checkpoint resharder (the elected snapshot's manifest
    names the previous leg's world), and runs to ``n_steps`` with
    per-iteration snapshots; the final params must land on the
    uninterrupted single-world oracle trajectory.

    ``resume_wave`` composes the two: the wave leg first restores the
    elected snapshot through the resharder (so a breathing world can be
    preempted AGAIN after it grew), then runs the manual loop from the
    restored step to ``wave_at`` — the absolute step the wave fires at.
    """
    import warnings

    import numpy as np
    from chainermn_tpu.fleet.chain import momentum_oracle
    from chainermn_tpu.resilience import fault_injection as fi

    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))
    n_steps = int(args["n_steps"])
    wave_at = args.get("wave_at")
    linger = float(args.get("linger_s", 1.5))
    oracle = momentum_oracle(n_steps, lr=lr, mom=mom, dim=dim)

    if wave_at is not None:
        # -- wave leg (manual loop: Trainer.run would hang in the wave
        # step's collective once the first victim dies) --------------
        import jax.numpy as jnp
        import chainermn_tpu as cmn

        wave_at = int(wave_at)
        comm = cmn.create_communicator("tpu")
        got = _lockstep_allgather(comm, pid, site="fleet.chain_leg.rendezvous")
        assert got == list(range(nproc)), got
        opt, step, ckpt, rows = _chain_pieces(comm, scratch, lr, mom, dim)
        p0 = {"w": jnp.zeros((dim,))}
        params, opt_state = step.place(p0, opt.init(p0))
        start = 1
        if args.get("resume_wave"):
            # mid-chain wave: restore the elected snapshot THROUGH the
            # checkpoint resharder first (a throwaway Trainer carries
            # the state templates), then run the manual loop from the
            # restored step to the ABSOLUTE wave step
            from chainermn_tpu.iterators import SerialIterator
            from chainermn_tpu.training.trainer import Trainer, Updater

            it = SerialIterator(rows, 2, shuffle=False)
            t = Trainer(Updater(it, step, params, opt_state),
                        stop_trigger=(wave_at, "iteration"))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                restored = ckpt.restore_trainer(t)
            assert restored is not None, "resume_wave needs a snapshot"
            params, opt_state = t.updater.params, t.updater.opt_state
            start = int(restored) + 1
        batch = np.stack(rows)
        for s in range(start, wave_at):
            fi.fire("trainer.update")
            params, opt_state, _m = step(params, opt_state, batch)
            ckpt.save(s, {
                "params": params,
                "opt_state": opt_state,
                "trainer": {"iteration": s, "iterator": None},
            })
            np.testing.assert_allclose(
                np.asarray(params["w"]), oracle[s - 1], rtol=1e-5
            )
        # Wide-world defect, surfaced by this scenario at 16 processes
        # and never at 2: the instant the wave's victims die, the
        # coordination service broadcasts the dead peers and every
        # SURVIVOR's error-poll thread hard-aborts its own process
        # (xla's client.h "Terminating process..." path) — racing the
        # survivor's epilogue.  So the epilogue runs BEFORE the wave
        # point: artifacts exported, RESULT printed, stdout flushed —
        # the post-mortem is already safe when the runtime reaps the
        # survivors, exactly as in a real preemption (the launcher
        # accepts runtime-reaped survivors for wave legs: see
        # FleetWorld.REAPED).  The victims' own die records reach disk
        # through the streaming sink inside fire().
        _export_artifacts()
        print("RESULT " + json.dumps({
            "steps_saved": wave_at - start,
            "resumed_step": start - 1 if start > 1 else None,
            "w": float(np.asarray(params["w"])[0]),
        }), flush=True)
        sys.stdout.flush()
        fi.fire("trainer.update")  # the wave: victims die in here
        # survivors linger so every victim's exit lands while the
        # coordinator still serves, then exit hard — the runtime may
        # reap them first, which is fine: the paperwork is done
        time.sleep(linger)
        os._exit(0)

    # -- resume leg ----------------------------------------------------
    from chainermn_tpu import observability as obs
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training.trainer import Trainer, Updater

    straggler = args.get("straggler")
    report_holder = {}

    def build(comm):
        import jax.numpy as jnp

        opt, step, ckpt, rows = _chain_pieces(comm, scratch, lr, mom, dim)
        p0 = {"w": jnp.zeros((dim,))}
        params, opt_state = step.place(p0, opt.init(p0))
        it = SerialIterator(rows, 2, shuffle=False)
        trainer = Trainer(Updater(it, step, params, opt_state),
                          stop_trigger=(n_steps, "iteration"))
        trainer.extend(ckpt, trigger=(1, "iteration"))
        if straggler:
            # per-iteration windows: the first window after a resume is
            # compile-dominated and excluded from conviction BY
            # CONTRACT (MetricsReport's warmup_windows=1 default — the
            # trainer log carries elastic_restart at initialize), so
            # conviction comes from the later, steady windows — the leg
            # reports the UNION of flags across windows (read off the
            # straggler events)
            rep = obs.MetricsReport(
                comm, trigger=(int(args.get("report_every", 1)),
                               "iteration"),
                filename=None,
            )
            trainer.extend(rep)
            report_holder["rep"] = rep
        return trainer

    with warnings.catch_warnings():
        # the resharder warns (by design) about reset trainer-template
        # slots the wave leg's manual saves did not carry
        warnings.simplefilter("ignore")
        trainer = Trainer.run_elastic(build, communicator_name="tpu")

    ev = trainer.resilience_log.events("elastic_restart")
    assert ev, "run_elastic must record its restart"
    restored = ev[0].info.get("restored_step")
    resized = ev[0].info.get("resized")
    assert trainer.iteration == n_steps, trainer.iteration
    got = np.asarray(trainer.updater.params["w"])
    ok = bool(np.allclose(got, oracle[n_steps - 1], rtol=1e-5))
    assert ok, (got, oracle[n_steps - 1])
    # events recorded directly on the trainer log (elastic_restart,
    # restart) never reach the global sink — export them for the report
    from chainermn_tpu.fleet.report import export_resilience_log

    export_resilience_log(
        trainer.resilience_log,
        os.path.join(scratch, f"{label}_p{pid}_trainer_events.jsonl"),
    )
    stragglers = None
    if report_holder.get("rep") is not None:
        stragglers = sorted({
            int(e.info["process"])
            for e in trainer.resilience_log.events("straggler")
        })
    return {
        "resumed_step": restored,
        "resized": list(resized) if resized else None,
        "oracle_match": ok,
        "iteration": trainer.iteration,
        "final_w": float(got[0]),
        "stragglers": stragglers,
    }


# ----------------------------------------------------------------------
def scenario_adaptive_leg(pid, nproc, scratch, label, args):
    """The self-healing runtime's demote leg (ISSUE 15): a straggler
    (possibly migrating between ranks — the schedule decides) is
    convicted by ``MetricsReport``, the :class:`~chainermn_tpu.
    resilience.adaptive.AdaptPolicy` first REBALANCES (weighted
    re-scatter of the shared dataset, agreed cross-rank through the
    lockstep-retried exchange, live iterator cursor remapped) and, once
    the conviction streak outlives the hysteresis window, DEMOTES: a
    snapshot is committed at the decision iteration and
    ``DemotionRequiredError`` raises on every rank together.  The next
    leg (the plain ``chain_leg`` resume at N−1) re-forms the world and
    must land on the single-world oracle from exactly that step.

    The dataset is constant 0.5-rows scattered across processes, so ANY
    weighted shard's batch mean is 0.5 — the numpy sgd+momentum oracle
    holds through every rebalance, making the data skew a real
    re-scatter rather than a decision-only event.
    """
    import numpy as np
    import jax.numpy as jnp

    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.datasets import scatter_dataset
    from chainermn_tpu.fleet.chain import momentum_oracle
    from chainermn_tpu.fleet.report import export_resilience_log
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.resilience.adaptive import (
        AdaptiveExecution,
        AdaptPolicy,
    )
    from chainermn_tpu.resilience.errors import DemotionRequiredError
    from chainermn_tpu.training.trainer import Trainer, Updater

    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))
    n_steps = int(args["n_steps"])

    comm = cmn.create_communicator("tpu")
    got = _lockstep_allgather(comm, pid, site="fleet.adaptive_leg.rendezvous")
    assert got == list(range(nproc)), got

    # the SAME pieces (loss, ZeRO sgd+momentum optimizer, step, and —
    # critically — the checkpointer name/path the N−1 chain_leg resume
    # elects from) as every chain leg; only the dataset differs
    opt, step, ckpt, _rows = _chain_pieces(comm, scratch, lr, mom, dim)
    full = [np.full((dim,), 0.5, np.float32)] * (nproc * 4)
    shard = scatter_dataset(full, comm, shuffle=False, seed=0)
    width0 = len(shard)
    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    it = SerialIterator(shard, 2, shuffle=False)
    trainer = Trainer(Updater(it, step, params, opt_state),
                      stop_trigger=(n_steps, "iteration"))
    trainer.extend(ckpt, trigger=(1, "iteration"))
    trainer.extend(obs.MetricsReport(comm, trigger=(1, "iteration"),
                                     filename=None))
    policy = AdaptPolicy(
        rebalance_after=int(args.get("rebalance_after", 1)),
        demote_after=int(args.get("demote_after", 3)),
        cooldown_windows=int(args.get("cooldown_windows", 1)),
        max_rebalances=int(args.get("max_rebalances", 2)),
    )
    trainer.extend(AdaptiveExecution(policy, comm=comm))

    demoted = None
    try:
        trainer.run()
    except DemotionRequiredError as err:
        demoted = int(err.peer)
    # the completed prefix sits on the oracle (the rebalances changed
    # shard maps, never batch statistics)
    w = np.asarray(trainer.updater.params["w"])
    oracle_ok = True
    if trainer.iteration > 0:
        oracle = momentum_oracle(trainer.iteration, lr=lr, mom=mom,
                                 dim=dim)
        oracle_ok = bool(np.allclose(
            w, oracle[trainer.iteration - 1], rtol=1e-5
        ))
    rebalances = trainer.resilience_log.events(
        "adapt_action", "adaptive.rebalance"
    )
    export_resilience_log(
        trainer.resilience_log,
        os.path.join(scratch, f"{label}_p{pid}_trainer_events.jsonl"),
    )
    stragglers = sorted({
        int(e.info["process"])
        for e in trainer.resilience_log.events("straggler")
    })
    out = {
        "demoted": demoted,
        "iteration": trainer.iteration,
        "oracle_match": oracle_ok,
        "stragglers": stragglers,
        "n_rebalances": len(rebalances),
        "rebalance_applied": bool(
            rebalances and rebalances[0].info.get("applied")
        ),
        "shard_width": [width0,
                        len(trainer.updater.iterator.dataset)],
        "w": float(w[0]),
    }
    # every rank exits together after the agreed demotion, but the exit
    # race with the runtime's peer-death propagation is real (the first
    # os._exit may reap the rest) — paperwork first, REAPED accepted
    finish_and_exit(out, linger_s=float(args.get("linger_s", 1.5)))


# ----------------------------------------------------------------------
def scenario_grow_leg(pid, nproc, scratch, label, args):
    """The scale-UP leg (ISSUE 16): an N-process training world runs
    with a :class:`~chainermn_tpu.resilience.adaptive.CapacityWatcher`
    over the shared scratch's presence manifests.  Candidate hosts
    (concurrent 1-process ``probe_host`` worlds) publish per-window
    manifests; the watcher holds each under probation until its probe
    step means clear the straggler rule for ``probation_windows``
    consecutive NEW windows, the policy holds the ready set until
    ``promote_quorum`` hosts can join in ONE restart, and the agreed
    decision commits a snapshot and raises
    :class:`~chainermn_tpu.resilience.errors.PromotionRequiredError`
    on every rank together.  The next leg (a plain ``chain_leg`` resume
    at N+k) re-forms the world and must land on the single-world oracle
    from exactly the decision step.
    """
    import warnings

    import numpy as np
    import jax.numpy as jnp

    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.datasets import scatter_dataset
    from chainermn_tpu.fleet.chain import momentum_oracle
    from chainermn_tpu.fleet.report import export_resilience_log
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.resilience.adaptive import (
        AdaptiveExecution,
        AdaptPolicy,
        CapacityWatcher,
    )
    from chainermn_tpu.resilience.errors import PromotionRequiredError
    from chainermn_tpu.training.trainer import Trainer, Updater

    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))
    n_steps = int(args["n_steps"])

    comm = cmn.create_communicator("tpu")
    got = _lockstep_allgather(comm, pid, site="fleet.grow_leg.rendezvous")
    assert got == list(range(nproc)), got

    # the SAME pieces (and checkpointer root) as every chain leg, so
    # the N+k resume elects this leg's decision snapshot
    opt, step, ckpt, _rows = _chain_pieces(comm, scratch, lr, mom, dim)
    full = [np.full((dim,), 0.5, np.float32)] * (nproc * 4)
    shard = scatter_dataset(full, comm, shuffle=False, seed=0)
    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    it = SerialIterator(shard, 2, shuffle=False)
    trainer = Trainer(Updater(it, step, params, opt_state),
                      stop_trigger=(n_steps, "iteration"))
    trainer.extend(ckpt, trigger=(1, "iteration"))
    trainer.extend(obs.MetricsReport(
        comm,
        trigger=(int(args.get("report_every", 1)), "iteration"),
        filename=None,
    ))
    policy = AdaptPolicy(
        demote_after=int(args.get("demote_after", 3)),
        probation_windows=int(args.get("probation_windows", 2)),
        promote_quorum=int(args.get("promote_quorum", 1)),
        readmit_cooldown_windows=int(
            args.get("readmit_cooldown_windows", 0)
        ),
    )
    watcher = CapacityWatcher(
        scratch,
        probation_windows=policy.probation_windows,
        straggler_factor=float(args.get("probe_straggler_factor", 1.5)),
    )
    trainer.extend(AdaptiveExecution(
        policy, comm=comm, watcher=watcher,
        hosts=[f"h{i}" for i in range(nproc)],
    ))
    restored = None
    if args.get("resume"):
        with warnings.catch_warnings():
            # the resharder warns about reset trainer-template slots a
            # wave leg's manual saves did not carry
            warnings.simplefilter("ignore")
            restored = ckpt.restore_trainer(trainer)
    promote = None
    try:
        trainer.run()
    except PromotionRequiredError as err:
        promote = {"hosts": [str(h) for h in err.hosts],
                   "new_world": int(err.new_world)}
    # the completed prefix sits on the oracle (probation is decision
    # state, never batch statistics)
    w = np.asarray(trainer.updater.params["w"])
    oracle_ok = True
    if trainer.iteration > 0:
        oracle = momentum_oracle(trainer.iteration, lr=lr, mom=mom,
                                 dim=dim)
        oracle_ok = bool(np.allclose(
            w, oracle[trainer.iteration - 1], rtol=1e-5
        ))
    export_resilience_log(
        trainer.resilience_log,
        os.path.join(scratch, f"{label}_p{pid}_trainer_events.jsonl"),
    )
    out = {
        "promote": promote,
        "iteration": trainer.iteration,
        "resumed_step": restored,
        "oracle_match": oracle_ok,
        "promote_total": policy.totals.get("promote", 0),
        "w": float(w[0]),
    }
    # every rank exits together after the agreed promotion, but the
    # exit race with the runtime's peer-death propagation is real —
    # paperwork first, REAPED accepted (same epilogue as the demote leg)
    finish_and_exit(out, linger_s=float(args.get("linger_s", 1.5)))


def scenario_probe_host(pid, nproc, scratch, label, args):
    """A returning/new host's probation protocol (ISSUE 16): a
    1-process world that trains on a WEIGHT-0 scatter shard (pure
    permutation-head padding — rank ``world`` of a ``world+1``-wide
    weighted split owns no sample, so it steps at world cadence while
    holding no state; and it mounts NO checkpointer, so the chain's
    snapshot root is untouched).  Each report window it measures its
    step mean through ``MetricsReport`` and publishes one presence
    manifest (atomic tmp+rename), pacing itself to the training world's
    window cadence.  It keeps probing until the training world's agreed
    promote decision posts its ADMISSION marker
    (``AdaptiveExecution._promote`` publishes it on rank 0 and
    withdraws the presence manifest) — the candidate exits on the
    marker; the N+k resume leg is its first participation in the
    world.  A schedule may
    straggle its early steps (``delay`` at ``trainer.update``): the
    watcher holds it (``probation_hold``) until the dirty windows age
    out, which is the heal-then-readmit path.
    """
    import numpy as np
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.datasets.scatter_dataset import scatter_index
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience.adaptive import (
        admission_path,
        clear_admission,
        clear_presence,
        publish_presence,
    )
    from chainermn_tpu.training.trainer import Trainer, Updater

    assert nproc == 1, "a probe is a 1-process world"
    host = str(args["host"])
    world = int(args.get("world", 1))  # the training world it joins
    spw = int(args.get("steps_per_window", 3))
    max_windows = int(args.get("max_windows", 200))
    window_sleep = float(args.get("window_sleep_s", 0.25))
    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))

    comm = cmn.create_communicator("tpu")
    # the candidate's shard: rank ``world`` of a ``world+1``-wide split
    # with weight 0 — an equalized pad drawn from the permutation head,
    # so the probe steps in world cadence while OWNING no sample
    full = [np.full((dim,), 0.5, np.float32)] * (world * 4)
    order, start, end = scatter_index(
        len(full), world + 1, world,
        weights=[1.0] * world + [0.0], equalize=True,
    )
    shard = [full[int(i)] for i in order[start:end]]
    assert shard, "the equalized weight-0 shard pads, never empties"

    def loss_fn(params, batch):
        return 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(lr, momentum=mom), comm, zero_redundancy=True
    )
    step = build_train_step(comm, loss_fn, opt, donate=False)
    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    it = SerialIterator(shard, 2, shuffle=False)
    trainer = Trainer(Updater(it, step, params, opt_state),
                      stop_trigger=(max_windows * spw, "iteration"))
    rep = obs.MetricsReport(comm, trigger=(spw, "iteration"),
                            filename=None)
    trainer.extend(rep)

    # a fresh probe must not read its ancestor's admission
    clear_admission(scratch, host)
    state = {"window": 0}

    class _Promoted(Exception):
        pass

    class _Publish:
        """Presence publisher: one manifest per report window."""

        priority = 80  # after MetricsReport (120) in the same pass
        trigger = (spw, "iteration")
        name = "presence"

        def __call__(self, t):
            if os.path.exists(admission_path(scratch, host)):
                # the agreed decision answered: admitted
                raise _Promoted()
            mean = rep.process_means("step").get(0)
            if mean is None:
                return  # no measurement yet — publish nothing
            state["window"] += 1
            publish_presence(scratch, host, window=state["window"],
                             step_mean_s=mean)
            # pace probe windows to the training world's cadence: the
            # watcher only advances a streak on NEW windows, one per
            # scan, so racing far ahead just freezes the manifest
            time.sleep(window_sleep)

    trainer.extend(_Publish())
    promoted = False
    admission = None
    try:
        trainer.run()
    except _Promoted:
        promoted = True
        with open(admission_path(scratch, host)) as f:
            admission = json.load(f)
    clear_presence(scratch, host)  # idempotent: gone if promoted
    return {
        "host": host,
        "promoted": promoted,
        "admission": admission,
        "windows": state["window"],
        "steps": trainer.iteration,
    }


# ----------------------------------------------------------------------
def _assert_bit_identical(a, b, what):
    """0-tolerance leaf equality, shard-aware: a ZeRO leaf is a global
    array whose host view is per-process — compare addressable shards
    by index instead of materializing (np.asarray on a cross-process
    global array raises)."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), (what, len(la), len(lb))
    for x, y in zip(la, lb):
        if hasattr(x, "is_fully_addressable") and \
                not x.is_fully_addressable:
            sx = sorted(x.addressable_shards, key=lambda s: str(s.index))
            sy = sorted(y.addressable_shards, key=lambda s: str(s.index))
            assert len(sx) == len(sy), (what, len(sx), len(sy))
            for u, v in zip(sx, sy):
                assert u.index == v.index, (what, u.index, v.index)
                assert np.array_equal(
                    np.asarray(u.data), np.asarray(v.data)
                ), f"{what}: shard {u.index} differs"
        else:
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"{what}: leaf differs"


def _recover_trainer(step, opt, rows, dim, n_steps):
    """A throwaway Trainer carrying the state templates a collective
    restore needs (the resume_wave pattern)."""
    import jax.numpy as jnp
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training.trainer import Trainer, Updater

    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    it = SerialIterator(rows, 2, shuffle=False)
    return Trainer(Updater(it, step, params, opt_state),
                   stop_trigger=(n_steps, "iteration"))


def scenario_peer_recover_leg(pid, nproc, scratch, label, args):
    """The sub-second-recovery A/B leg (ISSUE 19): one world trains
    the standard chain pieces, snapshotting each step into ONE tier —
    ``tier="peer"`` replicates into the RAM ring
    (:class:`~chainermn_tpu.resilience.peer_ckpt.PeerCheckpointStore`),
    ``tier="fs"`` saves through the shared-FS checkpointer — then a
    single rank loses its state at ``lose_at`` (modeled in-process:
    params/opt_state re-zeroed, its peer RAM forgotten; the world stays
    formed so the A/B times RECOVERY, not relaunch) and every rank runs
    the collective restore.  The ``recover_action`` → ``recovered``
    event gap is the tier's recovery latency; the bench prices the two
    legs against each other.

    The peer leg additionally FS-saves the election step (outside the
    timed window) and, after recovery, restores it back through the FS
    checkpointer to pin the acceptance contract: peer-restored state is
    bit-identical — 0 tolerance, ZeRO blocked leaves included — to the
    FS restore of the same step.  Both legs then train on to
    ``n_steps`` and must land on the single-world numpy oracle."""
    import warnings

    import numpy as np
    import jax.numpy as jnp
    import chainermn_tpu as cmn
    from chainermn_tpu.fleet.chain import momentum_oracle
    from chainermn_tpu.resilience import PeerCheckpointStore
    from chainermn_tpu.resilience.log import emit

    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))
    n_steps = int(args["n_steps"])
    lose_at = int(args["lose_at"])
    tier = str(args.get("tier", "peer"))
    victim = int(args.get("victim", 1))
    assert victim != 0, "process 0 is the jax.distributed coordinator"
    assert 1 < lose_at <= n_steps, (lose_at, n_steps)

    comm = cmn.create_communicator("tpu")
    got = _lockstep_allgather(comm, pid, site="fleet.peer_recover.rendezvous")
    assert got == list(range(nproc)), got
    opt, step, ckpt, rows = _chain_pieces(comm, scratch, lr, mom, dim)
    peer = PeerCheckpointStore(comm) if tier == "peer" else None
    oracle = momentum_oracle(n_steps, lr=lr, mom=mom, dim=dim)
    # the throwaway restore target doubles as the trainer-state
    # template: manual saves must carry the full state_dict shape or
    # the same-world orbax restore rejects the like-template mismatch
    t = _recover_trainer(step, opt, rows, dim, n_steps)
    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    batch = np.stack(rows)
    for s in range(1, lose_at):
        params, opt_state, _m = step(params, opt_state, batch)
        state = {"params": params, "opt_state": opt_state,
                 "trainer": dict(t.state_dict(), iteration=s)}
        if peer is not None:
            peer.replicate(s, state)
            if s == lose_at - 1:
                # the election step also lands on the FS tier — OUTSIDE
                # the timed window — purely for the post-recovery
                # bit-identity cross-check below
                ckpt.save(s, state)
        else:
            ckpt.save(s, state)

    # -- the loss: one rank's state (and peer RAM) evaporates.  Purely
    # local (drop the references): a victim-only re-place would run a
    # host collective alone and shift the world's exchange stream -----
    if pid == victim:
        params = opt_state = None
        if peer is not None:
            peer.forget()
    emit("recover_action", "fleet.recover", tier=tier, victim=victim,
         step=lose_at - 1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        if peer is not None:
            restored = peer.restore_trainer(t)
        else:
            restored = ckpt.restore_trainer(t)
    assert restored == lose_at - 1, (restored, lose_at - 1)
    params, opt_state = t.updater.params, t.updater.opt_state
    emit("recovered", "fleet.recover", tier=tier, step=int(restored))

    bit_identical = None
    if peer is not None:
        # acceptance pin: the SAME step back through the FS cold tier
        # must match the peer restore bit for bit
        t2 = _recover_trainer(step, opt, rows, dim, n_steps)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            fs_step = ckpt.restore_trainer(t2)
        assert fs_step == restored, (fs_step, restored)
        _assert_bit_identical(params, t2.updater.params, "params")
        _assert_bit_identical(opt_state, t2.updater.opt_state,
                              "opt_state")
        bit_identical = True

    for s in range(int(restored) + 1, n_steps + 1):
        params, opt_state, _m = step(params, opt_state, batch)
        if peer is not None:
            peer.replicate(s, {
                "params": params, "opt_state": opt_state,
                "trainer": {"iteration": s, "iterator": None},
            })
    w = np.asarray(params["w"])
    np.testing.assert_allclose(w, oracle[n_steps - 1], rtol=1e-5)
    return {
        "tier": tier,
        "restored_step": int(restored),
        "bit_identical": bit_identical,
        "oracle_match": True,
        "w": float(w[0]),
    }


def scenario_peer_ring_broken(pid, nproc, scratch, label, args):
    """Correlated loss (ISSUE 19 satellite): a rank AND its ring
    replica holder lose their RAM in one wave — the slice-loss shape —
    so no peer snapshot has complete owner coverage.  The collective
    peer restore must detect the broken ring (``peer_ring_broken``
    logged), return empty-handed, and the survivors degrade to the FS
    COLD tier (the per-step checkpoints the same loop committed),
    landing on the single-world numpy oracle."""
    import warnings

    import numpy as np
    import jax.numpy as jnp
    import chainermn_tpu as cmn
    from chainermn_tpu.fleet.chain import momentum_oracle
    from chainermn_tpu.resilience import PeerCheckpointStore
    from chainermn_tpu.resilience.log import emit

    lr = float(args.get("lr", 0.1))
    mom = float(args.get("mom", 0.9))
    dim = int(args.get("dim", 4))
    n_steps = int(args["n_steps"])
    lose_at = int(args["lose_at"])
    victim = int(args.get("victim", 1))
    assert victim != 0, "process 0 is the jax.distributed coordinator"

    comm = cmn.create_communicator("tpu")
    got = _lockstep_allgather(comm, pid, site="fleet.ring_broken.rendezvous")
    assert got == list(range(nproc)), got
    opt, step, ckpt, rows = _chain_pieces(comm, scratch, lr, mom, dim)
    peer = PeerCheckpointStore(comm)
    holder = peer.holder if pid == victim else (victim + 1) % nproc
    oracle = momentum_oracle(n_steps, lr=lr, mom=mom, dim=dim)
    t = _recover_trainer(step, opt, rows, dim, n_steps)
    p0 = {"w": jnp.zeros((dim,))}
    params, opt_state = step.place(p0, opt.init(p0))
    batch = np.stack(rows)
    for s in range(1, lose_at):
        params, opt_state, _m = step(params, opt_state, batch)
        state = {"params": params, "opt_state": opt_state,
                 "trainer": dict(t.state_dict(), iteration=s)}
        peer.replicate(s, state)
        ckpt.save(s, state)  # the cold tier the fallback lands on

    # correlated loss: the victim AND its replica holder forget — the
    # victim's envelope now survives NOWHERE in the ring.  Purely
    # local, as in the A/B leg (no victim-only collectives)
    if pid in (victim, holder):
        params = opt_state = None
        peer.forget()
    emit("recover_action", "fleet.recover", tier="peer_then_fs",
         victim=victim, holder=holder, step=lose_at - 1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        restored = peer.restore_trainer(t)
        assert restored is None, "a broken ring must not elect"
        restored = ckpt.restore_trainer(t)  # the FS cold fallback
    assert restored == lose_at - 1, (restored, lose_at - 1)
    params, opt_state = t.updater.params, t.updater.opt_state
    emit("recovered", "fleet.recover", tier="fs_cold",
         step=int(restored))
    for s in range(int(restored) + 1, n_steps + 1):
        params, opt_state, _m = step(params, opt_state, batch)
    w = np.asarray(params["w"])
    np.testing.assert_allclose(w, oracle[n_steps - 1], rtol=1e-5)
    return {
        "restored_step": int(restored),
        "fell_back": True,
        "oracle_match": True,
        "w": float(w[0]),
    }


# ----------------------------------------------------------------------
def _serving_fixture(n_requests: int):
    """Deterministic tiny LM (same seed on every process → identical
    params → greedy decode of any request is bit-identical no matter
    which replica runs it) + the scripted request stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chainermn_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=64, d_model=32, n_heads=4,
                          n_layers=2, max_len=64)
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, 8), jnp.int32),
    )
    rng = np.random.RandomState(5)
    stream = [
        ("c%d" % i, rng.randint(0, 64, int(rng.randint(3, 10))).tolist(),
         6)
        for i in range(n_requests)
    ]
    return model, params, stream


def _serving_engine(model, params):
    from chainermn_tpu.serving.decode import DecodeEngine

    return DecodeEngine(model, params, capacity=2, page_size=8)


def scenario_serving_wave(pid, nproc, scratch, label, args):
    """Fleet-shaped serving churn, phase 1: N replicas (>= 4) partition
    one journaled stream by ``seq % N``; the schedule kills several in
    ONE wave (process-targeted ``die`` at ``serving.decode_step``).
    Survivors complete exactly their own shares — verified against the
    seq-mod contract — and the victims' shares stay journaled."""
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.replica import DecodeReplica, RequestJournal

    n_requests = int(args.get("n_requests", 16))
    model, params, stream = _serving_fixture(n_requests)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    if pid == 0:
        journal.submit_all([Request(p, m, id=i) for i, p, m in stream])
    # journal-level rendezvous (no collectives: a dead peer must never
    # wedge a survivor)
    journal.wait_until(len(stream))
    replica = DecodeReplica(
        _serving_engine(model, params), journal,
        replica_index=pid, n_replicas=nproc,
    )
    served = replica.serve()  # victims die inside (schedule spec)
    # the survivor served ITS seq-mod share, whole and nothing else
    by_id = {r["id"]: r for r in journal.requests()}
    for rid in served:
        assert int(by_id[rid]["seq"]) % nproc == pid, (rid, pid)
    want = {r["id"] for r in by_id.values()
            if int(r["seq"]) % nproc == pid}
    assert set(served) == want, (sorted(served), sorted(want))
    # RESULT before the linger: the survivor may be reaped by the
    # runtime's peer-death propagation at any point after the kill
    # (the launcher accepts REAPED for wave survivors)
    finish_and_exit({"served": sorted(served), "replica": pid},
                    linger_s=float(args.get("linger_s", 1.5)))


def scenario_serving_resume(pid, nproc, scratch, label, args):
    """Phase 2: the survivors re-form at the new replica count via
    ``serve_elastic``; the pending partition re-derives over ``seq %
    n_survivors``, so the dead replicas' shares migrate without
    coordination, and every journaled request completes bit-identically
    to a fresh single-engine oracle."""
    from chainermn_tpu.serving.replica import (
        RequestJournal,
        serve_elastic,
    )

    n_requests = int(args.get("n_requests", 16))
    model, params, stream = _serving_fixture(n_requests)
    root = os.path.join(scratch, "serve_journal")
    journal = RequestJournal(root)
    pending_before = len(journal.pending())
    assert pending_before > 0, "phase 1 should have left unserved work"
    # the re-derived partition this replica is about to claim
    my_share = {r["id"] for r in journal.pending()
                if int(r["seq"]) % nproc == pid}

    def build(comm):
        from chainermn_tpu.serving.replica import DecodeReplica

        return DecodeReplica(
            _serving_engine(model, params), journal,
            replica_index=pid, n_replicas=nproc,
        )

    replica = serve_elastic(
        build, root, communicator_name="tpu",
        replica_index=pid, n_replicas=nproc,
    )
    served = set(replica.batcher.finished)
    assert served == my_share, (sorted(served), sorted(my_share))
    # wait for the OTHER survivors' results before the global checks
    journal.wait_until_complete(n_requests)
    results = journal.results()
    assert sorted(results) == sorted(i for i, _p, _m in stream)
    oracle_eng = _serving_engine(model, params)
    mismatches = [
        rid for rid, prompt, max_new in stream
        if results[rid]["tokens"] != oracle_eng.generate(prompt, max_new)
    ]
    assert not mismatches, mismatches
    return {
        "pending_before": pending_before,
        "completed": len(results),
        "bit_identical": True,
        "served": sorted(served),
    }


def scenario_serving_autoscale(pid, nproc, scratch, label, args):
    """Load-driven autoscale over a pool of resident replica slots
    (ISSUE 16): every process is one slot serving in pool mode
    (``serve(until_complete=...)``); the highest slots start
    drain-marked (standbys).  Process 0 is ALSO the single decision
    maker: it trickles the offered load into the journal and runs one
    :class:`~chainermn_tpu.serving.replica.ReplicaAutoscaler` observe
    per decision window — the opening burst's backlog scales the pool
    UP (``clear_draining``; the standby's ``seq % n`` share re-derives
    on its next claim pass), and the post-load calm scales it back DOWN
    to ``min_replicas`` (``mark_draining``).  The atomic drain markers
    are the only coordination.  Every request completes bit-identically
    to a fresh single-engine oracle; an ownership handoff at an
    activation instant may duplicate decode WORK (the claim is
    lease-free by design), but greedy decode is deterministic and
    result writes are idempotent overwrites, so never a result."""
    import threading

    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.replica import (
        DecodeReplica,
        ReplicaAutoscaler,
        RequestJournal,
    )

    n_requests = int(args.get("n_requests", 30))
    burst = int(args.get("burst", 18))
    wave = int(args.get("wave", 4))
    min_replicas = int(args.get("min_replicas", 2))
    observe_s = float(args.get("observe_s", 0.4))
    serve_timeout = float(args.get("serve_timeout_s", 200.0))
    model, params, stream = _serving_fixture(n_requests)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    if pid == 0:
        # standbys first (markers must precede any claimable work),
        # then the opening burst
        for slot in range(min_replicas, nproc):
            journal.mark_draining(slot)
        journal.submit_all([Request(p, m, id=i)
                            for i, p, m in stream[:burst]])
    # journal-level rendezvous (no collectives: autoscale must never
    # couple the slots' control planes)
    journal.wait_until(burst)
    replica = DecodeReplica(_serving_engine(model, params), journal,
                            replica_index=pid, n_replicas=nproc)

    def serve():
        return replica.serve(until_complete=n_requests,
                             timeout_s=serve_timeout)

    if pid != 0:
        served = serve()
        journal.wait_until_complete(n_requests,
                                    timeout_s=serve_timeout)
        return {"served": sorted(served), "replica": pid,
                "was_standby": pid >= min_replicas}

    # process 0: its replica slot serves in a thread; the main thread
    # is the pool's one decision maker
    served_box = {}
    t = threading.Thread(target=lambda: served_box.update(serve()))
    t.start()
    scaler = ReplicaAutoscaler(
        journal, nproc, min_replicas=min_replicas,
        queue_per_replica=int(args.get("queue_per_replica", 4)),
        scale_after=int(args.get("scale_after", 2)),
        cooldown_windows=int(args.get("cooldown_windows", 1)),
    )
    actions = []
    submitted = burst
    deadline = time.monotonic() + serve_timeout
    while time.monotonic() < deadline:
        if submitted < n_requests:  # the trickle behind the burst
            nxt = stream[submitted:submitted + wave]
            journal.submit_all([Request(p, m, id=i)
                                for i, p, m in nxt])
            submitted += len(nxt)
        a = scaler.observe()
        if a:
            actions.append(a)
        done = len(journal.results()) >= n_requests
        # keep observing through the post-load calm until the pool has
        # breathed back down — relief at an empty queue is the
        # scale-down signal, exactly like a real idle pool
        if (done and scaler.totals["scale_down"] >= 1
                and len(scaler.active()) <= min_replicas):
            break
        time.sleep(observe_s)
    t.join(timeout=60)
    results = journal.results()
    assert len(results) == n_requests, (len(results), n_requests)
    oracle_eng = _serving_engine(model, params)
    mismatches = [
        rid for rid, prompt, max_new in stream
        if results[rid]["tokens"] != oracle_eng.generate(prompt, max_new)
    ]
    assert not mismatches, mismatches
    return {
        "served": sorted(served_box), "replica": 0,
        "actions": actions,
        "totals": dict(scaler.totals),
        "active_final": scaler.active(),
    }


def scenario_serving_drain_cycle(pid, nproc, scratch, label, args):
    """Drain -> heal -> re-claim, end to end (ISSUE 16 satellite):
    replica ``nproc-1`` starts drain-marked (``drain_replica`` — the
    adaptive-layer entry point, so the report carries the decision
    trail) and polls as a standby while the healthy replicas complete
    batch 1, the drained slot's reassigned share included.  Once batch
    1 is fully served — the queue is empty, so ownership can change
    with NOTHING pending — process 0 lifts the marker and submits batch
    2: the returned replica re-derives its pure ``seq % n`` share of
    the new work.  With the marker flip at a pending-empty instant the
    shares are disjoint BY CONSTRUCTION (same seqs, same draining set
    on every reader): no request is served twice and none is
    orphaned."""
    import threading

    from chainermn_tpu.resilience.adaptive import drain_replica
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.replica import DecodeReplica, RequestJournal

    b1 = int(args.get("batch1", 12))
    b2 = int(args.get("batch2", 12))
    total = b1 + b2
    serve_timeout = float(args.get("serve_timeout_s", 200.0))
    model, params, stream = _serving_fixture(total)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    drained = nproc - 1
    if pid == 0:
        drain_replica(journal, drained)
        journal.submit_all([Request(p, m, id=i)
                            for i, p, m in stream[:b1]])
    journal.wait_until(b1)
    replica = DecodeReplica(_serving_engine(model, params), journal,
                            replica_index=pid, n_replicas=nproc)

    def serve():
        return replica.serve(until_complete=total,
                             timeout_s=serve_timeout)

    if pid != 0:
        served = serve()
        journal.wait_until_complete(total, timeout_s=serve_timeout)
        return {"served": sorted(served), "replica": pid}
    served_box = {}
    t = threading.Thread(target=lambda: served_box.update(serve()))
    t.start()
    # batch 1 completes WITHOUT the drained slot: its share migrated
    journal.wait_until_complete(b1, timeout_s=serve_timeout)
    assert journal.draining() == [drained], journal.draining()
    journal.clear_draining(drained)  # heal: re-admit the slot
    journal.submit_all([Request(p, m, id=i)
                        for i, p, m in stream[b1:]])
    results = journal.wait_until_complete(total, timeout_s=serve_timeout)
    t.join(timeout=60)
    oracle_eng = _serving_engine(model, params)
    mismatches = [
        rid for rid, prompt, max_new in stream
        if results[rid]["tokens"] != oracle_eng.generate(prompt, max_new)
    ]
    assert not mismatches, mismatches
    return {"served": sorted(served_box), "replica": 0,
            "batch1": b1, "batch2": b2}


# ----------------------------------------------------------------------
def _spec_fixture(n_requests: int):
    """Speculative-burst stream: every prompt opens with the SAME
    page-aligned 8-token system prefix (page_size is 8, so admission
    aliases exactly one page cross-request), then a distinct tail.
    ``max_new`` is staggered so requests retire at different steps and
    the shared page's refcount walks down one release at a time."""
    import numpy as np

    model, params, _ = _serving_fixture(0)
    rng = np.random.RandomState(11)
    sys_prefix = rng.randint(0, 64, 8).tolist()
    stream = [
        ("s%d" % i,
         sys_prefix + rng.randint(0, 64, 1 + i % 3).tolist(),
         5 + i % 3)
        for i in range(n_requests)
    ]
    return model, params, stream


def _spec_replica(model, params, journal, pid, nproc, k):
    """A :class:`DecodeReplica` running a :class:`SpeculativeBatcher`:
    a half-width 1-layer draft (deterministic seed — identical on every
    process) proposes against the target fixture, with the draft cache
    built to the target's exact geometry."""
    import jax
    import jax.numpy as jnp
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving.decode import DecodeEngine
    from chainermn_tpu.serving.replica import DecodeReplica
    from chainermn_tpu.serving.speculative import SpeculativeBatcher

    engine = _serving_engine(model, params)
    draft_model = TransformerLM(vocab_size=64, d_model=16, n_heads=2,
                                n_layers=1, max_len=64)
    draft_params = draft_model.init(
        {"params": jax.random.PRNGKey(7),
         "dropout": jax.random.PRNGKey(8)},
        jnp.zeros((1, 8), jnp.int32),
    )
    draft = DecodeEngine(
        draft_model, draft_params,
        capacity=engine.capacity, page_size=engine.page_size,
        pages_per_slot=engine.pages_per_slot,
        num_pages=engine.cache.num_pages,
    )
    batcher = SpeculativeBatcher(engine, draft, k=k)
    return DecodeReplica(engine, journal, replica_index=pid,
                         n_replicas=nproc, batcher=batcher), batcher


def scenario_serving_spec_burst(pid, nproc, scratch, label, args):
    """ISSUE 17 fleet leg, phase 1: N speculative replicas (draft +
    target riding one allocator each) partition a shared-prefix stream;
    the schedule kills one replica at its 2nd ``serving.spec_verify``
    call — mid-burst, with draft proposals in flight, live shared pages
    (refcount > 1), and the target cache mid-reservation.  Survivors
    complete exactly their own shares; each checks its allocator drained
    clean (refcount invariants hold, every page back on the free list,
    in BOTH caches) — a speculative crash must not leak the survivors'
    sharing state."""
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.replica import RequestJournal, claim

    n_requests = int(args.get("n_requests", 12))
    k = int(args.get("k", 4))
    model, params, stream = _spec_fixture(n_requests)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    if pid == 0:
        journal.submit_all([Request(p, m, id=i) for i, p, m in stream])
    journal.wait_until(len(stream))
    replica, batcher = _spec_replica(model, params, journal, pid, nproc,
                                     k)
    served = replica.serve()  # the victim dies inside (schedule spec)
    by_id = {r["id"]: r for r in journal.requests()}
    want = {r["id"] for r in claim(list(by_id.values()), pid, nproc)}
    assert set(served) == want, (sorted(served), sorted(want))
    # the speculative path actually ran, and sharing was live
    assert batcher.verify_steps > 0, "no verify step fired"
    assert batcher.prefix_hits >= 1, "shared prefix never aliased"
    # drained clean: refcounts walked back to zero, conservation holds
    for cache in (replica.engine.cache, batcher.draft.cache):
        cache.check_invariants()
        assert cache.used_pages == 0, cache.used_pages
    finish_and_exit({
        "served": sorted(served), "replica": pid,
        "verify_steps": batcher.verify_steps,
        "prefix_hits": batcher.prefix_hits,
        "tokens_proposed": batcher.tokens_proposed,
        "tokens_accepted": batcher.tokens_accepted,
    }, linger_s=float(args.get("linger_s", 1.5)))


def scenario_serving_spec_resume(pid, nproc, scratch, label, args):
    """Phase 2: the survivors re-form at the new replica count; the
    victim's pending share re-derives over ``seq % n_survivors`` and
    each resumed request serves SPECULATIVELY again — and every
    journaled request, phase-1 and resumed alike, matches a fresh
    single-engine plain-decode oracle bit-for-bit (greedy-exact
    acceptance makes the speculative transcript the plain transcript by
    construction, draft crash or no)."""
    from chainermn_tpu.serving.replica import RequestJournal, claim

    n_requests = int(args.get("n_requests", 12))
    k = int(args.get("k", 4))
    model, params, stream = _spec_fixture(n_requests)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    pending = journal.pending()
    pending_before = len(pending)
    assert pending_before > 0, "phase 1 should have left unserved work"
    my_share = {r["id"] for r in claim(pending, pid, nproc)}
    replica, batcher = _spec_replica(model, params, journal, pid, nproc,
                                     k)
    served = replica.serve()
    assert set(served) == my_share, (sorted(served), sorted(my_share))
    journal.wait_until_complete(n_requests)
    results = journal.results()
    assert sorted(results) == sorted(i for i, _p, _m in stream)
    oracle_eng = _serving_engine(model, params)
    mismatches = [
        rid for rid, prompt, max_new in stream
        if results[rid]["tokens"] != oracle_eng.generate(prompt, max_new)
    ]
    assert not mismatches, mismatches
    for cache in (replica.engine.cache, batcher.draft.cache):
        cache.check_invariants()
        assert cache.used_pages == 0, cache.used_pages
    return {
        "served": sorted(served), "replica": pid,
        "pending_before": pending_before,
        "completed": len(results),
        "bit_identical": True,
        "verify_steps": batcher.verify_steps,
        "prefix_hits": batcher.prefix_hits,
        "acceptance_rate": batcher.acceptance_rate,
    }


def scenario_serving_disagg(pid, nproc, scratch, label, args):
    """ISSUE 18 fleet leg: disaggregated role pools under a prefill
    death.  4 processes: pids 0/1 are the DECODE pool
    (``DisaggDecodeReplica``, ingesting published handoffs), pids 2/3
    the PREFILL pool (``seq % 2`` over the pool-scoped drain markers)
    — the victim must NOT be process 0, whose death would take the
    ``jax.distributed`` coordinator (and so every survivor) down with
    it.  The schedule kills prefill replica 0 (process 2) at its 4th
    ``serving.prefill`` call — mid-share, with handoffs published and
    the rest of its share unpublished.  Prefill replica 1 finishes its
    own share, then (after an idle grace with uncovered requests still
    pending) marks the dead replica draining in the PREFILL namespace
    and re-derives its share; publishing is idempotent, so a racing
    duplicate overwrites with identical bytes.  The decode pool never
    orphans (generous ``handoff_timeout_s``) — every request completes
    FROM A HANDOFF, bit-identical to the fresh single-engine oracle."""
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.disagg import (
        DisaggDecodeReplica,
        PrefillReplica,
    )
    from chainermn_tpu.serving.replica import RequestJournal, claim

    assert nproc == 4, "scenario is shaped for 2 decode + 2 prefill"
    n_requests = int(args.get("n_requests", 12))
    grace_s = float(args.get("grace_s", 1.5))
    serve_timeout = float(args.get("serve_timeout_s", 240.0))
    model, params, stream = _serving_fixture(n_requests)
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    if pid == 0:
        journal.submit_all([Request(p, m, id=i) for i, p, m in stream])
    journal.wait_until(len(stream))

    if pid in (2, 3):
        pr = PrefillReplica(
            _serving_engine(model, params), journal,
            replica_index=pid - 2, n_replicas=2, codec="bf16",
        )
        # process 2 dies inside (schedule spec); process 3 loops until
        # every still-pending request is covered by a handoff, marking
        # the dead replica draining after the idle grace
        marked = False
        deadline = time.monotonic() + grace_s
        while True:
            n = pr.prefill_round()
            todo = [d for d in journal.pending()
                    if not journal.has_handoff(d["id"])]
            if not todo:
                break
            if n > 0:
                deadline = time.monotonic() + grace_s
            elif not marked and time.monotonic() > deadline:
                # replica 0's share is uncovered and nothing claims it:
                # declare it dead in the prefill marker namespace
                journal.mark_draining(0, pool=pr.pool)
                marked = True
            else:
                time.sleep(0.05)
        finish_and_exit({
            "replica": pid - 2, "pool": "prefill",
            "published": pr.published, "rederived": marked,
            "wire_bytes": pr.wire_bytes,
        }, linger_s=float(args.get("linger_s", 1.5)))

    dr = DisaggDecodeReplica(
        _serving_engine(model, params), journal,
        replica_index=pid, n_replicas=2,
        handoff_timeout_s=float(args.get("handoff_timeout_s", 300.0)),
    )
    served = dr.serve(until_complete=n_requests, timeout_s=serve_timeout)
    by_id = {r["id"]: r for r in journal.requests()}
    want = {r["id"] for r in claim(list(by_id.values()), pid, 2)}
    assert set(served) == want, (sorted(served), sorted(want))
    # every request rode a handoff — the death never forced an orphan
    # fallback, and the allocator drained clean
    assert dr.local_prefills == 0, dr.local_prefills
    assert dr.ingested == len(served), (dr.ingested, len(served))
    dr.engine.cache.check_invariants()
    assert dr.engine.cache.used_pages == 0
    journal.wait_until_complete(n_requests)
    results = journal.results()
    assert sorted(results) == sorted(i for i, _p, _m in stream)
    oracle_eng = _serving_engine(model, params)
    mismatches = [
        rid for rid, prompt, max_new in stream
        if results[rid]["tokens"] != oracle_eng.generate(prompt, max_new)
    ]
    assert not mismatches, mismatches
    finish_and_exit({
        "replica": pid, "pool": "decode",
        "served": sorted(served), "ingested": dr.ingested,
        "local_prefills": dr.local_prefills,
        "completed": len(results), "bit_identical": True,
    }, linger_s=float(args.get("linger_s", 1.5)))


# ----------------------------------------------------------------------
def main():
    scenario, port, pid, nproc, scratch, label, args_json = sys.argv[1:8]
    pid, nproc = int(pid), int(nproc)
    args = json.loads(args_json)

    # process-targeted FaultSpec(process=k) resolves the index from this
    # env var — the launcher sets it, but belt-and-braces for direct use
    os.environ.setdefault("CHAINERMN_TPU_FAULT_PROCESS_INDEX", str(pid))

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=nproc, process_id=pid
    )

    from chainermn_tpu import observability as obs
    from chainermn_tpu.resilience.log import JsonlFileSink, attach

    tel = obs.Telemetry(label=f"{label}_p{pid}")
    obs.install(tel)
    # the streaming sink: every fault/retry/reform/reshard event is on
    # disk the moment it is emitted, so even a `die` victim's record
    # survives for the merged FleetReport
    sink = JsonlFileSink(
        os.path.join(scratch, f"{label}_p{pid}_events.jsonl")
    )
    attach(sink)
    # opt-in host-protocol recorder (CHAINERMN_TPU_PROTOCOL_RECORD=1):
    # every obj-store exchange this worker issues is logged in order,
    # exported next to the trace for FleetReport.protocol_divergence
    from chainermn_tpu.resilience import protocol as _proto

    rec = _proto.install_from_env(
        label=f"{label}_p{pid}", rank=pid, world=nproc
    )
    _CTX.update(
        telemetry=tel,
        trace_path=os.path.join(scratch, f"{label}_p{pid}_trace.jsonl"),
        protocol=rec,
        protocol_path=os.path.join(
            scratch, f"{label}_p{pid}_protocol.jsonl"
        ),
    )

    out = globals()[f"scenario_{scenario}"](pid, nproc, scratch, label,
                                            args)
    _export_artifacts()
    print("RESULT " + json.dumps(out or {}), flush=True)


if __name__ == "__main__":
    main()
