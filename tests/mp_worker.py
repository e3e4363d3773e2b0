"""Multi-process test worker — runs one scenario inside a real
``jax.distributed`` process.

Parity: the reference's distributed tests are real multi-process runs
(``mpiexec -n 2 pytest``, SURVEY.md section 4 "real small world, no
mocks").  The TPU rebuild's analogue: ``test_multiprocess.py`` spawns N
copies of this script, each initializing ``jax.distributed`` against a
shared local coordinator, with CPU devices standing in for per-host TPU
chips.  Every multi-host-only code path (KV-store object transport,
``broadcast_one_to_all``, ``make_array_from_process_local_data``,
checkpoint agreement, barrier, the global except hook) executes for real.

Invocation (by test_multiprocess.py, not by hand):
    python mp_worker.py <scenario> <coordinator_port> <process_id> \
        <num_processes> <scratch_dir>

Prints ``RESULT <json>`` on success; exit code 0.  Scenarios that are
*supposed* to die (except hook) exit non-zero by design.
"""

import json
import os
import sys
import time


def main():
    scenario, port, pid, nproc, scratch = (
        sys.argv[1],
        sys.argv[2],
        int(sys.argv[3]),
        int(sys.argv[4]),
        sys.argv[5],
    )

    # process-targeted fault specs (FaultSpec(process=...)) resolve the
    # index from this env var — set before any injector can fire
    os.environ.setdefault("CHAINERMN_TPU_FAULT_PROCESS_INDEX", str(pid))

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=nproc, process_id=pid
    )

    out = globals()[f"scenario_{scenario}"](pid, nproc, scratch)
    print("RESULT " + json.dumps(out or {}), flush=True)


def _comm(name="tpu", **kw):
    import chainermn_tpu as cmn

    return cmn.create_communicator(name, **kw)


# ----------------------------------------------------------------------
def scenario_obj_transport(pid, nproc, scratch):
    """MultiprocessObjStore: send/recv (KV store), bcast/gather/allgather
    (host collectives), chunk protocol, tuple + array payloads."""
    import numpy as np

    comm = _comm()
    assert comm.process_count == nproc

    # ring send/recv of a composite payload (tuple with an array, as in
    # the reference's _MessageType protocol tests)
    payload = ({"pid": pid}, np.arange(pid + 3, dtype=np.float32))
    comm.send_obj(payload, dest=(pid + 1) % nproc, tag=5)
    got = comm.recv_obj(source=(pid - 1) % nproc, tag=5)
    src = (pid - 1) % nproc
    assert got[0] == {"pid": src}, got
    np.testing.assert_array_equal(got[1], np.arange(src + 3, dtype=np.float32))

    # two queued messages to the same (dest, tag) arrive FIFO
    comm.send_obj("first", dest=(pid + 1) % nproc, tag=6)
    comm.send_obj("second", dest=(pid + 1) % nproc, tag=6)
    assert comm.recv_obj(source=src, tag=6) == "first"
    assert comm.recv_obj(source=src, tag=6) == "second"

    # collectives
    assert comm.bcast_obj(f"from-{pid}") == "from-0"
    assert comm.allgather_obj(pid * 11) == [i * 11 for i in range(nproc)]
    assert comm.gather_obj(pid + 1) == list(range(1, nproc + 1))
    assert comm.allreduce_obj(pid + 1) == sum(range(1, nproc + 1))

    # a payload above one chunk would need >256MB; instead verify a
    # multi-MB array round-trips intact through the KV store
    big = np.random.RandomState(pid).bytes(2_000_000)
    comm.send_obj(big, dest=(pid + 1) % nproc, tag=7)
    got = comm.recv_obj(source=src, tag=7)
    assert got == np.random.RandomState(src).bytes(2_000_000)
    return {"size": comm.size}


def scenario_bcast_data(pid, nproc, scratch):
    """bcast_data must make every process agree bit-for-bit with process
    0's parameters (parity: initial-weight sync of bcast_data(model))."""
    import numpy as np

    comm = _comm()
    tree = {
        "w": np.full((4, 4), float(pid + 1), np.float32),
        "b": np.arange(4, dtype=np.float32) + 100 * pid,
        "nested": [np.float32(pid), np.ones((2,), np.float32) * pid],
    }
    out = comm.bcast_data(tree)
    want = {
        "w": np.full((4, 4), 1.0, np.float32),
        "b": np.arange(4, dtype=np.float32),
        "nested": [np.float32(0.0), np.zeros((2,), np.float32)],
    }
    np.testing.assert_array_equal(np.asarray(out["w"]), want["w"])
    np.testing.assert_array_equal(np.asarray(out["b"]), want["b"])
    np.testing.assert_array_equal(
        np.asarray(out["nested"][1]), want["nested"][1]
    )
    # replicated across every device of the mesh
    assert len(out["w"].sharding.device_set) == comm.size
    return {}


def scenario_train_step(pid, nproc, scratch):
    """build_train_step with per-process local batches: the multi-process
    ``_place_batch`` path (make_array_from_process_local_data) + psum
    gradient sync must reproduce the single-controller oracle."""
    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.optimizers import build_train_step

    comm = _comm()
    n_local = comm.size // comm.process_count

    def loss_fn(params, batch):
        x = batch
        return 0.5 * jnp.sum((params["w"] - x.mean(axis=0)) ** 2)

    opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = {"w": jnp.zeros((4,))}
    step = build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))

    # global batch row r = all-r; this process holds rows
    # [pid*n_local, (pid+1)*n_local)
    local_rows = np.stack(
        [
            np.full((4,), float(pid * n_local + i), np.float32)
            for i in range(n_local)
        ]
    )
    w = np.zeros((4,), np.float64)
    for _ in range(3):
        params, opt_state, metrics = step(params, opt_state, local_rows)
        # oracle: grad = mean_r(w - r)
        w = w - 0.1 * (w - np.mean(np.arange(comm.size)))
    got = np.asarray(params["w"])
    np.testing.assert_allclose(got, w, rtol=1e-5)
    return {"final_w": float(got[0]), "loss": float(metrics["loss"])}


def scenario_checkpoint(pid, nproc, scratch):
    """Checkpoint save / newest-common-step agreement / resume across
    real processes (parity: the allgather-inventories protocol)."""
    import numpy as np
    import jax.numpy as jnp
    import chainermn_tpu as cmn

    comm = _comm()

    # Part 1: shared-FS orbax checkpoint of *global* (mesh-replicated)
    # arrays — collective save, agreement, resume, bit-equal restore.
    ckpt = cmn.create_multi_node_checkpointer(
        "mp", comm, path=os.path.join(scratch, "ckpt")
    )
    state3 = {
        "params": comm.bcast_data({"w": jnp.arange(8.0)}),
        "meta": {"it": 3},
    }
    ckpt.save(3, state3)
    state7 = {
        "params": comm.bcast_data({"w": jnp.arange(8.0) + 7}),
        "meta": {"it": 7},
    }
    ckpt.save(7, state7)
    assert ckpt.newest_common_step() == 7

    step, restored = ckpt.resume(like=state7)
    assert step == 7, step
    np.testing.assert_allclose(
        np.asarray(restored["params"]["w"]), np.arange(8.0) + 7
    )
    assert int(np.asarray(restored["meta"]["it"])) == 7

    # Part 2: the agreement protocol itself with genuinely divergent
    # inventories — per-process directories mimic the reference's
    # per-rank local disk: process 0 has {1,2,5}, others {1,5,8};
    # the newest COMMON step is 5.
    local = cmn.create_multi_node_checkpointer(
        "loc", comm, path=os.path.join(scratch, f"local_{pid}")
    )
    mine = [1, 2, 5] if pid == 0 else [1, 5, 8]
    for s in mine:
        os.makedirs(local._step_dir(s), exist_ok=True)
    assert sorted(local._available_steps()) == mine
    assert local.newest_common_step() == 5
    return {"resumed_step": step}


def scenario_composed_mesh(pid, nproc, scratch):
    """The composed DP x SP x TP x EP step across real processes: a
    (2, 2, 2) mesh spanning two jax.distributed processes (4 CPU chips
    each), MoeTransformerLM with ring attention / Megatron TP / expert
    all_to_all / vocab-parallel embedding+head, per-process local batch
    rows.  Asserts the loss is finite, identical on every process, and
    decreasing."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        moe_lm_loss,
        moe_param_specs,
    )
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.parallel import sharded_init

    comm = _comm("mesh", sp_size=2, tp_size=2)
    assert comm.process_count == nproc and comm.size == 8

    B, S, V = 4, 16, 64
    model = MoeTransformerLM(
        vocab_size=V, d_model=32, n_heads=4, n_layers=2, n_experts=4,
        d_ff=64, moe_every=2, k=2, capacity=B * S * 2, max_len=S,
        dtype=jnp.float32, seq_axis="mn_seq", tp_axis="mn_model",
        expert_axis="mn_model", vocab_parallel=True,
        aux_stat_axes=("mn_data", "mn_seq", "mn_model"),
    )
    toks_global = np.random.RandomState(0).randint(0, V, (B, S))
    sample = jnp.asarray(toks_global)  # replicated sample for init shape
    params, specs = sharded_init(
        lambda t: model.init(jax.random.PRNGKey(0), t),
        comm.mesh, (P("mn_data", "mn_seq"),), moe_param_specs, sample,
    )
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)

    def loss_fn(p, b):
        return moe_lm_loss(
            model.apply(p, b), b, seq_axis="mn_seq",
            model_axis="mn_model", aux_coef=1e-2, vocab_parallel=True,
        )

    step = build_train_step(
        comm, loss_fn, opt, data_axes=comm.data_axis_names,
        param_specs=specs, batch_specs=P("mn_data", "mn_seq"),
        donate=False,
    )
    params, opt_state = step.place(params, opt.init(params))

    # per-process rows: the data axis spans processes, so each process
    # feeds its own slice of the global batch
    rows_per_proc = B // nproc
    local = toks_global[pid * rows_per_proc: (pid + 1) * rows_per_proc]
    losses = []
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, local)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # every process must see the identical (psum'd) loss sequence
    all_losses = comm.allgather_obj(losses)
    for other in all_losses[1:]:
        np.testing.assert_allclose(other, all_losses[0], rtol=1e-6)
    return {"losses": losses}


def scenario_iterators(pid, nproc, scratch):
    """Multi-process data layer (reference: _multi_node_iterator /
    _synchronized_iterator under mpiexec): the per-batch ``bcast_obj``
    loop of create_multi_node_iterator and the seed agreement of
    create_synchronized_iterator across real processes — including a
    non-zero ``rank_master`` owned by the LAST process, pinning the
    root-aware bcast_obj contract."""
    import numpy as np
    from chainermn_tpu.iterators import (
        SerialIterator,
        create_multi_node_iterator,
        create_synchronized_iterator,
    )

    comm = _comm()
    last = comm.size - 1  # a rank owned by the last process

    # root-aware object collectives: the payload must come from the
    # process owning rank `root`, not silently from process 0
    assert comm.bcast_obj(f"from-{pid}", root=last) == f"from-{nproc - 1}"
    try:
        comm.bcast_obj("x", root=comm.size)
        raise AssertionError("out-of-range root must raise")
    except ValueError:
        pass

    # multi-node iterator: per-process datasets DIFFER; the wrapped
    # stream must equal the master's (master rank on the last process)
    ds = [int(x) for x in (np.arange(8) + 1000 * pid)]
    it = create_multi_node_iterator(
        SerialIterator(ds, 4, shuffle=False), comm, rank_master=last
    )
    got = [list(it.next()) for _ in range(2)]
    want = np.arange(8) + 1000 * (nproc - 1)
    assert got[0] == list(want[:4]), got
    assert got[1] == list(want[4:]), got

    # synchronized iterator: differently-seeded iterators must agree on
    # the shuffle order after synchronization
    sit = create_synchronized_iterator(
        SerialIterator(list(range(16)), 8, shuffle=True, seed=pid), comm
    )
    order = [int(v) for v in sit.next()]
    orders = comm.allgather_obj(order)
    assert all(o == orders[0] for o in orders), orders
    assert sorted(order) != order, "shuffle should not be identity"
    return {"first_batch": [int(v) for v in got[0]]}


def scenario_allreduce_persistent(pid, nproc, scratch):
    """Per-process drifted host stats must converge to the cross-process
    mean (parity: AllreducePersistent before snapshot/eval)."""
    import numpy as np
    from chainermn_tpu.extensions.allreduce_persistent import (
        AllreducePersistent,
    )

    comm = _comm()
    arp = AllreducePersistent(comm)
    stats = {"bn": {"mean": np.full((4,), float(pid), np.float32)}}
    out = arp.reduce(stats)
    want = np.full((4,), np.mean(np.arange(nproc)), np.float32)
    np.testing.assert_allclose(np.asarray(out["bn"]["mean"]), want)
    return {}


def scenario_barrier(pid, nproc, scratch):
    """barrier() must actually rendezvous: a process arriving late must
    make the early one wait."""
    comm = _comm()
    t0 = time.monotonic()
    if pid == 1:
        time.sleep(1.5)
    comm.barrier()
    waited = time.monotonic() - t0
    if pid == 0:
        assert waited >= 1.0, f"barrier did not wait ({waited:.2f}s)"
    return {"waited": waited}


def _kill_test_pieces(comm):
    """Shared by the kill_mid_checkpoint phases: a deterministic 2-proc
    training step (closed-form oracle) + a per-rank LOCAL checkpointer.

    Loss 0.5*||w - mean(rank_values)||^2 on a replicated w: each update
    is w <- w - lr*(w - c) with c = mean over the global batch rows, so
    w after k steps has the closed form c*(1-(1-lr)^k) from w0=0 —
    every phase can recompute any step's exact params without replay.
    """
    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.optimizers import build_train_step

    lr, c = 0.1, float(np.mean(np.arange(comm.size)))

    def loss_fn(params, batch):
        return 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)

    opt = cmn.create_multi_node_optimizer(optax.sgd(lr), comm)
    step = build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place({"w": jnp.zeros((4,))},
                                   opt.init({"w": jnp.zeros((4,))}))
    n_local = comm.size // comm.process_count
    rows = np.stack([
        np.full((4,), float(comm.process_index * n_local + i), np.float32)
        for i in range(n_local)
    ])

    def w_at(k):  # closed form
        return c * (1.0 - (1.0 - lr) ** k)

    return step, params, opt_state, rows, w_at


def scenario_kill_mid_checkpoint_phase1(pid, nproc, scratch):
    """Fault injection on the agreement protocol (VERDICT r4 #6), run A:
    both ranks train and snapshot steps 1 and 2 to PER-RANK LOCAL disk
    (the reference's storage model — npz tier); then rank 1 writes step
    3's snapshot and DIES (os._exit) before any agreement round.  Rank 0
    never has step 3.  Phase 2 (a fresh world over the same scratch)
    must agree on step 2 — the newest step present on ALL ranks."""
    import numpy as np
    import jax
    import chainermn_tpu as cmn

    comm = _comm()
    step, params, opt_state, rows, w_at = _kill_test_pieces(comm)
    ckpt = cmn.create_multi_node_checkpointer(
        "kill", comm, path=os.path.join(scratch, f"local_{pid}"),
        use_orbax=False,
    )
    for s in (1, 2):
        params, opt_state, _m = step(params, opt_state, rows)
        state = {
            "params": jax.device_get(params),
            "opt_state": jax.device_get(opt_state),
            "meta": {"it": s},
        }
        ckpt.save(s, state)
        np.testing.assert_allclose(   # sanity: oracle matches training
            np.asarray(params["w"]), np.full((4,), w_at(s)), rtol=1e-6
        )
    if pid == 1:
        # rank 1 raced ahead: its step-3 snapshot lands on ITS disk,
        # then the process dies before any cross-rank coordination —
        # exactly the window the newest-common-step protocol exists for.
        # (The step-3 params come from the closed form: the real step()
        # is a collective and rank 0 is no longer stepping.)
        w3 = {"w": np.full((4,), w_at(3), np.float32)}
        ckpt.save(3, {"params": w3, "opt_state": None, "meta": {"it": 3}})
        print("RANK1_WROTE_STEP3_AND_DIED", flush=True)
        os._exit(42)
    # rank 0 "survives" the event but is torn down with the job (a
    # graceful exit would hang in jax.distributed shutdown waiting for
    # the dead coordinator client — exactly like a real preemption,
    # where survivors are reaped too and recovery happens at RESTART,
    # which is phase 2).  It waits for rank 1's step-3 snapshot to LAND
    # first: rank 0 hosts the coordination service, and exiting while
    # rank 1 is still mid-write would kill rank 1 with the leader — a
    # harness race, not the preemption under test.
    import glob as _glob

    deadline = time.monotonic() + 60
    pattern = os.path.join(scratch, "local_1", "kill", "**",
                           "step_000000000003")
    while time.monotonic() < deadline:
        if _glob.glob(pattern, recursive=True):
            break
        time.sleep(0.05)
    print("RESULT " + json.dumps(
        {"w2": float(np.asarray(params["w"])[0])}
    ), flush=True)
    os._exit(0)


def scenario_kill_mid_checkpoint_phase2(pid, nproc, scratch):
    """Run B (restart after the kill): inventories diverge (rank 0 has
    {1,2}, rank 1 has {1,2,3}); agreement must land on step 2 = N-1,
    resume must restore step 2's exact params on BOTH ranks — rank 1's
    newer snapshot is correctly IGNORED — and training must continue
    from there (loss finite, params follow the closed form)."""
    import numpy as np
    import jax
    import chainermn_tpu as cmn

    comm = _comm()
    step, params, opt_state, rows, w_at = _kill_test_pieces(comm)
    ckpt = cmn.create_multi_node_checkpointer(
        "kill", comm, path=os.path.join(scratch, f"local_{pid}"),
        use_orbax=False,
    )
    mine = ckpt._available_steps()
    assert mine == ([1, 2] if pid == 0 else [1, 2, 3]), mine
    agreed = ckpt.newest_common_step()
    assert agreed == 2, f"agreement must pick N-1=2, got {agreed}"
    got_step, state = ckpt.resume()
    assert got_step == 2, got_step
    np.testing.assert_allclose(
        np.asarray(state["params"]["w"]), np.full((4,), w_at(2)),
        rtol=1e-6,
    )
    assert int(state["meta"]["it"]) == 2
    # training continues from the restored state: steps 3 and 4 land on
    # the closed-form trajectory
    params = jax.device_put(state["params"],
                            step.replicated_sharding)
    opt_state = jax.device_put(state["opt_state"],
                               step.replicated_sharding)
    for k in (3, 4):
        params, opt_state, m = step(params, opt_state, rows)
        np.testing.assert_allclose(
            np.asarray(params["w"]), np.full((4,), w_at(k)), rtol=1e-6
        )
        assert np.isfinite(float(m["loss"]))
    return {"resumed_step": got_step,
            "w4": float(np.asarray(params["w"])[0])}


def scenario_async_checkpoint(pid, nproc, scratch):
    """``use_async=True`` across a real 2-process world: ``save`` returns
    while the write continues on a background thread; a second save
    serializes behind the in-flight one; ``wait_until_finished`` +
    ``newest_common_step`` + ``resume`` observe the committed snapshots
    (previously async was only exercised single-process)."""
    import numpy as np
    import jax.numpy as jnp
    import chainermn_tpu as cmn

    comm = _comm()
    ckpt = cmn.create_multi_node_checkpointer(
        "amp", comm, path=os.path.join(scratch, "ckpt"), use_async=True
    )
    state2 = {
        "params": comm.bcast_data({"w": jnp.arange(8.0)}),
        "meta": {"it": 2},
    }
    ckpt.save(2, state2)
    state5 = {
        "params": comm.bcast_data({"w": jnp.arange(8.0) + 5}),
        "meta": {"it": 5},
    }
    ckpt.save(5, state5)  # must serialize behind the in-flight step-2 save
    ckpt.wait_until_finished()
    comm.barrier()  # every process committed before the agreement scan
    assert ckpt.newest_common_step() == 5
    step, restored = ckpt.resume(like=state5)
    assert step == 5, step
    np.testing.assert_allclose(
        np.asarray(restored["params"]["w"]), np.arange(8.0) + 5
    )
    assert int(np.asarray(restored["meta"]["it"])) == 5
    ckpt.finalize()
    return {"resumed_step": step}


def scenario_resilience(pid, nproc, scratch):
    """The resilience tentpole in a REAL 2-process world (faults injected
    via the CHAINERMN_TPU_FAULTS env var set by the spawning test):

    (a) an injected transient obj-store timeout (first exchange, both
        processes) is absorbed by the retry schedule — the allgather
        completes;
    (b) a NaN gradient on ONE process's rows is skipped in cross-rank
        agreement (the compiled pmin flag) — no deadlock, bit-identical
        params everywhere;
    (c) an injected mid-run failure at update call 4 (both processes)
        triggers auto-resume from ``newest_common_step()`` and training
        reaches the stop trigger with ``max_restarts`` respected.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.training.trainer import Trainer, Updater
    from chainermn_tpu.iterators import SerialIterator

    comm = _comm()

    # (a) retried obj-store exchange: the env spec fires a timeout on the
    # FIRST obj_store.exchange call of every process; the retry joins the
    # collective late (tail latency, not deadlock) and it completes.
    got = comm.allgather_obj(pid * 7)
    assert got == [i * 7 for i in range(nproc)], got

    # (b) cross-rank NaN skip agreement.
    lr, c = 0.1, float(np.mean(np.arange(comm.size)))

    def loss_fn(params, batch):
        return 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)

    opt = cmn.create_multi_node_optimizer(optax.sgd(lr), comm)
    step = build_train_step(comm, loss_fn, opt, donate=False,
                            nonfinite="skip")
    params, opt_state = step.place(
        {"w": jnp.zeros((4,))}, opt.init({"w": jnp.zeros((4,))})
    )
    n_local = comm.size // comm.process_count
    rows = np.stack([
        np.full((4,), float(pid * n_local + i), np.float32)
        for i in range(n_local)
    ])
    bad = rows.copy()
    if pid == 0:  # non-finite data on ONE process only
        bad[0, 0] = np.nan

    def w_at(k):
        return c * (1.0 - (1.0 - lr) ** k)

    params, opt_state, m1 = step(params, opt_state, rows)
    assert float(m1["grads_finite"]) == 1.0
    params, opt_state, m2 = step(params, opt_state, bad)
    assert float(m2["grads_finite"]) == 0.0, (
        "every rank must agree the NaN step is skipped"
    )
    np.testing.assert_allclose(  # skipped: params still at step 1
        np.asarray(params["w"]), np.full((4,), w_at(1)), rtol=1e-6
    )
    params, opt_state, m3 = step(params, opt_state, rows)
    assert float(m3["grads_finite"]) == 1.0
    flags = comm.allgather_obj(
        [float(m1["grads_finite"]), float(m2["grads_finite"]),
         float(m3["grads_finite"])]
    )
    assert all(f == flags[0] for f in flags), flags

    # (c) auto-resume across processes: train 6 iterations with a
    # per-iteration collective checkpoint; the env spec kills update
    # call 4 with a transient fault on BOTH processes (same
    # deterministic call count), so both roll back to step 3 together.
    opt2 = cmn.create_multi_node_optimizer(optax.sgd(lr), comm)
    step2 = build_train_step(comm, loss_fn, opt2, donate=False)
    p2, s2 = step2.place(
        {"w": jnp.zeros((4,))}, opt2.init({"w": jnp.zeros((4,))})
    )
    it = SerialIterator([rows[i] for i in range(n_local)], n_local,
                        shuffle=False)
    trainer = Trainer(Updater(it, step2, p2, s2),
                      stop_trigger=(6, "iteration"))
    ckpt = cmn.create_multi_node_checkpointer(
        "resume", comm, path=os.path.join(scratch, "resume_ckpt")
    )
    trainer.extend(ckpt, trigger=(1, "iteration"))
    trainer.run(max_restarts=2)
    assert trainer.iteration == 6, trainer.iteration
    assert trainer.restarts == 1, trainer.restarts
    counts = trainer.resilience_log.counts
    assert counts.get("restart") == 1, counts
    assert counts.get("fault_injected", 0) >= 1, counts
    np.testing.assert_allclose(
        np.asarray(trainer.updater.params["w"]), np.full((4,), w_at(6)),
        rtol=1e-6,
    )
    finals = comm.allgather_obj(
        float(np.asarray(trainer.updater.params["w"])[0])
    )
    assert all(abs(f - finals[0]) < 1e-6 for f in finals), finals
    return {"final_w": finals[0], "restarts": trainer.restarts}


def scenario_wire_int8(pid, nproc, scratch):
    """ISSUE 4 satellite: the bucketed+int8 gradient wire end to end in
    a real 2-process world, under the fault injector.

    The spawning test sets CHAINERMN_TPU_FAULTS to truncate the FIRST
    ``obj_store.exchange`` payload on every process: each process
    truncates its *own* outgoing plan-hash payload, so every process
    observes the corruption (`PayloadCorruptionError`) and retries the
    exchange in lockstep — the collective stream stays aligned, the
    retry's clean exchange agrees on the plan hash, and the compiled
    int8+error-feedback run completes with bit-identical params on all
    processes.
    """
    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.comm_wire import (
        WireConfig, plan_agreement, plan_of_tree,
    )
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience import fault_injection as fi

    comm = _comm()
    rng = np.random.RandomState(0)  # same seed -> same model everywhere
    params = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
    }
    wire = WireConfig(codec="int8", error_feedback=True)

    # plan agreement: the first exchange carries a truncated payload ->
    # PayloadCorruptionError -> retried -> every process agrees
    plan = plan_of_tree(params, wire.bucket_bytes, wire.max_buckets)
    agreed = plan_agreement(comm, plan)
    assert agreed == plan.plan_hash()
    inj = fi.active()
    assert inj is not None, "fault injector must be env-activated"
    assert inj.log.counts.get("fault_injected", 0) >= 1, (
        "the truncate fault must have fired before the retry succeeded"
    )

    # compiled bucketed+int8+EF training across the 2-process mesh
    w_true = rng.randn(8, 4).astype(np.float32)
    x_all = rng.randn(16, 8).astype(np.float32)
    y_all = x_all @ w_true

    def loss_fn(p, b):
        bx, by = b
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean((h @ p["w2"] - by) ** 2)

    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm,
                                          wire=wire)
    step = build_train_step(comm, loss_fn, opt, donate=False)
    p, o = step.place(params, opt.init(params))
    lo = pid * (16 // nproc)  # per-process slice of the global batch
    hi = lo + 16 // nproc
    batch = (x_all[lo:hi], y_all[lo:hi])
    first = last = None
    for _ in range(20):
        p, o, m = step(p, o, batch)
        last = float(m["loss"])
        if first is None:
            first = last
    assert last < first, (first, last)
    assert isinstance(o.wire_residual, tuple) and o.wire_residual

    # bit-identical replicated params on every process (sha256, not
    # hash(): bytes hashing is salted per process)
    import hashlib

    digests = comm.allgather_obj(hashlib.sha256(
        b"".join(np.asarray(p[k]).tobytes() for k in sorted(p))
    ).hexdigest())
    assert all(d == digests[0] for d in digests), digests
    return {"first_loss": first, "final_loss": last,
            "faults": inj.log.counts.get("fault_injected", 0)}


def scenario_overlap_fault(pid, nproc, scratch):
    """ISSUE 8 satellite: the overlap-scheduled compiled step in a real
    2-process world, under the fault injector.

    The spawning test truncates the plan-agreement AND trace-guard
    exchanges (``obj_store.exchange`` calls #1/#3) on every process:
    each transient is observed by every rank in lockstep, retried, and
    — the point of this scenario — the retry must not reorder or drop
    any of the overlapped program's buckets.  Pinned three ways:

    * the overlap step's collective trace hash, re-derived AFTER the
      faulted run, equals the pre-run hash and agrees across ranks
      (nothing reordered);
    * every bucket psum still issues at its dependency frontier
      (``analysis.check_overlap`` returns no findings);
    * the loss trajectory and final params are BIT-IDENTICAL to the
      synchronous (overlap="none") run of the same world with no fault
      in flight (the injected faults are call-count-addressed to the
      overlap run's exchanges only).
    """
    import hashlib

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.analysis import check_overlap
    from chainermn_tpu.comm_wire import WireConfig, plan_of_tree
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience import fault_injection as fi

    comm = _comm()
    rng = np.random.RandomState(0)
    params = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
        "w3": jnp.asarray(rng.randn(4, 4) * 0.3, jnp.float32),
    }
    # tiny buckets -> one per leaf: a genuinely multi-bucket program
    wire = WireConfig(codec="none", bucket_bytes=64, max_buckets=0)
    w_true = rng.randn(8, 4).astype(np.float32)
    x_all = rng.randn(16, 8).astype(np.float32)
    y_all = x_all @ w_true

    def loss_fn(p, b):
        bx, by = b
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean(((h @ p["w2"]) @ p["w3"] - by) ** 2)

    lo = pid * (16 // nproc)
    hi = lo + 16 // nproc
    batch = (x_all[lo:hi], y_all[lo:hi])

    def run(overlap):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, wire=wire, overlap=overlap
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        pre_hash = step.collective_trace(p, o, batch).trace_hash()
        losses = []
        for _ in range(10):
            p, o, m = step(p, o, batch)
            losses.append(float(m["loss"]))
        post_hash = step.collective_trace(p, o, batch).trace_hash()
        return step, p, o, pre_hash, post_hash, losses

    # overlap run first: its exchanges (plan agreement = exchange #1,
    # trace guard = #3) absorb the injected truncations
    step_b, p_b, o_b, pre_b, post_b, losses_b = run("bucket")
    inj = fi.active()
    assert inj is not None, "fault injector must be env-activated"
    assert inj.log.counts.get("fault_injected", 0) >= 2, (
        "both injected truncations must have fired",
        dict(inj.log.counts),
    )
    # retried transients did not reorder the program
    assert pre_b == post_b
    hashes = comm.allgather_obj(post_b)
    assert all(h == hashes[0] for h in hashes), hashes
    # ...and did not drop a bucket: every bucket psum still issues at
    # its dependency frontier
    plan = plan_of_tree(params, wire.bucket_bytes, wire.max_buckets)
    assert plan.n_buckets >= 3
    # inspect the variant the faulted run actually EXECUTED: the step
    # places the per-process local rows into the global batch before
    # dispatch, and OverlappedStep caches per aval signature — handing
    # it the raw local batch would trace (and validate) a different,
    # never-run variant
    placed_batch = step_b.place_batch(batch)
    jb = step_b.get_jitted(p_b, o_b).scheduled_jaxpr(
        p_b, o_b, placed_batch
    )
    findings = check_overlap(jb, plan)
    assert not findings, [str(f) for f in findings]

    # no-fault synchronous reference: bit-identical losses and params
    step_s, p_s, o_s, pre_s, post_s, losses_s = run("none")
    assert losses_b == losses_s, (losses_b, losses_s)
    assert pre_b != pre_s  # ordering genuinely moved vs sync
    for k in sorted(params):
        np.testing.assert_array_equal(
            np.asarray(p_b[k]), np.asarray(p_s[k])
        )
    digests = comm.allgather_obj(hashlib.sha256(
        b"".join(np.asarray(p_b[k]).tobytes() for k in sorted(p_b))
    ).hexdigest())
    assert all(d == digests[0] for d in digests), digests
    return {
        "faults": inj.log.counts.get("fault_injected", 0),
        "final_loss": losses_b[-1],
        "buckets": plan.n_buckets,
    }


def scenario_multihop_fault(pid, nproc, scratch):
    """ISSUE 11 satellite: the hier_rs_ag multi-hop wire in a REAL
    2-proc hierarchical world (2 processes x 2 local CPU devices: the
    process grouping IS the slice grouping, so the mesh genuinely
    factorizes ('mn_inter', 'mn_intra') = (2, 2)), under the fault
    injector.

    The spawning test truncates ``obj_store.exchange`` calls #1 and #3
    on every process — the standalone schedule/plan agreement below and
    the one ``opt.init`` re-runs inside the training run: each torn
    payload is observed by every rank in lockstep, retried, and the
    multi-hop program must come through untouched —

    * the agreed WirePlan hash covers bucket layout AND per-bucket
      schedule, and every rank lands on the same one;
    * the step's collective trace carries the full rs→ar→ag triple per
      hier bucket, hashes identically before and after the faulted run,
      and agrees across ranks;
    * the loss trajectory and final params are BIT-IDENTICAL to a
      no-fault run of the same schedule (the injected faults are
      call-count-addressed to the first run's exchanges only).
    """
    import hashlib

    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.comm_wire import WireConfig, plan_agreement, plan_wire
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience import fault_injection as fi

    comm = _comm("hierarchical")
    assert dict(comm.mesh.shape) == {"mn_inter": nproc,
                                     "mn_intra": comm.size // nproc}, (
        dict(comm.mesh.shape)
    )
    rng = np.random.RandomState(0)  # same seed -> same model everywhere
    params = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
        "w3": jnp.asarray(rng.randn(4, 4) * 0.3, jnp.float32),
    }
    # tiny buckets -> one per leaf: a genuinely multi-bucket multi-hop
    # program (every bucket staged rs -> ar -> ag)
    wire = WireConfig(schedule="hier_rs_ag", bucket_bytes=64,
                      max_buckets=0)

    # schedule/plan agreement: the first exchange carries a truncated
    # payload -> PayloadCorruptionError on EVERY rank -> lockstep retry
    # -> every rank agrees on layout AND schedule
    wplan = plan_wire(params, wire, comm.mesh)
    assert set(wplan.schedules) == {"hier_rs_ag"}, wplan.schedules
    agreed = plan_agreement(comm, wplan)
    assert agreed == wplan.plan_hash()
    inj = fi.active()
    assert inj is not None, "fault injector must be env-activated"
    assert inj.log.counts.get("fault_injected", 0) >= 1, (
        "the truncate fault must have fired before the retry succeeded"
    )

    w_true = rng.randn(8, 4).astype(np.float32)
    x_all = rng.randn(16, 8).astype(np.float32)
    y_all = x_all @ w_true

    def loss_fn(p, b):
        bx, by = b
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean(((h @ p["w2"]) @ p["w3"] - by) ** 2)

    lo = pid * (16 // nproc)
    hi = lo + 16 // nproc
    batch = (x_all[lo:hi], y_all[lo:hi])

    def run():
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, wire=wire
        )
        step = build_train_step(comm, loss_fn, opt, donate=False)
        p, o = step.place(params, opt.init(params))
        pre_hash = step.collective_trace(p, o, batch).trace_hash()
        losses = []
        for _ in range(10):
            p, o, m = step(p, o, batch)
            losses.append(float(m["loss"]))
        post_hash = step.collective_trace(p, o, batch).trace_hash()
        return step, p, o, pre_hash, post_hash, losses

    # faulted run first: opt.init's plan-agreement exchange is call #3
    # and absorbs the second injected truncation
    step_a, p_a, o_a, pre_a, post_a, losses_a = run()
    assert inj.log.counts.get("fault_injected", 0) >= 2, (
        "both injected truncations must have fired",
        dict(inj.log.counts),
    )
    # retried transients did not reorder or drop a hop
    assert pre_a == post_a
    hashes = comm.allgather_obj(post_a)
    assert all(h == hashes[0] for h in hashes), hashes
    tr = step_a.collective_trace(p_a, o_a, batch)
    n_buckets = wplan.n_buckets
    assert n_buckets >= 3
    census = tr.census()
    assert census.get("reduce_scatter", 0) == n_buckets, census
    assert census.get("all_gather", 0) == n_buckets, census
    assert census.get("all_reduce", 0) == n_buckets + 1, census

    # no-fault reference run of the same schedule: bit-identical
    step_b, p_b, o_b, pre_b, post_b, losses_b = run()
    assert losses_a == losses_b, (losses_a, losses_b)
    for k in sorted(params):
        np.testing.assert_array_equal(
            np.asarray(p_a[k]), np.asarray(p_b[k])
        )
    digests = comm.allgather_obj(hashlib.sha256(
        b"".join(np.asarray(p_a[k]).tobytes() for k in sorted(p_a))
    ).hexdigest())
    assert all(d == digests[0] for d in digests), digests
    return {
        "faults": inj.log.counts.get("fault_injected", 0),
        "final_loss": losses_a[-1],
        "buckets": n_buckets,
        "mesh": dict(comm.mesh.shape),
    }


def scenario_tuned_wire_fault(pid, nproc, scratch):
    """ISSUE 12 satellite: the measured-feedback autotuner in a REAL
    2-proc hierarchical world (2 processes x 2 local CPU devices —
    process grouping = slice grouping, mesh (2, 2)).

    Phase A — shared profile under faults: rank 0 writes ONE
    BandwidthProfile file (atomic rename) into the shared scratch, both
    ranks load it through ``create_multi_node_optimizer(profile=path)``.
    The spawning test truncates obj-store exchanges #1 and #3 (the
    standalone plan agreement below and the one ``opt.init`` re-runs):
    each torn payload surfaces on every rank in lockstep, is retried,
    and the tuned plan comes through with the profile hash folded into
    the agreed ``WirePlan.plan_hash()`` — identical on every rank.  The
    profile's slow-inter/fast-intra curves stage every bucket, so the
    trace must carry the rs→ar→ag triple per bucket, and a short
    training run completes with bit-identical digests across ranks.

    Phase B — mismatched profile: rank 1 swaps in a perturbed profile
    (one bandwidth point changed -> different content hash).  A fresh
    optimizer's ``init`` must raise ``WirePlanMismatchError`` on BOTH
    ranks BEFORE any collective — the schedules may even coincide on
    this model; the hash-folded profile is what guarantees the
    divergence is caught now rather than on the first model where the
    decisions split.
    """
    import hashlib

    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.comm_wire import (
        BandwidthProfile, WireConfig, WirePlanMismatchError,
        plan_agreement,
    )
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience import fault_injection as fi

    comm = _comm("hierarchical")
    assert dict(comm.mesh.shape) == {"mn_inter": nproc,
                                     "mn_intra": comm.size // nproc}, (
        dict(comm.mesh.shape)
    )

    def make_profile(inter_bw):
        # slow inter, fast intra: the measured decision stages every
        # bucket (predicted hier time beats the flat psum for any
        # payload on these curves)
        return BandwidthProfile(
            mesh_axes=tuple(dict(comm.mesh.shape).items()),
            curves={
                ("inter", "all_reduce"): [(64, inter_bw),
                                          (1 << 22, inter_bw)],
                ("intra", "all_reduce"): [(64, 1e12), (1 << 22, 1e12)],
                ("intra", "reduce_scatter"): [(64, 1e12),
                                              (1 << 22, 1e12)],
                ("intra", "all_gather"): [(64, 1e12), (1 << 22, 1e12)],
                ("mixed", "all_reduce"): [(64, inter_bw),
                                          (1 << 22, inter_bw)],
            },
            latency={"inter": 1e-9, "intra": 1e-9, "mixed": 1e-9},
            label="tuned_wire_fault",
        )

    profile_path = os.path.join(scratch, "wire_profile.json")
    if pid == 0:
        tmp = profile_path + ".tmp"
        make_profile(1e6).save(tmp)
        os.replace(tmp, profile_path)  # readers never see a torn file
    deadline = time.time() + 60
    while not os.path.exists(profile_path):
        if time.time() > deadline:
            raise RuntimeError("rank 0 never published the profile")
        time.sleep(0.05)

    rng = np.random.RandomState(0)  # same seed -> same model everywhere
    params = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 4) * 0.3, jnp.float32),
        "w3": jnp.asarray(rng.randn(4, 4) * 0.3, jnp.float32),
    }
    # tiny buckets -> one per leaf: a genuinely multi-bucket tuned
    # program; schedule="auto" so the PROFILE (not a forced knob) is
    # what stages the buckets
    wire = WireConfig(bucket_bytes=64, max_buckets=0)

    opt0 = cmn.create_multi_node_optimizer(
        optax.sgd(0.05), comm, wire=wire, profile=profile_path
    )
    wplan = opt0.wire_plan(params)
    assert set(wplan.schedules) == {"hier_rs_ag"}, wplan.schedules
    assert wplan.profile_hash == opt0.profile.profile_hash()

    # exchange #1 (truncated -> lockstep retry): the agreed hash covers
    # layout AND schedules AND the profile content hash
    agreed = plan_agreement(comm, wplan)
    assert agreed == wplan.plan_hash()
    inj = fi.active()
    assert inj is not None, "fault injector must be env-activated"
    assert inj.log.counts.get("fault_injected", 0) >= 1, (
        "the truncate fault must have fired before the retry succeeded"
    )

    w_true = rng.randn(8, 4).astype(np.float32)
    x_all = rng.randn(16, 8).astype(np.float32)
    y_all = x_all @ w_true

    def loss_fn(p, b):
        bx, by = b
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean(((h @ p["w2"]) @ p["w3"] - by) ** 2)

    lo = pid * (16 // nproc)
    hi = lo + 16 // nproc
    batch = (x_all[lo:hi], y_all[lo:hi])

    # the training run: opt.init's plan-agreement exchange is obj-store
    # call #3 and absorbs the second injected truncation
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(0.05), comm, wire=wire, profile=profile_path
    )
    step = build_train_step(comm, loss_fn, opt, donate=False)
    p, o = step.place(params, opt.init(params))
    assert inj.log.counts.get("fault_injected", 0) >= 2, (
        "both injected truncations must have fired",
        dict(inj.log.counts),
    )
    losses = []
    for _ in range(5):
        p, o, m = step(p, o, batch)
        losses.append(float(m["loss"]))
    tr = step.collective_trace(p, o, batch)
    census = tr.census()
    n_buckets = wplan.n_buckets
    assert n_buckets >= 3
    assert census.get("reduce_scatter", 0) == n_buckets, census
    assert census.get("all_gather", 0) == n_buckets, census
    assert census.get("all_reduce", 0) == n_buckets + 1, census
    hashes = comm.allgather_obj(tr.trace_hash())
    assert all(h == hashes[0] for h in hashes), hashes
    digests = comm.allgather_obj(hashlib.sha256(
        b"".join(np.asarray(p[k]).tobytes() for k in sorted(p))
    ).hexdigest())
    assert all(d == digests[0] for d in digests), digests

    # phase B: rank 1 tunes from a PERTURBED profile — both ranks must
    # raise WirePlanMismatchError at init, before any collective
    my_profile = (
        make_profile(2e6) if pid == 1
        else BandwidthProfile.load(profile_path)
    )
    opt_bad = cmn.create_multi_node_optimizer(
        optax.sgd(0.05), comm, wire=wire, profile=my_profile
    )
    mismatch_raised = False
    try:
        opt_bad.init(params)
    except WirePlanMismatchError:
        mismatch_raised = True
    assert mismatch_raised, (
        "mismatched profiles must fail plan agreement on every rank"
    )
    return {
        "faults": inj.log.counts.get("fault_injected", 0),
        "final_loss": losses[-1],
        "buckets": n_buckets,
        "mesh": dict(comm.mesh.shape),
        "profile_hash": wplan.profile_hash,
        "plan_hash": agreed,
        "mismatch_raised": mismatch_raised,
    }


def scenario_trace_divergence(pid, nproc, scratch):
    """ISSUE 5 satellite: two processes build INTENTIONALLY divergent
    train steps (the rank named by CHAINERMN_TPU_DIVERGE_RANK adds one
    extra psum to its loss), and the collective divergence guard —
    wired into build_train_step's first dispatch — raises the
    non-recoverable ``CollectiveTraceMismatchError`` on BOTH ranks
    before any device collective runs.  Without the guard this world
    deadlocks at the first mis-paired collective (the spawning test's
    timeout is the regression detector for that)."""
    import numpy as np
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu.functions import collectives as cc
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.resilience.errors import CollectiveTraceMismatchError

    comm = _comm()
    diverge_rank = int(os.environ["CHAINERMN_TPU_DIVERGE_RANK"])

    def loss_fn(params, batch):
        l = 0.5 * jnp.sum((params["w"] - batch.mean(axis=0)) ** 2)
        if pid == diverge_rank:
            # the divergent collective: an extra (value-neutral) psum
            # only THIS rank's program contains
            l = l + 0.0 * cc.psum(l, comm.axis_names)
        return l

    opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = {"w": jnp.zeros((4,))}
    step = build_train_step(comm, loss_fn, opt, donate=False)
    # opt.init's wire-plan agreement PASSES (same shapes everywhere);
    # only the collective TRACE diverges — exactly the gap ISSUE 5's
    # guard exists to close
    p, o = step.place(params, opt.init(params))
    n_local = comm.size // comm.process_count
    rows = np.zeros((n_local, 4), np.float32)
    try:
        step(p, o, rows)
    except CollectiveTraceMismatchError as e:
        assert e.recoverable is False
        return {"raised": type(e).__name__,
                "hash_len": len(step.collective_trace(
                    p, o, rows).trace_hash())}
    raise AssertionError(
        "divergence guard did not fire on a divergent world"
    )


def scenario_protocol_divergence(pid, nproc, scratch):
    """ISSUE 20: the HOST-protocol guard fires on every rank before a
    divergent control plane can deadlock.

    Phase 1 proves the guard rides the lockstep retry: symmetric
    obj-store traffic, then ``protocol_agreement`` with a truncate
    fault injected on the guard's OWN agreement exchange — every
    process observes the torn payload (``PayloadCorruptionError``),
    every process retries together, and the agreement succeeds with
    identical hashes.

    Phase 2 diverges the protocol two ways at once: the rank named by
    CHAINERMN_TPU_DIVERGE_RANK issues an EXTRA obj-store ``send_obj``
    (a non-blocking KV publish — deliberately chosen so the world is
    still alive for the guard; an extra host *collective* would
    deadlock at transport before any check could run), and the two
    ranks issue their two lockstep agreement sites in OPPOSITE order
    (transport still pairs — both run two allgathers — but the ordered
    site tokens differ).  ``protocol_agreement`` must raise the
    non-recoverable ``ProtocolDivergenceError`` on BOTH ranks."""
    from chainermn_tpu.analysis.checks import protocol_agreement
    from chainermn_tpu.resilience import fault_injection as fi
    from chainermn_tpu.resilience import protocol as proto
    from chainermn_tpu.resilience.errors import ProtocolDivergenceError
    from chainermn_tpu.resilience.retry import lockstep_allgather

    # install BEFORE the communicator so world-formation exchanges are
    # recorded symmetrically on every rank (launcher sets the env)
    rec = proto.install_from_env(label=f"protodiv_p{pid}", rank=pid,
                                 world=nproc)
    assert rec is not None, "CHAINERMN_TPU_PROTOCOL_RECORD must be set"
    comm = _comm()
    diverge = int(os.environ["CHAINERMN_TPU_DIVERGE_RANK"])

    # -- phase 1: symmetric traffic; torn payload on the guard itself --
    comm.send_obj({"pid": pid}, dest=(pid + 1) % nproc, tag=7)
    got = comm.recv_obj(source=(pid - 1) % nproc, tag=7)
    assert got == {"pid": (pid - 1) % nproc}, got
    lockstep_allgather(comm, pid, site="mp.protocol.phase1")
    with fi.inject_faults([
        fi.FaultSpec("obj_store.exchange", "truncate", at=[1])
    ]):
        # each process truncates its own outgoing agreement payload on
        # attempt 1; ALL observe the corruption, ALL retry in lockstep
        h1 = protocol_agreement(comm, label="phase1")
        inj = fi.active()
        assert inj.log.counts.get("fault_injected", 0) >= 1, (
            "the truncate fault must have fired on the guard's exchange"
        )

    # -- phase 2: one extra KV publish + swapped agreement-site order --
    if pid == diverge:
        comm.send_obj({"extra": True}, dest=(pid + 1) % nproc, tag=6)
    sites = ["mp.protocol.siteA", "mp.protocol.siteB"]
    if pid == diverge:
        sites.reverse()
    for s in sites:
        lockstep_allgather(comm, pid, site=s)
    try:
        protocol_agreement(comm, label="phase2")
    except ProtocolDivergenceError as e:
        assert e.recoverable is False
        # export for the FleetReport merge the spawning test asserts on
        rec.to_jsonl(os.path.join(
            scratch, f"protodiv_p{pid}_protocol.jsonl"
        ))
        return {"raised": type(e).__name__, "phase1": h1,
                "entries": len(rec)}
    raise AssertionError(
        "host-protocol guard did not fire on a divergent world"
    )


def scenario_mismatched_sharding(pid, nproc, scratch):
    """ISSUE 6 satellite: rank 1 is handed a MISMATCHED input sharding
    (row-sharded where every other rank declares replicated), so its
    compiled program carries partitioner-inserted all-gathers the
    author never wrote.  The ``implicit_collectives`` check — its
    cross-process form ``implicit_agreement`` — exchanges per-rank
    implicit counts over the host control plane and raises
    ``ImplicitCollectiveError`` on BOTH ranks before any dispatch, with
    an equation-level citation naming the responsible dot_general."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chainermn_tpu.analysis import (
        ImplicitCollectiveError,
        implicit_agreement,
        shardflow,
        trace_collectives,
    )

    comm = _comm()
    mismatch_rank = int(os.environ["CHAINERMN_TPU_MISMATCH_RANK"])

    def f(x):
        return x @ x.T

    # the mismatched rank shards rows into a program whose matmul the
    # partitioner can only resolve by gathering; everyone else runs the
    # replicated (collective-free) program
    spec = P("mn", None) if pid == mismatch_rank else P()
    jitted = jax.jit(
        f,
        in_shardings=NamedSharding(comm.mesh, spec),
        out_shardings=NamedSharding(comm.mesh, P()),
    )
    sds = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    txt = jitted.lower(sds).compile().as_text()  # static — no dispatch
    tr = trace_collectives(f, sds)
    flow = shardflow(f, sds, in_specs=(spec,), out_specs=(P(),))
    assert len(tr) == 0  # nothing authored — any HLO collective is implicit
    try:
        implicit_agreement(comm, tr, txt, flow=flow, label="mismatched")
    except ImplicitCollectiveError as e:
        msg = str(e)
        assert f"rank {mismatch_rank}" in msg, msg
        # equation-level citation from the XLA metadata / flow pass
        assert "dot_general" in msg, msg
        return {"raised": type(e).__name__,
                "cited_dot": "dot_general" in msg}
    raise AssertionError(
        "implicit_collectives agreement did not fire on a world with a "
        "mismatched input sharding"
    )


def _spot_reclaim_pieces(comm, scratch, lr=0.1, mom=0.9):
    """Shared by the spot_reclaim phases: a ZeRO (sgd+momentum) world
    whose momentum state is BLOCKED over the ranks — the state that must
    genuinely reshard N→M — plus the shared-FS orbax checkpointer.

    Loss 0.5*||w - batch.mean||^2 with global batch rows {0, 1}: the
    gradient is elementwise w - 0.5 at EVERY world size that feeds the
    same global rows, so the single-world trajectory (a numpy simulation
    of sgd+momentum from w0=0) is the oracle for any resize point."""
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
        "spot", comm, path=os.path.join(scratch, "spot_ckpt")
    )
    return opt, step, ckpt


def _spot_oracle(n_steps, lr=0.1, mom=0.9, c=0.5, dim=4):
    """Numpy simulation of the same sgd+momentum math, world-free."""
    import numpy as np

    w = np.zeros(dim)
    v = np.zeros(dim)
    traj = []
    for _ in range(n_steps):
        g = w - c
        v = mom * v + g
        w = w - lr * v
        traj.append(w.copy())
    return traj


def scenario_spot_reclaim_phase1(pid, nproc, scratch):
    """ISSUE 7 satellite, run A (the reclaim): a 2-proc ZeRO world
    (momentum state blocked (2, k) over the ranks) trains and
    collectively snapshots steps 1-3 — each save writes the world
    manifest (world_size=2) beside the orbax dir.  Update 4 then begins
    and the fault injector preempts worker 1 at the ``trainer.update``
    site (env-injected ``die`` spec targeted at process 1) BEFORE it
    dispatches: a spot reclaim mid-step.  Worker 0's slice is gone with
    it — real preemption reaps the survivors too, and recovery happens
    at RESTART (phase 2, world size 1)."""
    import numpy as np
    import jax.numpy as jnp
    from chainermn_tpu.resilience import fault_injection as fi

    comm = _comm()
    opt, step, ckpt = _spot_reclaim_pieces(comm, scratch)
    p0 = {"w": jnp.zeros((4,))}
    params, opt_state = step.place(p0, opt.init(p0))
    rows = np.full((1, 4), float(pid), np.float32)  # global rows {0, 1}
    oracle = _spot_oracle(3)
    for s in (1, 2, 3):
        fi.fire("trainer.update")
        params, opt_state, _m = step(params, opt_state, rows)
        ckpt.save(s, {
            "params": params,
            "opt_state": opt_state,
            "trainer": {"iteration": s, "iterator": None},
        })
        np.testing.assert_allclose(  # sanity: ZeRO matches the oracle
            np.asarray(params["w"]), oracle[s - 1], rtol=1e-5
        )
    # update 4 begins; the injector reclaims worker 1 here (die,
    # process-targeted) — worker 0 is reaped with the job by design.
    # Worker 0 (the coordination-service host) lingers briefly so the
    # reclaim lands before the leader disappears (worker 1's remaining
    # path after the save barrier is fire -> os._exit, sub-ms).
    fi.fire("trainer.update")
    if pid == 0:
        time.sleep(1.0)
    print("RESULT " + json.dumps({"steps_saved": 3}), flush=True)
    os._exit(0)


def scenario_spot_reclaim_phase2(pid, nproc, scratch):
    """Run B (the elastic restart): world size 1 re-forms via
    ``Trainer.run_elastic``; the elected snapshot's manifest names world
    2, so ``resume`` routes through the resharder — the momentum blocks
    re-partition (2, 2) -> (1, 4) bit-identically to a fresh partition
    of the gathered global state — and training continues steps 4-6.
    The loss trajectory after resume must land on the single-world
    oracle (the same sgd+momentum math simulated in numpy over all 6
    steps with no interruption)."""
    import warnings

    import numpy as np
    import jax.numpy as jnp
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training.trainer import Trainer, Updater

    assert nproc == 1
    rows = [np.full((4,), 0.0, np.float32),
            np.full((4,), 1.0, np.float32)]  # the FULL global batch now

    def build(comm):
        opt, step, ckpt = _spot_reclaim_pieces(comm, scratch)
        p0 = {"w": jnp.zeros((4,))}
        params, opt_state = step.place(p0, opt.init(p0))
        it = SerialIterator(rows, 2, shuffle=False)
        trainer = Trainer(Updater(it, step, params, opt_state),
                          stop_trigger=(6, "iteration"))
        trainer.extend(ckpt, trigger=(1, "iteration"))
        return trainer

    with warnings.catch_warnings():
        # the resharder warns (by design) about the reset trainer
        # template slots the manual phase-1 saves did not carry
        warnings.simplefilter("ignore")
        trainer = Trainer.run_elastic(build, communicator_name="tpu")

    ev = trainer.resilience_log.events("elastic_restart")
    assert ev and ev[0].info["restored_step"] == 3, ev
    resized = ev[0].info["resized"]
    assert tuple(resized) == (2, 1), resized
    assert trainer.iteration == 6, trainer.iteration
    oracle = _spot_oracle(6)
    got = np.asarray(trainer.updater.params["w"])
    ok = bool(np.allclose(got, oracle[5], rtol=1e-5))
    assert ok, (got, oracle[5])
    return {"resumed_step": ev[0].info["restored_step"],
            "resized": list(resized),
            "oracle_match": ok,
            "final_w": float(got[0])}


def _serving_fixture():
    """Shared by the serving_churn phases: a deterministic tiny LM
    (same seed on every process -> identical params -> greedy decode
    of any request is bit-identical no matter WHICH replica runs it)
    and the scripted request stream."""
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
        for i in range(8)
    ]
    return model, params, stream


def _serving_engine(model, params):
    from chainermn_tpu.serving.decode import DecodeEngine

    return DecodeEngine(model, params, capacity=2, page_size=8)


def scenario_serving_churn_phase1(pid, nproc, scratch):
    """ISSUE 13 satellite, run A (the churn): two single-process decode
    replicas share one journal directory and partition a scripted
    8-request stream by submission seq.  The fault injector kills
    replica 1 (process-targeted ``die`` at the ``serving.decode_step``
    site) mid-stream — a hard reclaim, no drain.  Replica 0 completes
    its own share; replica 1's unserved requests stay journaled
    (results are atomic files, so no torn result can exist).  Recovery
    happens at restart (phase 2, world size 1)."""
    from chainermn_tpu.serving.batcher import Request
    from chainermn_tpu.serving.replica import DecodeReplica, RequestJournal

    model, params, stream = _serving_fixture()
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    if pid == 0:
        journal.submit_all([
            Request(p, m, id=i) for i, p, m in stream
        ])
    # journal-level rendezvous (no collectives: a dead peer must not
    # wedge the survivor) — wait until the full stream is visible
    deadline = time.monotonic() + 60
    while len(journal.requests()) < len(stream):
        if time.monotonic() > deadline:
            raise RuntimeError("journal never filled")
        time.sleep(0.05)
    replica = DecodeReplica(
        _serving_engine(model, params), journal,
        replica_index=pid, n_replicas=nproc,
    )
    served = replica.serve()  # process 1 dies inside (env fault spec)
    # replica 0 (the coordination-service host) lingers so the targeted
    # kill lands before the leader disappears, then exits hard —
    # jax.distributed teardown would block on the dead peer.  Ten
    # seconds: on a loaded host replica 1 has been seen more than one
    # second behind, and then dies of the lost leader, not of its fault
    print("RESULT " + json.dumps(
        {"served": sorted(served), "replica": pid}
    ), flush=True)
    time.sleep(10.0)
    os._exit(0)


def scenario_serving_churn_phase2(pid, nproc, scratch):
    """Run B (the elastic completion): the surviving world re-forms at
    replica count 1 via ``serve_elastic`` — the pending partition
    re-derives over ONE replica, so the dead replica's share migrates —
    and every journaled request completes with outputs BIT-IDENTICAL
    to a no-fault run (greedy decode is deterministic in the request,
    not in the replica that runs it: pinned here by comparing every
    result against a fresh in-process oracle engine)."""
    from chainermn_tpu.serving.replica import RequestJournal, serve_elastic

    assert nproc == 1
    model, params, stream = _serving_fixture()
    journal = RequestJournal(os.path.join(scratch, "serve_journal"))
    pending_before = len(journal.pending())
    assert pending_before > 0, (
        "phase 1's kill should have left unserved requests"
    )

    def build(comm):
        from chainermn_tpu.serving.replica import DecodeReplica

        return DecodeReplica(
            _serving_engine(model, params), journal,
            replica_index=0, n_replicas=1,
        )

    replica = serve_elastic(
        build, os.path.join(scratch, "serve_journal"),
        communicator_name="tpu", replica_index=0, n_replicas=1,
    )
    assert len(journal.pending()) == 0
    results = journal.results()
    assert sorted(results) == sorted(i for i, _p, _m in stream)
    # the no-fault oracle: every request decoded directly
    oracle_eng = _serving_engine(model, params)
    mismatches = []
    for rid, prompt, max_new in stream:
        want = oracle_eng.generate(prompt, max_new)
        if results[rid]["tokens"] != want:
            mismatches.append(rid)
    assert not mismatches, mismatches
    ev = replica.batcher.engine  # engine served at least the migrated share
    return {
        "pending_before": pending_before,
        "completed": len(results),
        "bit_identical": True,
        "survivor_steps": int(ev.steps),
    }


def scenario_telemetry(pid, nproc, scratch):
    """ISSUE 10 satellite: runtime telemetry in a REAL 2-process world
    (faults via CHAINERMN_TPU_FAULTS set by the spawning test):

    (a) an injected obj-store timeout on the FIRST exchange is absorbed
        by the lockstep retry — and both the fault and its retry land
        in the exported timeline, in order;
    (b) a delay fault at ``trainer.update`` TARGETED at process 1 makes
        it the straggler: the cross-rank ``MetricsReport`` (allgathered
        phase summaries) flags process 1 on BOTH ranks;
    (c) a process-local eager bucketed allreduce_grad contributes
        per-bucket ``collective.psum`` spans to the same stream;
    (d) the merged Chrome-trace/JSONL export validates: step spans,
        bucket collective spans, and resilience events in one
        time-ordered timeline.
    """
    import json as _json

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.optimizers import build_train_step
    from chainermn_tpu.training.trainer import Trainer, Updater
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.resilience.log import (
        ResilienceLog, attach, detach,
    )

    tel = obs.Telemetry(label=f"proc{pid}")
    obs.install(tel)
    slog = ResilienceLog()  # catches emits outside trainer.run
    attach(slog)
    try:
        comm = _comm()

        # (a) the env spec fires a timeout on the FIRST
        # obj_store.exchange of every process; the lockstep retry
        # absorbs it and records fault_injected + retry on the sink
        got = comm.allgather_obj(pid)
        assert got == list(range(nproc)), got
        assert slog.counts.get("fault_injected", 0) >= 1, slog.counts
        assert slog.counts.get("retry", 0) >= 1, slog.counts

        # (b) trainer with a targeted slow rank.  The delay fault at
        # trainer.update fires only on process 1 (FaultSpec(process=1)),
        # so its per-step host time dominates; MetricsReport allgathers
        # the window summaries and every rank computes the same flags.
        lr = 0.1

        def loss_fn(params, batch):
            return 0.5 * jnp.sum(
                (params["w"] - batch.mean(axis=0)) ** 2
            )

        opt = cmn.create_multi_node_optimizer(optax.sgd(lr), comm)
        step = build_train_step(comm, loss_fn, opt, donate=False)
        params, opt_state = step.place(
            {"w": jnp.zeros((4,))}, opt.init({"w": jnp.zeros((4,))})
        )
        n_local = comm.size // comm.process_count
        rows = np.stack([
            np.full((4,), float(pid * n_local + i), np.float32)
            for i in range(n_local)
        ])
        it = SerialIterator([rows[i] for i in range(n_local)], n_local,
                            shuffle=False)
        trainer = Trainer(Updater(it, step, params, opt_state),
                          stop_trigger=(6, "iteration"))
        rep = obs.MetricsReport(comm, trigger=(3, "iteration"),
                                filename=None)
        trainer.extend(rep)
        trainer.run()
        assert trainer.iteration == 6
        # the LAST window (iterations 4-6) is past both ranks' compile
        # cost: the targeted delay dominates process 1's step mean
        assert rep.straggler_processes == [1], (
            rep.straggler_processes, rep.last_report,
        )

        # (c) process-local eager wire: real multi-device bucket psums
        # within this process's 2 local CPU devices
        local_comm = cmn.create_communicator(
            "tpu", devices=jax.local_devices()
        )
        # two 3 MB leaves: each exceeds what the 4 MiB open bucket
        # could absorb alongside the other -> a 2-bucket plan
        grads = {
            "a": jnp.ones((local_comm.size, 750_000), jnp.float32),
            "b": jnp.ones((local_comm.size, 750_000), jnp.float32),
        }
        local_comm.allreduce_grad(grads)
        psums = tel.timeline.spans("collective.psum")
        assert len(psums) >= 2, len(psums)

        # (d) merge + export + validate
        tel.timeline.merge_resilience(slog)
        tel.timeline.merge_resilience(trainer.resilience_log)  # dedup
        chrome = os.path.join(scratch, f"trace_p{pid}.json")
        jsonl = os.path.join(scratch, f"trace_p{pid}.jsonl")
        tel.timeline.to_chrome_trace(chrome)
        tel.timeline.to_jsonl(jsonl)

        doc = _json.load(open(chrome))
        assert isinstance(doc["traceEvents"], list)
        for e in doc["traceEvents"]:
            assert e["ph"] in ("M", "X", "i"), e
            assert "name" in e and "pid" in e
            if e["ph"] == "X":
                assert e["dur"] >= 0
        rows_out = [_json.loads(l) for l in open(jsonl)]
        ts = [r["t"] for r in rows_out]
        assert ts == sorted(ts), "jsonl not time-ordered"
        names = [r["name"] for r in rows_out]
        assert "step" in names
        assert "collective.psum" in names
        fault_i = names.index("resilience.fault_injected")
        retry_i = names.index("resilience.retry")
        straggler_i = names.index("resilience.straggler")
        assert fault_i < retry_i < straggler_i, (
            fault_i, retry_i, straggler_i,
        )
        # the straggler event names the slow process on every rank
        strag = rows_out[straggler_i]
        assert strag["args"]["process"] == 1, strag
        return {
            "stragglers": rep.straggler_processes,
            "n_events": len(rows_out),
            "n_bucket_psums": len(psums),
            "faults": slog.counts.get("fault_injected", 0),
        }
    finally:
        detach(slog)
        obs.install(None)


def scenario_except_hook(pid, nproc, scratch):
    """Failure containment: process 1 raises; its global except hook
    shuts the distributed client down; process 0, blocked in a KV recv,
    errors out instead of hanging.  BOTH exit non-zero by design."""
    import chainermn_tpu as cmn

    cmn.global_except_hook.add_hook()
    comm = _comm()
    comm.barrier()
    if pid == 1:
        raise RuntimeError("injected failure on process 1")
    # blocks until the (dead) peer's message or the bounded timeout
    # (CHAINERMN_TPU_OBJ_TIMEOUT_MS, set small by the spawning test)
    comm.recv_obj(source=1, tag=99)
    return {}


if __name__ == "__main__":
    main()
