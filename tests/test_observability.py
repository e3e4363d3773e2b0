"""Runtime telemetry tests (ISSUE 10).

Load-bearing pins, in order:

* the DISABLED-path overhead contract: with no telemetry active, the
  full per-step span-site cost is <= 1 % of a compiled MLP step on the
  8-device CPU mesh (the instrumentation is permanently in the hot
  path — the contract is what makes that acceptable);
* a 3-step CPU-mesh trainer run exports a Chrome trace whose JSON
  shape is valid (the tier-1 smoke of the satellite checklist);
* ``observability.attribute`` joins the ResNet-50 step's 5 all-reduce
  records (4 bucket psums + the loss pmean) to measured collective
  spans BYTE-EXACTLY, with achieved-bandwidth figures (the acceptance
  criterion);
* ``ResilienceEvent`` now carries monotonic + wall time and the
  process index, ``emit`` shares ONE event object across sinks, and
  ``Timeline.merge_resilience`` is idempotent across logs (the
  satellite fix that makes the merged stream deterministic);
* ``time_steps`` returns its raw paired-difference samples and
  ``Histogram.protocol_fields`` defers to the one shared min-of-N
  helper.
"""

import glob
import itertools
import json
import os
import re
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu import observability as obs
from chainermn_tpu.iterators import prefetch_to_device
from chainermn_tpu.observability import timeline as tl_mod
from chainermn_tpu.resilience.log import (
    ResilienceLog,
    attach,
    detach,
    emit,
)
from chainermn_tpu.training.trainer import Trainer, Updater
from chainermn_tpu.utils.benchmarking import protocol_fields, time_steps


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


@pytest.fixture(autouse=True)
def _no_leaked_telemetry():
    """Every test must leave the process-global telemetry disabled."""
    yield
    assert obs.active() is None, "test leaked an installed Telemetry"
    obs.install(None)


def _mlp_batch(rows=16):
    x = np.random.RandomState(0).rand(rows, 28, 28).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, (rows,)).astype(np.int32)
    return x, y


def _mlp_step(comm, n_units=50):
    """A fresh step object over an MLP, with its placed state."""
    from chainermn_tpu.models import MLP

    model = MLP(n_units=n_units)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))

    def loss_fn(p, b):
        x, y = b
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, x), y
        ).mean()

    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    p, o = step.place(params, opt.init(params))
    return step, p, o


def _mlp_trainer(comm, n_units=50, stop=(3, "iteration")):
    step, p, o = _mlp_step(comm, n_units)
    it = itertools.cycle([_mlp_batch()])
    return Trainer(Updater(it, step, p, o), stop_trigger=stop)


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
class TestMetrics:
    def test_histogram_and_snapshot(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.count == 4
        assert h.mean == 2.5
        assert h.percentile(50) == 2.5
        assert h.max == 4.0
        snap = reg.snapshot()
        assert snap["histograms"]["h"]["count"] == 4

    def test_get_or_create_is_stable(self):
        reg = obs.MetricsRegistry()
        assert reg.histogram("x") is reg.histogram("x")
        assert not reg.has_histogram("y")

    def test_histogram_protocol_fields_share_the_bench_helper(self):
        """One source for spread: Histogram.protocol_fields ==
        utils.benchmarking.protocol_fields on the same samples."""
        h = obs.Histogram("t")
        samples = [0.01, 0.012, 0.011, -0.001]
        h.extend(samples)
        assert h.protocol_fields() == protocol_fields(samples)
        assert h.spread_max_over_min == pytest.approx(0.012 / 0.01)

    def test_histogram_spread_absent_below_two_positive(self):
        h = obs.Histogram("t")
        h.observe(0.01)
        assert h.protocol_fields() == {"n_measurements": 1}
        assert h.spread_max_over_min is None


# ----------------------------------------------------------------------
# timeline + activation
# ----------------------------------------------------------------------
class TestTimeline:
    def test_disabled_span_is_null(self):
        assert obs.active() is None
        cm = obs.span("anything", x=1)
        assert cm is obs.NULL_SPAN
        with cm as sp:
            sp.set(y=2)  # no-op, must not raise

    def test_nesting_records_parent_ids(self):
        with obs.observe() as tel:
            with obs.span("outer"):
                with obs.span("inner"):
                    pass
        spans = {s["name"]: s for s in tel.timeline.spans()}
        assert spans["inner"]["parent"] == spans["outer"]["sid"]
        assert spans["outer"]["parent"] == 0

    def test_observe_nesting_restores_previous(self):
        with obs.observe() as a:
            assert obs.active() is a
            with obs.observe() as b:
                assert obs.active() is b
            assert obs.active() is a
        assert obs.active() is None

    def test_span_durations_feed_histograms(self):
        with obs.observe() as tel:
            with obs.span("phase"):
                pass
            with obs.span("phase"):
                pass
        h = tel.registry.histogram("phase")
        assert h.count == 2
        assert all(v >= 0 for v in h.values)

    def test_set_attaches_args_mid_span(self):
        with obs.observe() as tel:
            with obs.span("s") as sp:
                sp.set(bytes=42)
        assert tel.timeline.spans("s")[0]["args"]["bytes"] == 42

    def test_events_sorted_by_time(self):
        with obs.observe() as tel:
            tel.timeline.instant("late", t=tel.timeline.t0 + 100.0)
            tel.timeline.instant("early", t=tel.timeline.t0 + 1.0)
        names = [e["name"] for e in tel.timeline.events()]
        assert names == ["early", "late"]

    def test_env_activation(self, monkeypatch):
        monkeypatch.setenv(tl_mod.ENV_TELEMETRY, "1")
        tl_mod._from_env()
        try:
            assert obs.active() is not None
        finally:
            obs.install(None)
        monkeypatch.setenv(tl_mod.ENV_TELEMETRY, "0")
        tl_mod._from_env()  # "0" must NOT activate
        assert obs.active() is None

    def test_chrome_trace_shape(self, tmp_path):
        with obs.observe() as tel:
            with obs.span("s", bucket=1):
                pass
            obs.instant("mark")
        path = tel.timeline.to_chrome_trace(
            str(tmp_path / "trace.json")
        )
        doc = json.loads(open(path).read())
        assert isinstance(doc["traceEvents"], list)
        phs = [e["ph"] for e in doc["traceEvents"]]
        assert "M" in phs and "X" in phs and "i" in phs
        for e in doc["traceEvents"]:
            assert "name" in e and "pid" in e and "tid" in e
            if e["ph"] == "X":
                assert e["dur"] >= 0 and isinstance(e["ts"], float)

    def test_jsonl_export(self, tmp_path):
        with obs.observe() as tel:
            with obs.span("s"):
                pass
        path = tel.timeline.to_jsonl(str(tmp_path / "t.jsonl"))
        rows = [json.loads(l) for l in open(path)]
        assert rows and rows[0]["type"] == "span"
        assert rows[0]["name"] == "s" and rows[0]["dur"] >= 0


class TestResilienceMerge:
    def test_event_carries_both_clocks_and_process(self):
        log = ResilienceLog()
        before = time.monotonic()
        ev = log.record("fault_injected", "site", fault="timeout")
        assert before <= ev.monotonic <= time.monotonic()
        assert ev.time > 0  # wall clock
        assert ev.process == 0
        # the query surface is unchanged
        assert log.counts == {"fault_injected": 1}

    def test_emit_shares_one_event_object_across_sinks(self):
        a, b = ResilienceLog(), ResilienceLog()
        attach(a)
        attach(b)
        try:
            emit("retry", "s", attempt=1)
        finally:
            detach(a)
            detach(b)
        assert len(a) == len(b) == 1
        assert a.events()[0] is b.events()[0]

    def test_merge_positions_and_idempotence(self):
        a, b = ResilienceLog(), ResilienceLog()
        attach(a)
        attach(b)
        try:
            emit("fault_injected", "obj_store.recv", fault="timeout")
            emit("retry", "obj_store.recv", attempt=1)
        finally:
            detach(a)
            detach(b)
        with obs.observe() as tel:
            assert tel.timeline.merge_resilience(a) == 2
            # same event OBJECTS via the other sink: deduped
            assert tel.timeline.merge_resilience(b) == 0
            assert tel.timeline.merge_resilience(a) == 0
        evs = tel.timeline.events()
        assert [e["name"] for e in evs] == [
            "resilience.fault_injected", "resilience.retry",
        ]
        assert evs[0]["t"] <= evs[1]["t"]
        assert evs[0]["args"]["site"] == "obj_store.recv"

    def test_merge_survives_garbage_collected_prior_log(self):
        """Review regression: the merge dedupe must HOLD the merged
        event objects — a bare id() set lets a freed log's event
        addresses recycle into later logs' events, which then silently
        vanish from the export."""
        import gc

        with obs.observe() as tel:
            log_a = ResilienceLog()
            for i in range(5):
                log_a.record("fault_injected", f"a{i}")
            assert tel.timeline.merge_resilience(log_a) == 5
            del log_a
            gc.collect()
            log_b = ResilienceLog()
            for i in range(5):
                log_b.record("retry", f"b{i}")
            assert tel.timeline.merge_resilience(log_b) == 5

    def test_own_telemetry_uninstalled_when_run_raises(self, comm):
        """Review regression: extension finalize runs on error exits
        too — a MetricsReport that installed its own process-global
        telemetry must not leak it past a failed run."""
        from chainermn_tpu.resilience import FaultSpec, inject_faults
        from chainermn_tpu.resilience.errors import (
            RestartBudgetExceededError,
            TransientCommError,
        )

        trainer = _mlp_trainer(comm)
        trainer.extend(obs.MetricsReport(
            comm, trigger=(1, "iteration"), filename=None
        ))
        assert obs.active() is None
        with inject_faults([
            FaultSpec("trainer.update", "timeout", at=[1, 2, 3, 4, 5]),
        ]):
            with pytest.raises(
                (RestartBudgetExceededError, TransientCommError)
            ):
                trainer.run(max_restarts=1)
        assert obs.active() is None

    def test_trainer_run_auto_merges_into_active_timeline(self, comm):
        from chainermn_tpu.resilience import FaultSpec, inject_faults

        trainer = _mlp_trainer(comm)
        with obs.observe() as tel:
            with inject_faults([
                FaultSpec("trainer.update", "timeout", at=[2]),
            ]):
                trainer.run(max_restarts=1)
        names = [e["name"] for e in tel.timeline.events()]
        assert "resilience.fault_injected" in names
        assert "resilience.restart" in names
        # and the instants sit inside the span stream, time-ordered
        ts = [e["t"] for e in tel.timeline.events()]
        assert ts == sorted(ts)


# ----------------------------------------------------------------------
# instrumented trainer (the tier-1 chrome-trace smoke)
# ----------------------------------------------------------------------
class TestTrainerInstrumentation:
    def test_three_step_run_exports_valid_chrome_trace(
        self, comm, tmp_path
    ):
        trainer = _mlp_trainer(comm)
        with obs.observe() as tel:
            trainer.run()
        assert trainer.iteration == 3
        for name in ("step", "update", "data.wait", "compute.dispatch"):
            assert len(tel.timeline.spans(name)) == 3, name
            assert tel.registry.histogram(name).count == 3
        # step nests update nests data.wait/compute.dispatch
        spans = tel.timeline.spans()
        by_id = {s["sid"]: s for s in spans}
        for s in spans:
            if s["name"] == "data.wait":
                assert by_id[s["parent"]]["name"] == "update"
            if s["name"] == "update":
                assert by_id[s["parent"]]["name"] == "step"
        path = tel.timeline.to_chrome_trace(
            str(tmp_path / "train.json")
        )
        doc = json.loads(open(path).read())
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) >= 12  # 4 span kinds x 3 steps
        assert all(e["dur"] >= 0 for e in xs)
        assert any(e["name"] == "step" for e in xs)

    def test_disabled_run_records_nothing_and_matches_numerics(
        self, comm
    ):
        t1 = _mlp_trainer(comm)
        t1.run()
        with obs.observe() as tel:
            t2 = _mlp_trainer(comm)
            t2.run()
        np.testing.assert_array_equal(
            np.asarray(jax.tree_util.tree_leaves(t1.updater.params)[0]),
            np.asarray(jax.tree_util.tree_leaves(t2.updater.params)[0]),
        )
        assert len(tel.timeline) > 0

    def test_disabled_overhead_at_most_one_percent_of_step(self, comm):
        """The overhead contract pinned: per-step disabled-path span
        cost (every span site the step taxonomy hits, with headroom)
        must be <= 1 % of a compiled MLP step on the 8-device mesh."""
        assert obs.active() is None
        n = 20000
        t0 = time.monotonic()
        for _ in range(n):
            with obs.span("x"):
                pass
        per_span = (time.monotonic() - t0) / n

        trainer = _mlp_trainer(comm, stop=(12, "iteration"))
        trainer.run()  # warm compile + a few iterations
        upd = trainer.updater
        before = obs.process_record()
        t0 = time.monotonic()
        for _ in range(10):
            upd.update()
        jax.block_until_ready(upd.last_metrics["loss"])
        step_s = (time.monotonic() - t0) / 10
        # the always-on process record is once a process, never a step:
        # ten cached steps add no span and reach no JAX listener
        after = obs.process_record()
        assert len(after["spans"]) == len(before["spans"])
        assert after["counters"] == before["counters"]

        spans_per_step = 8  # 4 taxonomy sites + generous headroom
        assert spans_per_step * per_span <= 0.01 * step_s, (
            f"disabled span cost {per_span * 1e6:.2f}us x "
            f"{spans_per_step} vs step {step_s * 1e3:.2f}ms"
        )


# ----------------------------------------------------------------------
# eager wire spans + attribution
# ----------------------------------------------------------------------
class TestWireSpans:
    def test_eager_bucket_psums_recorded_with_bytes(self, comm):
        from chainermn_tpu.comm_wire import make_plan

        grads = {
            "a": jnp.ones((comm.size, 2_000_000), jnp.float32),
            "b": jnp.ones((comm.size, 64), jnp.float32),
        }
        plan = make_plan([grads["a"][0], grads["b"][0]])
        assert plan.n_buckets >= 2
        with obs.observe() as tel:
            out = comm.allreduce_grad(grads)
        psums = tel.timeline.spans("collective.psum")
        assert len(psums) == plan.n_buckets
        for k, sp in enumerate(sorted(
            psums, key=lambda s: s["args"]["bucket"]
        )):
            b = plan.buckets[k]
            assert sp["args"]["bytes"] == b.size * np.dtype(
                b.dtype
            ).itemsize
        assert len(tel.timeline.spans("wire.ship")) == plan.n_buckets
        assert len(tel.timeline.spans("wire.pack")) == 1
        # telemetry must not change the numbers
        base = comm.allreduce_grad(grads)
        np.testing.assert_array_equal(
            np.asarray(out["a"]), np.asarray(base["a"])
        )

    def test_measured_issue_report_delays_nonnegative(self, comm):
        grads = {"a": jnp.ones((comm.size, 2_000_000), jnp.float32)}
        with obs.observe() as tel:
            comm.allreduce_grad(grads)
        groups = obs.measured_issue_report(tel)
        assert len(groups) == 1
        for issue in groups[0]:
            assert issue.delay_s >= 0
            assert issue.duration_s > 0
            assert issue.bucket >= 0

    def test_host_staged_tier_records_reduce_and_ship(self, devices8):
        nca = cmn.create_communicator(
            "non_cuda_aware", devices=devices8
        )
        grads = {"w": jnp.ones((nca.size, 50_000), jnp.float32)}
        with obs.observe() as tel:
            out = nca.allreduce_grad(grads)
        assert len(tel.timeline.spans("wire.reduce")) >= 1
        assert len(tel.timeline.spans("wire.ship")) >= 1
        r = tel.timeline.spans("wire.reduce")[0]
        assert r["args"]["bytes"] == 50_000 * 4
        base = nca.allreduce_grad(grads)
        np.testing.assert_array_equal(
            np.asarray(out["w"]), np.asarray(base["w"])
        )

    def test_obj_store_spans(self, comm):
        with obs.observe() as tel:
            comm.send_obj({"k": 1}, dest=1, tag=9)
            comm.recv_obj(source=0, tag=9, dest=1)
            comm.allgather_obj([1, 2])
        assert len(tel.timeline.spans("obj_store.send")) == 1
        assert len(tel.timeline.spans("obj_store.recv")) == 1
        assert len(tel.timeline.spans("obj_store.exchange")) == 1
        for s in tel.timeline.spans("obj_store.send"):
            assert s["args"]["bytes"] > 0

    def test_checkpoint_spans(self, comm, tmp_path):
        ckpt = cmn.create_multi_node_checkpointer(
            "obs", comm, path=str(tmp_path), use_orbax=False
        )
        state = {"a": np.arange(4, dtype=np.float32)}
        with obs.observe() as tel:
            ckpt.save(3, state)
            step, got = ckpt.resume()
        assert step == 3
        np.testing.assert_array_equal(got["a"], state["a"])
        assert len(tel.timeline.spans("checkpoint.save")) == 1
        assert len(tel.timeline.spans("checkpoint.resume")) == 1
        assert len(tel.timeline.spans("checkpoint.agreement")) == 1


class TestAttribution:
    def test_attribute_joins_resnet50_bucket_psums(self, comm):
        """The acceptance criterion: the ResNet-50 step's 5 all-reduce
        records (4 default-plan bucket psums + the loss pmean) join to
        measured collective spans byte-exactly, each priced with an
        achieved-bandwidth figure.  Static side: the compiled step's
        trace over eval_shape params (nothing runs).  Measured side:
        the eager bucketed wire on a 2-device sub-communicator (same
        shapes -> same deterministic plan -> same per-rank bucket
        bytes), plus one eager scalar mean for the pmean analogue."""
        from chainermn_tpu.comm_wire import plan_of_tree
        from chainermn_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, train=False)
        pshapes = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jnp.zeros((1, 32, 32, 3)),
        )
        plan = plan_of_tree(pshapes)

        def loss_fn(p, b):
            x, y = b
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x), y
            ).mean()

        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
        ostate = jax.eval_shape(opt.init, pshapes)
        batch = (
            jax.device_put(jnp.zeros((8, 32, 32, 3)),
                           step.batch_sharding),
            jax.device_put(jnp.zeros((8,), jnp.int32),
                           step.batch_sharding),
        )
        trace = step.collective_trace(pshapes, ostate, batch)
        assert trace.count("all_reduce") == plan.n_buckets + 1

        comm2 = cmn.create_communicator(
            "tpu", devices=jax.devices()[:2]
        )
        leaves, treedef = jax.tree_util.tree_flatten(pshapes)
        grads = jax.tree_util.tree_unflatten(treedef, [
            np.zeros((2,) + tuple(l.shape), l.dtype) for l in leaves
        ])
        with obs.observe() as tel:
            comm2.allreduce_grad(grads)
            comm2.allreduce(np.zeros((2,), np.float32), op="mean")
        report = obs.attribute(tel, trace)
        assert report.n_matched >= 5
        assert not report.unmatched_records
        assert not report.unmatched_spans
        assert all(a.byte_exact for a in report.matched)
        buckets = report.buckets()
        assert len(buckets) == plan.n_buckets
        for a in report.matched:
            assert a.bytes_on_wire and a.bytes_on_wire > 0
            assert a.achieved_bytes_per_sec is not None
            assert a.achieved_bytes_per_sec > 0
        assert report.total_achieved_bytes_per_sec() > 0

    def test_byteless_span_cannot_steal_a_byte_exact_record(self):
        """Review regression: byte-exact pairs are resolved for ALL
        spans before the order fallback — an earlier bytes-less span
        must not consume the record a later span matches exactly."""
        from chainermn_tpu.analysis import CollectiveRecord, CollectiveTrace

        def rec(payload):
            return CollectiveRecord(
                primitive="psum", cls="all_reduce", axes=("mn",),
                dtypes=("float32",), shapes=((payload // 4,),),
                context=(), axis_sizes=(2,), payload_bytes=payload,
                bytes_on_wire=payload,
            )

        trace = CollectiveTrace(records=(rec(400), rec(100)))
        with obs.observe() as tel:
            with obs.span("collective.allreduce", bytes=None):
                pass
            with obs.span("collective.psum", bucket=0, bytes=400):
                pass
        report = obs.attribute(tel, trace)
        by_name = {a.span_name: a for a in report.matched}
        psum = by_name["collective.psum"]
        assert psum.byte_exact and psum.record.payload_bytes == 400
        fallback = by_name["collective.allreduce"]
        assert not fallback.byte_exact
        assert fallback.record.payload_bytes == 100
        assert not report.unmatched_records

    def test_unmatched_sides_are_reported(self):
        """A span with no record of its class, and records no span
        measured, land in the report's unmatched lists — never
        silently dropped."""
        from chainermn_tpu.analysis import trace_collectives
        from chainermn_tpu.functions.collectives import pmean
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("mn",))

        def f(x):
            return pmean(x, "mn")

        body = jax.shard_map(
            f, mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("mn"),
            out_specs=jax.sharding.PartitionSpec("mn"),
            check_vma=False,
        )
        trace = trace_collectives(body, jnp.zeros((2, 4)))
        assert trace.count("all_reduce") >= 1
        with obs.observe() as tel:
            with obs.span("collective.alltoall", bytes=128):
                pass
        report = obs.attribute(tel, trace)
        assert report.n_matched == 0
        assert len(report.unmatched_spans) == 1
        assert len(report.unmatched_records) == len(trace.records)


# ----------------------------------------------------------------------
# MetricsReport
# ----------------------------------------------------------------------
class TestMetricsReport:
    def test_rows_and_jsonl_carry_the_min_of_n_protocol(
        self, comm, tmp_path
    ):
        trainer = _mlp_trainer(comm)
        rep = obs.MetricsReport(
            comm, trigger=(1, "iteration"), out=str(tmp_path),
            filename="metrics.jsonl",
        )
        trainer.extend(rep)
        with obs.observe():
            trainer.run()
        assert rep.last_report is not None
        rows = rep.last_report["rows"]
        phases = {r["phase"] for r in rows}
        assert "step" in phases and "update" in phases
        for r in rows:
            assert r["p50_ms"] > 0 and r["p99_ms"] >= r["p50_ms"]
            assert r["n_measurements"] >= 1
        # single-controller world: one process, nobody to straggle
        assert rep.last_report["stragglers"] == []
        # the JSONL rows are the report's, one phase a line
        lines = [json.loads(l)
                 for l in open(tmp_path / "metrics.jsonl")]
        assert all("phase" in l for l in lines)
        assert {l["phase"] for l in lines} >= {"step", "update"}
        assert all(l["p50_ms"] > 0 and l["n_measurements"] >= 1
                   for l in lines)

    def test_report_enables_own_telemetry_when_none_active(self, comm):
        trainer = _mlp_trainer(comm)
        rep = obs.MetricsReport(
            comm, trigger=(1, "iteration"), filename=None
        )
        trainer.extend(rep)
        assert obs.active() is None
        trainer.run()
        assert obs.active() is None  # finalize uninstalled it
        assert rep.last_report is not None
        assert rep.last_report["rows"]

    def test_straggler_flagged_from_synthetic_summaries(self):
        """The cross-rank rule in isolation: process 1's mean step time
        3x the median -> flagged, event emitted."""
        rep = obs.MetricsReport(comm=None, straggler_factor=1.5)
        by_proc = {
            0: {"process": 0, "phases": {"step": [0.01, 0.011]}},
            1: {"process": 1, "phases": {"step": [0.03, 0.032]}},
        }

        class _T:
            iteration = 7
            observation = {}

        sink = ResilienceLog()
        attach(sink)
        try:
            rep._flag_stragglers(by_proc, _T())
        finally:
            detach(sink)
        assert rep.straggler_processes == [1]
        evs = sink.events("straggler")
        assert len(evs) == 1
        assert evs[0].info["process"] == 1
        assert evs[0].info["ratio"] > 1.4

    def test_no_straggler_when_balanced(self):
        rep = obs.MetricsReport(comm=None)
        by_proc = {
            0: {"process": 0, "phases": {"step": [0.01]}},
            1: {"process": 1, "phases": {"step": [0.011]}},
        }

        class _T:
            iteration = 1
            observation = {}

        rep._flag_stragglers(by_proc, _T())
        assert rep.straggler_processes == []

    def test_lockstep_straggler_convicted_by_host_phase(self):
        """The real-world shape: lockstep SPMD equalizes wall-clock
        step time (the healthy rank blocks in the collective), so the
        convicting evidence is the rank-LOCAL update.host phase."""
        rep = obs.MetricsReport(comm=None)
        by_proc = {
            0: {"process": 0, "phases": {
                "step": [0.255], "update.host": [0.0001],
            }},
            1: {"process": 1, "phases": {
                "step": [0.262], "update.host": [0.250],
            }},
        }

        class _T:
            iteration = 6
            observation = {}

        sink = ResilienceLog()
        attach(sink)
        try:
            rep._flag_stragglers(by_proc, _T())
        finally:
            detach(sink)
        assert rep.straggler_processes == [1]
        ev = sink.events("straggler")[0]
        assert ev.info["phase"] == "update.host"

    def test_materiality_floor_ignores_bookkeeping_noise(self):
        """A 4x ratio on a 20-MICROsecond host phase is noise, not a
        straggler: below min_step_fraction of step time it cannot
        convict."""
        rep = obs.MetricsReport(comm=None)
        by_proc = {
            0: {"process": 0, "phases": {
                "step": [0.25], "update.host": [0.00002],
            }},
            1: {"process": 1, "phases": {
                "step": [0.25], "update.host": [0.00008],
            }},
        }

        class _T:
            iteration = 1
            observation = {}

        rep._flag_stragglers(by_proc, _T())
        assert rep.straggler_processes == []

    def test_windows_are_incremental(self, comm):
        """Each report summarizes only the NEW samples since the last
        one (a late straggler cannot be averaged away)."""
        rep = obs.MetricsReport(comm=None, phases=("p",))
        with obs.observe() as tel:
            tel.registry.histogram("p").extend([1.0, 2.0])
            s1 = rep._local_summary()
            tel.registry.histogram("p").observe(9.0)
            s2 = rep._local_summary()
        assert s1["phases"]["p"] == [1.0, 2.0]
        assert s2["phases"]["p"] == [9.0]

    def test_no_step_baseline_refuses_to_convict(self):
        """Review regression: without a recorded step phase the
        materiality floor is undefined — a non-step phase must then
        never convict (floor=0 would re-admit microsecond noise)."""
        rep = obs.MetricsReport(comm=None, phases=("data.wait",))
        by_proc = {
            0: {"process": 0, "phases": {"data.wait": [0.000015]}},
            1: {"process": 1, "phases": {"data.wait": [0.000030]}},
        }

        class _T:
            iteration = 1
            observation = {}

        rep._flag_stragglers(by_proc, _T())
        assert rep.straggler_processes == []

    def test_straggler_factor_validated(self):
        with pytest.raises(ValueError):
            obs.MetricsReport(straggler_factor=1.0)

    def test_failed_exchange_rolls_back_the_window(self):
        """Review regression: a retry-exhausted exchange must not
        consume the window's samples — the next report still covers
        the interval that contained the faults."""

        class _BadComm:
            process_index = 0
            process_count = 2

            def allgather_obj(self, obj):
                raise RuntimeError("exchange down")

        rep = obs.MetricsReport(_BadComm(), phases=("p",))

        class _T:
            iteration = 3
            observation = {}

        with obs.observe() as tel:
            tel.registry.histogram("p").extend([1.0, 2.0])
            with pytest.raises(RuntimeError):
                rep(_T())
            # the samples survived for the next report
            assert rep._local_summary()["phases"]["p"] == [1.0, 2.0]

    def test_finalize_isolated_per_extension(self, comm):
        """Review regression: one raising finalize must neither mask
        the others (later cleanups still run) nor vanish on a clean
        run (the first failure is re-raised)."""
        trainer = _mlp_trainer(comm)
        ran = []

        class _Boom:
            name = "boom"
            trigger = (1000, "iteration")

            def __call__(self, t):
                pass

            def finalize(self, t=None):
                ran.append("boom")
                raise RuntimeError("finalize failed")

        class _After:
            name = "after"
            trigger = (1000, "iteration")

            def __call__(self, t):
                pass

            def finalize(self, t=None):
                ran.append("after")

        trainer.extend(_Boom())
        trainer.extend(_After())
        with pytest.raises(RuntimeError, match="finalize failed"):
            trainer.run()
        assert ran == ["boom", "after"]  # later finalize still ran
        assert trainer.resilience_log.counts.get("finalize_error") == 1


# ----------------------------------------------------------------------
# time_steps satellite
# ----------------------------------------------------------------------
class TestTimeStepsSamples:
    def test_returns_samples_per_repeat(self):
        calls = []

        def run():
            calls.append(1)
            return np.zeros((1,))

        dt, samples = time_steps(run, steps=2, warmup=1, repeats=3)
        assert len(samples) == 3
        assert dt > 0 or dt == samples[-1]
        # protocol fields derive from the SAME samples
        pf = protocol_fields(samples)
        assert pf["n_measurements"] == 3

    def test_reported_dt_is_min_positive_sample(self):
        def run():
            return np.zeros((1,))

        dt, samples = time_steps(run, steps=1, warmup=1, repeats=4)
        pos = [s for s in samples if s > 0]
        if pos:
            assert dt == min(pos)


# ----------------------------------------------------------------------
# PR 27: spans in the profiler's trace, feed spans, device scopes,
# kernel names
# ----------------------------------------------------------------------
def _host_events(trace_dir):
    """``{event name: [stats dict, ...]}`` of the ``/host:CPU`` plane of
    the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.fixture(scope="module")
def profiled(comm, tmp_path_factory):
    """One CPU ``jax.profiler`` trace over: a plain active span, two
    direct ``Updater.update()`` calls, a two-iteration ``Trainer.run``
    — and the timeline that recorded the same spans."""
    trainer = _mlp_trainer(comm, stop=(2, "iteration"))
    trainer.updater.update()  # compile outside the trace
    step, p, o = _mlp_step(comm)
    step(p, o, _mlp_batch(16))  # its first program, outside the trace
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with obs.observe() as tel:
            with obs.span("feed.test", bytes=77):
                pass
            with obs.phase("setup.test_phase", rows=32):
                pass
            step(p, o, _mlp_batch(32))  # a second shape: recompiles
            trainer.updater.update()
            trainer.updater.update()
            trainer.run()
            jax.block_until_ready(trainer.updater.last_metrics["loss"])
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir), tel


class TestProfilerAnnotations:
    def test_active_span_is_a_trace_annotation_of_its_name(self, profiled):
        events, _ = profiled
        assert [int(s["bytes"]) for s in events["feed.test"]] == [77]

    @pytest.mark.parametrize("name", ["data.wait", "compute.dispatch"])
    def test_every_updater_span_is_in_the_trace(self, profiled, name):
        events, tel = profiled
        assert len(events[name]) == len(tel.timeline.spans(name)) == 4

    def test_update_is_a_step_annotation_with_step_num(self, profiled):
        events, _ = profiled
        direct = [s for s in events["train"] if s["span"] == "update"]
        # step_num is the Updater's own count; one update ran before
        assert [int(s["step_num"]) for s in direct] == [1, 2]

    def test_trainer_step_is_the_step_annotation_update_nests(self,
                                                              profiled):
        """Under ``Trainer.run`` the outermost step span (``step``) is
        the profiler's step; ``update`` inside it is a plain
        annotation, so that no step holds another."""
        events, _ = profiled
        steps = [s for s in events["train"] if s["span"] == "step"]
        assert [int(s["step_num"]) for s in steps] == [0, 1]
        assert len(events["update"]) == 2
        assert len(events["train"]) == 4

    def test_a_phase_under_a_profile_is_a_trace_annotation(self, profiled):
        events, _ = profiled
        assert [int(s["rows"]) for s in events["setup.test_phase"]] == [32]

    def test_a_recompile_leaves_its_annotation_on_the_host_plane(
            self, profiled):
        events, tel = profiled
        note, = events["step.recompile"]
        assert "_step" in note["fun_name"] and int(note["ordinal"]) == 2
        assert float(note["seconds"]) > 0
        here, = tel.timeline.events("step.recompile")
        assert here["args"]["ordinal"] == 2

    def test_span_events_carry_no_wall_field(self, profiled):
        _, tel = profiled
        spans = tel.timeline.spans()
        assert spans and all("wall" not in s for s in spans)
        assert tel.timeline.wall0 > 0  # the anchor stays

    def test_disabled_span_enters_no_annotation(self, comm, monkeypatch):
        assert obs.active() is None
        assert obs.span("update", step_num=3) is tl_mod.NULL_SPAN
        # and a cached step through Updater.update and the step object
        # enters no span, makes no annotation and reads no clock in
        # the telemetry's code
        upd = _mlp_trainer(comm).updater
        upd.update()  # first call: compiles
        upd.update()
        seen = []

        class _Clock:
            def __getattr__(self, name):
                seen.append("time." + name)
                return getattr(time, name)

        monkeypatch.setattr(tl_mod, "time", _Clock())
        monkeypatch.setattr(
            jax.profiler, "TraceAnnotation",
            lambda *a, **k: seen.append("annotation"))
        monkeypatch.setattr(
            tl_mod.Timeline, "span",
            lambda *a, **k: seen.append("span"))
        for _ in range(3):
            upd.update()
        jax.block_until_ready(upd.last_metrics["loss"])
        assert seen == []


_CHILD = r"""
import json, time
t_script = time.time()
import chainermn_tpu
from chainermn_tpu import observability as obs
rec = obs.process_record()
to_wall = time.time() - time.monotonic()
print(json.dumps({
    "origin": rec["origin"], "t_script": t_script,
    "start_wall": rec["start"] + to_wall,
    "spans": [[e["name"], e["t"] - rec["start"], e["dur"], e["sid"],
               e["parent"]] for e in rec["spans"]]}))
"""


class TestProcessRecord:
    def test_zero_is_the_os_process_start_and_import_follows_it(self):
        """In a fresh process: the record's zero lies between the
        parent's clock before the spawn and the child's first line,
        ``setup.before_program`` runs from it to where ``setup.import``
        begins, and both are children of the root ``setup``."""
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t_before = time.time()
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, check=True,
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(cmn.__file__))))
        got = json.loads(out.stdout.strip().splitlines()[-1])
        assert got["origin"] == "os"
        tick = 1.0 / os.sysconf("SC_CLK_TCK")
        assert t_before - 2 * tick <= got["start_wall"] \
            <= got["t_script"] + 2 * tick
        root, before, imp = got["spans"][:3]
        assert root[0] == "setup" and root[1] == 0.0 and root[4] == 0
        assert before[0] == "setup.before_program" and before[1] == 0.0
        assert imp[0] == "setup.import"
        assert before[1] + before[2] == pytest.approx(imp[1], abs=1e-9)
        assert before[4] == imp[4] == root[3]
        # the root ends where the last phase under it ended
        assert root[2] == pytest.approx(imp[1] + imp[2], abs=1e-9)

    def test_phases_nest_under_setup_and_do_not_overlap(self, comm):
        _mlp_step(comm)  # optimizer, build_step, place_state at least
        with obs.phase("setup.outer", k=1):
            with obs.phase("setup.inner"):
                pass
        rec = obs.process_record()
        root = rec["spans"][0]
        assert root["name"] == "setup" and root["t"] == rec["start"]
        by_id = {e["sid"]: e for e in rec["spans"]}
        phases = [e for e in rec["spans"][1:]
                  if e["name"].startswith(("setup.", "step."))]
        names = {e["name"] for e in phases}
        assert {"setup.before_program", "setup.import",
                "setup.communicator", "setup.optimizer",
                "setup.build_step", "setup.place_state",
                "setup.outer", "setup.inner"} <= names
        inner = [e for e in phases if e["name"] == "setup.inner"][-1]
        assert by_id[inner["parent"]]["name"] == "setup.outer"
        assert by_id[inner["parent"]]["args"] == {"k": 1}
        for e in phases:  # every phase lies inside its parent
            parent = by_id[e["parent"]]
            assert parent["t"] <= e["t"]
            if not e["args"].get("recompile"):
                assert e["t"] + e["dur"] <= \
                    parent["t"] + parent["dur"] + 1e-9
        # and the phases of one parent on one thread follow each other
        main = threading.main_thread().ident
        kids = sorted((e for e in phases if e["parent"] == root["sid"]
                       and e["ident"] == main), key=lambda e: e["t"])
        for a, b in zip(kids, kids[1:]):
            assert a["t"] + a["dur"] <= b["t"] + 1e-9, (a, b)

    def test_a_jit_inside_a_phase_is_its_children_and_is_counted(self):
        def fresh_program_of_this_test(x):
            return jnp.cos(x) * 3

        x = jnp.ones((5,))
        before = obs.process_record()
        with obs.phase("setup.test_jit"):
            jax.block_until_ready(jax.jit(fresh_program_of_this_test)(x))
        rec = obs.process_record()
        phase = [e for e in rec["spans"]
                 if e["name"] == "setup.test_jit"][-1]
        kids = [e for e in rec["spans"] if e["parent"] == phase["sid"]]
        assert [e["name"] for e in kids] == ["jax.trace", "jax.lower",
                                             "jax.compile"]
        for e in kids:
            assert "fresh_program_of_this_test" in e["args"]["fun_name"]
            assert phase["t"] <= e["t"] and e["t"] + e["dur"] \
                <= phase["t"] + phase["dur"] + 1e-9
        assert kids[2]["args"]["cache"] in ("hit", "miss", "uncached")
        assert rec["counters"]["compile.programs"] \
            == before["counters"]["compile.programs"] + 1
        assert rec["by_phase"]["setup.test_jit"]["compile.programs"] \
            == before["by_phase"].get("setup.test_jit", {}).get(
                "compile.programs", 0) + 1
        # a program compiled outside every phase is the root's child
        # and does not move the root's end: phases alone do
        jax.block_until_ready(jax.jit(lambda x: x * 7 - 1)(x))
        after = obs.process_record()
        loose = after["spans"][-1]
        assert loose["name"] == "jax.compile" and loose["parent"] == -1
        assert after["spans"][0]["dur"] == rec["spans"][0]["dur"]

    def test_a_second_shape_after_a_cached_call_is_one_recompile(
            self, comm):
        step, p, o = _mlp_step(comm)
        before = obs.process_record()
        p, o, _ = step(p, o, _mlp_batch(16))   # call 1: first program
        p, o, _ = step(p, o, _mlp_batch(16))   # call 2: cached
        with obs.observe() as tel:
            p, o, _ = step(p, o, _mlp_batch(32))  # call 3: recompiles
            p, o, _ = step(p, o, _mlp_batch(32))  # call 4: cached
        rec = obs.process_record()
        new = rec["recompiles"][len(before["recompiles"]):]
        assert len(new) == 1
        note = new[0]["args"]
        assert note["ordinal"] == 3 and "_step" in note["fun_name"]
        assert note["seconds"] > 0
        assert note["cache"] in ("hit", "miss", "uncached")
        shown, = tel.timeline.events("step.recompile")
        assert shown["args"] == note and shown["t"] == new[0]["t"]
        calls = [e for e in rec["spans"] if e["name"] == "step.first_call"
                 ][-2:]
        assert [c["args"]["ordinal"] for c in calls] == [1, 3]
        assert "recompile" not in calls[0]["args"]
        assert calls[1]["args"]["recompile"] is True
        for c in calls:  # trace, lower, compile of the step under each
            kids = [e["name"] for e in rec["spans"]
                    if e["parent"] == c["sid"]]
            assert kids == ["jax.trace", "jax.lower", "jax.compile"]
        # a recompile is no part of set-up: the root does not reach it
        root = rec["spans"][0]
        assert root["t"] + root["dur"] <= calls[1]["t"]

    def test_a_step_traced_but_not_compiled_is_no_first_call(self, comm):
        step, p, o = _mlp_step(comm)
        before = len(obs.process_record()["spans"])
        batch = step.place_batch(_mlp_batch(16))
        step.collective_trace(p, o, batch)  # a jaxpr walk only
        step.get_jitted(p, o).lower(p, o, batch)  # lowered, not compiled
        with obs.phase("setup.after_the_walk"):
            pass
        new = obs.process_record()["spans"][before:]
        names = [e["name"] for e in new]
        assert "step.first_call" not in names
        traced, = [e for e in new if e["name"] == "step.trace"]
        assert traced["args"] == {"fun_name": "_step", "ordinal": 0}
        # the walk's trace, the lowering's (which finds it cached), and
        # the lowering
        assert [e["name"] for e in new if e["parent"] == traced["sid"]] \
            == ["jax.trace", "jax.trace", "jax.lower"]

    def test_telemetry_installed_after_setup_shows_the_earlier_phases(
            self):
        with obs.phase("setup.earlier", k=2):
            pass
        with obs.observe() as tel:
            with obs.span("later"):
                pass
        rec = obs.process_record()
        mine = [e for e in rec["spans"] if e["name"] == "setup.earlier"][-1]
        doc = tel.timeline.chrome_trace()
        by_name = {}
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                by_name.setdefault(e["name"], []).append(e)
        assert by_name["setup"][0]["ts"] == 0.0  # the process's start
        assert by_name["setup.before_program"][0]["ts"] == 0.0
        shown = by_name["setup.earlier"][-1]
        assert shown["ts"] == pytest.approx(
            (mine["t"] - rec["start"]) * 1e6)
        assert shown["args"] == {"k": 2} and shown["cat"] == "process"
        later, = by_name["later"]
        assert later["ts"] >= shown["ts"] + shown["dur"]
        assert all(e["ts"] >= 0 for es in by_name.values() for e in es)
        # the timeline's own queries stay the telemetry's own spans
        assert [e["name"] for e in tel.timeline.events()] == ["later"]

    def test_inner_traces_are_a_count_on_their_stage(self):
        """JAX's events as the listeners get them: the trace of an inner
        jit inside a program's trace is no span of its own, a compile
        inside it (an eager operation at trace time) is."""
        rec = tl_mod.ProcessRecord(start=time.monotonic())
        trace = "/jax/core/compile/jaxpr_trace_duration"
        compile_ = "/jax/core/compile/backend_compile_duration"
        with rec.phase("setup.test"):
            rec._stage_starts(trace, 0.0, fun_name="outer")
            for _ in range(3):
                rec._stage_starts(trace, 0.0, fun_name="inner")
                rec._stage_ends(trace, 10.0, 10.25, fun_name="inner")
            rec._stage_starts(compile_, 0.0, fun_name="jit(iota)")
            rec._stage_ends(compile_, 11.0, 11.5, fun_name="jit(iota)")
            rec._stage_ends(trace, 10.0, 12.0, fun_name="outer")
        spans = rec.snapshot()["spans"]
        assert [e["name"] for e in spans] == [
            "setup", "jax.compile", "jax.trace", "setup.test"]
        _, inner_compile, outer, phase = spans
        assert outer["args"] == {"fun_name": "outer", "nested_traces": 3,
                                 "nested_s": pytest.approx(0.75)}
        assert outer["dur"] == pytest.approx(2.0)
        assert inner_compile["parent"] == outer["sid"]
        assert outer["parent"] == phase["sid"]
        assert rec.snapshot()["counters"]["compile.programs"] == 1

    def test_the_record_is_bounded(self):
        rec = tl_mod.ProcessRecord(start=time.monotonic())
        rec.MAX_SPANS = 4
        for _ in range(6):
            with rec.phase("setup.again"):
                pass
        snap = rec.snapshot()
        assert len(snap["spans"]) == 1 + 4 and snap["dropped"] == 2
        assert snap["origin"] == "given"


class TestWhatTheBlocksKeep:
    """``examples/lm/train_lm.py --remat-blocks`` says how far its plan
    engaged (``models.transformer.remat_plan``) on the process record's
    ``setup.build_step`` and in its set-up line."""

    _ARGV = ["--cpu-mesh", "--rmsnorm", "--gated-mlp", "--no-positions",
             "--n-kv-heads", "2", "--layer-types", "mamba,attention,mamba",
             "--ssm-heads", "4", "--ssm-head-dim", "8", "--ssm-state",
             "16", "--ssm-chunk", "16", "--d-model", "32", "--n-layers",
             "3", "--seq-len", "32", "--vocab", "64", "--steps", "2",
             "--report-every", "1", "--generate", "0"]

    @staticmethod
    def _example():
        import importlib.util

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples", "lm", "train_lm.py")
        spec = importlib.util.spec_from_file_location("train_lm", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    @staticmethod
    def _last_build_step():
        return [e for e in obs.process_record()["spans"]
                if e["name"] == "setup.build_step"][-1]["args"]

    def test_build_step_carries_the_plan_and_the_cached_step_no_clock(
            self, monkeypatch, capsys):
        import types

        from chainermn_tpu.models import transformer

        # a device that reports a limit, as a TPU does: 1 GiB over the
        # reserve is room for every result of these sizes
        budget = transformer.remat_budget
        monkeypatch.setattr(
            transformer, "remat_budget",
            lambda device, *a: budget(types.SimpleNamespace(
                memory_stats=lambda: {
                    "bytes_limit": 2 * transformer.REMAT_CLEAR_BYTES}), *a))
        out = self._example().main(self._ARGV + ["--remat-blocks"])
        # 2 rows of 32 tokens a device: mlp_in 2 x 128 wide in three
        # layers, ssm_in 2 x 32 + 2 x 16 + 4 in the two mamba layers
        assert self._last_build_step() == {
            "remat.kept": "mlp_in x3, ssm_in x2",
            "remat.kept_bytes": 64 * 2 * (3 * 256 + 2 * 100)}
        assert "build_step: remat.kept mlp_in x3, ssm_in x2, " \
            "remat.kept_bytes 123904" in capsys.readouterr().out
        assert out["model"].options.remat_budget_bytes > 0
        # the step that keeps them, cached: no span, no annotation, no
        # clock read in the telemetry's code
        seen = []

        class _Clock:
            def __getattr__(self, name):
                seen.append("time." + name)
                return getattr(time, name)

        monkeypatch.setattr(tl_mod, "time", _Clock())
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda *a, **k: seen.append("annotation"))
        step, p, o = out["step"], out["params"], out["opt_state"]
        for _ in range(2):
            p, o, metrics = step(p, o, out["batch"])
        jax.block_until_ready(metrics["loss"])
        assert seen == []

    @pytest.mark.parametrize("flags", [[], ["--remat-blocks"]],
                             ids=["no_recomputation", "no_limit_reported"])
    def test_nothing_kept_says_nothing(self, flags):
        """Without ``--remat-blocks``, and with it on a device that
        reports no limit (the CPU: budget 0)."""
        out = self._example().main(self._ARGV + flags)
        assert self._last_build_step() == {}
        assert out["model"].options.remat_budget_bytes == 0

    def test_attributes_wait_for_the_phase_of_their_name(self):
        rec = tl_mod.ProcessRecord(start=time.monotonic())
        rec.phase_attributes("setup.b", k=1)
        rec.phase_attributes("setup.b", j="x")
        for name in ("setup.a", "setup.b", "setup.b"):
            with rec.phase(name, own=0):
                pass
        assert [e["args"] for e in rec.snapshot()["spans"][1:]] == [
            {"own": 0}, {"own": 0, "k": 1, "j": "x"}, {"own": 0}]


class TestSetupMetrics:
    """The benchmark's set-up metrics (``BENCHMARK.json``, all of them
    ``moves: setup_s``) read what the program records under the names it
    records it: each reader on this process's record, after a step's
    first call."""

    @pytest.mark.parametrize("name", [
        "setup_before_program_s", "setup_import_s", "setup_init_params_s",
        "setup_step_trace_s", "setup_step_load_s", "setup_programs",
        "setup_cache_misses"])
    def test_reader_finds_the_programs_phases(self, comm, name):
        import importlib
        import types

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            entry, = [m for m in json.load(f)["per_layer"]
                      if m["name"] == name]
        assert entry["moves"] == "setup_s"
        with open(os.path.join(root, "cellbench", "layer_metrics",
                               name + ".json")) as f:
            how = json.load(f)
        reader = importlib.import_module(
            "cellbench.readers." + how["reader"])
        with obs.phase("setup.init_params"):
            step, p, o = _mlp_step(comm)
        step(p, o, _mlp_batch())
        ctx = types.SimpleNamespace(
            spec=types.SimpleNamespace(rehearse=False))
        value = reader.read(ctx, **how["args"])
        if entry["unit"] == "count":
            # the CPU runs get no persistent cache: nothing is a miss
            assert value == 0 if "misses" in name else value >= 1
        else:
            assert value > 0
        ctx.spec.rehearse = True
        assert reader.read(ctx, **how["args"]) is None


def _host_batches(n):
    for i in range(n):
        yield np.full((4, 8), i, np.float32)


class TestFeedSpans:
    def test_off_no_thread_and_no_spans(self):
        before = set(threading.enumerate())
        it = prefetch_to_device(_host_batches(5), jax.device_put, 2)
        got = [np.asarray(b) for b in it]
        assert len(got) == 5
        assert set(threading.enumerate()) <= before

    @pytest.mark.parametrize("name,count", [
        ("feed.collate", 6),  # the sixth finds the iterator exhausted
        ("feed.place", 0),  # taken out: it timed the enqueue, read by none
        ("feed.h2d", 5)])
    def test_on_records_the_feed_spans(self, name, count):
        # built BEFORE telemetry is installed, as the runner does
        it = prefetch_to_device(_host_batches(5), jax.device_put, 2)
        with obs.observe() as tel:
            got = [np.asarray(b) for b in it]
            deadline = time.monotonic() + 5.0
            while len(tel.timeline.spans("feed.h2d")) < 5 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
        assert len(tel.timeline.spans(name)) == count
        if name == "feed.h2d":
            sp = tel.timeline.spans(name)
            assert {s["args"]["bytes"] for s in sp} == {4 * 8 * 4}
            main = tel.timeline.spans("feed.collate")[0]["tid"]
            assert all(s["tid"] != main for s in sp)  # off the main line
        np.testing.assert_array_equal(
            np.stack(got), np.stack(list(_host_batches(5))))

    def test_feed_spans_nest_under_data_wait(self, comm):
        trainer = _mlp_trainer(comm)
        up = trainer.updater
        up.iterator = prefetch_to_device(up.iterator,
                                         up.step_fn.place_batch, 2)
        with obs.observe() as tel:
            up.update()
        wait, = tel.timeline.spans("data.wait")
        assert {s["parent"] for s in tel.timeline.spans("feed.collate")} \
            == {wait["sid"]}

    def test_each_copy_has_its_own_observer_which_ends_with_it(self):
        """A span per batch that opens at the enqueue, whatever earlier
        copies are doing: one short-lived daemon thread a batch, none
        left once the copies are done, none started with telemetry
        off again."""
        def observers():
            return [t for t in threading.enumerate()
                    if t.name == "feed-h2d"]

        it = prefetch_to_device(_host_batches(8), jax.device_put, 2)
        with obs.observe() as tel:
            next(it)
            started = observers()
            assert all(t.daemon for t in started)
            for t in started:
                t.join(timeout=5.0)
            assert not observers()
            assert len(tel.timeline.spans("feed.h2d")) == 3
        next(it)
        assert not observers()

    def test_a_batch_deleted_before_it_was_ready_marks_its_span(self):
        """A donating step may delete the batch first: the span is then
        no copy's time and says so; nothing is raised on the thread."""
        from chainermn_tpu.iterators.device_prefetch import _await_copy

        gone = jax.device_put(np.zeros((4, 8), np.float32))
        gone.delete()
        with obs.observe() as tel:
            _await_copy(gone)
            _await_copy(jax.device_put(np.zeros((4, 8), np.float32)))
        aborted, ready = tel.timeline.spans("feed.h2d")
        assert aborted["args"]["aborted"].endswith("RuntimeError")
        assert "aborted" not in ready["args"]


@pytest.fixture(scope="module")
def lm_step_hlo(comm):
    """The compiled HLO text of a tiny LM step through
    ``create_multi_node_optimizer`` + ``build_train_step``."""
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss

    model = TransformerLM(vocab_size=64, d_model=32, n_heads=2,
                          n_layers=1, max_len=16)
    toks = jnp.zeros((comm.size, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    opt = cmn.create_multi_node_optimizer(optax.adamw(1e-3), comm)
    step = cmn.build_train_step(
        comm, lambda p, b: lm_loss(model.apply(p, b), b), opt,
        donate=False)
    p, o = step.place(params, opt.init(params))
    return step.get_jitted(p, o).lower(
        p, o, step.place_batch(toks)).compile().as_text()


class TestDeviceScopes:
    @pytest.mark.parametrize("scope", [
        "head_ce", "optimizer", "optimizer/grad_sync", "LayerNorm_0"])
    def test_compiled_lm_step_carries_the_scope(self, lm_step_hlo, scope):
        names = re.findall(r'op_name="([^"]*)"', lm_step_hlo)
        assert any(f"{scope}/" in n or f"({scope})" in n for n in names)

    def test_head_ce_scopes_forward_and_backward(self, lm_step_hlo):
        names = [n for n in re.findall(r'op_name="([^"]*)"', lm_step_hlo)
                 if "head_ce" in n]
        assert any("transpose(" in n for n in names)  # backward
        assert any("transpose(" not in n for n in names)  # forward

    @pytest.mark.parametrize("kernel", [
        "_flash_forward", "_flash_backward_dq", "_flash_backward_dkdv"])
    def test_flash_kernels_keep_their_names_for_the_chip(self, kernel):
        """Lowered for the TPU (no chip needed): each ``pallas_call`` is
        a ``tpu_custom_call`` under the name the trace readers match."""
        from chainermn_tpu.ops.pallas_attention import flash_attention

        assert re.match(r"_flash_(forward|backward)", kernel)
        q = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)

        def loss(q, k, v):
            out = flash_attention(q, k, v, True, None, None, None, False)
            return out.astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, q, q).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count(f'kernel_name = "{kernel}"') == 1
