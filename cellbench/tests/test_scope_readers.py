"""The readers PR 27 added (``scope_ms``, ``kernel_roofline_pct``,
``span_ms``) and their helper ``_xplane``: on
hand-made event lists, on a trace this process writes, and on a recorded
v5e trace kept with its ``op_name``s (``recorded_scoped_trace.json.gz``:
one step of the one-chip LM cell and three of the ResNet cell)."""

import glob
import gzip
import json
import os
import types

import pytest

from cellbench import flops, run
from cellbench.readers import _xplane, kernel_roofline_pct, scope_ms, \
    span_ms

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 10 ** 9  # picoseconds


def _use(monkeypatch, trace):
    monkeypatch.setattr(_xplane, "load", lambda ctx=None, path="": trace)


# -- scope_ms on hand-made events ------------------------------------------
STEP = "jit(_step)/shard_map/"
OPS = [
    # name, start, duration, op_name
    ("%fusion.1", 0, 4 * MS, STEP + "jvp(head_ce)/log"),
    ("%fusion.2", 4 * MS, 6 * MS,
     STEP + "transpose(jvp(TransformerLM))/head_ce/dot_general"),
    ("%fusion.3", 10 * MS, 5 * MS,
     STEP + "transpose(jvp(TransformerLM))/TransformerBlock_3/"
     "LayerNorm_0/mul"),
    ("%fusion.4", 15 * MS, 3 * MS, STEP + "optimizer/add"),
    ("%all-reduce.1", 18 * MS, 2 * MS,
     STEP + "optimizer/grad_sync/psum"),
    ("%all-reduce.2", 20 * MS, 1 * MS,
     STEP + "transpose(jvp(TransformerLM))/TransformerBlock_0/"
     "MlpBlock_0/Dense_0/psum"),
    ("%copy.7", 21 * MS, 1 * MS, None),
    ("%fusion.5", 22 * MS, 2 * MS,
     STEP + "jvp(TransformerLM)/TransformerBlock_0/MlpBlock_0/Dense_1/"
     "dot_general"),
]


def _trace(ops=OPS, steps=((0, 24 * MS), (24 * MS, 48 * MS)), host=None):
    return _xplane.Trace(list(ops), list(steps), host or {})


def test_scopes_of_strips_wrappers_serials_and_the_primitive():
    assert scope_ms.scopes_of(
        "jit(_step)/shard_map/transpose(jvp(TransformerLM))/"
        "TransformerBlock_3/LayerNorm_0/mul") \
        == ["TransformerLM", "TransformerBlock", "LayerNorm"]
    assert scope_ms.scopes_of("jit(_step)/jvp(head_ce)/log") == ["head_ce"]
    assert scope_ms.scopes_of("jit(_step)/mul") == []


def test_a_fusion_under_transpose_jvp_head_ce_counts(monkeypatch):
    _use(monkeypatch, _trace())
    # 4 + 6 ms over the two whole steps the trace is said to hold
    assert scope_ms.read(None, "head_ce") == pytest.approx(5.0)


def test_partition_sums_to_busy_and_keeps_unscoped_and_collectives():
    rows = scope_ms.partition(OPS)
    assert rows == {"head_ce": 10 * MS, "LayerNorm": 5 * MS,
                    "optimizer": 3 * MS, "grad_sync": 2 * MS,
                    "collective outside grad_sync": 1 * MS,
                    "unscoped": 1 * MS, "MlpBlock": 2 * MS}
    assert sum(rows.values()) == 24 * MS


def test_a_collective_is_known_by_its_operation_too():
    # autodiff names the tied embedding's all-reduce after the primitive
    name = ("%psum_invariant.3 = f32[50257,1536]{1,0:T(8,128)} "
            "all-reduce(f32[50257,1536]{1,0:T(8,128)} %fusion.9), "
            "channel_id=9")
    op = STEP + "transpose(jvp(TransformerLM))/head_ce/psum_invariant"
    assert scope_ms.row_of(name, op) == "collective outside grad_sync"
    assert scope_ms.row_of(
        "%fusion.9 = (f32[4,2048]{1,0:T(4,128)}, bf16[8]{0}) fusion(f32[8] "
        "%all-reduce.1)", op) == "head_ce"


def test_a_scope_s_time_leaves_out_the_collectives_under_it(monkeypatch):
    """The tied embedding's all-reduce carries ``head_ce`` on its
    ``op_name``: it is the exchange's time, in one cell of two."""
    reduce = ("%psum_invariant.3 = f32[50257,1536]{1,0:T(8,128)} "
              "all-reduce(f32[50257,1536]{1,0:T(8,128)} %fusion.9)",
              24 * MS, 5 * MS,
              STEP + "transpose(jvp(TransformerLM))/head_ce/psum_invariant")
    _use(monkeypatch, _trace(ops=OPS + [reduce],
                             steps=((0, 29 * MS), (29 * MS, 58 * MS))))
    assert scope_ms.read(None, "head_ce") == pytest.approx(5.0)


def test_an_operation_holds_the_time_of_those_nested_in_it_once():
    ops = [("%while.1", 0, 10 * MS, STEP + "optimizer/while"),
           ("%fusion.9", 2 * MS, 3 * MS, STEP + "head_ce/exp"),
           ("%fusion.8", 5 * MS, 4 * MS, None)]
    assert scope_ms.partition(ops) == {
        "optimizer": 3 * MS, "head_ce": 3 * MS, "unscoped": 4 * MS}


@pytest.mark.parametrize("trace", [
    None,                                        # no trace at all
    _trace(ops=[(n, s, d, None) for n, s, d, _ in OPS]),  # no op_name kept
    _trace(steps=()),                            # no whole step
])
def test_scope_ms_reads_none_where_the_stat_is_missing(monkeypatch,
                                                        trace):
    _use(monkeypatch, trace)
    assert scope_ms.read(None, "head_ce") is None


def test_scope_ms_reads_none_for_a_scope_no_operation_has(monkeypatch):
    _use(monkeypatch, _trace())
    assert scope_ms.read(None, r"BatchNorm_\d+") is None


# -- span_ms ------------------------------------------------------------------
def _fed_trace():
    # three steps of 10 ms; an operation runs in [2, 8) of each
    steps = [(k * 10 * MS, k * 10 * MS + 9 * MS) for k in range(3)]
    ops = [("%fusion.1", lo + 2 * MS, 6 * MS, None) for lo, _ in steps]
    host = {
        "feed.collate": [(lo, 3 * MS // 2, "python3", {})
                         for lo, _ in steps],
        "feed.h2d": [(lo + 1 * MS, 3 * MS, "python3", {"bytes": 77})
                     for lo, _ in steps],
    }
    return _xplane.Trace(ops, steps, host)


def test_span_ms_is_the_median_duration(monkeypatch):
    tr = _fed_trace()
    tr.host["feed.collate"][0] = (0, 600 * MS, "python3", {})  # a stall
    _use(monkeypatch, tr)
    assert span_ms.read(None, "feed.collate") == pytest.approx(1.5)
    assert span_ms.read(None, "feed.h2d") == pytest.approx(3.0)
    assert span_ms.read(None, "feed.place") is None
    _use(monkeypatch, None)
    assert span_ms.read(None, "feed.collate") is None


# -- kernel_roofline_pct -------------------------------------------------------
def _ctx(ops):
    spec = types.SimpleNamespace(
        sizes={"n_head": 12, "n_embd": 1536},
        traffic={"per_chip_batch": 4, "seq_len": 2048})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(spec=spec, trace={"ops": ops},
                                 peaks=lambda: peaks)


def test_kernel_roofline_by_the_kernels_own_name():
    shape = dict(b=4, h=12, s=2048, dh=128)
    least = flops.flash_call_flops("dq", **shape) / 197e12
    ctx = _ctx({
        "%_flash_backward_dq.3 = bf16[48,2048,128] custom-call(...)":
            (4 * least, 2),
        "%_flash_backward_dkdv.3 = (bf16[48,2048,128], ...) custom-call":
            (1.0, 2),
        "%_flash_forward.3 = (...) custom-call(...)": (1.0, 2)})
    assert kernel_roofline_pct.read(
        ctx, kernel="_flash_backward_dq", kind="dq") == pytest.approx(50.0)
    # the parent's one name for both backward kernels matches neither
    old = _ctx({"%_flash_backward.3 = bf16[48,2048,128] custom-call": (1, 2)})
    for kernel, kind in (("_flash_backward_dq", "dq"),
                         ("_flash_backward_dkdv", "dkv")):
        assert kernel_roofline_pct.read(old, kernel=kernel,
                                        kind=kind) is None


# -- _xplane on a trace this process writes -----------------------------------
def test_xplane_reads_what_profile_data_reads(tmp_path):
    """The field-by-field reader against JAX's own: the events of every
    Python thread's host line, by name, start and duration, and the
    annotations' stats."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, StepTraceAnnotation, \
        TraceAnnotation

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for i in range(3):
        with StepTraceAnnotation("train", step_num=i, span="update"):
            with TraceAnnotation("feed.h2d", bytes=77, rate=1.5):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))

    theirs = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if not _xplane.python_line(line.name):
                    continue  # a runtime thread's line is not read
                for e in line.events:
                    theirs.setdefault(e.name, []).append(
                        (round(e.start_ns * 1000), round(e.duration_ns
                                                         * 1000)))
    tr = _xplane.read_trace(path, host_prefix_skipped="\0")
    mine = {name: [(s, d) for s, d, _, _ in events]
            for name, events in tr.host.items()}
    assert set(mine) == set(theirs)
    for name in theirs:
        assert sorted(mine[name]) == sorted(theirs[name]), name
    assert [e[3] for e in tr.host["feed.h2d"]] \
        == [{"bytes": 77, "rate": 1.5}] * 3
    assert [e[3]["step_num"] for e in tr.host["train"]] == [0, 1, 2]
    assert tr.ops == [] and tr.steps == []  # no TPU plane on the CPU
    # the Python tracer's calls are left out unless asked for
    assert not any(n.startswith("$")
                   for n in _xplane.read_trace(path).host)


def test_load_finds_the_newest_trace_and_survives_a_broken_one(
        tmp_path, monkeypatch):
    monkeypatch.setattr(_xplane.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert _xplane.load() is None  # no traced run left anything
    run_dir = tmp_path / "cellbench_trace_x" / "plugins" / "profile" / "1"
    run_dir.mkdir(parents=True)
    (run_dir / "vm.xplane.pb").write_bytes(b"\xff\xff\xff not a trace")
    assert _xplane.load() is None  # unreadable: None, not an exception


@pytest.mark.parametrize("reduced,same", [
    ({"steps": 3, "window_s": 0.029}, True),
    ({"steps": 16, "window_s": 0.029}, False),  # another run's count
    ({"steps": 3, "window_s": 3.2}, False),     # another run's window
])
def test_a_trace_of_another_run_is_refused(monkeypatch, reduced, same):
    """Two traced cells under one temporary directory, or a directory a
    killed run left: the newest trace is then not this run's, and reads
    as none rather than as this run's numbers."""
    tr = _fed_trace()
    assert _xplane.same_run(tr, reduced) is same
    monkeypatch.setattr(_xplane, "newest_trace_path", lambda: "/t.pb")
    monkeypatch.setattr(_xplane.os.path, "getmtime", lambda p: 0.0)
    monkeypatch.setattr(_xplane, "_cached", lambda path, mtime: tr)
    ctx = types.SimpleNamespace(trace=reduced)
    assert (_xplane.load(ctx) is tr) is same
    assert _xplane.same_run(_xplane.Trace([], [], {}),
                            {"steps": 0, "window_s": 0.0})


# -- the recorded v5e trace -----------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            HERE, "recorded_scoped_trace.json.gz"), "rt") as f:
        raw = json.load(f)
    return {cell: _xplane.Trace(
        [tuple(o) for o in t["ops"]], [tuple(s) for s in t["steps"]],
        {k: [tuple(e) for e in v] for k, v in t["host"].items()})
        for cell, t in raw.items()}


def test_recorded_lm_step_partition_sums_to_busy(recorded):
    """One step of ``cgpt590m_train_s2048`` on a v5e (PR 27): every
    operation falls in a row, the rows sum to the time an operation
    runs, and what carries no scope is a small share."""
    tr = recorded["cgpt590m_train_s2048"]
    rows = scope_ms.partition(tr.ops)
    busy = sum(e - s for s, e in tr.busy())
    assert sum(rows.values()) == pytest.approx(busy, rel=1e-6)
    assert busy == pytest.approx(245.0 * MS, rel=2e-3)  # never idle
    assert rows["unscoped"] < 0.05 * busy
    for row in ("head_ce", "optimizer", "MlpBlock", "SelfAttention",
                "kernel _flash_forward", "kernel _flash_backward_dq",
                "kernel _flash_backward_dkdv"):
        assert rows[row] > 0, row
    assert "grad_sync" not in rows  # one chip: nothing to exchange


def test_recorded_lm_scope_metrics(monkeypatch, recorded):
    _use(monkeypatch, recorded["cgpt590m_train_s2048"])
    # head + CE forward and backward: 27 ms of the 245 ms step
    assert scope_ms.read(None, "head_ce") == pytest.approx(27.06, abs=0.1)
    # XLA fuses LayerNorm into the matmul fusions beside it and names a
    # fusion after its matmul: what is left under LayerNorm_k is a
    # five-hundredth of the step, which is why no metric reads it
    assert 0.1 < scope_ms.read(None, r"LayerNorm_\d+") < 1.0
    assert scope_ms.read(None, r"BatchNorm_\d+") is None


def test_recorded_kernel_names_reach_the_trace(recorded):
    tr = recorded["cgpt590m_train_s2048"]
    ops = {}
    for name, _, d, _ in tr.ops:
        sec, count = ops.get(name, (0.0, 0))
        ops[name] = (sec + d / 1e12, count + 1)
    ctx = _ctx(ops)
    shares = {kind: kernel_roofline_pct.read(ctx, kernel=kernel, kind=kind)
              for kernel, kind in (("_flash_forward", "fwd"),
                                   ("_flash_backward_dq", "dq"),
                                   ("_flash_backward_dkdv", "dkv"))}
    assert shares["fwd"] == pytest.approx(46.9, abs=0.5)
    assert shares["dq"] == pytest.approx(59.0, abs=0.5)
    assert shares["dkv"] == pytest.approx(52.4, abs=0.5)
    calls = sum(count for name, (_, count) in ops.items()
                if name.startswith("%_flash_"))
    assert calls == 3 * 18  # one of each kernel a layer


def test_recorded_resnet_feed_spans(monkeypatch, recorded):
    """Three steps of ``resnet50_train_fed_b128`` under the profiler:
    the program's feed spans are on the host's lines, one ``train``
    annotation a step."""
    tr = recorded["resnet50_train_fed_b128"]
    _use(monkeypatch, tr)
    assert 7.0 < span_ms.read(None, "feed.collate") < 12.0
    assert span_ms.read(None, "feed.h2d") > 0.0
    assert all(stats["bytes"] == 77070852
               for _, _, _, stats in tr.host["feed.h2d"])


def test_recorded_resnet_scope_metrics(monkeypatch, recorded):
    tr = recorded["resnet50_train_fed_b128"]
    _use(monkeypatch, tr)
    rows = scope_ms.partition(tr.ops)
    assert sum(rows.values()) == sum(e - s for s, e in tr.busy())
    assert rows["Conv"] > 0.7 * sum(rows.values())
    # BatchNorm's statistics ride in the convolution fusions: what is
    # left under BatchNorm_k is 1 % of the step, and no metric reads it
    assert 0.1 < scope_ms.read(None, r"BatchNorm_\d+") < 2.0
    steps = {s["step_num"] for _, _, _, s in tr.host["train"]}
    assert len(steps) == len(tr.host["train"])  # one annotation a step


# -- every new per-layer entry is files ------------------------------------------
NEW = ["head_ce_ms.lm", "flash_fwd_roofline_pct.lm",
       "flash_dq_roofline_pct.lm", "flash_dkdv_roofline_pct.lm",
       "collate_ms.resnet"]


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_has_its_file_and_reader(name):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    with open(os.path.join(run.ROOT, "cellbench", "layer_metrics",
                           name + ".json")) as f:
        how = json.load(f)
    reader = __import__("cellbench.readers." + how["reader"],
                        fromlist=["read"])
    assert callable(reader.read)
    import inspect

    wants = set(inspect.signature(reader.read).parameters) - {"ctx"}
    assert wants == set(how.get("args", {}))
