"""The gradient wire ships a large leaf where it lies (PR 51).

On a mesh where single-leaf all-reduces run asynchronously (the rule
``optimizers._grad_reduce_compiler_options``: TPU chips, the reduced
axes span all of them and more than one) a gradient leaf of at least
``WireConfig.bucket_bytes`` crosses the wire in its own shape, one
``psum``, and only the smaller leaves are packed into buckets.  The
tests run on the CPU mesh with the rule's platform test answered as a
TPU would (``as_if_tpu``: the rule's own extents, and ``{}`` for its
options, which XLA:CPU would refuse):

* the lowering: a ``psum`` a large leaf plus one a packed bucket, no
  ``reshape`` or ``concatenate`` of a large leaf under ``grad_sync``,
  the synced gradients those of the packed wire and of ``"per_leaf"``
  bit for bit, the split a pure function of the shapes that moves with
  ``bucket_bytes``;
* what must not change: ``int8``, error feedback, ``hier_rs_ag``,
  ``overlap="bucket"``, double buffering, ZeRO, a one-device mesh and a
  CPU mesh keep the packed wire's jaxpr, equation for equation, and are
  handed no compile options.
"""

import math
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu import optimizers
from chainermn_tpu.communicators import _topology
from chainermn_tpu.comm_wire import WireConfig
from chainermn_tpu.comm_wire.codecs import _CAST_WIRE
from chainermn_tpu.observability import timeline
from chainermn_tpu.optimizers import (
    GRAD_SYNC_SCOPE,
    _split_wire,
    _sync_grads_per_leaf,
    _sync_grads_wire,
    build_train_step,
)

#: the tests' bucket target: a (32, 32) float32 leaf is at it
TARGET = 4096


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


@pytest.fixture(scope="module")
def hier_comm(devices8):
    orig = _topology._node_key
    _topology._node_key = lambda d: ("slice", d.id // 4)
    try:
        return cmn.create_communicator("hierarchical", devices=devices8)
    finally:
        _topology._node_key = orig


@pytest.fixture
def as_if_tpu(monkeypatch):
    """The builder's rule with the mesh's devices read as TPU chips:
    its own test of the extents, ``{}`` where it would hand options."""
    rule = optimizers._grad_reduce_compiler_options

    def on_tpus(mesh, axes):
        chips = np.full(mesh.devices.shape, SimpleNamespace(platform="tpu"))
        seen = SimpleNamespace(devices=chips, shape=dict(mesh.shape))
        return None if rule(seen, axes) is None else {}

    monkeypatch.setattr(optimizers, "_grad_reduce_compiler_options",
                        on_tpus)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


def _under_grad_sync(jaxpr, primitive):
    return [e for e in _equations(jaxpr)
            if e.primitive.name == primitive
            and GRAD_SYNC_SCOPE in str(e.source_info.name_stack)]


def _nbytes(aval):
    return math.prod(aval.shape) * aval.dtype.itemsize


def _tree(case):
    rng = np.random.RandomState(11)

    def leaf(shape, dtype=jnp.float32):
        return jnp.asarray(rng.randn(8, *shape), dtype)  # a row a device

    large = {"w0": leaf((32, 32)), "w1": leaf((64, 48)),
             "emb": leaf((3, 16, 32))}
    small = {"b0": leaf((32,)), "ln": leaf((7,)), "s": leaf(())}
    if case == "only_small":
        return small
    if case == "only_large":
        return large
    if case == "mixed_dtypes":
        return {**large, **small, "h": leaf((64, 64), jnp.bfloat16),
                "hb": leaf((5,), jnp.bfloat16)}
    return {**large, **small}


CASES = {
    # case: (wire, leaves in place, buckets packed)
    "large_and_small": (WireConfig(bucket_bytes=TARGET), 3, 1),
    "mixed_dtypes": (WireConfig(bucket_bytes=TARGET), 4, 2),
    "only_small": (WireConfig(bucket_bytes=TARGET), 0, 1),
    "only_large": (WireConfig(bucket_bytes=TARGET), 3, 0),
    "cast_codec": (WireConfig(codec="bf16", bucket_bytes=TARGET), 3, 1),
    "upcast_codec": (WireConfig(codec="f32", bucket_bytes=TARGET), 3, 1),
    "larger_target": (WireConfig(bucket_bytes=3 * TARGET), 1, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_large_leaves_cross_in_place_and_sum_as_packed(
    comm, as_if_tpu, monkeypatch, case
):
    wire, n_in_place, n_packed = CASES[case]
    stacked = _tree(case)
    local = jax.tree_util.tree_map(lambda x: x[0], stacked)
    axes = comm.axis_names

    def synced(sync):
        def body(tree):
            return sync(jax.tree_util.tree_map(lambda x: x[0], tree))

        return jax.shard_map(body, mesh=comm.mesh, in_specs=P(axes),
                             out_specs=P(), check_vma=False)

    def in_place(tree):
        return _sync_grads_wire(tree, comm, wire)[0]

    def packed(tree):
        return _sync_grads_wire(
            tree, comm, wire,
            split=_split_wire(tree, comm, wire, in_place=False))[0]

    def per_leaf(tree):
        return _sync_grads_per_leaf(tree, comm,
                                    _CAST_WIRE.get(wire.codec))

    # the split: a function of the shapes alone, that moves with the
    # target; a leaf is in place exactly when it is at or over it
    split = _split_wire(local, comm, wire)
    assert split == _split_wire(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), local),
        comm, wire)
    leaves = jax.tree_util.tree_leaves(local)
    assert [i for i, l in enumerate(leaves)
            if l.nbytes >= wire.bucket_bytes] == list(split.in_place)
    assert sorted(split.in_place + split.packed) == list(
        range(len(leaves)))
    assert (len(split.in_place), split.plan.n_buckets) == (
        n_in_place, n_packed)
    assert split.in_place_bytes + split.packed_bytes == sum(
        l.nbytes for l in leaves)
    everything = _split_wire(
        local, comm, wire._replace(bucket_bytes=1 << 30))
    assert not everything.in_place
    assert everything.plan == optimizers._MultiNodeOptimizer(
        optax.sgd(1.0), comm,
        wire=wire._replace(bucket_bytes=1 << 30)).wire_plan(local)

    # the lowering: one psum a leaf in place, one a bucket, and no
    # large leaf reshaped or concatenated on its way
    jaxpr = jax.make_jaxpr(synced(in_place))(stacked).jaxpr
    assert len(_under_grad_sync(jaxpr, "psum")) == n_in_place + n_packed
    for primitive in ("reshape", "concatenate"):
        for eqn in _under_grad_sync(jaxpr, primitive):
            assert all(_nbytes(v.aval) < wire.bucket_bytes
                       for v in eqn.invars), eqn
    psummed = sorted(e.invars[0].aval.shape
                     for e in _under_grad_sync(jaxpr, "psum"))
    for i in split.in_place:
        assert leaves[i].shape in psummed
    parent = jax.make_jaxpr(synced(packed))(stacked).jaxpr
    assert len(_under_grad_sync(parent, "psum")) == _split_wire(
        local, comm, wire, in_place=False).plan.n_buckets

    # the sums: the packed wire's and the per-leaf wire's, bit for bit
    got = jax.jit(synced(in_place))(stacked)
    for other in (packed, per_leaf):
        want = jax.jit(synced(other))(stacked)
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))


# ----------------------------------------------------------------------
# what must not change
# ----------------------------------------------------------------------
def _loss(params, batch):
    m = batch.mean()
    return sum(jnp.sum((p.astype(jnp.float32) - m) ** 2)
               for p in jax.tree_util.tree_leaves(params))


def _params():
    # "big": enough for schedule "auto" to stage its bucket on (2, 4)
    return {"w0": jnp.ones((32, 32)), "big": jnp.ones((256, 128)),
            "b0": jnp.ones((32,)), "ln": jnp.ones((8,))}


KEPT = {
    # case: (communicator, create_multi_node_optimizer's arguments)
    "int8": ("flat", dict(wire=WireConfig("int8", TARGET))),
    "error_feedback": ("flat", dict(wire=WireConfig(
        "bf16", TARGET, error_feedback=True))),
    "hier_rs_ag": ("hier", dict(wire=WireConfig(
        "none", TARGET, schedule="hier_rs_ag"))),
    "hier_by_auto": ("hier", dict(wire=WireConfig(
        "none", TARGET, max_buckets=1))),
    "overlap_bucket": ("flat", dict(wire=WireConfig("none", TARGET),
                                    overlap="bucket")),
    "double_buffering": ("flat", dict(wire=WireConfig("none", TARGET),
                                      double_buffering=True)),
    "zero_redundancy": ("flat", dict(wire=WireConfig("none", TARGET),
                                     zero_redundancy=True)),
    "per_leaf": ("flat", dict(wire="per_leaf")),
    "one_device": ("one", dict(wire=WireConfig("none", TARGET))),
    "cpu_mesh": ("flat", dict(wire=WireConfig("none", TARGET))),
}


def _step_jaxpr_and_options(comm, monkeypatch, **optimizer):
    """The step's jaxpr as text, the compile options its ``jax.jit``
    calls were handed, and what its builder's span says of the wire
    (the span's own dict: the record keeps 16384 spans a process)."""
    seen, spans = [], []
    real_jit = jax.jit
    real_attributes = timeline.open_phase_attributes

    def spy(*args, **kwargs):
        seen.append(kwargs.get("compiler_options"))
        return real_jit(*args, **kwargs)

    def attributes():
        spans.append(real_attributes())
        return spans[-1]

    opt = cmn.create_multi_node_optimizer(optax.adam(0.05), comm,
                                          **optimizer)
    params = _params()
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", spy)
        m.setattr(timeline, "open_phase_attributes", attributes)
        step = build_train_step(comm, _loss, opt, donate=False)
        p, s = step.place(params, opt.init(params))
        batch = step.place_batch(jnp.arange(8.0).reshape(8, 1))
        seen.clear()  # place() and init may jit helpers of their own
        jitted = step.get_jitted(p, s)
        if hasattr(jitted, "trace"):
            text = str(jitted.trace(p, s, batch).jaxpr)
        else:  # an OverlappedStep: the function its pass reorders
            text = str(jax.make_jaxpr(jitted._fn)(p, s, batch))
    (said,) = spans
    return text, seen, said


@pytest.mark.parametrize("case", sorted(KEPT))
def test_these_keep_the_packed_wires_program(
    request, devices8, as_if_tpu, monkeypatch, case
):
    which, optimizer = KEPT[case]
    comm = (cmn.create_communicator("tpu", devices=devices8[:1])
            if which == "one" else request.getfixturevalue(
                "hier_comm" if which == "hier" else "comm"))
    rule = optimizers._grad_reduce_compiler_options  # as_if_tpu's
    if case == "cpu_mesh":
        monkeypatch.undo()  # the rule itself, on the mesh as it is
    text, options, said = _step_jaxpr_and_options(
        comm, monkeypatch, **optimizer)
    assert all(o is None for o in options)
    if "wire.in_place" in said:
        assert said["wire.in_place"] == "0 leaves 0 MB"
        assert said["wire.async_options"] == "off"
    else:  # no wire's buckets ship these gradients
        assert case in ("zero_redundancy", "per_leaf")

    # the parent's program: the wire where the rule answers no
    split = optimizers._split_wire
    with monkeypatch.context() as m:
        if case == "cpu_mesh":  # ... or is not asked
            m.setattr(optimizers, "_split_wire",
                      lambda *a, **kw: split(*a, **{**kw,
                                                    "in_place": False}))
        else:
            m.setattr(optimizers, "_grad_reduce_compiler_options",
                      lambda mesh, axes: None)
        parent, _, _ = _step_jaxpr_and_options(comm, monkeypatch,
                                               **optimizer)
    assert text == parent

    if which == "flat" and case != "cpu_mesh":
        # the case's own reason, not the mesh: the same tree on the
        # same mesh goes in place under the plain wire
        monkeypatch.setattr(optimizers, "_grad_reduce_compiler_options",
                            rule)
        _, _, said = _step_jaxpr_and_options(
            comm, monkeypatch, wire=WireConfig("none", TARGET))
        assert said["wire.in_place"] == "2 leaves 0 MB"
        assert said["wire.packed"] == "1 bucket 0.0 MB"


def test_the_step_says_what_its_wire_does(comm, as_if_tpu, monkeypatch):
    """``setup.build_step``'s span carries the split and whether the
    options were handed over, and ``setup_line()`` prints them."""
    text, options, said = _step_jaxpr_and_options(
        comm, monkeypatch, wire=WireConfig("none", TARGET))
    # the two leaves', the bucket's and the loss's
    assert text.count(" psum[") == 2 + 1 + 1
    assert said == {"wire.in_place": "2 leaves 0 MB",
                    "wire.packed": "1 bucket 0.0 MB",
                    # as_if_tpu's rule hands {}: nothing to hand over
                    "wire.async_options": "off"}
    assert all(o is None for o in options)
    record = cmn.observability.process_record()
    if not record["dropped"]:
        assert record["spans"][-1]["args"] is not said  # a snapshot's
        assert "build_step: wire.in_place 2 leaves 0 MB, wire.packed " \
            "1 bucket 0.0 MB, wire.async_options off" in \
            cmn.observability.setup_line()

    # the rule's own options reach the step's jit where leaves go in
    # place, and only there (XLA:CPU refuses them: nothing is compiled)
    monkeypatch.setattr(
        optimizers, "_grad_reduce_compiler_options",
        lambda mesh, axes: dict(optimizers._ASYNC_GRAD_REDUCE_OPTIONS))
    _, options, said = _step_jaxpr_and_options(
        comm, monkeypatch, wire=WireConfig("none", TARGET))
    assert options == [optimizers._ASYNC_GRAD_REDUCE_OPTIONS]
    assert said["wire.async_options"] == "on"
    text, options, said = _step_jaxpr_and_options(
        comm, monkeypatch, wire=WireConfig("none", 1 << 30))
    assert text.count(" psum[") == 1 + 1
    assert all(o is None for o in options)
    assert said == {"wire.in_place": "0 leaves 0 MB",
                    "wire.packed": "1 bucket 0.1 MB",
                    "wire.async_options": "off"}
