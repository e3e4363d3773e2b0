"""The channel-wise delta rule's Pallas kernels (``ops/kda_kernels.py``),
interpreted on the CPU at the cell's head geometry cut short: against
the XLA form of ``ops.gated_delta`` and the position-by-position
recurrence, value and all five gradients (``g``'s a key channel's);
strongly decayed channels beside hardly decayed ones, one decay for all
channels against the scalar rule's kernels, padding, which sizes take
which path, the static account, and the device scope the kernels are
traced under."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from chainermn_tpu.models.transformer import (
    KDA_MIXER_SCOPE,
    KDA_SCAN_SCOPE,
    KDA_WORK,
    BlockOptions,
    KdaMixer,
)
from chainermn_tpu.ops import gated_delta as gdn
from chainermn_tpu.ops import gated_delta_kernels as scalar_rule
from chainermn_tpu.ops import kda_kernels as kernels
from test_kimi_linear import recurrence
from test_ssd_kernels import _pallas_calls

H, D, CHUNK = 2, 128, 64  # the cell's widths, a sixteenth of its heads
LEAVES = "q k v g beta".split()

CASES = {
    # a length that is no multiple of a grid point's four chunks (padded
    # to two grid points), two sequences
    "float32_off_boundary_batch_of_two": dict(s=300, b=2,
                                              dtype=jnp.float32),
    # the cell's precisions, one grid point
    "bfloat16_one_grid_point": dict(s=256, b=1, dtype=jnp.bfloat16),
}


def _inputs(s, b, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    return ((unit(jax.random.normal(k[0], (b, s, H, D)))
             * D ** -0.5).astype(dtype),
            unit(jax.random.normal(k[1], (b, s, H, D))).astype(dtype),
            jax.random.normal(k[2], (b, s, H, D)).astype(dtype),
            -0.3 * jnp.exp(jax.random.normal(k[5], (b, s, H, D))),
            jax.nn.sigmoid(jax.random.normal(k[6], (b, s, H))))


def _value_and_gradients(f, args):
    weigh = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(
        args[2].shape)
    o = f(*args)
    grads = jax.grad(lambda *a: (f(*a).astype(jnp.float32) * weigh).sum(),
                     argnums=range(5))(*args)
    return dict(zip(["o", *LEAVES], (o, *grads)))


def _scan(interpret, dtype=jnp.float32):
    return functools.partial(gdn.gated_delta_scan, chunk=CHUNK, dtype=dtype,
                             interpret=interpret)


@functools.lru_cache(maxsize=None)
def _forms(case):
    """The case's value and gradients by the kernels (interpreted), by
    the XLA form, and by the recurrence in float32.  Each case's first
    leaf pays for all six (over 40 s in the driver's run: the kernels'
    interpreter runs a grid point at a time, the recurrence a token at
    a time), and nothing cheaper sets the kernels against both."""
    c = CASES[case]
    args = _inputs(c["s"], c["b"], c["dtype"])
    return (_value_and_gradients(_scan(True, c["dtype"]), args),
            _value_and_gradients(_scan(None, c["dtype"]), args),
            _value_and_gradients(
                recurrence, tuple(a.astype(jnp.float32) for a in args)))


def _gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("leaf", ["o", *LEAVES])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_xla_form_value_and_gradients(case, leaf):
    by_kernels, xla, a_token = _forms(case)
    got, want = by_kernels[leaf], xla[leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    # another cut of the chunk (halves, not blocks of 16): float32 sums
    # in another order; in bfloat16 other operands are rounded
    assert _gap(got, want) < (2e-5 if CASES[case]["dtype"] == jnp.float32
                              else 0.02)
    # as near the recurrence a token as the XLA form is
    gap = _gap(got, a_token[leaf])
    if CASES[case]["dtype"] == jnp.float32:
        assert gap < 2e-5
    else:
        assert gap < 2 * _gap(want, a_token[leaf]) + 2e-3


def test_strongly_and_hardly_decayed_channels_side_by_side():
    """``g`` about -11 a position in every other channel (a softplus of
    0.7 under ``A_log = log 16``) and -1e-3 in the rest: ``e^{-G}`` of
    the split form ``(K e^G)(K e^-G)^T`` overflows float32 at the ninth
    position; the kernels' cuts evaluate no exponent above 0: finite
    everywhere and the XLA form's value and gradients."""
    q, k, v, g, beta = _inputs(256, 1, jnp.float32, seed=5)
    assert not bool(jnp.isfinite(jnp.exp(jnp.float32(11.0 * 9))))
    mixed = jnp.where(jnp.arange(D) % 2 == 0, -11.0, -1e-3) \
        * (1.0 + 0.1 * jnp.tanh(g))
    args = (q, k, v, mixed, beta)
    got = _value_and_gradients(_scan(True), args)
    want = _value_and_gradients(_scan(None), args)
    for name in ["o", *LEAVES]:
        assert bool(jnp.isfinite(got[name]).all()), name
        assert _gap(got[name], want[name]) < 2e-5, name
    assert _gap(got["o"], recurrence(*args)) < 2e-5


def test_one_decay_for_all_channels_is_the_scalar_rules_kernels():
    """``g`` the same in every key channel: the scalar rule's kernels'
    value and gradients (``g``'s summed over the channels), from the
    other kernels."""
    q, k, v, g, beta = _inputs(256, 1, jnp.float32, seed=4)
    scalar = g[..., 0]
    got = _value_and_gradients(
        lambda q, k, v, g, beta: _scan(True)(q, k, v, jnp.broadcast_to(
            g[..., None], (*g.shape, D)), beta),
        (q, k, v, scalar, beta))
    want = _value_and_gradients(_scan(True), (q, k, v, scalar, beta))
    for name in ["o", *LEAVES]:
        assert _gap(got[name], want[name]) < 2e-5, name


def test_padding_rows_leave_every_state_as_it_was_in_the_kernels():
    """A length that is no multiple of the grid point's span is padded
    with ``g = 0, beta = 0``: the first 200 positions of 512 and 200
    positions alone (padded to 256) are the same."""
    args = _inputs(512, 1, jnp.float32, seed=2)
    whole = _scan(True)(*args)
    cut = _scan(True)(*(a[:, :200] for a in args))
    np.testing.assert_allclose(cut, whole[:, :200], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("half", kernels.HALVES)
def test_a_levels_cut_and_pairs(half):
    """A level's cut is the last row of the lower half of each block of
    ``2 half`` rows; its pairs are the rows above the cut against the
    rows at or below it, in one block (``_apart`` between ``half`` and
    ``2 half``)."""
    run = jnp.asarray(np.random.default_rng(half).standard_normal(
        (CHUNK, D)), jnp.float32)
    at = (np.arange(CHUNK) // (2 * half)) * 2 * half + half - 1

    def kernel(run_ref, cut_ref):
        cut_ref[...] = kernels._cut_sums(run_ref[...], half)

    np.testing.assert_array_equal(
        pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            run.shape, run.dtype), interpret=True)(run),
        np.asarray(run)[at])
    i, j = np.mgrid[:CHUNK, :CHUNK]
    apart = np.asarray(kernels._apart(CHUNK))
    np.testing.assert_array_equal(
        (apart >= half) & (apart < 2 * half),
        (i // (2 * half) == j // (2 * half)) & (i > at[i]) & (j <= at[j]))
    # the six levels cover the lower triangle once
    np.testing.assert_array_equal(
        (apart >= min(kernels.HALVES)) & (apart < 2 * max(kernels.HALVES)),
        i > j)


@pytest.mark.parametrize("backend,shape,chunk,dtype,interpret,want", [
    # the cell's launch: chunk 64, 32 heads of 128, a key head each
    ("tpu", (2, 8192, 32, 32, 128, 128), 64, jnp.bfloat16, None, True),
    ("tpu", (1, 1000, 2, 2, 128, 128), 64, jnp.bfloat16, None, True),
    ("cpu", (2, 8192, 32, 32, 128, 128), 64, jnp.bfloat16, None, False),
    ("cpu", (2, 8192, 32, 32, 128, 128), 64, jnp.bfloat16, True, True),
    # float32 operands on a TPU: the XLA form, unless asked for
    ("tpu", (2, 8192, 32, 32, 128, 128), 64, jnp.float32, None, False),
    ("tpu", (2, 8192, 32, 32, 128, 128), 64, jnp.float32, True, True),
    # a head width, a chunk, a grouping that do not tile
    ("tpu", (2, 8192, 32, 32, 64, 64), 64, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 32, 32, 128, 128), 32, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 16, 32, 128, 128), 64, jnp.bfloat16, True, False),
    # the rehearsal's sizes
    ("tpu", (2, 96, 4, 4, 16, 16), 16, jnp.bfloat16, None, False),
])
def test_which_form_runs_is_read_off_the_input_and_the_platform(
        monkeypatch, backend, shape, chunk, dtype, interpret, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b, s, hk, h, dk, dv = shape
    k = jax.ShapeDtypeStruct((b, s, hk, dk), dtype)
    v = jax.ShapeDtypeStruct((b, s, h, dv), dtype)
    assert gdn._use_kernels(k, v, chunk, dtype, interpret, True) is want
    if interpret is None:
        assert gdn.runs_kernels(chunk, h, hk, dk, dv, dtype,
                                channels=True) is want
        # the reserve for the XLA form's working set goes with that form
        widths = BlockOptions(
            layer_types=("kda",), gdn_value_heads=h, gdn_key_dim=dk,
            gdn_value_dim=dv, gdn_chunk=chunk).remat_widths(64, 4, dtype)
        assert (KDA_WORK not in widths) is want
        assert widths["kda_in"] == 3 * h * dk


def test_sizes_that_do_not_tile_fall_to_the_xla_form():
    """Asked for interpreted kernels at a head width of 64, the scan
    runs its XLA form: the same result and no ``pallas_call``."""
    rng = np.random.default_rng(2)
    n = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    args = (n(1, 70, 2, 64), n(1, 70, 2, 64), n(1, 70, 2, 64),
            -jnp.exp(n(1, 70, 2, 64)), jax.nn.sigmoid(n(1, 70, 2)))
    np.testing.assert_array_equal(_scan(True)(*args), _scan(None)(*args))
    assert not list(_pallas_calls(jax.make_jaxpr(_scan(True))(*args).jaxpr))


def test_the_static_account_at_the_cells_shape_by_hand():
    """One 8192-token sequence of the cell: 32 heads of 128, chunk 64,
    four chunks a grid point."""
    census = gdn.gated_delta_census(8192, 64, 32, 128, 128,
                                    channel_decay=True)
    forward, backward = (census["kernels"][k] for k in ("forward",
                                                        "backward"))
    assert forward["grid"] == backward["grid"] == (1, 32, 32)
    assert forward["tiles"] == backward["tiles"] == 1024
    tile, decay = 256 * 128 * 2, 256 * 128 * 4        # q k v o; g
    rows = 8 * 128 * 4                                 # beta, lane-padded
    states, inverses = 4 * 128 * 128 * 4, 4 * 64 * 128 * 4
    state = 128 * 128 * 4
    assert forward["vmem_bytes"] == 2 * (
        4 * tile + decay + rows + states + inverses) + state
    assert backward["vmem_bytes"] == 2 * (
        7 * tile + 2 * decay + 2 * rows + states + inverses) + state
    # under the 16 MiB a kernel may scope inside the step
    assert backward["vmem_bytes"] < 4 << 20
    moved = lambda tiles, decays, row_tiles: 1024 * (
        tiles * tile + decays * decay + row_tiles * 8 * 64 * 4
        + states + 4 * 64 * 64 * 4)
    assert forward["hbm_bytes"] == moved(4, 1, 1)
    assert backward["hbm_bytes"] == moved(7, 2, 2)
    # the entering states and T written and read once: 1.83x the least
    assert forward["hbm_over_least"] == pytest.approx(
        forward["hbm_bytes"] / census["bytes_forward"])
    assert 1.8 < forward["hbm_over_least"] < 1.9
    # what the backward's residuals hold a sequence: 268 MB of states,
    # 67 MB of T
    assert scalar_rule.launch_account(
            8192, 64, 32, 32, plan=kernels.launch_plan) == {
        k: {n: v for n, v in census["kernels"][k].items()
            if n != "hbm_over_least"} for k in ("forward", "backward")}
    # a padded length counts whole grid points; sizes that do not tile
    # have no account
    assert gdn.gated_delta_census(8200, 64, 32, 128, 128,
                                  channel_decay=True)["kernels"][
        "forward"]["grid"] == (1, 32, 33)
    for sizes in ((64, 32, 64, 64), (32, 32, 128, 128)):
        assert gdn.gated_delta_census(
            8192, *sizes, channel_decay=True)["kernels"] is None
    assert gdn.gated_delta_census(8192, 64, 32, 128, 128, key_heads=16,
                                  channel_decay=True)["kernels"] is None
    assert scalar_rule.launch_account(8192, 64, 32, 16)["forward"][
        "grid"] == (1, 16, 32)


def test_every_kernel_of_the_mixers_gradient_lies_under_the_scan_scope(
        monkeypatch):
    """``kda_scan_ms.kimilinear`` and ``kda_scan_roofline_pct.kimilinear``
    read the ``kda_scan`` scope inside ``kda_mixer``: the forward kernel
    and the backward kernel of a mixer's gradient both carry it."""
    options = BlockOptions(norm="rmsnorm", gdn_value_heads=H,
                           gdn_key_dim=D, gdn_value_dim=D, gdn_chunk=CHUNK)
    mixer = KdaMixer(options=options, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 384, 64), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), x))
    # as on a TPU: tracing builds the kernels, nothing runs them
    monkeypatch.setattr(gdn, "_use_kernels", lambda *a: True)
    gdn.gated_delta_scan.clear_cache()
    try:
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p, x: mixer.apply(p, x).astype(jnp.float32).sum(),
            (0, 1)))(params, x)
    finally:
        gdn.gated_delta_scan.clear_cache()
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert sorted(name for name, _ in calls) == ["_kda_backward",
                                                 "_kda_forward"]
    for name, stack in calls:
        assert f"/{KDA_SCAN_SCOPE}/" in stack, stack
        assert stack.index(KDA_MIXER_SCOPE) < stack.index(KDA_SCAN_SCOPE) \
            < stack.index(gdn.GDN_SCAN_SCOPE)
    backward, = (s for name, s in calls if name == "_kda_backward")
    assert "transpose(" in backward
