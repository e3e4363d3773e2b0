"""The chunked gated delta rule's Pallas kernels
(``ops/gated_delta_kernels.py``), interpreted on the CPU at the cell's
head geometry cut short: against the XLA form of ``ops.gated_delta`` and
against the position-by-position recurrence, value and every gradient;
padding, strong decays, two sequences in a batch, the in-tile inverse,
which sizes take which path, and the device scope the kernels are traced
under."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models.transformer import BlockOptions, GatedDeltaMixer
from chainermn_tpu.ops import gated_delta as gdn
from chainermn_tpu.ops import gated_delta_kernels as kernels
from test_gated_delta import recurrence
from test_ssd_kernels import _pallas_calls

HK, H, D, CHUNK = 2, 4, 128, 64  # the cell's widths, an eighth of its heads
LEAVES = "q k v g beta".split()

CASES = {
    # two grid points of four chunks
    "float32_whole_chunks": dict(s=512, b=1, dtype=jnp.float32,
                                 seeded=False),
    # a length that is no multiple of the chunk, two sequences
    "float32_off_boundary_batch_of_two": dict(s=300, b=2,
                                              dtype=jnp.float32,
                                              seeded=False),
    # the cell's precisions, decays as the configuration seeds them
    "bfloat16_seeded_rates": dict(s=320, b=1, dtype=jnp.bfloat16,
                                  seeded=True),
}


def _inputs(s, b, dtype, seeded, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    if seeded:  # A in -(0, 16], the step's bias log-uniform in [1e-3, 1e-1]
        rates = -jax.random.uniform(k[3], (H,), minval=1e-4, maxval=16.0)
        step = jnp.exp(jax.random.uniform(
            k[4], (H,), minval=np.log(1e-3), maxval=np.log(1e-1)))
        g = rates * jax.nn.softplus(
            jax.random.normal(k[5], (b, s, H)) * 0.5
            + jnp.log(jnp.expm1(step)))
    else:
        g = -0.3 * jnp.exp(jax.random.normal(k[5], (b, s, H)))
    return ((unit(jax.random.normal(k[0], (b, s, HK, D)))
             * D ** -0.5).astype(dtype),
            unit(jax.random.normal(k[1], (b, s, HK, D))).astype(dtype),
            jax.random.normal(k[2], (b, s, H, D)).astype(dtype), g,
            jax.nn.sigmoid(jax.random.normal(k[6], (b, s, H))))


def _value_and_gradients(f, args):
    weigh = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(
        args[2].shape)
    o = f(*args)
    grads = jax.grad(lambda *a: (f(*a).astype(jnp.float32) * weigh).sum(),
                     argnums=range(5))(*args)
    return dict(zip(["o", *LEAVES], (o, *grads)))


@functools.lru_cache(maxsize=None)
def _forms(case):
    """The case's value and gradients by the kernels (interpreted), by
    the XLA form, and by the recurrence in float32.  Each case's first
    leaf pays for all six (over 40 s in the driver's run: the kernels'
    interpreter runs a grid point at a time, the recurrence a token at
    a time), and nothing cheaper sets the kernels against both."""
    c = CASES[case]
    args = _inputs(c["s"], c["b"], c["dtype"], c["seeded"])
    scan = lambda interpret: functools.partial(
        gdn.gated_delta_scan, chunk=CHUNK, dtype=c["dtype"],
        interpret=interpret)
    return (_value_and_gradients(scan(True), args),
            _value_and_gradients(scan(None), args),
            _value_and_gradients(
                recurrence, tuple(a.astype(jnp.float32) for a in args)))


def _gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("leaf", ["o", *LEAVES])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_xla_form_value_and_gradients(case, leaf):
    got, want = (form[leaf] for form in _forms(case)[:2])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    if leaf == "o":
        # the same products of the same rounded operands
        limit = 1e-6 if CASES[case]["dtype"] == jnp.float32 else 0.008
    else:
        # the backward rounds other intermediates than autodiff's does
        limit = 2e-5 if CASES[case]["dtype"] == jnp.float32 else 0.02
    assert _gap(got, want) < limit


@pytest.mark.parametrize("leaf", ["o", *LEAVES])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_recurrence_value_and_gradients(case, leaf):
    """As near the recurrence a token as the XLA form is."""
    by_kernels, xla, a_token = _forms(case)
    gap = _gap(by_kernels[leaf], a_token[leaf])
    if CASES[case]["dtype"] == jnp.float32:
        assert gap < 2e-5
    else:
        assert gap < 0.02
        assert gap < 2 * _gap(xla[leaf], a_token[leaf]) + 2e-3


def _kernel_scan(*args):
    return gdn.gated_delta_scan(*args, chunk=CHUNK, dtype=jnp.float32,
                                interpret=True)


def test_strong_decay_and_full_writes_stay_finite_in_the_kernels():
    """``g`` far below zero (a state forgotten inside a chunk: its decay
    underflows to 0, never overflows) and ``beta = 1``."""
    q, k, v, g, beta = _inputs(256, 1, jnp.float32, False, seed=5)
    args = (q, k, v, 40.0 * g, jnp.ones_like(beta))
    got = _kernel_scan(*args)
    grads = jax.grad(lambda *a: _kernel_scan(*a).sum(),
                     argnums=range(5))(*args)
    assert all(bool(jnp.isfinite(t).all()) for t in (got, *grads))
    # a running sum of thousands rounds its differences: 1e-4 of them
    assert _gap(got, recurrence(*args)) < 2e-3


def test_padding_rows_leave_every_state_as_it_was_in_the_kernels():
    args = _inputs(512, 1, jnp.float32, False)
    whole = _kernel_scan(*args)
    cut = _kernel_scan(*(a[:, :200] for a in args))
    np.testing.assert_allclose(cut, whole[:, :200], rtol=1e-5, atol=1e-6)


def test_the_carried_state_is_reset_between_the_sequences_of_a_batch():
    args = _inputs(256, 2, jnp.float32, False, seed=1)
    both = _kernel_scan(*args)
    for i in range(2):
        alone = _kernel_scan(*(a[i:i + 1] for a in args))
        np.testing.assert_allclose(both[i:i + 1], alone, rtol=1e-6,
                                   atol=1e-7)


def _strictly_lower(kind, seed):
    """``A`` of a chunk as the kernels meet it: plain noise, or ``beta_i
    (k_i . k_j) e^{G_i - G_j}`` of unit keys with weak or strong
    decays."""
    rng = np.random.default_rng(seed)
    if kind == "noise":
        a = rng.standard_normal((CHUNK, CHUNK)) * 0.2
    else:
        k = rng.standard_normal((CHUNK, D))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        if kind == "alike":  # keys that nearly repeat: a large inverse
            k = k[:1] + 0.3 * k
            k /= np.linalg.norm(k, axis=-1, keepdims=True)
        run = np.cumsum(-{"weak": 0.01, "strong": 2.0, "alike": 0.01}[kind]
                        * rng.random(CHUNK))
        beta = 1 / (1 + np.exp(-rng.standard_normal(CHUNK)))
        a = beta[:, None] * (k @ k.T) * np.exp(np.minimum(
            run[:, None] - run[None, :], 0.0))
    return jnp.asarray(np.tril(a, -1), jnp.float32)


@pytest.mark.parametrize("kind", ["noise", "weak", "strong", "alike"])
def test_inverse_in_the_tile_is_as_near_float64_as_the_series(kind):
    """No further from the float64 inverse than twice the XLA form's
    nilpotent series is on the same matrices (the kernels' substitution
    and merges are exact in exact arithmetic, as the series is)."""
    matrices = [_strictly_lower(kind, seed) for seed in range(4)]
    got = kernels._inverse_unit_lower(matrices)
    for a, t in zip(matrices, got):
        want = np.linalg.inv(np.eye(CHUNK) + np.asarray(a, np.float64))
        off = lambda m: np.abs(np.asarray(m, np.float64) - want).max()
        assert off(t) <= 2 * off(gdn._inverse_unit_lower(a)) + 1e-9
        assert off(t) < 1e-5 * np.abs(want).max()


def test_sizes_that_do_not_tile_fall_to_the_xla_form():
    """Asked for interpreted kernels at the other tests' sizes (chunk
    16, 4 value heads of 8 over 2 key heads of 16), the scan runs its
    XLA form: the same result and no ``pallas_call``."""
    rng = np.random.default_rng(2)
    n = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    args = (n(2, 70, 2, 16), n(2, 70, 2, 16), n(2, 70, 4, 8),
            -jnp.exp(n(2, 70, 4)), jax.nn.sigmoid(n(2, 70, 4)))
    asked = functools.partial(gdn.gated_delta_scan, chunk=16,
                              dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(
        asked(*args),
        gdn.gated_delta_scan(*args, chunk=16, dtype=jnp.float32))
    assert not list(_pallas_calls(jax.make_jaxpr(asked)(*args).jaxpr))


@pytest.mark.parametrize("backend,shape,chunk,dtype,interpret,want", [
    # the cell's launch: chunk 64, 16 key and 32 value heads of 128
    ("tpu", (2, 8192, 16, 32, 128, 128), 64, jnp.bfloat16, None, True),
    ("tpu", (1, 1000, 2, 4, 128, 128), 64, jnp.bfloat16, None, True),
    ("tpu", (1, 1000, 4, 4, 128, 128), 64, jnp.bfloat16, None, True),
    ("cpu", (2, 8192, 16, 32, 128, 128), 64, jnp.bfloat16, None, False),
    ("cpu", (2, 8192, 16, 32, 128, 128), 64, jnp.bfloat16, True, True),
    # float32 operands on a TPU: the XLA form, unless asked for
    ("tpu", (2, 8192, 16, 32, 128, 128), 64, jnp.float32, None, False),
    ("tpu", (2, 8192, 16, 32, 128, 128), 64, jnp.float32, True, True),
    # a chunk, a grouping, a key width, a value width that do not tile
    ("tpu", (2, 8192, 16, 32, 128, 128), 128, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 16, 32, 128, 128), 16, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 4, 32, 128, 128), 64, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 16, 32, 64, 128), 64, jnp.bfloat16, None, False),
    ("tpu", (2, 8192, 16, 32, 128, 256), 64, jnp.bfloat16, True, False),
    # the rehearsal's sizes
    ("tpu", (2, 256, 2, 4, 16, 16), 16, jnp.bfloat16, None, False),
])
def test_which_form_runs_is_read_off_the_input_and_the_platform(
        monkeypatch, backend, shape, chunk, dtype, interpret, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b, s, hk, h, dk, dv = shape
    k = jax.ShapeDtypeStruct((b, s, hk, dk), dtype)
    v = jax.ShapeDtypeStruct((b, s, h, dv), dtype)
    assert gdn._use_kernels(k, v, chunk, dtype, interpret) is want


def test_every_kernel_of_the_mixers_gradient_lies_under_the_scan_scope(
        monkeypatch):
    """``gdn_scan_ms.qwen3next`` and ``gdn_scan_roofline_pct.qwen3next``
    read the ``gdn_scan`` scope inside ``gdn_mixer``: the forward kernel
    and the backward kernel of a mixer's gradient both carry it."""
    options = BlockOptions(norm="rmsnorm", gdn_key_heads=HK,
                           gdn_value_heads=H, gdn_key_dim=D,
                           gdn_value_dim=D, gdn_chunk=CHUNK)
    mixer = GatedDeltaMixer(options=options, dtype=jnp.bfloat16)
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
    assert sorted(name for name, _ in calls) == ["_gdn_backward",
                                                 "_gdn_forward"]
    for name, stack in calls:
        assert f"/{gdn.GDN_SCAN_SCOPE}/" in stack, stack
        assert stack.index("gdn_mixer") < stack.index(gdn.GDN_SCAN_SCOPE)
    backward, = (s for name, s in calls if name == "_gdn_backward")
    assert "transpose(" in backward
