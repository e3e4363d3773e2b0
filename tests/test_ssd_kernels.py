"""The chunked scan's Pallas kernels (``ops/ssd_kernels.py``), interpreted
on the CPU: against the XLA form of ``ops.ssd_scan`` and against the
position-by-position recurrence, value and every gradient; padding, two
sequences in a batch, which sizes take which path, the device scopes the
kernels are traced under, and the static account of a launch."""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench.reference import granite_hybrid as ref  # noqa: E402
from chainermn_tpu.models.transformer import (  # noqa: E402
    BlockOptions,
    Mamba2Mixer,
)
from chainermn_tpu.ops import ssd_kernels  # noqa: E402
from chainermn_tpu.ops import ssd_scan as ssd  # noqa: E402

H, P, N = 8, 64, 128  # the smallest sizes the kernels tile
LEAVES = "x dt A B C D".split()

CASES = {
    # decays near 1 and near 0 among the heads, two chunks
    "float32_two_chunks": dict(s=256, b=1, chunk=128, dtype=jnp.float32,
                               seeded_rates=False),
    # a length that is no multiple of the chunk, two sequences
    "float32_ragged_batch_of_two": dict(s=300, b=2, chunk=128,
                                        dtype=jnp.float32,
                                        seeded_rates=False),
    # the cell's chunk and precisions, rates and steps as the
    # configuration seeds them
    "bfloat16_chunk_256": dict(s=512, b=1, chunk=256, dtype=jnp.bfloat16,
                               seeded_rates=True),
}


def _inputs(s, b, dtype, seeded_rates, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    if seeded_rates:  # A in -[1, 16], dt log-uniform in [1e-3, 1e-1]
        step = jnp.exp(jax.random.uniform(
            k[1], (H,), minval=np.log(1e-3), maxval=np.log(1e-1)))
        dt = jax.nn.softplus(jax.random.normal(k[2], (b, s, H)) * 0.5
                             + jnp.log(jnp.expm1(step)))
        A = -jax.random.uniform(k[3], (H,), minval=1.0, maxval=16.0)
    else:
        dt = jax.random.uniform(k[1], (b, s, H), jnp.float32, 0.5, 1.5)
        A = jnp.log(jnp.asarray([0.999, 0.99, 0.9, 0.7, 0.5, 0.1, 1e-2,
                                 1e-4], jnp.float32))
    return ((0.5 * jax.random.normal(k[0], (b, s, H, P))).astype(dtype),
            dt, A, jax.random.normal(k[4], (b, s, N)).astype(dtype),
            jax.random.normal(k[5], (b, s, N)).astype(dtype),
            jax.random.normal(k[6], (H,)))


def _value_and_gradients(f, args):
    weigh = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)
    y = f(*args)
    grads = jax.grad(lambda *a: (f(*a).astype(jnp.float32) * weigh).sum(),
                     argnums=range(6))(*args)
    return dict(zip(["y", *LEAVES], (y, *grads)))


@functools.lru_cache(maxsize=None)
def _forms(case):
    """The case's value and gradients by the kernels (interpreted), by
    the XLA form, and by the recurrence in float32.  Each case's first
    leaf pays for all six (over 40 s in the driver's run: the kernels'
    interpreter runs a grid point at a time, the recurrence a token at
    a time), and nothing cheaper sets the kernels against both."""
    c = CASES[case]
    args = _inputs(c["s"], c["b"], c["dtype"], c["seeded_rates"])
    scan = lambda interpret: functools.partial(
        ssd.ssd_scan, chunk=c["chunk"], dtype=c["dtype"],
        interpret=interpret)
    recurrence = lambda *a: jax.vmap(
        ref.recurrence, in_axes=(0, 0, None, 0, 0, None))(*a)
    return (_value_and_gradients(scan(True), args),
            _value_and_gradients(scan(None), args),
            _value_and_gradients(
                recurrence, tuple(a.astype(jnp.float32) for a in args)))


def _gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("leaf", ["y", *LEAVES])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_xla_form_value_and_gradients(case, leaf):
    kernels, xla, _ = _forms(case)
    got, want = kernels[leaf], xla[leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    if leaf == "y":
        # the same products of the same rounded operands
        limit = 1e-6 if CASES[case]["dtype"] == jnp.float32 else 0.008
    else:
        # the backward rounds other intermediates than autodiff's does
        limit = 2e-5 if CASES[case]["dtype"] == jnp.float32 else 0.02
    assert _gap(got, want) < limit


@pytest.mark.parametrize("leaf", ["y", *LEAVES])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_recurrence_value_and_gradients(case, leaf):
    """As near the step-by-step recurrence as the XLA form is: the
    gradient of the rates ``A`` is a sum of differences that cancel, and
    a backward that rounds the two sides apart loses it (it reads 0.2
    here where the XLA form reads 0.002)."""
    kernels, xla, recurrence = _forms(case)
    gap = _gap(kernels[leaf], recurrence[leaf])
    if CASES[case]["dtype"] == jnp.float32:
        assert gap < (2e-5 if leaf == "y" else 1e-3)
    else:
        assert gap < 0.02
        assert gap < 2 * _gap(xla[leaf], recurrence[leaf]) + 2e-3


def _kernel_scan(*args, chunk=128):
    return ssd.ssd_scan(*args, chunk=chunk, dtype=jnp.float32,
                        interpret=True)


def test_padding_rows_leave_every_state_as_it_was_in_the_kernels():
    args = _inputs(256, 1, jnp.float32, False)
    whole = _kernel_scan(*args)
    cut = _kernel_scan(*(a[:, :200] if a.ndim > 1 else a for a in args))
    np.testing.assert_allclose(cut, whole[:, :200], rtol=1e-5, atol=1e-5)


def test_the_carried_state_is_reset_between_the_sequences_of_a_batch():
    args = _inputs(256, 2, jnp.float32, False, seed=1)
    both = _kernel_scan(*args)
    for i in range(2):
        alone = _kernel_scan(
            *(a[i:i + 1] if a.ndim > 1 else a for a in args))
        np.testing.assert_allclose(both[i:i + 1], alone, rtol=1e-6,
                                   atol=1e-6)


def _pallas_calls(jaxpr, prefix=""):
    """``(kernel name, name stack)`` of every ``pallas_call`` under
    ``jaxpr``, an inner equation's stack after its callers'."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield stack.rsplit("/", 1)[-1], stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner, stack)


def test_sizes_that_do_not_tile_fall_to_the_xla_form():
    """Asked for interpreted kernels at the other tests' sizes (chunk 32,
    4 heads of 8, state 16), the scan runs its XLA form: the same result
    and no ``pallas_call``."""
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    args = (jax.random.normal(k[0], (2, 70, 4, 8)),
            jax.random.uniform(k[1], (2, 70, 4), jnp.float32, 0.5, 1.5),
            -jax.random.uniform(k[2], (4,), jnp.float32, 0.1, 1.0),
            jax.random.normal(k[3], (2, 70, 16)),
            jax.random.normal(k[4], (2, 70, 16)), jnp.ones((4,)))
    asked = functools.partial(ssd.ssd_scan, chunk=32, dtype=jnp.float32,
                              interpret=True)
    np.testing.assert_array_equal(
        asked(*args), ssd.ssd_scan(*args, chunk=32, dtype=jnp.float32))
    assert not list(_pallas_calls(jax.make_jaxpr(asked)(*args).jaxpr))


@pytest.mark.parametrize("backend,shape,chunk,dtype,interpret,kernels", [
    # the cell's launch: chunk 256, 64 heads of 64, state 128, bfloat16
    ("tpu", (1, 8192, 64, 64, 128), 256, jnp.bfloat16, None, True),
    ("tpu", (2, 1000, 8, 64, 128), 128, jnp.bfloat16, None, True),
    ("cpu", (1, 8192, 64, 64, 128), 256, jnp.bfloat16, None, False),
    ("cpu", (1, 8192, 64, 64, 128), 256, jnp.bfloat16, True, True),
    # float32 operands on a TPU: the tiles are sized for two bytes
    ("tpu", (1, 8192, 64, 64, 128), 256, jnp.float32, None, False),
    ("tpu", (1, 8192, 64, 64, 128), 256, jnp.float32, True, True),
    # a chunk, a head count, a head width, a state that do not tile
    ("tpu", (1, 8192, 64, 64, 128), 64, jnp.bfloat16, None, False),
    ("tpu", (1, 8192, 64, 64, 128), 512, jnp.bfloat16, None, False),
    ("tpu", (1, 8192, 4, 64, 128), 256, jnp.bfloat16, None, False),
    ("tpu", (1, 8192, 32, 128, 128), 256, jnp.bfloat16, None, False),
    ("tpu", (1, 8192, 64, 64, 64), 256, jnp.bfloat16, True, False),
])
def test_which_path_runs_is_read_off_the_input_and_the_platform(
        monkeypatch, backend, shape, chunk, dtype, interpret, kernels):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b, s, h, p, n = shape
    x = jax.ShapeDtypeStruct((b, s, h, p), dtype)
    B = jax.ShapeDtypeStruct((b, s, n), dtype)
    assert ssd._use_kernels(x, B, chunk, dtype, interpret) is kernels


def test_every_kernel_of_the_mixers_gradient_lies_under_the_scan_scope(
        monkeypatch):
    """``ssm_scan_ms.granite`` and ``ssm_scan_roofline_pct.granite`` read
    the ``ssm_scan`` scope inside ``ssm_mixer``: the forward kernel and
    the backward kernel of a mixer's gradient both carry it."""
    options = BlockOptions(norm="rmsnorm", ssm_heads=H, ssm_head_dim=P,
                           ssm_state=N, ssm_chunk=128)
    mixer = Mamba2Mixer(options=options, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 384, 64), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), x))
    # as on a TPU: tracing builds the kernels, nothing runs them
    monkeypatch.setattr(ssd, "_use_kernels", lambda *a: True)
    ssd.ssd_scan.clear_cache()
    try:
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p, x: mixer.apply(p, x).astype(jnp.float32).sum(),
            (0, 1)))(params, x)
    finally:
        ssd.ssd_scan.clear_cache()
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert sorted(name for name, _ in calls) == ["_ssd_backward",
                                                 "_ssd_forward"]
    for name, stack in calls:
        assert name.startswith("_ssd_")
        assert f"/{ssd.SSM_SCAN_SCOPE}/" in stack, stack
        assert stack.index("ssm_mixer") < stack.index(ssd.SSM_SCAN_SCOPE)
    backward, = (s for name, s in calls if name == "_ssd_backward")
    assert "transpose(" in backward


def test_census_accounts_for_the_kernels_at_the_cells_shape():
    """Hand-worked: 8 groups of 8 heads over 32 chunks of 256; a tile of
    ``x`` is 256 x 512 bfloat16 = 256 KiB, the group's states 4 x 128 x
    128 float32 = 256 KiB, ``B`` / ``C`` / their transposes 64 KiB, a
    ``(256, 8)`` float32 column form 128 KiB in VMEM (8 KiB in HBM), an
    ``(8, 256)`` row form 8 KiB."""
    kib = 1024
    got = ssd.ssd_census(8192, 256, 64, 64, 128)["kernels"]
    points = 8 * 32
    assert got["forward"]["grid"] == got["backward"]["grid"] == (1, 8, 32)
    assert got["forward"]["tiles"] == got["backward"]["tiles"] == points
    # forward: x, y, states | B, C, Bt | 2 column forms | 2 row forms | D
    blocks = (3 * 256 + 3 * 64 + 2 * 128 + 2 * 8 + 16) * kib
    assert got["forward"]["vmem_bytes"] == 2 * blocks + 256 * kib
    moved = points * (3 * 256 + 3 * 64 + 4 * 8) * kib + 8 * 2 * kib
    assert got["forward"]["hbm_bytes"] == moved
    # backward: x, dy, dx, states | B, C, Ct | cum, dt columns and the
    # four float32 (256, 128)-padded results (dB, dC, ddt, dcum) | two
    # row forms | D and dD
    blocks = (4 * 256 + 3 * 64 + 6 * 128 + 2 * 8 + 2 * 16) * kib
    assert got["backward"]["vmem_bytes"] == 2 * blocks + 256 * kib
    moved = points * (4 * 256 + 3 * 64 + 2 * 128 + 6 * 8) * kib \
        + 8 * (2 + 16) * kib
    assert got["backward"]["hbm_bytes"] == moved
    least = ssd.ssd_census(8192, 256, 64, 64, 128)["bytes_forward"]
    assert got["forward"]["hbm_over_least"] == pytest.approx(
        got["forward"]["hbm_bytes"] / least)
    assert 1.8 < got["forward"]["hbm_over_least"] < 1.9
    # under the 16 MiB a kernel may use by default
    assert got["backward"]["vmem_bytes"] < 8 * 2 ** 20
    # sizes the kernels do not tile have no account
    assert ssd.ssd_census(100, 32, 4, 8, 16)["kernels"] is None
    assert ssd_kernels.tiles(256, 64, 64, 128)
    assert not ssd_kernels.tiles(32, 4, 8, 16)
