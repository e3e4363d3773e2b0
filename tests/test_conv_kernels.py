"""The convolution's backward kernel (``ops/conv_kernels.py``),
interpreted on the CPU: ``dx``, ``dtaps`` and ``dbias`` against
``jax.grad`` of the XLA form, several tiles a sequence, a column range
of a wider ``x``, causality of ``dx``, which calls take which gradient,
the forward bit for bit, the device scope the kernel is traced under and
the static account of a launch."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import conv_kernels
from chainermn_tpu.ops import ssd_scan as ssd

CASES = {
    # four tiles a sequence: both halos inside, both ends of the sequence
    "taps4_bias_four_tiles": dict(b=2, s=64, c=128, k=4, bias=True,
                                  tile=(16, 128)),
    "taps2_two_tiles_two_channel_tiles": dict(b=2, s=64, c=256, k=2,
                                              bias=False, tile=(32, 128)),
    # columns 128..256 of an x of 384
    "taps4_columns_at_128": dict(b=2, s=64, c=128, k=4, bias=True,
                                 start=128, total=384, tile=(16, 128)),
    "taps4_no_bias_columns_at_256": dict(b=1, s=48, c=256, k=4, bias=False,
                                         start=256, total=640,
                                         tile=(16, 256)),
    # the most taps, the tile the sizes choose for themselves
    "taps8_own_tile": dict(b=1, s=48, c=128, k=8, bias=True),
    "one_tap": dict(b=1, s=32, c=128, k=1, bias=True, tile=(16, 128)),
}


def _inputs(b, s, c, k, bias, dtype=jnp.float32, start=0, total=None,
            seed=0, **_):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (b, s, total or c)).astype(dtype)
    taps = jax.random.uniform(keys[1], (k, c), jnp.float32, -0.5, 0.5)
    bias = jax.random.normal(keys[2], (c,)) if bias else None
    dy = jax.random.normal(keys[3], (b, s, c)).astype(dtype)
    return x, taps, bias, dy


def _autodiff(x, taps, bias, dy, start=0):
    """The cotangents autodiff gives the XLA form, ``dx`` over the
    convolved columns."""
    columns = slice(start, start + taps.shape[1])
    _, vjp = jax.vjp(lambda x, taps, bias: ssd._conv_xla(
        x[..., columns], taps, bias, True), x, taps, bias)
    dx, dtaps, dbias = vjp(dy)
    return dx[..., columns], dtaps, dbias


def _close(got, want, tol):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("leaf", ["dx", "dtaps", "dbias"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_gradient_is_autodiffs(case, leaf):
    cfg = CASES[case]
    x, taps, bias, dy = _inputs(**cfg)
    start = cfg.get("start", 0)
    got = conv_kernels.conv_backward(x, dy, taps, bias, start,
                                     interpret=True, tile=cfg.get("tile"))
    want = _autodiff(x, taps, bias, dy, start)
    at = ["dx", "dtaps", "dbias"].index(leaf)
    if want[at] is None:  # no bias, no gradient for one
        assert got[at] is None
        return
    assert got[at].shape == want[at].shape
    assert got[at].dtype == want[at].dtype
    _close(got[at], want[at], 2e-6)


@pytest.mark.parametrize("leaf", ["dx", "dtaps", "dbias"])
def test_kernel_gradient_in_bfloat16_rounds_dx_once(leaf):
    """The cell's precisions: ``x``, ``dy`` and ``dx`` bfloat16, the taps
    and their gradients float32 sums."""
    cfg = dict(b=2, s=128, c=256, k=4, bias=True, dtype=jnp.bfloat16)
    x, taps, bias, dy = _inputs(**cfg)
    got = conv_kernels.conv_backward(x, dy, taps, bias, interpret=True,
                                     tile=(32, 128))
    exact = _autodiff(*(t.astype(jnp.float32) for t in (x, taps, bias, dy)))
    at = ["dx", "dtaps", "dbias"].index(leaf)
    assert got[at].dtype == (jnp.bfloat16 if leaf == "dx" else jnp.float32)
    # dx one rounding to 8 bits away from the float32 gradient (whose
    # forward rounds the pre-activation; the kernel's does not)
    _close(got[at], exact[at], 2 ** -7 if leaf == "dx" else 2e-3)


@pytest.mark.parametrize("t", [0, 15, 16, 31, 47, 63])
def test_dx_is_causal_and_k_wide(t):
    """A change of ``dy`` at position ``t`` moves ``dx`` at ``t - (k - 1)
    .. t`` and nowhere else, across tile edges and at both ends."""
    cfg = dict(b=1, s=64, c=128, k=4, bias=True)
    x, taps, bias, dy = _inputs(**cfg)
    grad = functools.partial(conv_kernels.conv_backward, x, taps=taps,
                             bias=bias, interpret=True, tile=(16, 128))
    moved = np.asarray(grad(dy=dy.at[:, t].add(1.0))[0] - grad(dy=dy)[0])
    rows = np.flatnonzero(np.abs(moved[0]).max(-1))
    assert rows.min() >= max(t - 3, 0) and rows.max() <= t
    assert set(rows) >= {max(t - 3, 0), t}


def _grad(silu_conv, x, taps, bias):
    weigh = jnp.cos(jnp.arange(x.shape[1] * taps.shape[1], dtype=jnp.float32)
                    ).reshape(1, x.shape[1], taps.shape[1])
    return jax.grad(lambda *a: (silu_conv(*a).astype(jnp.float32)
                                * weigh).sum(), argnums=(0, 1, 2))(
        x, taps, bias)


@pytest.mark.parametrize("case,cfg", [
    # what the predicate refuses off the chip: every call
    ("off_the_tpu", dict(b=1, s=64, c=128, k=4)),
    # and what it refuses wherever it is asked (interpret=True)
    ("channels_100", dict(b=1, s=64, c=100, k=4, interpret=True)),
    ("ragged_length", dict(b=1, s=50, c=128, k=4, interpret=True)),
    ("nine_taps", dict(b=1, s=64, c=128, k=9, interpret=True)),
    ("columns_at_64", dict(b=1, s=64, c=128, k=4, start=64, total=256,
                           interpret=True)),
])
def test_refused_calls_fall_back_to_autodiff(case, cfg):
    interpret = cfg.pop("interpret", None)
    x, taps, bias, _ = _inputs(bias=True, **cfg)
    start, c = cfg.get("start", 0), cfg["c"]
    assert not ssd._use_conv_kernel(x, taps, start, interpret)
    conv = functools.partial(ssd.causal_conv1d, silu=True,
                             first_column=start, interpret=interpret)
    text = str(jax.make_jaxpr(functools.partial(_grad, conv))(x, taps, bias))
    assert "pallas_call" not in text and "custom_vjp" not in text
    want = _grad(lambda x, taps, bias: jax.nn.silu(ssd.causal_conv1d(
        x[..., start:start + c], taps, bias)), x, taps, bias)
    for got_leaf, want_leaf in zip(_grad(conv, x, taps, bias), want):
        np.testing.assert_array_equal(got_leaf, want_leaf)


def test_float32_on_a_tpu_is_refused_and_bfloat16_taken(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, taps, _, _ = _inputs(b=1, s=64, c=128, k=4, bias=False)
    assert not ssd._use_conv_kernel(x, taps, 0, None)
    assert ssd._use_conv_kernel(x.astype(jnp.bfloat16), taps, 0, None)
    assert not ssd._use_conv_kernel(x.astype(jnp.bfloat16)[:, :50], taps, 0,
                                    None)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_activated_convolution_differentiates_through_the_kernel(dtype,
                                                                 bias):
    """``causal_conv1d(silu=True, interpret=True)`` over a column range:
    the forward bit for bit the plain form's, the gradient the kernel's,
    ``x``'s over the whole width with zeros beside the range."""
    cfg = dict(b=2, s=64, c=128, k=4, bias=bias, start=128, total=384,
               dtype=dtype)
    x, taps, bias, _ = _inputs(**cfg)
    conv = functools.partial(ssd.causal_conv1d, silu=True, first_column=128,
                             interpret=True)
    plain = lambda x, taps, bias: jax.nn.silu(
        ssd.causal_conv1d(x[..., 128:256], taps, bias))
    np.testing.assert_array_equal(conv(x, taps, bias), plain(x, taps, bias))
    np.testing.assert_array_equal(jax.jit(conv)(x, taps, bias),
                                  jax.jit(plain)(x, taps, bias))
    got, want = _grad(conv, x, taps, bias), _grad(plain, x, taps, bias)
    assert got[0].shape == x.shape and got[0].dtype == dtype
    np.testing.assert_array_equal(got[0][..., :128], 0)
    np.testing.assert_array_equal(got[0][..., 256:], 0)
    tol = 2e-6 if dtype == jnp.float32 else 2 ** -6
    for got_leaf, want_leaf in zip(got, want):
        if want_leaf is not None:
            _close(got_leaf, want_leaf, tol)
    assert got[2] is None if bias is None else got[2].shape == (128,)


def test_kernel_is_traced_under_the_callers_scope_and_recomputation():
    """Under ``jax.checkpoint`` the gradient is still the kernel, traced
    under the scope the caller names, and its residuals are the
    operands: no float32 tensor and none the size of ``x`` but ``x``."""
    x, taps, bias, _ = _inputs(b=1, s=64, c=128, k=4, bias=True,
                               dtype=jnp.bfloat16)
    conv = jax.checkpoint(functools.partial(
        ssd.causal_conv1d, silu=True, scope="kda_conv", interpret=True))
    lowered = jax.jit(functools.partial(_grad, conv)).lower(x, taps, bias)
    text = lowered.as_text(debug_info=True)
    assert "kda_conv/_conv_backward" in text.replace("/pallas_call", "")
    _, residuals = ssd._conv_silu_fwd(x, taps, bias, 0, True)
    assert [r.shape for r in residuals] == [x.shape, taps.shape, bias.shape]
    assert residuals[0].dtype == jnp.bfloat16


#: the cells' launches: (b, s, channels, start, columns of x, bias)
CELLS = {
    "kimilinear48b": (2, 8192, 12288, 0, 12288, False),
    "qwen3next80b": (2, 8192, 8192, 0, 12288, False),
    "granite4hmicro": (1, 8192, 4352, 4096, 8512, True),
}


@pytest.mark.parametrize("cell", CELLS)
def test_launch_account_at_the_cells_shapes(cell):
    b, s, c, start, total, bias = CELLS[cell]
    account = conv_kernels.launch_account(b, s, c, 4, start, total, bias)
    rows, cols = account["tile"]
    assert (rows, cols) == conv_kernels.tiles(s, c, start, 4)
    assert c % cols == 0 and start % cols == 0 and s % rows == 0
    assert account["grid"] == (c // cols, b, s // rows)
    assert account["vmem_bytes"] < account["vmem_limit"] \
        == conv_kernels.VMEM_LIMIT_BYTES
    # x and dy read and dx written once; beside them the slabs (16 rows
    # a tile of each) and the weights with their gradient's partial sums
    assert 1.0 < account["hbm_over_least"] < 1.0 + 2 * 16 / rows
    assert account["hbm_bytes"] > 3 * b * s * c * 2


def test_tiles_are_whole_lane_tiles_and_slabs():
    assert conv_kernels.tiles(8192, 12288) == (1024, 512)
    assert conv_kernels.tiles(8192, 4352, 4096) == (2048, 256)
    assert conv_kernels.tiles(8192, 4352, 128) == (4096, 128)
    assert conv_kernels.tiles(48, 128) == (16, 128)
    assert conv_kernels.tiles(96, 128) == (32, 128)
    for refused in ((8192, 100), (8200, 128), (8192, 128, 64),
                    (8192, 128, 0, 9), (8192, 0)):
        assert conv_kernels.tiles(*refused) is None

