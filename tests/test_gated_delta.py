"""The chunked gated delta rule (``ops/gated_delta.py``) against the
recurrence it stands for, position by position; its census by hand."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops import gated_delta
from chainermn_tpu.ops.gated_delta import (
    gated_delta_census,
    gated_delta_scan,
)


def recurrence(q, k, v, g, beta):
    """``S <- e^g S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q``, a
    position after another."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    q, k = (jnp.repeat(t, h // hk, axis=2) for t in (q, k))

    def one(S, at):
        q, k, v, g, beta = at
        S = jnp.exp(g)[..., None, None] * S
        delta = (v - jnp.einsum("bhkv,bhk->bhv", S, k)) * beta[..., None]
        S = S + k[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    _, o = lax.scan(one, jnp.zeros((b, h, dk, dv)),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _operands(s, seed=0, b=2, hk=2, h=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(n(b, s, hk, dk)) * dk ** -0.5, unit(n(b, s, hk, dk)),
            n(b, s, h, dv), -0.3 * jnp.exp(n(b, s, h)),
            jax.nn.sigmoid(n(b, s, h)))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("s", [128, 150], ids=["whole_chunks", "off_boundary"])
def test_chunked_scan_is_the_recurrence(s, chunk):
    """Values and all five gradients, at lengths on and off a chunk
    boundary and two chunk sizes, float32 products."""
    args = _operands(s)
    got = gated_delta_scan(*args, chunk=chunk, dtype=jnp.float32)
    want = recurrence(*args)
    assert got.shape == want.shape == (2, s, 4, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)
    weight = jnp.cos(jnp.arange(8.0))
    grads = jax.grad(lambda *a: (gated_delta_scan(
        *a, chunk=chunk, dtype=jnp.float32) * weight).sum(),
        argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert float(jnp.abs(g - w).max()) \
            < 2e-5 * float(jnp.abs(w).max()), name


def test_bfloat16_products_stay_near_the_recurrence():
    """Operands rounded to bfloat16, sums, decays, the solve and the
    carried state float32: a per cent of the largest element."""
    args = _operands(192, seed=3)
    got = gated_delta_scan(*args, chunk=64, dtype=jnp.bfloat16)
    want = recurrence(*args)
    assert got.dtype == jnp.float32  # v's
    assert float(jnp.abs(got - want).max()) \
        < 0.02 * float(jnp.abs(want).max())


def test_strong_decay_and_full_writes_stay_finite():
    """``g`` far below zero (a state forgotten inside a chunk: its decay
    underflows to 0, never overflows) and ``beta = 1``."""
    q, k, v, g, beta = _operands(128, seed=5)
    g, beta = 40.0 * g, jnp.ones_like(beta)
    got = gated_delta_scan(q, k, v, g, beta, chunk=64, dtype=jnp.float32)
    grads = jax.grad(lambda *a: gated_delta_scan(
        *a, chunk=64, dtype=jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(
        q, k, v, g, beta)
    assert all(bool(jnp.isfinite(t).all()) for t in (got, *grads))
    # a running sum of thousands rounds its differences: 1e-4 of them
    want = recurrence(q, k, v, g, beta)
    assert float(jnp.abs(got - want).max()) \
        < 2e-3 * float(jnp.abs(want).max())


def test_inverse_of_a_unit_lower_triangle():
    rng = np.random.default_rng(1)
    for c in (16, 64):
        a = jnp.tril(jnp.asarray(rng.standard_normal((3, c, c)),
                                 jnp.float32) * 0.2, -1)
        got = gated_delta._inverse_unit_lower(a)
        want = jnp.linalg.inv(jnp.eye(c) + a)
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_scan_refuses_value_heads_no_key_head_serves():
    q, k, v, g, beta = _operands(64, hk=3, h=4)
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_scan(q, k, v, g, beta)


def test_census_at_the_cells_shape_by_hand():
    """8192 positions, chunk 64, 16 key and 32 value heads of 128."""
    census = gated_delta_census(8192, 64, 32, 128, 128, key_heads=16)
    assert (census["chunks"], census["padded"]) == (128, 8192)
    parts = census["flops"]
    assert parts["kk"] == parts["qk"] == 2 * 128 * 16 * 64 * 64 * 128
    assert parts["solve"] == 128 * 32 * 64 * 64 * 256
    assert parts["read"] == parts["from_state"] == parts["state"] \
        == 2 * 128 * 32 * 64 * 128 * 128
    assert parts["inside"] == 2 * 128 * 32 * 64 * 64 * 128
    assert census["flops_forward"] == sum(parts.values()) \
        == pytest.approx(38.7e9, rel=0.01)
    assert census["flops_backward"] == 2 * census["flops_forward"] \
        + parts["kk"] + parts["qk"]
    assert census["bytes_forward"] == 8192 * (
        (2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 4 * 32)
    # off a boundary the last chunk is padded
    assert gated_delta_census(100, 64, 4, 16, 8)["padded"] == 128


def test_census_accounts_for_the_kernels_at_the_cells_shape():
    """Hand-worked: 16 key heads over 32 grid points of 4 chunks; a grid
    point's tiles of ``q`` and ``k`` are 256 x 128 bfloat16 = 64 KiB,
    of ``v`` and ``o`` 256 x 256 = 128 KiB, the two heads' entering
    states of its four chunks 4 x 2 x 128 x 128 float32 = 512 KiB, their
    ``T`` 4 x 2 x 64 x 64 = 128 KiB (256 in VMEM, 64 lanes padded to
    128), the running sums and ``beta`` 4 x 8 x 64 float32 = 8 KiB (16
    in VMEM); the two states in scratch 128 KiB."""
    from chainermn_tpu.ops import gated_delta_kernels

    kib = 1024
    census = gated_delta_census(8192, 64, 32, 128, 128, key_heads=16)
    got = census["kernels"]
    points = 16 * 32
    assert got["forward"]["grid"] == got["backward"]["grid"] == (1, 16, 32)
    assert got["forward"]["tiles"] == got["backward"]["tiles"] == points
    # forward: q, k | v, o | rows | states | inverses
    assert got["forward"]["vmem_bytes"] == (
        2 * (2 * 64 + 2 * 128 + 16 + 512 + 256) + 128) * kib
    assert got["forward"]["hbm_bytes"] == points * (
        2 * 64 + 2 * 128 + 8 + 512 + 128) * kib
    # backward: q, k, dq, dk | v, do, dv | rows, drows | states | inverses
    assert got["backward"]["vmem_bytes"] == (
        2 * (4 * 64 + 3 * 128 + 2 * 16 + 512 + 256) + 128) * kib
    assert got["backward"]["hbm_bytes"] == points * (
        4 * 64 + 3 * 128 + 2 * 8 + 512 + 128) * kib
    for launch in got.values():
        assert launch["hbm_over_least"] == pytest.approx(
            launch["hbm_bytes"] / census["bytes_forward"])
    # the entering states and T are the traffic: 2.7x and 3.3x the least
    assert 2.6 < got["forward"]["hbm_over_least"] < 2.7
    assert 3.3 < got["backward"]["hbm_over_least"] < 3.4
    # far under the 16 MiB a kernel may use by default
    assert got["backward"]["vmem_bytes"] < 4 * 2 ** 20
    # a length that is no whole grid point is padded to one
    assert gated_delta_census(8200, 64, 32, 128, 128, key_heads=16)[
        "kernels"]["forward"]["grid"] == (1, 16, 33)
    # sizes the kernels do not tile have no account
    assert gated_delta_census(100, 16, 4, 16, 8, key_heads=2)[
        "kernels"] is None
    assert gated_delta_kernels.tiles(64, 32, 16, 128, 128)
    assert not gated_delta_kernels.tiles(16, 4, 2, 16, 8)
    assert not gated_delta_kernels.tiles(64, 32, 4, 128, 128)
