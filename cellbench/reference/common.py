"""What the plain references share: the key of ``--seed``, the float8
rounding of the control, and which leaves count as small."""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: leaves of at most this many elements are also compared element by
#: element (LayerNorm / BatchNorm gains and biases, biases)
SMALL = 16384


def seed_key(seed: int):
    """The key of ``--seed``, which may pass 2**31: two 31-bit words."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _scaled_fp8(x, dtype):
    """Round to a float8 format under a per-tensor scale that puts the
    largest magnitude at the format's largest, as an fp8 path would."""
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(
        jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """An operand as a float8 path holds it: e4m3 going forward, and its
    cotangent e5m2 coming back (the usual pairing), so that the backward
    pass's products take float8 operands too."""
    return _scaled_fp8(x, jnp.float8_e4m3fn)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_scaled_fp8(g, jnp.float8_e5m2),))
