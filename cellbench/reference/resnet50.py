"""Plain reference for ResNet-50 (bottleneck blocks [3, 4, 6, 3],
224 px, train-mode BatchNorm) with momentum SGD.

Straight ``jax.numpy``/``lax`` in float32 with precision ``highest``:
no flax, nothing imported from the program.  It makes the seeded
weights, follows the first steps on whole batches (BatchNorm's
statistics couple the rows) with each block recomputed in the backward
pass, and can run the same mathematics with every convolution and
matmul operand rounded to scaled float8 -- the *control* of ``correct``.

Departure from He et al. Table 1, shared with the program
(``chainermn_tpu/models/resnet.py``): a down-sampling block strides its
3x3 convolution, not its first 1x1 (the "v1.5" placement).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import SMALL, fp8, seed_key  # noqa: F401 (runners use them)

STAGES = (3, 4, 6, 3)
BN_EPS = 1e-5
_HI = lax.Precision.HIGHEST


def _shapes(cfg: dict) -> dict:
    """Leaf name (``/``-joined path below the program's ``params``) ->
    shape, in the order the forward pass uses them."""
    f0, out = cfg["num_filters"], {}
    out["conv_init/kernel"] = (7, 7, 3, f0)
    out["BatchNorm_0/scale"] = out["BatchNorm_0/bias"] = (f0,)
    c_in, i = f0, 0
    for stage, count in enumerate(STAGES):
        f = f0 * 2 ** stage
        for j in range(count):
            b = f"Bottleneck_{i}"
            out[f"{b}/Conv_0/kernel"] = (1, 1, c_in, f)
            out[f"{b}/Conv_1/kernel"] = (3, 3, f, f)
            out[f"{b}/Conv_2/kernel"] = (1, 1, f, 4 * f)
            widths = [f, f, 4 * f]
            if j == 0:  # projection shortcut
                out[f"{b}/Conv_3/kernel"] = (1, 1, c_in, 4 * f)
                widths.append(4 * f)
            for k, w in enumerate(widths):
                out[f"{b}/BatchNorm_{k}/scale"] = (w,)
                out[f"{b}/BatchNorm_{k}/bias"] = (w,)
            c_in, i = 4 * f, i + 1
    out["Dense_0/kernel"] = (c_in, cfg["num_classes"])
    out["Dense_0/bias"] = (cfg["num_classes"],)
    return out


def leaf_keys(cfg: dict):
    return list(_shapes(cfg))


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights, where a training job starts: convolutions
    and the classifier N(0, 2 / fan_in); biases 0 and BatchNorm gains 1
    -- 0 for the last BatchNorm of a block's residual branch, the usual
    zero-initialised residual, which the program's model has too -- each
    with N(0, 0.02) added, so that no term is exactly 0 or 1 and a term
    the program dropped would show."""
    out = {}
    for i, (name, shape) in enumerate(_shapes(cfg).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        if name.endswith("kernel"):
            out[name] = n * float(np.sqrt(2.0 / np.prod(shape[:-1])))
        elif name.endswith("BatchNorm_2/scale"):
            out[name] = 0.02 * n
        elif name.endswith("scale"):
            out[name] = 1.0 + 0.02 * n
        else:
            out[name] = 0.02 * n
    return out


def _conv(x, k, stride, pad, lowp):
    if lowp:
        x, k = fp8(x), fp8(k)
    return lax.conv_general_dilated(
        x, k, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)


def _bn(x, w, name):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * w[name + "/scale"] \
        + w[name + "/bias"]


def _bottleneck(x, w, b, stride, lowp):
    y = jax.nn.relu(_bn(_conv(x, w[f"{b}/Conv_0/kernel"], 1, 0, lowp),
                        w, f"{b}/BatchNorm_0"))
    y = jax.nn.relu(_bn(_conv(y, w[f"{b}/Conv_1/kernel"], stride, 1, lowp),
                        w, f"{b}/BatchNorm_1"))
    y = _bn(_conv(y, w[f"{b}/Conv_2/kernel"], 1, 0, lowp),
            w, f"{b}/BatchNorm_2")
    if f"{b}/Conv_3/kernel" in w:
        x = _bn(_conv(x, w[f"{b}/Conv_3/kernel"], stride, 0, lowp),
                w, f"{b}/BatchNorm_3")
    return jax.nn.relu(y + x)


def logits_fn(w, images, lowp=False):
    """``(n, h, w, 3)`` float32 images -> ``(n, classes)`` logits, with
    BatchNorm on the batch's own statistics (train mode)."""
    x = _conv(images, w["conv_init/kernel"], 2, 3, lowp)
    x = jax.nn.relu(_bn(x, w, "BatchNorm_0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    i = 0
    for stage, count in enumerate(STAGES):
        for j in range(count):
            b = f"Bottleneck_{i}"
            sub = {k: v for k, v in w.items() if k.startswith(b + "/")}
            stride = 2 if stage > 0 and j == 0 else 1
            x = jax.checkpoint(functools.partial(
                _bottleneck, b=b, stride=stride, lowp=lowp))(x, sub)
            i += 1
    x = x.mean((1, 2))
    if lowp:
        return jnp.matmul(fp8(x), fp8(w["Dense_0/kernel"]),
                          precision=_HI) + w["Dense_0/bias"]
    return jnp.matmul(x, w["Dense_0/kernel"], precision=_HI) \
        + w["Dense_0/bias"]


def loss_fn(w, images, labels, lowp=False):
    logits = logits_fn(w, images, lowp)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - picked).mean()


def small_leaves(tree: dict) -> dict:
    return {k: v for k, v in tree.items() if v.size <= SMALL}


def train_readings(seed, cfg, batches, optimizer, lowp=False):
    """Follow the first ``len(batches)`` momentum-SGD steps from the
    seeded weights.  ``batches``: ``[(images, labels), ...]`` as the
    program was fed them.  Returns host floats: ``losses``,
    ``grad_norms`` (first step, per leaf), ``grad_small`` (the first
    gradient's small leaves, whole) and ``delta_norms`` (parameters after
    the last step minus the seeded ones, per leaf)."""
    lr, mom = optimizer["lr"], optimizer["momentum"]
    tm = jax.tree_util.tree_map

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(w, trace, images, labels):
        loss, g = jax.value_and_grad(loss_fn)(w, images, labels, lowp)
        first = (tm(jnp.linalg.norm, g), small_leaves(g))
        trace = tm(lambda t, g: g + mom * t, trace, g)
        return tm(lambda p, t: p - lr * t, w, trace), trace, loss, first

    key = seed_key(seed)

    @jax.jit
    def delta_norms(w, key):
        return tm(lambda a, b: jnp.linalg.norm(a - b), w,
                  init_weights(key, cfg))

    w = jax.jit(lambda k: init_weights(k, cfg))(key)
    trace = tm(jnp.zeros_like, w)
    losses, grad_norms, grad_small = [], None, None
    for t, (images, labels) in enumerate(batches):
        w, trace, loss, (norms, small) = step(w, trace, jnp.asarray(images),
                                              jnp.asarray(labels))
        losses.append(float(loss))
        if t == 0:
            grad_norms = {k: float(x) for k, x in norms.items()}
            grad_small = {k: np.asarray(x) for k, x in small.items()}
    deltas = {k: float(x) for k, x in delta_norms(w, key).items()}
    del w, trace
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_small": grad_small, "delta_norms": deltas}
