"""Plain reference for the GPT-2-shaped decoder LM (Cerebras-GPT family).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program.  It makes the
seeded weights (the runner lays the same arrays out in the program's
tree), follows AdamW through the first steps a row at a time with each
block recomputed in the backward pass (so it fits beside nothing else on
one chip), and can run the same mathematics with every matmul operand
rounded to scaled float8 -- the *control* of ``correct``: the nearest precision
below the bfloat16 the configuration states.

Departures from the published block, all shared with the program
(``chainermn_tpu/models/transformer.py``): the attention projections
carry no bias, and GELU is the tanh form (flax ``nn.gelu`` default).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import SMALL, fp8, seed_key  # noqa: F401 (runners use them)

# AdamW as ``optax.adamw(lr, weight_decay=wd)`` defines it
B1, B2, EPS = 0.9, 0.999, 1e-8

#: stacked per-layer leaves, in the order a block uses them
LAYER_LEAVES = ("ln1_g", "ln1_b", "w_qkv", "w_o", "ln2_g", "ln2_b",
                "w_fc", "b_fc", "w_proj", "b_proj")
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b")


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights: matrices N(0, 0.02), biases N(0, 0.02),
    LayerNorm gains 1 + N(0, 0.02) -- nothing is left at a value (0, 1)
    that would hide a term the program drops.  Per-layer leaves are
    stacked on a leading ``n_layer`` axis."""
    d, L, V, S = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], \
        cfg["n_positions"]
    f = cfg["n_inner"]
    shapes = {
        "wte": (V, d), "wpe": (S, d), "lnf_g": (d,), "lnf_b": (d,),
        "ln1_g": (L, d), "ln1_b": (L, d), "w_qkv": (L, d, 3 * d),
        "w_o": (L, d, d), "ln2_g": (L, d), "ln2_b": (L, d),
        "w_fc": (L, d, f), "b_fc": (L, f), "w_proj": (L, f, d),
        "b_proj": (L, d),
    }
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
        out[name] = 1.0 + w if name.endswith("_g") else w
    return out


def leaf_keys(cfg: dict):
    """One key per parameter leaf as the program holds them: top-level
    names, and ``name.<layer>`` for the stacked ones."""
    keys = list(TOP_LEAVES)
    for l in range(cfg["n_layer"]):
        keys += [f"{n}.{l}" for n in LAYER_LEAVES]
    return keys


def _mm(lowp):
    """The matmul of this run: float32 at ``highest``, or the control's,
    whose operands are rounded to float8 (e4m3) first."""
    if not lowp:
        return functools.partial(jnp.matmul,
                                 precision=lax.Precision.HIGHEST)

    def mm(a, b):
        return jnp.matmul(fp8(a), fp8(b),
                          precision=lax.Precision.HIGHEST)
    return mm


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-6) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, n_head, mm):
    s, d = x.shape
    dh = d // n_head
    h = _ln(x, w["ln1_g"], w["ln1_b"])
    q, k, v = jnp.split(mm(h, w["w_qkv"]), 3, axis=-1)
    q, k, v = (t.reshape(s, n_head, dh).transpose(1, 0, 2)
               for t in (q, k, v))
    att = mm(q, k.transpose(0, 2, 1)) * dh ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = mm(att, v).transpose(1, 0, 2).reshape(s, d)
    x = x + mm(o, w["w_o"])
    h = _ln(x, w["ln2_g"], w["ln2_b"])
    h = _gelu_tanh(mm(h, w["w_fc"]) + w["b_fc"])
    return x + mm(h, w["w_proj"]) + w["b_proj"]


def logits_fn(weights, tokens, cfg, lowp=False):
    """One sequence ``(s,)`` of token ids -> ``(s, vocab)`` logits."""
    mm = _mm(lowp)
    s = tokens.shape[0]
    x = weights["wte"][tokens] + weights["wpe"][:s]
    stacked = {n: weights[n] for n in LAYER_LEAVES}
    block = jax.checkpoint(
        lambda x, w: (_block(x, w, cfg["n_head"], mm), None))
    x, _ = lax.scan(block, x, stacked)
    x = _ln(x, weights["lnf_g"], weights["lnf_b"])
    return mm(x, weights["wte"].T)


def row_loss(weights, tokens, cfg, lowp=False):
    """Mean next-token cross entropy of one sequence."""
    logits = logits_fn(weights, tokens, cfg, lowp)[:-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return (logz - picked).mean()


def _loss_and_grad(weights, tokens, cfg, lowp):
    """Mean loss and gradient over ``tokens (n_dev, rows, s)``: rows one
    after another (``scan``), the leading axis side by side (``vmap``;
    sharded over devices when the caller shards ``tokens``)."""
    vg = jax.value_and_grad(row_loss)

    def per_dev(rows):
        def body(carry, row):
            l, g = vg(weights, row, cfg, lowp)
            return (carry[0] + l, jax.tree_util.tree_map(
                jnp.add, carry[1], g)), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like,
                                                      weights))
        (l, g), _ = lax.scan(body, zero, rows)
        return l, g

    l, g = jax.vmap(per_dev)(tokens)
    n = tokens.shape[0] * tokens.shape[1]
    return l.sum() / n, jax.tree_util.tree_map(lambda x: x.sum(0) / n, g)


def _adamw(weights, m, v, g, t, lr, wd):
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: B1 * m + (1 - B1) * g, m, g)
    v = tm(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    new = tm(lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS)
                                       + wd * p), weights, m, v)
    return new, m, v


def small_leaves(tree, cfg) -> dict:
    """The leaves of at most ``SMALL`` elements, keyed as ``leaf_keys``."""
    out = {n: tree[n] for n in TOP_LEAVES if tree[n].size <= SMALL}
    for n in LAYER_LEAVES:
        if tree[n][0].size <= SMALL:
            out.update({f"{n}.{l}": tree[n][l]
                        for l in range(cfg["n_layer"])})
    return out


def _leaf_norms(tree, cfg):
    """float32 L2 norm of every program leaf, keyed as ``leaf_keys``."""
    out = {n: jnp.linalg.norm(tree[n]) for n in TOP_LEAVES}
    for n in LAYER_LEAVES:
        per = jnp.sqrt((tree[n] ** 2).reshape(cfg["n_layer"], -1).sum(1))
        for l in range(cfg["n_layer"]):
            out[f"{n}.{l}"] = per[l]
    return out


def train_readings(seed, cfg, batches, optimizer, lowp=False):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  ``batches``: int32 ``(steps, n_dev, rows, s)``; the
    ``n_dev`` axis is laid over that many devices.  Returns host floats:
    ``losses``, ``grad_norms`` (first step, per leaf), ``grad_small`` (the
    first gradient's small leaves, whole) and ``delta_norms`` (parameters
    after the last step minus the seeded ones, per leaf)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    mesh = Mesh(np.array(jax.devices()[:batches.shape[1]]), ("rows",))
    everywhere = NamedSharding(mesh, P())
    by_rows = NamedSharding(mesh, P("rows"))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(weights, m, v, tokens, t):
        loss, g = _loss_and_grad(weights, tokens, cfg, lowp)
        first = (_leaf_norms(g, cfg), small_leaves(g, cfg))
        weights, m, v = _adamw(weights, m, v, g, t, lr, wd)
        return weights, m, v, loss, first

    key = seed_key(seed)

    @jax.jit
    def delta_norms(weights, key):
        w0 = init_weights(key, cfg)
        return _leaf_norms(jax.tree_util.tree_map(jnp.subtract, weights,
                                                  w0), cfg)

    weights = jax.jit(lambda k: init_weights(k, cfg),
                      out_shardings=everywhere)(key)
    zeros = jax.jit(lambda w: jax.tree_util.tree_map(jnp.zeros_like, w))
    m, v = zeros(weights), zeros(weights)
    losses, grad_norms, grad_small = [], None, None
    for t, tokens in enumerate(batches, start=1):
        tokens = jax.device_put(tokens, by_rows)
        weights, m, v, loss, (norms, small) = step(weights, m, v, tokens,
                                                   jnp.float32(t))
        losses.append(float(loss))
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms.items()}
            grad_small = {k: np.asarray(x) for k, x in small.items()}
    deltas = {k: float(x) for k, x in delta_norms(weights, key).items()}
    del weights, m, v
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_small": grad_small, "delta_norms": deltas}
