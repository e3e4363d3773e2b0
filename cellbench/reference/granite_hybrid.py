"""Plain reference for a Mamba-2 / attention hybrid decoder LM (IBM
Granite 4.0-H, ``model_type`` ``granitemoehybrid`` with no experts).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program, and **the
state-space recurrence itself, position by position**.  ``h = m_e
E[tokens]``; a layer of kind ``layer_types[l]``, with ``m_r`` the
residual multiplier:

    h = h + m_r mix(rms(h))
    h = h + m_r W_out(silu(g) * u),   [g | u] = W_in rms(h)

``mix`` of an ``attention`` layer: q, k, v without bias to ``hq`` /
``hkv`` / ``hkv`` heads, causal softmax of ``q k^T * attention_multiplier``
with no position anywhere, query head ``i`` reading key/value head ``i
// (hq / hkv)``, then ``W_o``.  ``mix`` of a ``mamba`` layer on ``x (s,
d)``:

    [z | xBC | dt] = W_in x               widths inner | inner + 2n | heads
    xBC = silu(conv(xBC))                 depthwise, causal, k taps, bias
    [x' | B | C] = xBC                    x' as heads of p; B, C shared
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T;   y_t = S_t C_t + D x'_t
    W_out rms(y * silu(z))                gate before the norm, a gain

After the last layer ``rms``, logits ``h E^T / logits_scaling`` over the
vocabulary rows held, mean cross-entropy of the next tokens.

Only so that a step fits one chip beside its 12 bytes a parameter of
float32 state: each layer is computed again in the backward pass, and
inside it the mixer's two ends; the recurrence runs in stretches
(``lax.scan`` over the positions of a stretch inside ``lax.scan`` over
stretches, the inner one under ``jax.checkpoint``), attention's dense
masked softmax, the MLP and the loss a block of rows at a time.  ``lowp=True`` rounds every product's
operands (the recurrence's ``x'``, ``B`` and ``C`` among them) to
scaled float8: the control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import fp8, seed_key  # noqa: F401 (runners use them)

# AdamW as ``optax.adamw(lr, weight_decay=wd)`` defines it
B1, B2, EPS = 0.9, 0.999, 1e-8

#: leaves of at most this many elements are also compared element by
#: element: the norms' gains, and all of the mixer's own (``A_log``,
#: ``dt_bias``, ``D``, the convolution's 4 x 4352 taps and its bias)
SMALL = 32768

TOP_LEAVES = ("wte", "normf_g")
#: leaves every layer has
LAYER_LEAVES = ("norm1_g", "norm2_g", "w_in", "w_out")
#: leaves of one kind of layer
KIND_LEAVES = {
    "mamba": ("m_in", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip",
              "mnorm_g", "m_out"),
    "attention": ("w_q", "w_k", "w_v", "w_o"),
}

#: positions of the recurrence whose states the backward pass holds at
#: once; rows a block of attention's scores, of the MLP and of the loss
STRETCH = 128
ROW_BLOCK = 256


def layer_kinds(cfg: dict) -> tuple:
    """The kind of each of the ``num_hidden_layers`` layers."""
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] \
            or set(kinds) - set(KIND_LEAVES):
        raise ValueError(f"layer_types must name {cfg['num_hidden_layers']}"
                         f" layers, each of {sorted(KIND_LEAVES)}")
    return kinds


def _widths(cfg: dict) -> dict:
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return {"inner": inner, "conv": inner + 2 * cfg["mamba_d_state"],
            "dh": cfg["hidden_size"] // cfg["num_attention_heads"]}


def _shapes(cfg: dict) -> dict:
    """The shape of a leaf of each name."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    f, h, k = cfg["intermediate_size"], cfg["mamba_n_heads"], \
        cfg["mamba_d_conv"]
    w = _widths(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "wte": (V, d), "normf_g": (d,),
        "norm1_g": (d,), "norm2_g": (d,), "w_in": (d, 2 * f),
        "w_out": (f, d),
        "m_in": (d, w["inner"] + w["conv"] + h),
        "conv_w": (k, w["conv"]), "conv_b": (w["conv"],),
        "a_log": (h,), "dt_bias": (h,), "d_skip": (h,),
        "mnorm_g": (w["inner"],), "m_out": (w["inner"], d),
        "w_q": (d, hq * w["dh"]), "w_k": (d, hkv * w["dh"]),
        "w_v": (d, hkv * w["dh"]), "w_o": (hq * w["dh"], d),
    }


def leaves(cfg: dict):
    """``(key, name, layer)`` of every leaf as the program holds them:
    the top-level names (``layer`` None), and ``name.<layer>`` for a
    layer's."""
    for n in TOP_LEAVES:
        yield n, n, None
    for l, kind in enumerate(layer_kinds(cfg)):
        for n in LAYER_LEAVES + KIND_LEAVES[kind]:
            yield f"{n}.{l}", n, l


def leaf_keys(cfg: dict):
    return [key for key, _, _ in leaves(cfg)]


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights, a leaf an entry under ``leaf_keys``'
    names, each from a key of its own (a stack of layers would be made
    and copied whole wherever one layer is wanted): matrices and the
    convolution's bias N(0, 0.02), norm gains 1 + N(0, 0.02), and the
    mixer's own initialisation where a normal draw would make the
    recurrence meaningless: ``A_log = log U(1, 16)``, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1],
    ``D = 1``, the taps uniform in +-1/2."""
    shapes = _shapes(cfg)
    order = {name: i for i, name in enumerate(sorted(shapes))}
    out = {}
    for leaf, name, layer in leaves(cfg):
        k = jax.random.fold_in(jax.random.fold_in(key, order[name]),
                               0 if layer is None else layer + 1)
        shape = shapes[name]
        if name == "a_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = step + jnp.log(-jnp.expm1(-step))
        elif name == "d_skip":
            w = jnp.ones(shape, jnp.float32)
        elif name == "conv_w":
            w = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        else:
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            if name.endswith("_g"):
                w = 1.0 + w
        out[leaf] = w
    return out


def _ein(lowp):
    """The product of this run: float32 at ``highest``, or the control's,
    whose operands are rounded to float8 (e4m3) first."""
    def ein(spec, a, b):
        if lowp:
            a, b = fp8(a), fp8(b)
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return ein


def _rms(x, g, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _by_rows(fn, x):
    """``fn`` on ``x (s, ...)`` a block of rows at a time, each block
    computed again in the backward pass."""
    s = x.shape[0]
    rb = math.gcd(s, ROW_BLOCK)
    out = lax.map(jax.checkpoint(fn), x.reshape(s // rb, rb, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def conv1d(x, taps, bias):
    """``y_t = bias + sum_j taps[j] x_{t - (k - 1) + j}`` on ``x (s,
    c)``, zeros before the sequence: depthwise and causal."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return bias + sum(taps[j] * padded[j:j + s] for j in range(k))


def recurrence(x, dt, A, B, C, D):
    """The state-space recurrence as it is written, one position after
    another: ``x (s, h, p)``, ``dt (s, h)``, ``A`` / ``D (h,)``, ``B`` /
    ``C (s, n)`` -> ``y (s, h, p)``."""
    s, h, p = x.shape
    n = B.shape[-1]

    def one(S, at):
        x_t, dt_t, B_t, C_t = at
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t
        return S, (S * C_t).sum(-1) + D[:, None] * x_t

    stretch = math.gcd(s, STRETCH)
    cut = lambda t: t.reshape(s // stretch, stretch, *t.shape[1:])
    _, y = lax.scan(jax.checkpoint(lambda S, ats: lax.scan(one, S, ats)),
                    jnp.zeros((h, p, n)), tuple(map(cut, (x, dt, B, C))))
    return y.reshape(s, h, p)


def _mamba(x, w, cfg, ein, lowp):
    s = x.shape[0]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * p

    @jax.checkpoint
    def project(x, w_in, taps, bias, dt_bias):
        z, xbc, dt = jnp.split(ein("sd,dk->sk", x, w_in),
                               [inner, 2 * inner + 2 * n], axis=-1)
        xs, B, C = jnp.split(jax.nn.silu(conv1d(xbc, taps, bias)),
                             [inner, inner + n], axis=-1)
        return z, xs, B, C, jax.nn.softplus(dt + dt_bias)

    @jax.checkpoint
    def gate_and_leave(y, z, gain, w_out):
        y = _rms(y.reshape(s, inner) * jax.nn.silu(z), gain,
                 cfg["rms_norm_eps"])
        return ein("sk,kd->sd", y, w_out)

    z, xs, B, C, dt = project(x, w["m_in"], w["conv_w"], w["conv_b"],
                              w["dt_bias"])
    if lowp:  # the operands of the recurrence's products
        xs, B, C = fp8(xs), fp8(B), fp8(C)
    y = recurrence(xs.reshape(s, h, p), dt, -jnp.exp(w["a_log"]), B, C,
                   w["d_skip"])
    return gate_and_leave(y, z, w["mnorm_g"], w["m_out"])


def _attention(x, w, cfg, ein, lowp):
    del lowp
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // hq
    q = ein("sd,dk->sk", x, w["w_q"]).reshape(s, hkv, hq // hkv, dh)
    k = ein("sd,dk->sk", x, w["w_k"]).reshape(s, hkv, dh)
    v = ein("sd,dk->sk", x, w["w_v"]).reshape(s, hkv, dh)
    keys = jnp.arange(s)

    def rows(args):
        q_blk, index = args
        sc = ein("qhgd,khd->hgqk", q_blk, k) * cfg["attention_multiplier"]
        sc = jnp.where(keys[None, :] <= index[:, None], sc, -jnp.inf)
        return ein("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v)

    rb = math.gcd(s, ROW_BLOCK)
    o = lax.map(jax.checkpoint(rows),
                (q.reshape(s // rb, rb, *q.shape[1:]),
                 keys.reshape(s // rb, rb)))
    return ein("sk,kd->sd", o.reshape(s, hq * dh), w["w_o"])


_MIX = {"mamba": _mamba, "attention": _attention}


def _layer(x, w, *, kind, cfg, ein, lowp):
    """One layer on one sequence ``x (s, d)``."""
    eps, m_r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = x + m_r * _MIX[kind](_rms(x, w["norm1_g"], eps), w, cfg, ein, lowp)

    def mlp(u):
        gate, up = jnp.split(ein("sd,df->sf", u, w["w_in"]), 2, axis=-1)
        return ein("sf,fd->sd", jax.nn.silu(gate) * up, w["w_out"])

    return x + m_r * _by_rows(mlp, _rms(x, w["norm2_g"], eps))


def hidden_fn(weights, tokens, cfg, lowp=False):
    """One sequence ``tokens (s,)`` -> the final-norm hidden states ``(s,
    d)``; every layer is computed again in the backward pass."""
    ein = _ein(lowp)
    x = cfg["embedding_multiplier"] * weights["wte"][tokens]
    for l, kind in enumerate(layer_kinds(cfg)):
        w = {n: weights[f"{n}.{l}"]
             for n in LAYER_LEAVES + KIND_LEAVES[kind]}
        x = jax.checkpoint(functools.partial(
            _layer, kind=kind, cfg=cfg, ein=ein, lowp=lowp))(x, w)
    return _rms(x, weights["normf_g"], cfg["rms_norm_eps"])


def logits_fn(weights, tokens, cfg, lowp=False):
    """One sequence -> ``(s, vocab)`` logits over the rows held."""
    return _ein(lowp)("sd,vd->sv", hidden_fn(weights, tokens, cfg, lowp),
                      weights["wte"]) / cfg["logits_scaling"]


def _block_nll(args, wte, cfg, ein):
    """Negative log-likelihood of a block of rows: ``(final-norm hidden
    states (r, d), targets (r,))`` -> ``(r,)``."""
    hidden, targets = args
    logits = ein("sd,vd->sv", hidden, wte) / cfg["logits_scaling"]
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]


def row_loss(weights, tokens, cfg, lowp=False):
    """Mean next-token cross entropy of one sequence, over its ``s - 1``
    targets."""
    s = tokens.shape[0]
    hidden = hidden_fn(weights, tokens, cfg, lowp)
    targets = jnp.roll(tokens, -1)  # the last position has none
    rb = math.gcd(s, ROW_BLOCK)
    per_position = lax.map(
        jax.checkpoint(functools.partial(
            _block_nll, wte=weights["wte"], cfg=cfg, ein=_ein(lowp))),
        (hidden.reshape(s // rb, rb, -1), targets.reshape(s // rb, rb)))
    return per_position.reshape(s)[:-1].mean()


def batch_loss(weights, tokens, cfg, lowp=False):
    """Mean loss over ``tokens (rows, s)``."""
    return jax.vmap(lambda row: row_loss(weights, row, cfg, lowp))(
        tokens).mean()


def _adamw(weights, m, v, g, t, lr, wd):
    """One AdamW step, a leaf at a time: ``(weights, m, v)`` after it."""
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    out = {}
    for k, p in weights.items():
        m_k = B1 * m[k] + (1 - B1) * g[k]
        v_k = B2 * v[k] + (1 - B2) * g[k] * g[k]
        out[k] = (p - lr * ((m_k / c1) / (jnp.sqrt(v_k / c2) + EPS)
                            + wd * p), m_k, v_k)
    return tuple({k: x[i] for k, x in out.items()} for i in range(3))


def small_leaves(tree) -> dict:
    """The leaves of at most ``SMALL`` elements."""
    return {k: x for k, x in tree.items() if x.size <= SMALL}


def _leaf_norms(tree):
    """float32 L2 norm of every leaf."""
    return {k: jnp.linalg.norm(x) for k, x in tree.items()}


def layer_leaves(kind: str, layer: int) -> dict:
    """``{name inside the layer: its key in the tree}`` of one layer."""
    return {n: f"{n}.{layer}" for n in LAYER_LEAVES + KIND_LEAVES[kind]}


def train_readings(seed, cfg, batches, optimizer, lowp=False):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  ``batches``: int32 ``(steps, rows, s)``.  Returns host
    floats: ``losses``, ``grad_norms`` (first step, per leaf),
    ``grad_small`` (the first gradient's small leaves, whole) and
    ``delta_norms`` (parameters after the last step minus the seeded
    ones, per leaf).

    The gradient is ``jax.grad(batch_loss)``'s, taken a layer at a time
    so that a step fits one chip at the cell's size: the forward pass
    keeps each layer's input, the backward pass walks the layers from
    the last, computes a layer again under ``jax.vjp`` and applies
    AdamW to its leaves at once, so no more than one layer's gradient
    and working set is ever live beside the float32 state (the tests
    hold it against ``jax.value_and_grad(batch_loss)``)."""
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    kinds, ein = layer_kinds(cfg), _ein(lowp)
    eps, m_e = cfg["rms_norm_eps"], cfg["embedding_multiplier"]

    def layer_of(kind):  # all rows of a batch through one layer
        return jax.vmap(functools.partial(
            _layer, kind=kind, cfg=cfg, ein=ein, lowp=lowp),
            in_axes=(0, None))

    forward = jax.jit(lambda x, w, kind: layer_of(kind)(x, w),
                      static_argnames="kind")

    def update(w, m, v, g, t):
        return _adamw(w, m, v, g, t, lr, wd) \
            + ((_leaf_norms(g), small_leaves(g)),)

    @functools.partial(jax.jit, static_argnames="kind",
                       donate_argnums=(1, 2, 3, 4))
    def backward(x, w, m, v, dx, t, kind):
        _, vjp = jax.vjp(layer_of(kind), x, w)
        dx, g = vjp(dx)
        return (dx,) + update(w, m, v, g, t)

    def head_loss(x, normf_g, wte, tokens):
        def row(x, tokens):
            s = tokens.shape[0]
            targets = jnp.roll(tokens, -1)
            rb = math.gcd(s, ROW_BLOCK)
            nll = lax.map(
                jax.checkpoint(functools.partial(
                    _block_nll, wte=wte, cfg=cfg, ein=ein)),
                (_rms(x, normf_g, eps).reshape(s // rb, rb, -1),
                 targets.reshape(s // rb, rb)))
            return nll.reshape(s)[:-1].mean()
        return jax.vmap(row)(x, tokens).mean()

    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def finish(top, m, v, g_head, dx, tokens, t):
        # the tied table's gradient: the head's and the embedding's
        g = {"normf_g": g_head["normf_g"],
             "wte": g_head["wte"].at[tokens].add(m_e * dx)}
        return update(top, m, v, g, t)

    seeded = jax.jit(lambda k: init_weights(k, cfg))
    delta_norms = jax.jit(lambda weights, key: _leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, weights, seeded(key))))
    zeros = jax.jit(lambda w: jax.tree_util.tree_map(jnp.zeros_like, w))
    pick = lambda tree, names: {n: tree[k] for n, k in names.items()}

    key = seed_key(seed)
    weights = seeded(key)
    m, v = zeros(weights), zeros(weights)
    top = {n: n for n in TOP_LEAVES}
    losses, grad_norms, grad_small = [], {}, {}
    for t, tokens in enumerate(batches, start=1):
        tokens, t32 = jnp.asarray(np.asarray(tokens)), jnp.float32(t)
        inputs = [m_e * weights["wte"][tokens]]
        for l, kind in enumerate(kinds):
            inputs.append(forward(
                inputs[-1], pick(weights, layer_leaves(kind, l)), kind))
        loss, (dx, g_norm, g_wte) = head(
            inputs.pop(), weights["normf_g"], weights["wte"], tokens)
        losses.append(float(loss))
        g_head = {"normf_g": g_norm, "wte": g_wte}
        del g_norm, g_wte
        steps = []  # (names, the layer's or the top's results)
        for l in reversed(range(len(kinds))):
            names = layer_leaves(kinds[l], l)
            dx, *new = backward(
                inputs.pop(), *(pick(tree, names)
                                for tree in (weights, m, v)),
                dx, t32, kinds[l])
            steps.append((names, new))
        steps.append((top, finish(*(pick(tree, top)
                                    for tree in (weights, m, v)),
                                  g_head, dx, tokens, t32)))
        for names, (w_new, m_new, v_new, (norms, small)) in steps:
            for n, k in names.items():
                weights[k], m[k], v[k] = w_new[n], m_new[n], v_new[n]
                if t == 1:
                    grad_norms[k] = float(norms[n])
                    if n in small:
                        grad_small[k] = np.asarray(small[n])
    deltas = {k: float(x) for k, x in delta_norms(weights, key).items()}
    del weights, m, v
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_small": grad_small, "delta_norms": deltas}
