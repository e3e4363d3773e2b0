"""Plain reference for a Gated DeltaNet / gated-attention hybrid with
sparse experts and a shared one (Qwen3-Next, ``model_type``
``qwen3_next``).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program, **the delta rule
itself, position by position**, and **a loop over the experts held**.
One chip's share of a layer that several chips hold together: the
router scores all ``router_experts`` experts and keeps the top
``num_experts_per_tok`` with weights renormalised over them; of those
routes only the ones to the ``num_experts`` experts held here (from
``first_expert`` on) are computed, and what the absent experts would
have added is left out; the shared expert is whole on every chip.  The
vocabulary is the slice held here.  With ``num_experts ==
router_experts`` and the whole vocabulary the same functions are the
uncut model (``share_of`` cuts a share's weights out of it).

Every norm but the mixer's gated one is zero-centred: ``rms(x) = x /
sqrt(mean(x^2) + eps) * (1 + w)``.  ``h = E[tokens]``; layer ``i`` is
full attention when ``(i + 1) % full_attention_interval == 0``, else
Gated DeltaNet:

    h = h + mix(rms(h))
    u = rms(h);  p = softmax(R u);  w = renorm(top_k(p))
    h = h + sum_{e in top_k, e held} w_e W_d^e(silu(W_g^e u) * W_u^e u)
          + sigmoid(w_s . u) W_d(silu(W_g u) * W_u u)

``mix`` of a Gated DeltaNet layer on ``x (s, d)``, ``hk`` key heads of
``dk``, ``hv`` value heads of ``dv``, key head ``j`` serving value heads
``j hv / hk ...``:

    [q | k | v | z] = W_qkvz x;   [b | a] = W_ba x
    [q | k | v] = silu(conv([q | k | v]))     depthwise, causal, no bias
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    q = q / |q| / sqrt(dk);  k = k / |k|      a head, 1e-6 under the root
    S_t = e^{g_t} S_{t-1};  S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t                           a value head, S_0 = 0
    W_o (rms_head(o) * w_norm * silu(z))      a plain gain over dv

``mix`` of an attention layer: ``[q | gate] = W_q x`` a head, ``k``,
``v``; zero-centred rms over each head of q and k; rotate-half rotation
of the first ``partial_rotary_factor`` of each head; causal softmax at
``head_dim ** -0.5``, query head ``i`` reading key/value head ``i //
(hq / hkv)``; ``W_o (attn * sigmoid(gate))``.

After the last layer ``rms``, logits against the untied head's rows
held, mean cross-entropy of the next tokens, plus ``aux_loss_coef``
times the layers' load-balancing terms (``router_experts * sum_e f_e
P_e``, over all tokens of the batch).

Only so that a step fits one chip beside its 12 bytes a parameter of
float32 state: the gradient is taken a layer at a time
(``train_readings``), the recurrence runs in stretches (``lax.scan``
over the positions of a stretch inside ``lax.scan`` over stretches, the
inner one under ``jax.checkpoint``), attention's dense masked softmax
and the loss a block of rows at a time, an expert's term under
``jax.checkpoint``.  ``lowp=True`` rounds every product's operands (the
recurrence's ``q``, ``k`` and ``v`` among them) to scaled float8: the
control; the router's softmax and the shared gate's logit stay
float32.

A top-10 of 512 is a discrete choice that flips on rounding where the
tenth and eleventh probabilities nearly tie, so the reference can be
handed the routes the program took (``routes``) and follows one inside
``route_tie_window``, exactly as ``sdar_moe.route`` does.
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
#: element: the norms' offsets and gains, the mixer's ``A_log`` and
#: ``dt_bias``, the convolution's 4 x 8192 taps, the shared gate
SMALL = 32768

TOP_LEAVES = ("wte", "head", "normf_g")
#: leaves every layer has
LAYER_LEAVES = ("norm1_g", "norm2_g", "router", "w_gate", "w_up", "w_down",
                "s_gate", "s_up", "s_down", "s_mix")
#: leaves of one kind of layer
KIND_LEAVES = {
    "linear_attention": ("l_in", "l_ba", "conv_w", "a_log", "dt_bias",
                         "lnorm_g", "l_out"),
    "attention": ("w_q", "w_k", "w_v", "w_o", "qn_g", "kn_g"),
}
#: zero-centred gains (stored as the offset ``w`` of ``1 + w``)
ZERO_CENTRED = ("normf_g", "norm1_g", "norm2_g", "qn_g", "kn_g")

#: positions of the recurrence whose states the backward pass holds at
#: once; rows a block of attention's scores and of the loss
STRETCH = 128
ROW_BLOCK = 256


def layer_kinds(cfg: dict) -> tuple:
    """The kind of each of the ``num_hidden_layers`` layers."""
    every = int(cfg["full_attention_interval"])
    return tuple("attention" if (i + 1) % every == 0
                 else "linear_attention"
                 for i in range(int(cfg["num_hidden_layers"])))


def _shapes(cfg: dict) -> dict:
    """The shape of a leaf of each name."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["router_experts"]
    return {
        "wte": (V, d), "head": (V, d), "normf_g": (d,),
        "norm1_g": (d,), "norm2_g": (d,), "router": (d, routed),
        "w_gate": (held, d, f), "w_up": (held, d, f), "w_down": (held, f, d),
        "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
        "s_mix": (d, 1),
        "l_in": (d, 2 * keys + 2 * values),
        "l_ba": (d, 2 * cfg["linear_num_value_heads"]),
        "conv_w": (cfg["linear_conv_kernel_dim"], 2 * keys + values),
        "a_log": (cfg["linear_num_value_heads"],),
        "dt_bias": (cfg["linear_num_value_heads"],),
        "lnorm_g": (cfg["linear_value_head_dim"],), "l_out": (values, d),
        "w_q": (d, 2 * hq * dh), "w_k": (d, hkv * dh),
        "w_v": (d, hkv * dh), "w_o": (hq * dh, d),
        "qn_g": (dh,), "kn_g": (dh,),
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


def n_parameters(cfg: dict) -> int:
    shapes = _shapes(cfg)
    return sum(math.prod(shapes[name]) for _, name, _ in leaves(cfg))


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights, a leaf an entry under ``leaf_keys``'
    names, each from a key of its own: matrices N(0, 0.02), the token
    embedding N(0, 1) (at 0.02 every router collapses onto the same few
    experts: ``sdar_moe.init_weights`` has the readings), zero-centred
    norm offsets N(0, 0.02), the mixer's plain norm gain 1 + N(0, 0.02),
    and the mixer's own initialisation where a normal draw would make
    the recurrence meaningless: ``A_log = log U(0, 16)``, ``dt_bias``
    the inverse softplus of a step drawn log-uniformly from [1e-3,
    1e-1], the convolution's taps uniform in +-1/2."""
    shapes = _shapes(cfg)
    order = {name: i for i, name in enumerate(sorted(shapes))}
    out = {}
    for leaf, name, layer in leaves(cfg):
        k = jax.random.fold_in(jax.random.fold_in(key, order[name]),
                               0 if layer is None else layer + 1)
        shape = shapes[name]
        if name == "a_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-4,
                                           16.0))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = step + jnp.log(-jnp.expm1(-step))
        elif name == "conv_w":
            w = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif name == "wte":
            w = jax.random.normal(k, shape, jnp.float32)
        else:
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            if name == "lnorm_g":
                w = 1.0 + w
        out[leaf] = w
    return out


def share_of(weights: dict, cfg: dict, first_expert: int, num_experts: int,
             first_row: int, rows: int) -> dict:
    """The weights one chip holds of an uncut model's: its experts of
    every layer, its rows of the embedding and of the head, and all of
    what every chip holds alike (the shared expert among it)."""
    out = dict(weights)
    for l in range(cfg["num_hidden_layers"]):
        for n in ("w_gate", "w_up", "w_down"):
            out[f"{n}.{l}"] = weights[f"{n}.{l}"][
                first_expert:first_expert + num_experts]
    for n in ("wte", "head"):
        out[n] = weights[n][first_row:first_row + rows]
    return out


def _ein(lowp):
    """The product of this run: float32 at ``highest``, or the control's,
    whose operands are rounded to float8 (e4m3) first."""
    def ein(spec, a, b):
        if lowp:
            a, b = fp8(a), fp8(b)
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return ein


def _rms(x, w, eps):
    """Zero-centred: the gain is ``1 + w``."""
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta, fraction):
    """Rotary positions on the first ``fraction`` of each head of ``x
    (n, heads, dh)``, the halves convention (``rotate_half``) among
    those channels; the rest pass."""
    turned = int(x.shape[-1] * fraction)
    half = turned // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:turned]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., turned:]], -1)


def conv1d(x, taps):
    """``y_t = sum_j taps[j] x_{t - (k - 1) + j}`` on ``x (s, c)``,
    zeros before the sequence: depthwise and causal, no bias."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return sum(taps[j] * padded[j:j + s] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule as it is written, one position after
    another: ``q`` / ``k (s, hk, dk)``, ``v (s, h, dv)``, ``g`` /
    ``beta (s, h)`` -> ``o (s, h, dv)``."""
    s, h, dv = v.shape
    hk, dk = k.shape[1:]
    q, k = (jnp.repeat(t, h // hk, axis=1) for t in (q, k))

    def one(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        S = jnp.exp(g_t)[:, None, None] * S
        delta = beta_t[:, None] * (v_t - (S * k_t[:, :, None]).sum(1))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, (S * q_t[:, :, None]).sum(1)

    stretch = math.gcd(s, STRETCH)
    cut = lambda t: t.reshape(s // stretch, stretch, *t.shape[1:])
    _, o = lax.scan(jax.checkpoint(lambda S, ats: lax.scan(one, S, ats)),
                    jnp.zeros((h, dk, dv)),
                    tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape(s, h, dv)


def _unit(t):
    return t * lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)


def _linear_attention(x, w, cfg, ein, lowp):
    s = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    keys, values = hk * dk, hv * dv

    @jax.checkpoint
    def project(x, l_in, l_ba, taps, a_log, dt_bias):
        qkv, z = jnp.split(ein("sd,dk->sk", x, l_in), [2 * keys + values],
                           axis=-1)
        b, a = jnp.split(ein("sd,dk->sk", x, l_ba), 2, axis=-1)
        q, k, v = jnp.split(jax.nn.silu(conv1d(qkv, taps)),
                            [keys, 2 * keys], axis=-1)
        q = _unit(q.reshape(s, hk, dk)) * dk ** -0.5
        k = _unit(k.reshape(s, hk, dk))
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        return q, k, v.reshape(s, hv, dv), z, g, jax.nn.sigmoid(b)

    @jax.checkpoint
    def gate_and_leave(o, z, gain, l_out):
        o = o * lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg["rms_norm_eps"]) * gain
        o = o * jax.nn.silu(z.reshape(s, hv, dv))
        return ein("sk,kd->sd", o.reshape(s, values), l_out)

    q, k, v, z, g, beta = project(x, w["l_in"], w["l_ba"], w["conv_w"],
                                  w["a_log"], w["dt_bias"])
    if lowp:  # the operands of the recurrence's products
        q, k, v = fp8(q), fp8(k), fp8(v)
    return gate_and_leave(delta_rule(q, k, v, g, beta), z, w["lnorm_g"],
                          w["l_out"])


def _attention(x, w, cfg, ein, lowp):
    del lowp
    s = x.shape[0]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, pos = cfg["rms_norm_eps"], jnp.arange(s)
    turn = lambda t, gain: _rope(_rms(t, gain, eps), pos, cfg["rope_theta"],
                                 cfg["partial_rotary_factor"])
    q, gate = jnp.split(
        ein("sd,dk->sk", x, w["w_q"]).reshape(s, hq, 2 * dh), 2, axis=-1)
    q = turn(q, w["qn_g"]).reshape(s, hkv, hq // hkv, dh)
    k = turn(ein("sd,dk->sk", x, w["w_k"]).reshape(s, hkv, dh), w["kn_g"])
    v = ein("sd,dk->sk", x, w["w_v"]).reshape(s, hkv, dh)

    def rows(args):
        q_blk, index = args
        sc = ein("qhgd,khd->hgqk", q_blk, k) * dh ** -0.5
        sc = jnp.where(pos[None, :] <= index[:, None], sc, -jnp.inf)
        return ein("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v)

    rb = math.gcd(s, ROW_BLOCK)
    o = lax.map(jax.checkpoint(rows),
                (q.reshape(s // rb, rb, *q.shape[1:]),
                 pos.reshape(s // rb, rb)))
    o = o.reshape(s, hq, dh) * jax.nn.sigmoid(gate)
    return ein("sk,kd->sd", o.reshape(s, hq * dh), w["w_o"])


_MIX = {"linear_attention": _linear_attention, "attention": _attention}


def route(u, router, cfg, prefer=None):
    """Router of one layer on ``u (n, d)``: softmax over all experts in
    float32 (in the float8 control too), the top ``k`` with weights
    renormalised over them.  ``prefer (n, k)``: routes a program took,
    followed where the reference's own probability of that expert is
    within ``route_tie_window`` (relative) of its own ``k``-th choice.
    Returns ``(probs (n, E), chosen (n, k), weights (n, k))``."""
    probs = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", u, router, precision=lax.Precision.HIGHEST), axis=-1)
    score = probs
    if prefer is not None:
        taken = jax.nn.one_hot(prefer, probs.shape[-1]).sum(1)
        score = probs * (1.0 + cfg["route_tie_window"] * taken)
    _, chosen = lax.top_k(score, cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, chosen, vals / vals.sum(-1, keepdims=True)


def routed_part(u, w, cfg, ein, prefer=None):
    """The held experts' part of the expert layer's result on ``u (n,
    d)``, a loop over them; the layer's load-balancing term (over all
    the router's experts); and what the tie window did: ``chosen`` as
    taken, ``followed`` (routes taken from the program over the
    reference's own top ``k``) and ``refused`` (routes of the program
    the reference did not take)."""
    probs, chosen, weights = route(u, w["router"], cfg, prefer)
    first, routed = cfg.get("first_expert", 0), cfg["router_experts"]

    @jax.checkpoint
    def term(ws):
        w_gate, w_up, w_down, e = ws
        gate = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        hidden = jax.nn.silu(ein("nd,df->nf", u, w_gate)) \
            * ein("nd,df->nf", u, w_up)
        return gate[:, None] * ein("nf,fd->nd", hidden, w_down)

    y, _ = lax.scan(lambda y, ws: (y + term(ws), None), jnp.zeros_like(u),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     jnp.arange(cfg["num_experts"])))
    hot = lambda idx: jax.nn.one_hot(idx, routed).sum(1)
    share = hot(chosen).sum(0) / chosen.size
    aux = routed * jnp.sum(share * probs.mean(0))
    own = hot(lax.top_k(probs, cfg["num_experts_per_tok"])[1])
    report = {"chosen": chosen,
              "followed": (hot(chosen) * (1.0 - own)).sum(),
              "refused": jnp.float32(0.0) if prefer is None
              else (hot(prefer) * (1.0 - hot(chosen))).sum()}
    return y, aux, report


def shared_part(u, w, ein):
    """The shared expert's result on ``u (n, d)`` under its sigmoid
    gate: what every chip computes alike."""
    hidden = jax.nn.silu(ein("nd,df->nf", u, w["s_gate"])) \
        * ein("nd,df->nf", u, w["s_up"])
    gate = jax.nn.sigmoid(jnp.einsum("nd,do->no", u, w["s_mix"],
                                     precision=lax.Precision.HIGHEST))
    return gate * ein("nf,fd->nd", hidden, w["s_down"])


def _layer(x, w, prefer=None, *, kind, cfg, lowp=False):
    """One layer on ``x (rows, s, d)``: the new stream, the layer's
    load-balancing term (over all rows together) and its routing
    report."""
    ein, eps = _ein(lowp), cfg["rms_norm_eps"]
    mix = jax.vmap(lambda row: _MIX[kind](
        _rms(row, w["norm1_g"], eps), w, cfg, ein, lowp))
    h = x + mix(x)
    u = _rms(h, w["norm2_g"], eps).reshape(-1, h.shape[-1])
    y, aux, report = routed_part(u, w, cfg, ein, prefer)
    y = y + jax.checkpoint(lambda u, w: shared_part(u, w, ein))(
        u, {n: w[n] for n in ("s_gate", "s_up", "s_down", "s_mix")})
    return h + y.reshape(h.shape), aux, report


def layer_leaves(kind: str, layer: int) -> dict:
    """``{name inside the layer: its key in the tree}`` of one layer."""
    return {n: f"{n}.{layer}" for n in LAYER_LEAVES + KIND_LEAVES[kind]}


def hidden_fn(weights, tokens, cfg, lowp=False, routes=None):
    """``tokens (rows, s)`` -> the final-norm hidden states ``(rows, s,
    d)``, the summed load-balancing term and the layers' routing
    reports; every layer is computed again in the backward pass.
    ``routes (layers, rows * s, k)``: a program's, see ``route``."""
    x, aux, reports = weights["wte"][tokens], 0.0, []
    for l, kind in enumerate(layer_kinds(cfg)):
        w = {n: weights[k] for n, k in layer_leaves(kind, l).items()}
        x, a, report = jax.checkpoint(functools.partial(
            _layer, kind=kind, cfg=cfg, lowp=lowp))(
            x, w, None if routes is None else routes[l])
        aux = aux + a
        reports.append(report)
    return _rms(x, weights["normf_g"], cfg["rms_norm_eps"]), aux, reports


def logits_fn(weights, tokens, cfg, lowp=False, routes=None):
    """``(rows, s, vocab)`` logits over the rows held."""
    hidden, _, _ = hidden_fn(weights, tokens, cfg, lowp, routes)
    return _ein(lowp)("rsd,vd->rsv", hidden, weights["head"])


def _block_nll(args, head, ein):
    """Negative log-likelihood of a block of rows: ``(final-norm hidden
    states (r, d), targets (r,))`` -> ``(r,)``."""
    hidden, targets = args
    logits = ein("sd,vd->sv", hidden, head)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]


def _next_token_loss(hidden, head, tokens, ein):
    """Mean next-token cross entropy of ``hidden (rows, s, d)`` against
    ``head (vocab, d)``, over each row's ``s - 1`` targets."""
    def row(hidden, tokens):
        s = tokens.shape[0]
        targets = jnp.roll(tokens, -1)  # the last position has none
        rb = math.gcd(s, ROW_BLOCK)
        nll = lax.map(
            jax.checkpoint(functools.partial(_block_nll, head=head,
                                             ein=ein)),
            (hidden.reshape(s // rb, rb, -1), targets.reshape(s // rb, rb)))
        return nll.reshape(s)[:-1].mean()
    return jax.vmap(row)(hidden, tokens).mean()


def batch_loss(weights, tokens, cfg, lowp=False, routes=None,
               report=False):
    """The loss of ``tokens (rows, s)``; with ``report`` the pair of it
    and the layers' routing reports."""
    hidden, aux, reports = hidden_fn(weights, tokens, cfg, lowp, routes)
    loss = _next_token_loss(hidden, weights["head"], tokens, _ein(lowp)) \
        + cfg["aux_loss_coef"] * aux
    return (loss, reports) if report else loss


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


def train_readings(seed, cfg, batches, optimizer, lowp=False, routes=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  ``batches``: int32 ``(steps, rows, s)``; ``routes``: the
    routes a program took on each, ``(layers, rows * s, k)`` a step.
    Returns host floats: ``losses``, ``grad_norms`` (first step, per
    leaf), ``grad_small`` (the first gradient's small leaves, whole),
    ``delta_norms`` (parameters after the last step minus the seeded
    ones, per leaf), and of the routing ``routes`` (as taken, a step),
    ``routes_followed`` and ``routes_refused`` (shares of all routes of
    the steps).

    The gradient is ``jax.grad(batch_loss)``'s, taken a layer at a time
    so that a step fits one chip at the cell's size: the forward pass
    keeps each layer's input, the backward pass walks the layers from
    the last, computes a layer again under ``jax.vjp`` (its stream and
    its load-balancing term, whose cotangent is ``aux_loss_coef``) and
    applies AdamW to its leaves at once, so no more than one layer's
    gradient and working set is ever live beside the float32 state (the
    tests hold it against ``jax.value_and_grad(batch_loss)``)."""
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    kinds, ein = layer_kinds(cfg), _ein(lowp)
    eps, coef = cfg["rms_norm_eps"], jnp.float32(cfg["aux_loss_coef"])

    def layer_of(kind):
        return functools.partial(_layer, kind=kind, cfg=cfg, lowp=lowp)

    forward = jax.jit(lambda x, w, prefer, kind: layer_of(kind)(
        x, w, prefer), static_argnames="kind")

    def update(w, m, v, g, t):
        return _adamw(w, m, v, g, t, lr, wd) \
            + ((_leaf_norms(g), small_leaves(g)),)

    @functools.partial(jax.jit, static_argnames="kind",
                       donate_argnums=(1, 2, 3, 4))
    def backward(x, w, m, v, dx, prefer, t, kind):
        _, vjp = jax.vjp(
            lambda x, w: layer_of(kind)(x, w, prefer)[:2], x, w)
        dx, g = vjp((dx, coef))
        return (dx,) + update(w, m, v, g, t)

    def head_loss(x, normf_g, head, tokens):
        return _next_token_loss(_rms(x, normf_g, eps), head, tokens, ein)

    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def finish(top, m, v, g_head, dx, tokens, t):
        g = dict(g_head, wte=jnp.zeros_like(top["wte"]).at[tokens].add(dx))
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
    chosen, followed, refused = [], 0.0, 0.0
    for t, tokens in enumerate(batches, start=1):
        tokens, t32 = jnp.asarray(np.asarray(tokens)), jnp.float32(t)
        prefer = [None] * len(kinds) if routes is None else \
            [jnp.asarray(r) for r in np.asarray(routes[t - 1])]
        inputs, aux, taken = [weights["wte"][tokens]], 0.0, []
        for l, kind in enumerate(kinds):
            x, a, report = forward(
                inputs[-1], pick(weights, layer_leaves(kind, l)),
                prefer[l], kind)
            inputs.append(x)
            aux += float(a)
            taken.append(np.asarray(report["chosen"]))
            followed += float(report["followed"])
            refused += float(report["refused"])
        chosen.append(np.stack(taken))
        loss, (dx, g_norm, g_out) = head(
            inputs.pop(), weights["normf_g"], weights["head"], tokens)
        losses.append(float(loss) + float(coef) * aux)
        g_head = {"normf_g": g_norm, "head": g_out}
        del g_norm, g_out
        steps = []  # (names, the layer's or the top's results)
        for l in reversed(range(len(kinds))):
            names = layer_leaves(kinds[l], l)
            dx, *new = backward(
                inputs.pop(), *(pick(tree, names)
                                for tree in (weights, m, v)),
                dx, prefer[l], t32, kinds[l])
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
    n_routes = sum(c.size for c in chosen)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_small": grad_small, "delta_norms": deltas,
            "routes": chosen, "routes_followed": followed / n_routes,
            "routes_refused": refused / n_routes}
