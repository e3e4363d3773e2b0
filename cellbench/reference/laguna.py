"""Plain reference for a model of window and full attention layers with
sparse experts behind a leading dense layer (Laguna-S-2.1, ``model_type``
``laguna``: 48 layers, three 512-key window layers of 72 query heads to
every full layer of 48, a gate a head on the attention output, top-10 of
256 experts beside a shared one).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program, **a dense mask**
(the window's too: every query against every key, the dead ones at
``-inf``), the rotation's frequencies **as ``transformers`` 4.57.6's
``_compute_yarn_parameters`` computes them**, written out here, and **a
loop over the experts held**.  One chip's share of a layer that several
chips hold together: the router scores all ``router_experts`` experts
and keeps ``num_experts_per_tok`` of them; of those routes only the ones
to the ``num_experts`` experts held here (from ``first_expert`` on) are
computed, and what the absent experts would have added is left out (a
departure from the published model: the guide's cut to one chip's
share); the shared expert and the dense layer are whole on every chip.
The vocabulary is the slice held here (a departure: the loss's softmax
is over these rows alone).  With ``num_experts == router_experts`` and
the whole vocabulary the same functions are the uncut model
(``share_of`` cuts a share's weights out of it).

``rms(x) = x / sqrt(mean(x^2) + eps) * w``.  ``h = E[tokens]``; layer
``l`` (from 0), ``u = rms(h)``:

    H = num_attention_heads_per_layer[l]; 8 key/value heads of 128,
        each serving H / 8 query heads
    q = W_q u;  k = W_k u;  v = W_v u;   q, k <- R_t q, R_t k
    full_attention:     R_t turns the first 64 channels of a head, halves
                        paired (c with c + 32), YaRN frequencies, cos and
                        sin times attention_factor; live: j <= i
    sliding_attention:  all 128 channels (c with c + 64), base 10 000;
                        live: i - sliding_window < j <= i
    a = softmax(q k^T / sqrt(128)) v     over the live keys
    g = sigmoid(W_g u)                   one number a head
    h = h + W_o (g_head * a_head)
    u = rms(h)
    dense (mlp_layer_types[l]):   h = h + W_d (silu(W_g' u) * W_u u)
    sparse:  p = softmax(R u);  chosen = top_k(p);
             w = moe_routed_scaling_factor * p_chosen / sum p_chosen
             h = h + sum_{e chosen, e held} w_e W_d^e(silu(W_g^e u) * W_u^e u)
                   + sigmoid(w_s . u) W_d(silu(W_g u) * W_u u)

After the last layer ``rms``, logits against the untied head's rows
held, mean cross-entropy of the next tokens, plus ``aux_loss_coef``
times the expert layers' load-balancing terms (``router_experts * sum_e
f_e P_e``, over all tokens of the batch).  AdamW.  These layers are
trained as a model with a final norm and head of their own (a
departure: a pipeline's first stage would send its output on).

Assumed, because the catalog's row has no key for it (each with its
reason in the configuration's ``assumed``): the gate's form, the
router's and the shared expert's (the convention of the family whose
keys the row uses), no q/k norm, a window that counts the token itself,
the pairing by halves.

Only so that a step fits one chip beside its 12 bytes a parameter of
float32 state: the gradient is taken a layer at a time
(``train_readings``), attention's scores and the loss a block of rows
at a time, an expert's term under ``jax.checkpoint`` (as
``reference/moonlight.py``, whose helpers of that kind, from
``reference/qwen3_next.py``, are used here).  ``lowp=True`` rounds
every product's operands to scaled float8: the control; the router's
softmax, the shared gate's logit and the rotation stay float32.

A top-10 of 256 is a discrete choice that flips on rounding where the
tenth and eleventh probabilities nearly tie, so the reference can be
handed the routes the program took (``routes``) and follows one inside
``route_tie_window``, as ``sdar_moe.route`` does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import fp8, seed_key  # noqa: F401 (runners use them)
from .qwen3_next import (  # noqa: F401 (B1 and SMALL: runners use them)
    B1,
    B2,
    EPS,
    SMALL,
    _ein,
    _leaf_norms,
    _next_token_loss,
    small_leaves,
)

TOP_LEAVES = ("wte", "head", "normf_g")
#: leaves every layer has: its norms and its mixer
LAYER_LEAVES = ("norm1_g", "norm2_g", "w_q", "w_k", "w_v", "w_g", "w_o")
#: leaves of a layer's MLP, by kind
MLP_LEAVES = {
    "dense": ("d_in", "d_out"),
    "sparse": ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
               "s_down", "s_mix"),
}
#: the configuration's ``layer_types`` -> the rotation's group of
#: ``rope_parameters`` is the same key; whether the layer has a window
WINDOWED = {"full_attention": False, "sliding_attention": True}

#: rows a block of attention's scores (72 heads of them against 8192
#: keys: 302 MB a block in float32)
ROW_BLOCK = 128


def layer_kinds(cfg: dict) -> tuple:
    """``(layer type, MLP kind, query heads)`` of each of the
    ``num_hidden_layers`` layers, from the configuration's lists."""
    n = int(cfg["num_hidden_layers"])
    kinds = tuple(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                      cfg["num_attention_heads_per_layer"][:n]))
    if len(kinds) != n or any(k not in WINDOWED or m not in MLP_LEAVES
                              for k, m, _ in kinds):
        raise ValueError(f"a list a layer is short of {n} layers or holds "
                         f"an unknown kind: {kinds}")
    return kinds


def _shapes(cfg: dict, heads: int) -> dict:
    """The shape of a leaf of each name in a layer of ``heads`` query
    heads (the top-level leaves' do not depend on it)."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["router_experts"]
    return {
        "wte": (V, d), "head": (V, d), "normf_g": (d,),
        "norm1_g": (d,), "norm2_g": (d,),
        "w_q": (d, heads * dh), "w_k": (d, hkv * dh), "w_v": (d, hkv * dh),
        "w_g": (d, heads), "w_o": (heads * dh, d),
        "d_in": (d, 2 * fd), "d_out": (fd, d),
        "router": (d, routed),
        "w_gate": (held, d, f), "w_up": (held, d, f), "w_down": (held, f, d),
        "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
        "s_mix": (d, 1),
    }


def layer_leaves(kinds, layer: int) -> dict:
    """``{name inside the layer: its key in the tree}`` of one layer of
    ``kinds = (layer type, MLP kind, query heads)``."""
    return {n: f"{n}.{layer}" for n in LAYER_LEAVES + MLP_LEAVES[kinds[1]]}


def leaves(cfg: dict):
    """``(key, name, layer)`` of every leaf as the program holds them:
    the top-level names (``layer`` None), and ``name.<layer>`` for a
    layer's."""
    for n in TOP_LEAVES:
        yield n, n, None
    for l, kinds in enumerate(layer_kinds(cfg)):
        for n, key in layer_leaves(kinds, l).items():
            yield key, n, l


def leaf_keys(cfg: dict):
    return [key for key, _, _ in leaves(cfg)]


def _leaf_shape(cfg: dict, kinds, name: str, layer):
    heads = cfg["num_attention_heads"] if layer is None else kinds[layer][2]
    return _shapes(cfg, heads)[name]


def n_parameters(cfg: dict) -> int:
    kinds = layer_kinds(cfg)
    return sum(math.prod(_leaf_shape(cfg, kinds, name, layer))
               for _, name, layer in leaves(cfg))


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights, a leaf an entry under ``leaf_keys``'
    names, each from a key of its own: matrices N(0, 0.02), the token
    embedding N(0, 1) (at 0.02 every router collapses onto the same few
    experts: ``sdar_moe.init_weights`` has the readings), norm gains 1 +
    N(0, 0.02)."""
    kinds = layer_kinds(cfg)
    order = {name: i for i, name in enumerate(sorted(_shapes(cfg, 1)))}
    out = {}
    for leaf, name, layer in leaves(cfg):
        k = jax.random.fold_in(jax.random.fold_in(key, order[name]),
                               0 if layer is None else layer + 1)
        shape = _leaf_shape(cfg, kinds, name, layer)
        if name == "wte":
            w = jax.random.normal(k, shape, jnp.float32)
        else:
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            if name.endswith("_g") and len(shape) == 1:  # a norm's gain
                w = 1.0 + w
        out[leaf] = w
    return out


def share_of(weights: dict, cfg: dict, first_expert: int, num_experts: int,
             first_row: int, rows: int) -> dict:
    """The weights one chip holds of an uncut model's: its experts of
    every expert layer, its rows of the embedding and of the head, and
    all of what every chip holds alike (the mixers, the router, the
    shared expert and its gate, the dense layer)."""
    out = dict(weights)
    for l, (_, mlp, _) in enumerate(layer_kinds(cfg)):
        if mlp == "sparse":
            for n in ("w_gate", "w_up", "w_down"):
                out[f"{n}.{l}"] = weights[f"{n}.{l}"][
                    first_expert:first_expert + num_experts]
    for n in ("wte", "head"):
        out[n] = weights[n][first_row:first_row + rows]
    return out


def _rms(x, w, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rotation(cfg: dict, layer_type: str):
    """``(frequencies (turned / 2,), factor on cos and sin, turned)`` of
    a layer type's rotation from ``rope_parameters[layer_type]``: the
    leading ``turned = head_dim * partial_rotary_factor`` channels turn.
    ``default``: ``theta ** (-2i / turned)``.  ``yarn``: those
    (extrapolated) and those over ``factor`` (interpolated), blended by
    the linear ramp over the correction range, as
    ``_compute_yarn_parameters`` does it: channel pair ``i`` completes
    ``original_max_position_embeddings * theta ** (-2i / turned) / 2
    pi`` turns over the trained length; the pairs that complete
    ``beta_fast`` or more keep their frequency, those that complete
    ``beta_slow`` or fewer are interpolated, the bounds cut to whole
    indices (``truncate``'s default)."""
    p = cfg["rope_parameters"][layer_type]
    theta = float(p["rope_theta"])
    turned = int(cfg["head_dim"] * p.get("partial_rotary_factor", 1.0))
    plain = 1.0 / theta ** (np.arange(0, turned, 2, dtype=np.float32)
                            / turned)
    if p["rope_type"] == "default":
        return jnp.asarray(plain, jnp.float32), 1.0, turned
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    factor = float(p["factor"])
    trained = p["original_max_position_embeddings"]

    def pair_of(turns):
        return turned * math.log(trained / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(p.get("beta_fast") or 32)), 0)
    high = min(math.ceil(pair_of(p.get("beta_slow") or 1)), turned - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(turned // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 where the frequency stays as trained
    freq = plain / factor * (1.0 - keep) + plain * keep
    on_cos_sin = p.get("attention_factor")
    if on_cos_sin is None:
        on_cos_sin = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return jnp.asarray(freq, jnp.float32), float(on_cos_sin), turned


def rotate(x, pos, freq, on_cos_sin, turned):
    """``R_t`` on ``x (s, heads, dh)``, ``t = pos (s,)``: the leading
    ``turned`` channels, halves paired (``rotate_half``), cos and sin
    times ``on_cos_sin``; the other channels pass, unscaled."""
    half = turned // 2
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * on_cos_sin)[:, None, :]
    sin = (jnp.sin(ang) * on_cos_sin)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:turned]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., turned:]], -1)


def live_keys(index, pos, window):
    """``(rows, keys)``: which key positions ``pos`` a query at
    ``index`` sees: ``j <= i``, and with a window ``j > i - window``
    (``window`` keys, the query's own among them)."""
    live = pos[None, :] <= index[:, None]
    if window is not None:
        live &= pos[None, :] > index[:, None] - window
    return live


def attention(x, w, cfg, ein, kinds):
    """The mixer of a layer of ``kinds`` on one sequence ``x (s, d)``."""
    s = x.shape[0]
    layer_type, _, hq = kinds
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    freq, on_cos_sin, turned = rotation(cfg, layer_type)
    window = int(cfg["sliding_window"]) if WINDOWED[layer_type] else None
    q = rotate(ein("sd,dk->sk", x, w["w_q"]).reshape(s, hq, dh), pos, freq,
               on_cos_sin, turned).reshape(s, hkv, hq // hkv, dh)
    k = rotate(ein("sd,dk->sk", x, w["w_k"]).reshape(s, hkv, dh), pos,
               freq, on_cos_sin, turned)
    v = ein("sd,dk->sk", x, w["w_v"]).reshape(s, hkv, dh)

    def rows(args):
        q_blk, index = args
        sc = ein("qhgd,khd->hgqk", q_blk, k) * dh ** -0.5
        sc = jnp.where(live_keys(index, pos, window), sc, -jnp.inf)
        return ein("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v)

    rb = math.gcd(s, ROW_BLOCK)
    o = lax.map(jax.checkpoint(rows),
                (q.reshape(s // rb, rb, *q.shape[1:]),
                 pos.reshape(s // rb, rb)))
    # one gate a head, from the layer's normed input
    gate = jax.nn.sigmoid(ein("sd,dh->sh", x, w["w_g"]))
    o = o.reshape(s, hq, dh) * gate[:, :, None]
    return ein("sk,kd->sd", o.reshape(s, hq * dh), w["w_o"])


def _gated(u, w_in_gate, w_in_up, w_out, ein):
    return ein("nf,fd->nd", jax.nn.silu(ein("nd,df->nf", u, w_in_gate))
               * ein("nd,df->nf", u, w_in_up), w_out)


def route(u, router, cfg, prefer=None):
    """Router of one layer on ``u (n, d)``: softmax over all experts in
    float32 (in the float8 control too), the top ``k``, weights
    renormalised over them and times ``moe_routed_scaling_factor``.
    ``prefer (n, k)``: routes a program took, followed where the
    reference's own probability of that expert is within
    ``route_tie_window`` (relative) of its own ``k``-th choice.
    Returns ``(probs (n, E), chosen (n, k), weights (n, k))``."""
    probs = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", u, router, precision=lax.Precision.HIGHEST), axis=-1)
    score = probs
    if prefer is not None:
        taken = jax.nn.one_hot(prefer, probs.shape[-1]).sum(1)
        score = probs * (1.0 + cfg["route_tie_window"] * taken)
    _, chosen = lax.top_k(score, cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, chosen, cfg["moe_routed_scaling_factor"] * vals / vals.sum(
        -1, keepdims=True)


def routed_part(u, w, cfg, ein, prefer=None):
    """The held experts' part of the expert layer's result on ``u (n,
    d)``, a loop over them; the layer's load-balancing term (over all
    the router's experts and all tokens); and what the tie window did:
    ``chosen`` as taken, ``followed`` (routes taken from the program
    over the reference's own top ``k``) and ``refused`` (routes of the
    program the reference did not take)."""
    probs, chosen, weights = route(u, w["router"], cfg, prefer)
    first, routed = cfg.get("first_expert", 0), cfg["router_experts"]

    @jax.checkpoint
    def term(ws):
        w_gate, w_up, w_down, e = ws
        gate = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        return gate[:, None] * _gated(u, w_gate, w_up, w_down, ein)

    # the absent experts' terms are left out (the cut to a chip's share)
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
    gate = jax.nn.sigmoid(jnp.einsum("nd,do->no", u, w["s_mix"],
                                     precision=lax.Precision.HIGHEST))
    return gate * _gated(u, w["s_gate"], w["s_up"], w["s_down"], ein)


def _layer(x, w, prefer=None, *, kinds, cfg, lowp=False):
    """One layer on ``x (rows, s, d)``: the new stream, the layer's
    load-balancing term (0 for a dense layer) and its routing report
    (``None`` for a dense layer)."""
    ein, eps = _ein(lowp), cfg["rms_norm_eps"]
    mix = jax.vmap(lambda row: attention(
        _rms(row, w["norm1_g"], eps), w, cfg, ein, kinds))
    h = x + mix(x)
    u = _rms(h, w["norm2_g"], eps).reshape(-1, h.shape[-1])
    if kinds[1] == "dense":
        y = jax.checkpoint(lambda u, d_in, d_out: _gated(
            u, *jnp.split(d_in, 2, axis=-1), d_out, ein))(
            u, w["d_in"], w["d_out"])
        return h + y.reshape(h.shape), jnp.float32(0.0), None
    y, aux, report = routed_part(u, w, cfg, ein, prefer)
    y = y + jax.checkpoint(lambda u, w: shared_part(u, w, ein))(
        u, {n: w[n] for n in ("s_gate", "s_up", "s_down", "s_mix")})
    return h + y.reshape(h.shape), aux, report


def _sparse(cfg: dict) -> list:
    """The layers that route, in order."""
    return [l for l, (_, mlp, _) in enumerate(layer_kinds(cfg))
            if mlp == "sparse"]


def hidden_fn(weights, tokens, cfg, lowp=False, routes=None):
    """``tokens (rows, s)`` -> the final-norm hidden states ``(rows, s,
    d)``, the summed load-balancing term and the expert layers' routing
    reports; every layer is computed again in the backward pass.
    ``routes (expert layers, rows * s, k)``: a program's, see
    ``route``."""
    x, aux, reports = weights["wte"][tokens], 0.0, []
    sparse = _sparse(cfg)
    for l, kinds in enumerate(layer_kinds(cfg)):
        w = {n: weights[k] for n, k in layer_leaves(kinds, l).items()}
        prefer = routes[sparse.index(l)] \
            if routes is not None and l in sparse else None
        x, a, report = jax.checkpoint(functools.partial(
            _layer, kinds=kinds, cfg=cfg, lowp=lowp))(x, w, prefer)
        aux = aux + a
        if report is not None:
            reports.append(report)
    return _rms(x, weights["normf_g"], cfg["rms_norm_eps"]), aux, reports


def logits_fn(weights, tokens, cfg, lowp=False, routes=None):
    """``(rows, s, vocab)`` logits over the rows held."""
    hidden, _, _ = hidden_fn(weights, tokens, cfg, lowp, routes)
    return _ein(lowp)("rsd,vd->rsv", hidden, weights["head"])


def batch_loss(weights, tokens, cfg, lowp=False, routes=None,
               report=False):
    """The loss of ``tokens (rows, s)``; with ``report`` the pair of it
    and the expert layers' routing reports."""
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


def train_readings(seed, cfg, batches, optimizer, lowp=False, routes=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  ``batches``: int32 ``(steps, rows, s)``; ``routes``: the
    routes a program took on each, ``(expert layers, rows * s, k)`` a
    step.  Returns host floats: ``losses``, ``grad_norms`` (first step,
    per leaf), ``grad_small`` (the first gradient's small leaves,
    whole), ``delta_norms`` (parameters after the last step minus the
    seeded ones, per leaf), and of the routing ``routes`` (as taken, a
    step), ``routes_followed`` and ``routes_refused`` (shares of all
    routes of the steps).

    The gradient is ``jax.grad(batch_loss)``'s, taken a layer at a time
    so that a step fits one chip at the cell's size, exactly as
    ``moonlight.train_readings`` takes it: the forward pass keeps each
    layer's input, the backward pass walks the layers from the last,
    computes a layer again under ``jax.vjp`` (its stream and its
    load-balancing term, whose cotangent is ``aux_loss_coef``) and
    applies AdamW to its leaves at once (the tests hold it against
    ``jax.value_and_grad(batch_loss)``)."""
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    kinds, ein = layer_kinds(cfg), _ein(lowp)
    sparse = _sparse(cfg)
    eps, coef = cfg["rms_norm_eps"], jnp.float32(cfg["aux_loss_coef"])

    def layer_of(kind):
        return functools.partial(_layer, kinds=kind, cfg=cfg, lowp=lowp)

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
        prefer = [None] * len(kinds)
        if routes is not None:
            for l, r in zip(sparse, np.asarray(routes[t - 1])):
                prefer[l] = jnp.asarray(r)
        inputs, aux, taken = [weights["wte"][tokens]], 0.0, []
        for l, kind in enumerate(kinds):
            x, a, report = forward(
                inputs[-1], pick(weights, layer_leaves(kind, l)),
                prefer[l], kind)
            inputs.append(x)
            aux += float(a)
            if report is not None:
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
