"""Plain reference for a latent-attention model with sparse experts
behind a leading dense layer (Moonlight-16B-A3B, ``model_type``
``deepseek_v3``: the architecture arXiv:2412.19437 section 2.1, the
model arXiv:2502.16982).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program, **the rotation on
neighbouring channels as the family writes it**, **a dense masked
softmax** and **a loop over the experts held**.  One chip's share of a
layer that several chips hold together: the router scores all
``router_experts`` experts and keeps ``num_experts_per_tok`` of them; of
those routes only the ones to the ``n_routed_experts`` experts held here
(from ``first_expert`` on) are computed, and what the absent experts
would have added is left out (a departure from the published model: the
guide's cut to one chip's share); the shared experts and the dense layer
are whole on every chip.  The vocabulary is the slice held here (a
departure: the loss's softmax is over these rows alone).  With
``n_routed_experts == router_experts`` and the whole vocabulary the same
functions are the uncut model (``share_of`` cuts a share's weights out
of it).

``rms(x) = x / sqrt(mean(x^2) + eps) * w``.  ``h = E[tokens]``; layer
``i`` (from 0): its MLP is dense while ``i < first_k_dense_replace``,
else an expert layer:

    h = h + mix(rms(h));   u = rms(h)
    dense:    h = h + W_d (silu(W_g u) * W_u u)
    experts:  s = sigmoid(R u);  chosen = top_k(s + b);  w = f s_chosen / (sum s_chosen + 1e-20)
              h = h + sum_{e chosen, e held} w_e W_d^e(silu(W_g^e u) * W_u^e u)
                    + W_d(silu(W_g u) * W_u u)            the shared experts, no gate

with ``f = routed_scaling_factor`` and ``b`` the selection biases, which
the weights do not see and no gradient reaches; the ``n_shared_experts``
shared experts are one MLP of their summed width, as the family runs
them.

``mix`` on ``x (s, d)``, position ``t`` from 0 inside the sequence:
``[q_a | q_b] = W_q x`` a head (``qk_nope_head_dim | qk_rope_head_dim``;
``q_lora_rank`` is null), ``[c | k_b] = W_kva x`` (``kv_lora_rank |
qk_rope_head_dim``), ``[k_a | v] = W_kvb rms(c)`` a head; ``q_b <- R_t
q_b`` a head and ``k_b <- R_t k_b``, ``R_t`` turning the channels ``(2i,
2i + 1)`` by ``t * rope_theta ** (-2i / qk_rope_head_dim)``; ``k = [k_a
| k_b]`` with ``k_b`` the same for every head; causal softmax at ``(nope
+ rope) ** -0.5`` (no ``rope_scaling``: no other factor); ``W_o``.

After the last layer ``rms``, logits against the untied head's rows
held, mean cross-entropy of the next tokens, plus ``aux_loss_coef``
times the expert layers' balance losses, each counted a sequence
(``seq_aux``): ``f_e = E / (k T) #{t: e chosen at t}``, ``P_e = mean_t
s_et / sum_j s_jt``, ``sum_e f_e P_e`` over all ``E = router_experts``,
averaged over the step's sequences.  AdamW (a departure: the model was
trained with Muon); ``b`` is a constant of the run (no gradient, no
decay; a departure: the rule that moves it during pre-training is left
out).  These layers are trained as a model with a final norm and head
of their own (a departure: a pipeline's first stage would send its
output on).

Only so that a step fits one chip beside its 12 bytes a parameter of
float32 state: the gradient is taken a layer at a time
(``train_readings``), attention's scores and the loss a block of rows
at a time, an expert's term under ``jax.checkpoint`` (as
``reference/kimi_linear.py``, whose helpers of that kind, from
``reference/qwen3_next.py``, are used here).  ``lowp=True`` rounds
every product's operands to scaled float8: the control; the router's
scores and the rotation stay float32.

A top-6 of 64 is a discrete choice that flips on rounding where the
sixth and seventh biased scores nearly tie, so the reference can be
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
    ROW_BLOCK,
    SMALL,
    _ein,
    _leaf_norms,
    _next_token_loss,
    small_leaves,
)

TOP_LEAVES = ("wte", "head", "normf_g")
#: leaves every layer has: its norms and its mixer
LAYER_LEAVES = ("norm1_g", "norm2_g", "w_q", "w_kva", "kvn_g", "w_kvb",
                "w_o")
#: leaves of a layer's MLP, by kind
MLP_LEAVES = {
    "dense": ("d_in", "d_out"),
    "experts": ("router", "r_bias", "w_gate", "w_up", "w_down", "s_gate",
                "s_up", "s_down"),
}
#: no gradient and no decay: constants of the run
CONSTANTS = ("r_bias",)
MIXER = "latent_attention"


def layer_kinds(cfg: dict) -> tuple:
    """``(mixer kind, MLP kind)`` of each of the ``num_hidden_layers``
    layers: latent attention in every one."""
    return tuple(
        (MIXER, "dense" if i < int(cfg["first_k_dense_replace"])
         else "experts") for i in range(int(cfg["num_hidden_layers"])))


def _shapes(cfg: dict) -> dict:
    """The shape of a leaf of each name."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    hq, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    held, routed = cfg["n_routed_experts"], cfg["router_experts"]
    return {
        "wte": (V, d), "head": (V, d), "normf_g": (d,),
        "norm1_g": (d,), "norm2_g": (d,),
        "w_q": (d, hq * (dn + dr)), "w_kva": (d, rank + dr),
        "kvn_g": (rank,), "w_kvb": (rank, hq * (dn + dv)),
        "w_o": (hq * dv, d),
        "d_in": (d, 2 * fd), "d_out": (fd, d),
        "router": (d, routed), "r_bias": (routed,),
        "w_gate": (held, d, f), "w_up": (held, d, f), "w_down": (held, f, d),
        "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
    }


def layer_leaves(kinds, layer: int) -> dict:
    """``{name inside the layer: its key in the tree}`` of one layer of
    ``kinds = (mixer kind, MLP kind)``."""
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


def n_parameters(cfg: dict) -> int:
    shapes = _shapes(cfg)
    return sum(math.prod(shapes[name]) for _, name, _ in leaves(cfg))


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights, a leaf an entry under ``leaf_keys``'
    names, each from a key of its own: matrices N(0, 0.02), the token
    embedding N(0, 1) (at 0.02 every router collapses onto the same few
    experts: ``sdar_moe.init_weights`` has the readings), norm gains 1 +
    N(0, 0.02), the selection biases N(0, 0.01)."""
    shapes = _shapes(cfg)
    order = {name: i for i, name in enumerate(sorted(shapes))}
    out = {}
    for leaf, name, layer in leaves(cfg):
        k = jax.random.fold_in(jax.random.fold_in(key, order[name]),
                               0 if layer is None else layer + 1)
        shape = shapes[name]
        if name == "wte":
            w = jax.random.normal(k, shape, jnp.float32)
        elif name == "r_bias":
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
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
    all of what every chip holds alike (the mixers, the shared experts,
    the router and its biases, the dense layer)."""
    out = dict(weights)
    for l, (_, mlp) in enumerate(layer_kinds(cfg)):
        if mlp == "experts":
            for n in ("w_gate", "w_up", "w_down"):
                out[f"{n}.{l}"] = weights[f"{n}.{l}"][
                    first_expert:first_expert + num_experts]
    for n in ("wte", "head"):
        out[n] = weights[n][first_row:first_row + rows]
    return out


def _rms(x, w, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rotate_pairs(x, pos, theta):
    """``R_t`` on the last axis of ``x (s, ..., dr)``, ``t = pos (s,)``:
    the neighbouring channels ``(2i, 2i + 1)`` turned by ``t * theta **
    (-2i / dr)``, ``(a, b) -> (a cos - b sin, a sin + b cos)``.  The
    pairing is an assumption (the open DeepSeek-V3 implementation as
    remembered; the catalog's row has no ``rope_interleave`` key)."""
    dr = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(x, w, cfg, ein):
    """The mixer on one sequence ``x (s, d)``."""
    s = x.shape[0]
    hq, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    pos, theta = jnp.arange(s), float(cfg["rope_theta"])
    q_a, q_b = jnp.split(
        ein("sd,dk->sk", x, w["w_q"]).reshape(s, hq, dn + dr), [dn], axis=-1)
    latent, shared = jnp.split(ein("sd,dk->sk", x, w["w_kva"]), [rank],
                               axis=-1)
    own, v = jnp.split(
        ein("sr,rk->sk", _rms(latent, w["kvn_g"], cfg["rms_norm_eps"]),
            w["w_kvb"]).reshape(s, hq, dn + dv), [dn], axis=-1)
    q = jnp.concatenate([q_a, rotate_pairs(q_b, pos, theta)], axis=-1)
    k = jnp.concatenate(
        [own, jnp.broadcast_to(rotate_pairs(shared, pos, theta)[:, None],
                               (s, hq, dr))], axis=-1)

    def rows(args):
        q_blk, index = args
        sc = ein("qhd,khd->hqk", q_blk, k) * (dn + dr) ** -0.5
        sc = jnp.where(pos[None, :] <= index[:, None], sc, -jnp.inf)
        return ein("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    rb = math.gcd(s, ROW_BLOCK)
    o = lax.map(jax.checkpoint(rows),
                (q.reshape(s // rb, rb, hq, dn + dr), pos.reshape(s // rb, rb)))
    return ein("sk,kd->sd", o.reshape(s, hq * dv), w["w_o"])


def _gated(u, w_in_gate, w_in_up, w_out, ein):
    return ein("nf,fd->nd", jax.nn.silu(ein("nd,df->nf", u, w_in_gate))
               * ein("nd,df->nf", u, w_in_up), w_out)


def route(u, router, bias, cfg, prefer=None):
    """Router of one layer on ``u (n, d)``: a sigmoid an expert in
    float32 (in the float8 control too), the ``k`` largest of ``s + b``,
    weights ``f s / (sum of the chosen s + 1e-20)``.  ``prefer (n, k)``:
    routes a program took, followed where the reference's own biased
    score of that expert is within ``route_tie_window`` (relative) of
    its own ``k``-th choice.  Returns ``(s (n, E), chosen (n, k),
    weights (n, k))``."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", u, router, precision=lax.Precision.HIGHEST))
    select = scores + lax.stop_gradient(bias)
    if prefer is not None:
        taken = jax.nn.one_hot(prefer, scores.shape[-1]).sum(1)
        select = select + cfg["route_tie_window"] * taken * jnp.abs(select)
    _, chosen = lax.top_k(select, cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, chosen, axis=-1)
    return scores, chosen, cfg["routed_scaling_factor"] * vals / (
        vals.sum(-1, keepdims=True) + 1e-20)


def balance_loss(scores, chosen, sequences: int):
    """The balance loss counted a sequence (``seq_aux``; arXiv:2412.19437
    eq. 17-20) of ``scores (n, E)`` and ``chosen (n, k)`` that hold
    ``sequences`` sequences of ``T`` positions one after another: ``f_e
    = E / (k T) #{t: e chosen at t}``, ``P_e = mean_t s_et / sum_j
    s_jt``, ``sum_e f_e P_e`` a sequence, averaged over them."""
    n, routed = scores.shape
    k, T = chosen.shape[-1], n // sequences
    count = jax.nn.one_hot(chosen, routed).sum(1).reshape(
        sequences, T, routed).sum(1)
    f = routed / (k * T) * count
    P = (scores / scores.sum(-1, keepdims=True)).reshape(
        sequences, T, routed).mean(1)
    return (f * P).sum(-1).mean()


def routed_part(u, w, cfg, ein, sequences: int, prefer=None):
    """The held experts' part of the expert layer's result on ``u (n,
    d)``, a loop over them; the layer's balance loss (over all the
    router's experts, a sequence); and what the tie window did:
    ``chosen`` as taken, ``followed`` (routes taken from the program
    over the reference's own top ``k``) and ``refused`` (routes of the
    program the reference did not take)."""
    scores, chosen, weights = route(u, w["router"], w["r_bias"], cfg, prefer)
    first, routed = cfg.get("first_expert", 0), cfg["router_experts"]
    k = cfg["num_experts_per_tok"]

    @jax.checkpoint
    def term(ws):
        w_gate, w_up, w_down, e = ws
        gate = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        return gate[:, None] * _gated(u, w_gate, w_up, w_down, ein)

    # the absent experts' terms are left out (the cut to a chip's share)
    y, _ = lax.scan(lambda y, ws: (y + term(ws), None), jnp.zeros_like(u),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     jnp.arange(cfg["n_routed_experts"])))
    hot = lambda idx: jax.nn.one_hot(idx, routed).sum(1)
    own = hot(lax.top_k(scores + w["r_bias"], k)[1])
    report = {"chosen": chosen,
              "followed": (hot(chosen) * (1.0 - own)).sum(),
              "refused": jnp.float32(0.0) if prefer is None
              else (hot(prefer) * (1.0 - hot(chosen))).sum()}
    return y, balance_loss(scores, chosen, sequences), report


def _layer(x, w, prefer=None, *, kinds, cfg, lowp=False):
    """One layer on ``x (rows, s, d)``: the new stream, the layer's
    balance loss (0 for a dense layer) and its routing report (``None``
    for a dense layer)."""
    ein, eps = _ein(lowp), cfg["rms_norm_eps"]
    mix = jax.vmap(lambda row: latent_attention(
        _rms(row, w["norm1_g"], eps), w, cfg, ein))
    h = x + mix(x)
    u = _rms(h, w["norm2_g"], eps).reshape(-1, h.shape[-1])
    if kinds[1] == "dense":
        y = jax.checkpoint(lambda u, d_in, d_out: _gated(
            u, *jnp.split(d_in, 2, axis=-1), d_out, ein))(
            u, w["d_in"], w["d_out"])
        return h + y.reshape(h.shape), jnp.float32(0.0), None
    y, aux, report = routed_part(u, w, cfg, ein, h.shape[0], prefer)
    # the shared experts: one MLP of their summed width, no gate
    y = y + jax.checkpoint(lambda u, *ws: _gated(u, *ws, ein))(
        u, w["s_gate"], w["s_up"], w["s_down"])
    return h + y.reshape(h.shape), aux, report


def _sparse(cfg: dict) -> list:
    """The layers that route, in order."""
    return [l for l, (_, mlp) in enumerate(layer_kinds(cfg))
            if mlp == "experts"]


def hidden_fn(weights, tokens, cfg, lowp=False, routes=None):
    """``tokens (rows, s)`` -> the final-norm hidden states ``(rows, s,
    d)``, the summed balance loss and the expert layers' routing
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
    """One AdamW step, a leaf at a time: ``(weights, m, v)`` after it.
    A constant of the run (``CONSTANTS``) has no gradient and no decay:
    it stays."""
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    out = {}
    for k, p in weights.items():
        m_k = B1 * m[k] + (1 - B1) * g[k]
        v_k = B2 * v[k] + (1 - B2) * g[k] * g[k]
        decay = 0.0 if k in CONSTANTS else wd
        out[k] = (p - lr * ((m_k / c1) / (jnp.sqrt(v_k / c2) + EPS)
                            + decay * p), m_k, v_k)
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
    ``kimi_linear.train_readings`` takes it: the forward pass keeps each
    layer's input, the backward pass walks the layers from the last,
    computes a layer again under ``jax.vjp`` (its stream and its
    balance loss, whose cotangent is ``aux_loss_coef``) and applies
    AdamW to its leaves at once (the tests hold it against
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
