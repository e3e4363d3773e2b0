"""Plain reference for SDAR-MoE block-diffusion training (SDAR-30B-A3B,
``model_type`` ``sdar_moe``; the objective of BD3-LM, arXiv:2503.09573).

Straight ``jax.numpy`` in float32 with matmul precision ``highest``: no
kernels, no flax, nothing imported from the program.  One chip's share
of a layer that several chips hold together: the router scores all
``router_experts`` experts and keeps the top ``num_experts_per_tok``
with weights renormalised over them; of those routes only the ones to
the ``num_experts`` experts held here (from ``first_expert`` on) are
computed, and what the absent experts would have added is left out.
The vocabulary is the slice held here.  With ``num_experts ==
router_experts`` and the whole vocabulary the same functions are the
uncut model (``share_of`` cuts a share's weights out of it).

Per layer, positions ``p`` of the concatenation [clean; noised]:

    h = x + O(Attn(rope(qn(Q rms(x))), rope(kn(K rms(x))), V rms(x)))
    u = rms(h);  w = renorm(top8(softmax(R u)))
    y = h + sum_{e in top8(p), e held} w_e(p) W_d^e(silu(W_g^e u) * W_u^e u)

Attention masks come from the definition: a clean query of block ``b``
sees the clean keys of blocks ``<= b``; a noised query of block ``b``
the clean keys of blocks ``< b`` and the noised keys of block ``b``;
both copies carry positions ``0..s-1``.  The loss is ``sum_i w_i * (-log
p(x0_i | ...))`` over the sample tokens, ``w_i = 1 / t_b`` on the
masked positions of block ``b`` and 0 elsewhere, logits taken on the
noised copy at the position itself, plus ``aux_loss_coef`` times the
layers' load-balancing terms (``router_experts * sum_e f_e P_e``).
``lowp=True`` rounds every matmul operand to scaled float8: the control.

A top-8 of 128 is a discrete choice, and with seeded weights a token's
eighth and ninth probabilities nearly tie for a few tokens in a hundred
(all the mask id's positions at once, where they do): a program in
bfloat16 then picks the other expert, and every gradient that follows
the route differs by that expert, which is rounding and no fault.  So
the reference can be handed the routes the program took (``routes``)
and follows one where its own float32 probabilities put the chosen
expert within ``route_tie_window`` (a relative bonus on the chosen
experts' probabilities before the top-8) of its own choice; outside
the window it keeps its own, and the gradients then differ as they
should.  The gate weights are always the reference's own probabilities
of the experts taken.  ``train_readings`` reports the share of routes it
took from the program over its own and the share of the program's it
refused.  The window's two ends are read with
``cellbench/tests/chip_route_window.py``: the program's gaps at narrower
windows, and router controls (``router_round``: only the router's
product in bfloat16 or scaled float8, routing for themselves) whose
routes the reference follows as it would a program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import SMALL, fp8, seed_key  # noqa: F401 (runners use them)

# AdamW as ``optax.adamw(lr, weight_decay=wd)`` defines it
B1, B2, EPS = 0.9, 0.999, 1e-8

#: stacked per-layer leaves, in the order a layer uses them
LAYER_LEAVES = ("norm1_g", "w_q", "w_k", "w_v", "qn_g", "kn_g", "w_o",
                "norm2_g", "router", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("wte", "head", "normf_g")

#: queries a block of the dense attention scores at once
QUERY_BLOCK = 256


def _shapes(cfg: dict) -> dict:
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    f, held, routed = cfg["moe_intermediate_size"], cfg["num_experts"], \
        cfg["router_experts"]
    return {
        "wte": (V, d), "head": (V, d), "normf_g": (d,),
        "norm1_g": (L, d), "w_q": (L, d, hq * dh), "w_k": (L, d, hkv * dh),
        "w_v": (L, d, hkv * dh), "qn_g": (L, dh), "kn_g": (L, dh),
        "w_o": (L, hq * dh, d), "norm2_g": (L, d),
        "router": (L, d, routed), "w_gate": (L, held, d, f),
        "w_up": (L, held, d, f), "w_down": (L, held, f, d),
    }


def init_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights: matrices N(0, 0.02), norm gains 1 + N(0,
    0.02), the token embedding N(0, 1).  Per-layer leaves are stacked on
    a leading layer axis.

    The embedding is the one leaf off 0.02: at 0.02 a row (norm 0.9) is
    smaller than the first attention layer's output, the stream of every
    position is then mostly one common vector, and each layer's router
    sends nearly every token to the same 8 experts (largest expert load
    15.9 times the balanced one, 1 % to 34 % of the routes on the 16
    held experts by seed and layer: read on the CPU at the published
    widths, s 1024).  A trained model's routers are balanced by its
    auxiliary loss; a stream that the tokens dominate gives the seeded
    one that balance (5 % to 20 %; the mask id's positions, a quarter of
    all, still go one way a layer, as they do in training)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        out[name] = 1.0 + 0.02 * w if name.endswith("_g") \
            else w if name == "wte" else 0.02 * w
    return out


def share_of(weights: dict, first_expert: int, num_experts: int,
             first_row: int, rows: int) -> dict:
    """The weights one chip holds of an uncut model's: its experts of
    every layer, its rows of the embedding and of the head, and all of
    what every chip holds alike."""
    out = dict(weights)
    for n in ("w_gate", "w_up", "w_down"):
        out[n] = weights[n][:, first_expert:first_expert + num_experts]
    for n in ("wte", "head"):
        out[n] = weights[n][first_row:first_row + rows]
    return out


def leaf_keys(cfg: dict):
    keys = list(TOP_LEAVES)
    for l in range(cfg["num_hidden_layers"]):
        keys += [f"{n}.{l}" for n in LAYER_LEAVES]
    return keys


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


def _rope(x, pos, theta):
    """Rotary positions on ``x (n, heads, dh)``: the halves convention
    (``rotate_half``) of the Qwen lineage."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def diffusion_mask(q_index, s: int, block: int):
    """Rows ``q_index`` of the (2s, 2s) mask over [clean; noised], from
    the definition."""
    k_index = jnp.arange(2 * s)
    q_noised, k_noised = (q_index >= s)[:, None], (k_index >= s)[None, :]
    qb = ((q_index % s) // block)[:, None]
    kb = ((k_index % s) // block)[None, :]
    clean_q = ~q_noised & ~k_noised & (kb <= qb)
    noised_q = q_noised & ((~k_noised & (kb < qb)) | (k_noised & (kb == qb)))
    return clean_q | noised_q


def _attention(q, k, v, s, block, ein):
    """``q (2s, hkv, g, dh)``, ``k`` / ``v (2s, hkv, dh)``: dense masked
    softmax, a block of queries at a time."""
    n = q.shape[0]
    qb = min(QUERY_BLOCK, n)
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def rows(args):
        q_blk, index = args
        sc = ein("qhgd,khd->hgqk", q_blk, k) * scale
        sc = jnp.where(diffusion_mask(index, s, block)[None, None], sc,
                       -jnp.inf)
        return ein("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v)

    out = lax.map(rows, (q.reshape(n // qb, qb, *q.shape[1:]),
                         jnp.arange(n).reshape(n // qb, qb)))
    return out.reshape(q.shape)


def _rounded(x, to):
    """``x`` as a product in precision ``to`` holds it (None: as it is)."""
    if to is None:
        return x
    return fp8(x) if to == "float8" else x.astype(to).astype(jnp.float32)


def route(u, router, cfg, prefer=None, round_to=None):
    """Router of one layer on ``u (n, d)``: softmax over all experts in
    float32 (in the float8 control too), the top ``k`` with weights
    renormalised over them.  ``prefer (n, k)``: routes a program took,
    followed inside the tie window (module docstring).  ``round_to``:
    the router controls', whose product takes both operands rounded to
    ``"bfloat16"`` or scaled ``"float8"``.  Returns ``(probs (n, E),
    chosen (n, k), weights (n, k))``."""
    probs = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", _rounded(u, round_to), _rounded(router, round_to),
        precision=lax.Precision.HIGHEST), axis=-1)
    score = probs
    if prefer is not None:
        taken = jax.nn.one_hot(prefer, probs.shape[-1]).sum(1)
        score = probs * (1.0 + cfg["route_tie_window"] * taken)
    _, chosen = lax.top_k(score, cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, chosen, vals / vals.sum(-1, keepdims=True)


def _routing_report(probs, chosen, prefer, cfg):
    """What the tie window did to one layer's routes: ``chosen`` as
    taken, ``followed`` (routes taken from the program over the
    reference's own top ``k``) and ``refused`` (routes of the program
    the reference did not take: outside the window)."""
    hot = lambda idx: jax.nn.one_hot(idx, probs.shape[-1]).sum(1)
    taken = hot(chosen)
    own = hot(lax.top_k(probs, cfg["num_experts_per_tok"])[1])
    followed = (taken * (1.0 - own)).sum()
    refused = jnp.float32(0.0) if prefer is None \
        else (hot(prefer) * (1.0 - taken)).sum()
    return {"chosen": chosen, "followed": followed, "refused": refused}


def expert_layer(u, w, cfg, ein, prefer=None, round_to=None, report=False):
    """The held experts' part of the expert layer's result on ``u (n,
    d)``, and the layer's load-balancing term (over all the router's
    experts); with ``report`` also ``_routing_report``'s."""
    probs, chosen, weights = route(u, w["router"], cfg, prefer, round_to)
    first, routed = cfg.get("first_expert", 0), cfg["router_experts"]

    @jax.checkpoint
    def one(y, ws):
        w_gate, w_up, w_down, e = ws
        gate = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        hidden = jax.nn.silu(ein("nd,df->nf", u, w_gate)) \
            * ein("nd,df->nf", u, w_up)
        return y + gate[:, None] * ein("nf,fd->nd", hidden, w_down), None

    y, _ = lax.scan(one, jnp.zeros_like(u),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     jnp.arange(cfg["num_experts"])))
    share = jax.nn.one_hot(chosen, routed).sum((0, 1)) / chosen.size
    aux = routed * jnp.sum(share * probs.mean(0))
    if report:
        return y, aux, _routing_report(probs, chosen, prefer, cfg)
    return y, aux


def _layer(x, w, cfg, s, ein, prefer=None, round_to=None):
    """One layer on ``x (rows, 2s, d)``; returns the new stream, the
    layer's load-balancing term, taken over all rows together, and its
    routing report."""
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, n = cfg["rms_norm_eps"], 2 * s
    pos = jnp.arange(n) % s

    def attend(x):
        h = _rms(x, w["norm1_g"], eps)
        q = ein("nd,dk->nk", h, w["w_q"]).reshape(n, hq, dh)
        k = ein("nd,dk->nk", h, w["w_k"]).reshape(n, hkv, dh)
        v = ein("nd,dk->nk", h, w["w_v"]).reshape(n, hkv, dh)
        q = _rope(_rms(q, w["qn_g"], eps), pos, cfg["rope_theta"])
        k = _rope(_rms(k, w["kn_g"], eps), pos, cfg["rope_theta"])
        o = _attention(q.reshape(n, hkv, hq // hkv, dh), k, v, s,
                       cfg["block_length"], ein)
        return x + ein("nk,kd->nd", o.reshape(n, hq * dh), w["w_o"])

    h = jax.vmap(attend)(x)
    u = _rms(h, w["norm2_g"], eps)
    y, aux, report = expert_layer(u.reshape(-1, u.shape[-1]), w, cfg, ein,
                                  prefer, round_to, report=True)
    return h + y.reshape(h.shape), (aux, report)


def hidden_fn(weights, tokens, cfg, lowp=False, routes=None,
              router_round=None, report=False):
    """``tokens (rows, 2s)``, clean copy then noised -> the final-norm
    hidden states ``(rows, 2s, d)`` and the summed load-balancing term.
    ``routes (layers, rows * 2s, k)``: a program's, see ``route``, as
    ``router_round``; ``report``: also the layers' routing reports,
    stacked."""
    ein = _ein(lowp)
    s = tokens.shape[1] // 2
    layer = jax.checkpoint(
        lambda x, w: _layer(x, w[0], cfg, s, ein, w[1], router_round))
    x, (aux, routing) = lax.scan(
        layer, weights["wte"][tokens],
        ({n: weights[n] for n in LAYER_LEAVES}, routes))
    out = _rms(x, weights["normf_g"], cfg["rms_norm_eps"]), aux.sum()
    return out + (routing,) if report else out


def logits_fn(weights, tokens, cfg, lowp=False, routes=None):
    """Logits of both copies ``(rows, 2s, vocab)`` (the loss reads the
    noised half) and the load-balancing term."""
    x, aux = hidden_fn(weights, tokens, cfg, lowp, routes)
    return _ein(lowp)("rnd,vd->rnv", x, weights["head"]), aux


def noised(tokens, mask, cfg):
    return jnp.where(mask, cfg["mask_token_id"], tokens)


def batch_loss(weights, batch, cfg, lowp=False, routes=None,
               router_round=None, report=False):
    """The block-diffusion loss of ``batch = (tokens, mask, weights)``,
    each ``(rows, s)``; with ``report`` the pair of it and the layers'
    routing reports."""
    tokens, mask, w = batch
    s = tokens.shape[1]
    both = jnp.concatenate([tokens, noised(tokens, mask, cfg)], axis=1)
    x, aux, routing = hidden_fn(weights, both, cfg, lowp, routes,
                                router_round, report=True)
    logits = _ein(lowp)("rnd,vd->rnv", x[:, s:], weights["head"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[..., None], axis=-1)[..., 0]
    loss = (nll * w).sum() / tokens.size + cfg["aux_loss_coef"] * aux
    return (loss, routing) if report else loss


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
                        for l in range(cfg["num_hidden_layers"])})
    return out


def _leaf_norms(tree, cfg):
    """float32 L2 norm of every program leaf, keyed as ``leaf_keys``."""
    L = cfg["num_hidden_layers"]
    out = {n: jnp.linalg.norm(tree[n]) for n in TOP_LEAVES}
    for n in LAYER_LEAVES:
        per = jnp.sqrt((tree[n] ** 2).reshape(L, -1).sum(1))
        out.update({f"{n}.{l}": per[l] for l in range(L)})
    return out


def train_readings(seed, cfg, batches, optimizer, lowp=False, routes=None,
                   router_round=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  ``batches``: a list of ``(tokens, mask, weights)`` triples
    of ``(rows, s)`` arrays as the program was fed them; ``routes``: the
    routes it took on each, ``(layers, rows * 2s, k)``.  Returns host
    floats: ``losses``, ``grad_norms`` (first step, per leaf),
    ``grad_small`` (the first gradient's small leaves, whole),
    ``delta_norms`` (parameters after the last step minus the seeded
    ones, per leaf), and of the routing ``routes`` (as taken, a step),
    ``routes_followed`` and ``routes_refused`` (shares of all routes of
    the steps: ``_routing_report``)."""
    lr, wd = optimizer["lr"], optimizer["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(weights, m, v, batch, t, taken):
        (loss, routing), g = jax.value_and_grad(batch_loss, has_aux=True)(
            weights, batch, cfg, lowp, taken, router_round, report=True)
        first = (_leaf_norms(g, cfg), small_leaves(g, cfg))
        weights, m, v = _adamw(weights, m, v, g, t, lr, wd)
        return weights, m, v, loss, first, routing

    key = seed_key(seed)

    @jax.jit
    def delta_norms(weights, key):
        w0 = init_weights(key, cfg)
        return _leaf_norms(jax.tree_util.tree_map(jnp.subtract, weights,
                                                  w0), cfg)

    weights = jax.jit(lambda k: init_weights(k, cfg))(key)
    zeros = jax.jit(lambda w: jax.tree_util.tree_map(jnp.zeros_like, w))
    m, v = zeros(weights), zeros(weights)
    losses, grad_norms, grad_small = [], None, None
    chosen, followed, refused = [], 0.0, 0.0
    for t, batch in enumerate(batches, start=1):
        batch = tuple(jnp.asarray(np.asarray(x)) for x in batch)
        taken = None if routes is None else jnp.asarray(routes[t - 1])
        weights, m, v, loss, (norms, small), routing = step(
            weights, m, v, batch, jnp.float32(t), taken)
        losses.append(float(loss))
        chosen.append(np.asarray(routing["chosen"]))
        followed += float(routing["followed"].sum())
        refused += float(routing["refused"].sum())
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms.items()}
            grad_small = {k: np.asarray(x) for k, x in small.items()}
    deltas = {k: float(x) for k, x in delta_norms(weights, key).items()}
    del weights, m, v
    n_routes = sum(c.size for c in chosen)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_small": grad_small, "delta_norms": deltas,
            "routes": chosen, "routes_followed": followed / n_routes,
            "routes_refused": refused / n_routes}
