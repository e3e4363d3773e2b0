"""Moonlight on the training path: the rotation on latent attention's
shared channels (neighbouring pairs, against complex multiplication by
hand, and the program's halves form of it), ``LatentAttention`` with and
without it, the two shared experts as one MLP, the balance loss counted
a sequence, the shares against the uncut layer, and ``MoeTransformerLM``
against ``cellbench/reference/moonlight.py`` (the rotation as written, a
dense masked softmax, a loop over the held experts)."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench.reference import kimi_linear  # noqa: E402
from cellbench.reference import moonlight as ref  # noqa: E402
from cellbench.runners import train_kimilinear, train_moonlight  # noqa: E402
from chainermn_tpu.models.moe_transformer import (  # noqa: E402
    COUNTERS,
    ROUTES,
    MoeMlp,
    MoeTransformerLM,
    RouterOptions,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    BlockOptions,
    LatentAttention,
    apply_rope,
    make_mixer,
    rotate_pairs,
)
from chainermn_tpu.parallel import expert_parallel  # noqa: E402
from chainermn_tpu.parallel.expert_parallel import (  # noqa: E402
    load_balancing_loss,
    sequence_balancing_loss,
)

with open(os.path.join(ROOT, "cellbench", "configs",
                       "moonlight-16b-a3b.json")) as _f:
    CONFIG = json.load(_f)

#: the configuration's rehearsal sizes, uncut (all 16 experts, 256 rows)
UNCUT = {**{k: v for k, v in CONFIG.items()
            if isinstance(v, (int, float, str)) and not isinstance(v, bool)},
         **CONFIG["rehearse"], "n_routed_experts": 16, "first_expert": 0}
UNCUT.pop("correct")
#: one chip's share of it: experts 4..7, the first 64 rows
SHARE = dict(UNCUT, n_routed_experts=4, first_expert=4, vocab_size=64)
THETA = float(CONFIG["rope_theta"])


@pytest.fixture
def small_blocks(monkeypatch):
    """Buffer blocks of 8 rows, so that a few dozen tokens fill and pad
    the sorted buffer."""
    monkeypatch.setattr(expert_parallel, "HELD_BLOCK_ROWS", 8)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# -- the rotation ------------------------------------------------------------
def _by_hand(x, pos, theta):
    """``(x_2i + j x_2i+1) e^{j t theta^(-2i / d)}`` in complex128."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * np.asarray(pos, np.float64)[:, None, None]
        * theta ** (-np.arange(0, d, 2) / d))
    return np.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _program_rotation(x, pos, theta):
    """``LatentAttention``'s, on every channel of ``x (s, heads, d)``."""
    return rotate_pairs(x[None], pos, theta, x.shape[-1])[0]


def pairs_to_halves(x):
    """The even channels before the odd ones: what brings the pairs
    ``(2i, 2i + 1)`` to ``apply_rope``'s ``(i, i + d / 2)``."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def test_rotation_is_complex_multiplication_of_neighbouring_pairs():
    """The reference's pairs as written, and the program's form of them
    (``x cos + (x swap) sin``), against the complex product by hand at
    the published base and width; position 0 is the identity, and
    channels before the turned ones pass to the bit."""
    x, pos = _normal(0, 40, 3, 64), jnp.arange(40) * 211  # up to 8229
    want = _by_hand(x, pos, THETA)
    # a float32 angle of 8229 rad is good to 5e-4 rad
    np.testing.assert_allclose(ref.rotate_pairs(x, pos, THETA), want,
                               atol=3e-3)
    np.testing.assert_allclose(ref.rotate_pairs(x[:, 0], pos, THETA),
                               want[:, 0], atol=3e-3)
    np.testing.assert_allclose(_program_rotation(x, pos, THETA), want,
                               atol=3e-3)
    # the program's angles are the reference's, to the bit
    np.testing.assert_allclose(_program_rotation(x, pos, THETA),
                               ref.rotate_pairs(x, pos, THETA), atol=1e-6)
    # a head of 128 | 64: the 128 pass as they are, the 64 are turned
    wide = jnp.concatenate([_normal(9, 40, 3, 128), x], axis=-1)
    got = rotate_pairs(wide[None], pos, THETA, 64)[0]
    np.testing.assert_array_equal(got[..., :128], wide[..., :128])
    np.testing.assert_allclose(got[..., 128:],
                               ref.rotate_pairs(x, pos, THETA), atol=1e-6)
    low = rotate_pairs(wide[None].astype(jnp.bfloat16), pos, THETA, 64)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(low[0, ..., :128],
                                  wide[..., :128].astype(jnp.bfloat16))
    near = jnp.arange(40)
    np.testing.assert_allclose(ref.rotate_pairs(x, near, THETA),
                               _by_hand(x, near, THETA), atol=2e-5)
    np.testing.assert_array_equal(ref.rotate_pairs(x, pos, THETA)[0], x[0])
    np.testing.assert_array_equal(_program_rotation(x, pos, THETA)[0], x[0])
    # a rotation: norms stay
    np.testing.assert_allclose(
        jnp.linalg.norm(ref.rotate_pairs(x, pos, THETA), axis=-1),
        jnp.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("side", ["reference", "program"])
def test_rotated_scores_depend_on_the_distance_only(side):
    """``(R_t q) . (R_u k)`` is the same at ``(t, u)`` and ``(t + 37, u
    + 37)``, and is not ``q . k`` away from ``t = u``."""
    rotate = ref.rotate_pairs if side == "reference" else _program_rotation
    q = jnp.broadcast_to(_normal(1, 1, 2, 64), (24, 2, 64))
    k = jnp.broadcast_to(_normal(2, 1, 1, 64), (24, 1, 64))
    score = lambda shift: jnp.einsum(
        "thd,ud->htu", rotate(q, jnp.arange(24) + shift, THETA),
        rotate(k, jnp.arange(24) + shift, THETA)[:, 0])
    np.testing.assert_allclose(score(0), score(37), atol=2e-4)
    plain = jnp.einsum("hd,d->h", q[0], k[0, 0])
    np.testing.assert_allclose(
        jnp.diagonal(score(0), axis1=1, axis2=2), plain[:, None]
        * jnp.ones(24), atol=1e-4)
    assert float(jnp.abs(score(0)[:, 5, 0] - plain).max()) > 1e-2


def test_halves_form_gives_the_pairs_forms_scores():
    """Both operands brought to even-channels-first and turned by halves
    (``apply_rope``; the form the family's open implementation takes):
    the scores of the neighbours' rotation, which the program and the
    reference compute."""
    q, k, pos = _normal(3, 32, 4, 64), _normal(4, 32, 1, 64), jnp.arange(32)
    pairs = jnp.einsum("thd,ud->htu", _program_rotation(q, pos, THETA),
                       ref.rotate_pairs(k, pos, THETA)[:, 0])
    by_halves = lambda x: apply_rope(pairs_to_halves(x)[None], pos, THETA)[0]
    halves = jnp.einsum("thd,ud->htu", by_halves(q), by_halves(k)[:, 0])
    np.testing.assert_allclose(halves, pairs, atol=1e-4)
    # and not those of halves taken without the permutation
    wrong = jnp.einsum("thd,ud->htu", apply_rope(q[None], pos, THETA)[0],
                       apply_rope(k[None], pos, THETA)[0][:, 0])
    assert float(jnp.abs(wrong - pairs).max()) > 0.1


# -- the mixer ---------------------------------------------------------------
def _options(cfg, **kw):
    kw.setdefault("rope_theta", float(cfg["rope_theta"]))
    return BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        layer_types=(ref.MIXER,), latent_kv_rank=cfg["kv_lora_rank"],
        latent_nope_dim=cfg["qk_nope_head_dim"],
        latent_shared_dim=cfg["qk_rope_head_dim"],
        latent_value_dim=cfg["v_head_dim"], gated_mlp=True, **kw)


def _layer_weights(weights, cfg, layer=1):
    return {n: weights[k] for n, k in ref.layer_leaves(
        ref.layer_kinds(cfg)[layer], layer).items()}


def _mixer_params(weights, cfg, layer):
    """One layer's mixer leaves in the program's names."""
    tree = train_moonlight.program_tree(ref, weights, cfg)["params"]
    block = dict(train_kimilinear._paths(ref, cfg))[f"norm1_g.{layer}"][0]
    return {"params": tree[block]["LatentAttention_0"]}


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "kernels"])
def test_latent_attention_with_a_rotation_against_the_reference(flash):
    """Dense and through the interpreted kernels, on the reference's
    seeded leaves against the reference's mixer (the rotation as
    written); without the rotation the layer reads otherwise."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(7), cfg)
    x = _normal(5, 2, 64, cfg["hidden_size"])
    w = _layer_weights(weights, cfg)
    want = jax.jit(jax.vmap(lambda row: ref.latent_attention(
        row, w, cfg, ref._ein(False))))(x)
    mixer = make_mixer(ref.MIXER, cfg["num_attention_heads"],
                       _options(cfg, use_flash=flash), jnp.float32)
    assert isinstance(mixer, LatentAttention)
    params = _mixer_params(weights, cfg, 1)
    assert _max_rel(jax.jit(mixer.apply)(params, x), want) < 2e-5
    assert set(params["params"]) == set(jax.eval_shape(
        mixer.init, jax.random.PRNGKey(0), x)["params"])
    bare = make_mixer(ref.MIXER, cfg["num_attention_heads"], _options(
        cfg, rope_theta=None, no_positions=True, use_flash=flash),
        jnp.float32)
    assert _max_rel(jax.jit(bare.apply)(params, x), want) > 1e-3


def test_unrotated_layer_holds_no_rotation_and_reads_as_before():
    """Under ``no_positions`` the lowering has no ``latent_rope`` scope
    and no sine, and the output is the sibling reference's unrotated
    mixer; with ``rope_theta`` the scope is on the operations, beside
    ``latent_proj`` and not inside it."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(8), cfg)
    x = _normal(6, 2, 64, cfg["hidden_size"])
    params = _mixer_params(weights, cfg, 1)
    heads = cfg["num_attention_heads"]
    bare = make_mixer(ref.MIXER, heads, _options(
        cfg, rope_theta=None, no_positions=True), jnp.float32)
    text = jax.jit(bare.apply).lower(params, x).as_text(debug_info=True)
    assert "latent_proj" in text and "latent_rope" not in text
    assert "sine" not in text and "cosine" not in text
    want = jax.jit(jax.vmap(lambda row: kimi_linear._latent_attention(
        row, _layer_weights(weights, cfg), cfg, ref._ein(False), False)))(x)
    assert _max_rel(jax.jit(bare.apply)(params, x), want) < 2e-5
    turned = make_mixer(ref.MIXER, heads, _options(cfg), jnp.float32)
    text = jax.jit(turned.apply).lower(params, x).as_text(debug_info=True)
    assert "latent_rope" in text and "sine" in text
    assert "latent_proj/latent_rope" not in text \
        and "latent_rope/latent_proj" not in text


# -- the expert layer ----------------------------------------------------------
ROUTER = RouterOptions(score="sigmoid", selection_bias=True,
                       routed_scale=2.446, shared_gated=False, seq_aux=True)


def _mlp_params(w):
    return {"params": {
        "router": w["router"], "router_bias": w["r_bias"],
        "expert_wg": w["w_gate"], "expert_wu": w["w_up"],
        "expert_wd": w["w_down"], "shared_wg": w["s_gate"],
        "shared_wu": w["s_up"], "shared_wd": w["s_down"]}}


def _mlp(held=None, router_options=ROUTER):
    return MoeMlp(16, 32, k=3, routing="dropless", shared_d_ff=64,
                  dtype=jnp.float32, router_options=router_options,
                  held=held)


def test_two_shared_experts_are_one_mlp_of_twice_the_width(small_blocks):
    """``n_shared_experts`` 2 of width ``moe_intermediate_size`` run as
    one ungated MLP of width 2 x that: the layer with its routed
    experts' down-projections zeroed is the two experts' sum by hand."""
    layer = _layer_weights(ref.init_weights(ref.seed_key(9), UNCUT), UNCUT)
    f = UNCUT["moe_intermediate_size"]
    assert layer["s_gate"].shape == (64, 2 * f)
    u = _normal(7, 1, 48, 64)
    one = lambda i: (jax.nn.silu(u[0] @ layer["s_gate"][:, i * f:(i + 1) * f])
                     * (u[0] @ layer["s_up"][:, i * f:(i + 1) * f])) \
        @ layer["s_down"][i * f:(i + 1) * f]
    quiet = dict(layer, w_down=jnp.zeros_like(layer["w_down"]))
    (y, _), _ = _mlp().apply(_mlp_params(quiet), u,
                             mutable=[COUNTERS, ROUTES])
    np.testing.assert_allclose(y[0], one(0) + one(1), atol=2e-5)
    np.testing.assert_allclose(
        ref._gated(u[0], layer["s_gate"], layer["s_up"], layer["s_down"],
                   ref._ein(False)), one(0) + one(1), atol=2e-5)


def _stats(seed, sequences, T, E=16, k=3, tilt=None):
    """Sigmoid scores over their sum and the routes' indicator of
    ``sequences`` sequences of ``T`` rows; ``tilt[i]`` pushes sequence
    ``i`` towards an expert of its own."""
    logits = _normal(seed, sequences, T, E)
    if tilt is not None:
        logits = logits + 4.0 * jax.nn.one_hot(jnp.asarray(tilt), E)[:, None]
    scores = jax.nn.sigmoid(logits).reshape(-1, E)
    chosen = jax.lax.top_k(scores, k)[1]
    return (scores / scores.sum(-1, keepdims=True),
            jax.nn.one_hot(chosen, E).sum(1), scores, chosen)


def test_balance_loss_a_sequence_by_hand():
    """``f_e = E / (k T) #{t: e chosen}``, ``P_e = mean_t s_et / sum_j
    s_jt``, ``sum_e f_e P_e`` a sequence, averaged over the sequences:
    the program's and the reference's against numpy; at one sequence it
    is ``load_balancing_loss``; on sequences that route differently it
    is not the batch's."""
    probs, routes, scores, chosen = _stats(11, 3, 40, tilt=[0, 5, 9])
    p, r = (np.asarray(t, np.float64).reshape(3, 40, 16)
            for t in (probs, routes))
    by_hand = np.mean([(16 / (3 * 40) * r[i].sum(0) * p[i].mean(0)).sum()
                       for i in range(3)])
    got = sequence_balancing_loss(probs, routes, 3)
    np.testing.assert_allclose(got, by_hand, rtol=1e-5)
    np.testing.assert_allclose(ref.balance_loss(scores, chosen, 3), by_hand,
                               rtol=1e-5)
    batch = float(load_balancing_loss(probs, routes))
    assert float(got) > 1.05 * batch  # each sequence crowds its expert
    np.testing.assert_allclose(sequence_balancing_loss(probs, routes, 1),
                               batch, rtol=1e-6)
    one = _stats(12, 1, 64)
    np.testing.assert_allclose(sequence_balancing_loss(*one[:2], 1),
                               load_balancing_loss(*one[:2]), rtol=1e-6)
    np.testing.assert_allclose(ref.balance_loss(*one[2:], 1),
                               load_balancing_loss(*one[:2]), rtol=1e-5)


def test_layer_takes_the_balance_loss_from_its_options(small_blocks):
    """``seq_aux`` in ``RouterOptions``: the layer's ``aux`` on a batch
    of two sequences is the reference's a sequence; without it, the
    batch's."""
    layer = _layer_weights(ref.init_weights(ref.seed_key(13), UNCUT), UNCUT)
    u = _normal(14, 2, 48, 64).at[1].add(2.0 * _normal(15, 64))
    aux = lambda options: _mlp(router_options=options).apply(
        _mlp_params(layer), u, mutable=[COUNTERS, ROUTES])[0][1]
    _, want, _ = ref.routed_part(u.reshape(-1, 64), layer, UNCUT,
                                 ref._ein(False), 2)
    np.testing.assert_allclose(aux(ROUTER), want, rtol=1e-5)
    batch = aux(dataclasses.replace(ROUTER, seq_aux=False))
    _, whole, _ = ref.routed_part(u.reshape(-1, 64), layer, UNCUT,
                                  ref._ein(False), 1)
    np.testing.assert_allclose(batch, whole, rtol=1e-5)
    assert abs(float(batch) - float(want)) > 1e-4 * float(want)
    with pytest.raises(ValueError, match="balance loss a sequence"):
        MoeMlp(4, 8, router_options=RouterOptions(seq_aux=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_shares_and_the_shared_experts_once_are_the_uncut_layer(
        small_blocks):
    """The guide's test at a small size: 8 shares of 2 of the 16 experts
    (the deployment's 8 of 8 of 64).  Each computes its own experts'
    routed part under the router all share, each route counted once;
    those and the shared experts counted **once** are the uncut layer,
    in the reference and in the program (whose every share adds the
    shared experts: eight sums hold them eight times); the balance loss
    is the same on every share."""
    ein = ref._ein(False)
    weights = ref.init_weights(ref.seed_key(21), UNCUT)
    weights["r_bias.1"] = 20.0 * weights["r_bias.1"]  # a bias that bites
    layer = _layer_weights(weights, UNCUT)
    u = _normal(16, 2, 24, 64)
    flat = u.reshape(-1, 64)
    routed, want_aux, _ = ref.routed_part(flat, layer, UNCUT, ein, 2)
    shared = ref._gated(flat, layer["s_gate"], layer["s_up"],
                        layer["s_down"], ein)
    (whole, aux), _ = _mlp().apply(_mlp_params(layer), u,
                                   mutable=[COUNTERS, ROUTES])
    whole = whole.reshape(-1, 64)
    np.testing.assert_allclose(whole, routed + shared, atol=2e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)

    total_ref, total, rows = 0.0, 0.0, 0
    for first in range(0, 16, 2):
        share = _layer_weights(
            ref.share_of(weights, UNCUT, first, 2, 0, 256), UNCUT)
        cfg = dict(UNCUT, n_routed_experts=2, first_expert=first)
        part, aux, _ = ref.routed_part(flat, share, cfg, ein, 2)
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        total_ref = total_ref + part
        (y, _), sown = _mlp(held=(first, 2)).apply(
            _mlp_params(share), u, mutable=[COUNTERS, ROUTES])
        assert int(sown[COUNTERS]["moe_dropped"][0]) == 0
        rows += int(sown[COUNTERS]["moe_rows_routed"][0])
        total = total + y.reshape(-1, 64)
    assert rows == 48 * 3  # every route on exactly one share
    np.testing.assert_allclose(total_ref + shared, routed + shared,
                               atol=2e-5)
    np.testing.assert_allclose(total - 7 * shared, whole, atol=1e-4)


# -- the whole model ----------------------------------------------------------
def _model(cfg, dtype=jnp.float32, options=None, **kw):
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=cfg["moe_layer_freq"],
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=options or _options(cfg), routing="dropless",
        held=(cfg["first_expert"], cfg["n_routed_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        router_options=ROUTER, first_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], tie_head=False, **kw)


def _tokens(cfg, rows=2, s=48, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, s), dtype=np.int32)


def _apply(model, tree, tokens):
    return model.apply(tree, tokens, mutable=[COUNTERS, ROUTES])[0]


def test_every_layer_is_latent_attention_behind_a_dense_first():
    """The tree is the reference's leaves, the parameters its count; the
    model holds no position table."""
    cfg = SHARE
    model = _model(cfg)
    assert [model.sparse_layer(i) for i in range(3)] == [False, True, True]
    tokens = _tokens(cfg)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    params = variables["params"]
    assert sorted(b for b in params if "Block" in b) == [
        "MoeTransformerBlock_0", "MoeTransformerBlock_1",
        "TransformerBlock_0"]
    assert all("LatentAttention_0" in params[b] for b in params
               if "Block" in b)
    assert "pos_embed" not in params
    got = train_moonlight.keyed_leaves(ref, {"params": params}, cfg)
    shapes = ref._shapes(cfg)
    assert {k: v.shape for k, v in got.items()} == {
        key: shapes[name] for key, name, _ in ref.leaves(cfg)}
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == ref.n_parameters(cfg)


def test_parameter_count_at_the_published_widths_by_hand():
    """The cut the configuration's file describes, its floor of five
    layers, and the whole model."""
    cut = CONFIG
    assert ref.n_parameters(cut) == 668_890_432
    shapes = ref._shapes(cut)
    count = lambda names: sum(int(np.prod(shapes[n])) for n in names)
    assert count(("w_q", "w_kva", "kvn_g", "w_kvb", "w_o")) == 13_763_072 \
        == 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 2048 * 2048
    assert count(ref.MLP_LEAVES["dense"]) == 69_206_016 == 3 * 2048 * 11264
    assert count(("router", "r_bias")) == 131_136
    assert count(("s_gate", "s_up", "s_down")) == 17_301_504 \
        == 2 * 3 * 2048 * 1408
    assert count(("w_gate", "w_up", "w_down")) == 8 * 8_650_752
    assert count(("wte", "head", "normf_g")) == 83_888_128
    assert 82_973_184 + 5 * 100_405_824 + 83_888_128 == 668_890_432
    assert ref.n_parameters(dict(cut, num_hidden_layers=5)) == 568_484_608
    whole = dict(cut, **cut["published"])
    assert whole["n_routed_experts"] == whole["router_experts"] == 64
    assert ref.n_parameters(whole) == 15_960_110_208
    assert [mlp for _, mlp in ref.layer_kinds(whole)] \
        == ["dense"] + ["experts"] * 26


def test_model_logits_against_reference():
    weights = ref.init_weights(ref.seed_key(3), SHARE)
    tokens = _tokens(SHARE)
    tree = train_moonlight.program_tree(ref, weights, SHARE)
    logits, _ = jax.jit(lambda p: _apply(_model(SHARE), p, tokens))(tree)
    want = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE))(weights)
    assert _max_rel(logits, want) < 1e-4
    # the control is the same mathematics in scaled float8: apart
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE, lowp=True))(
        weights)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)
    # and so is the model without its rotation
    bare = _model(SHARE, options=_options(SHARE, rope_theta=None,
                                          no_positions=True))
    unturned, _ = jax.jit(lambda p: _apply(bare, p, tokens))(tree)
    assert _max_rel(unturned, want) > 30 * _max_rel(logits, want)


def test_model_loss_gradients_and_an_adamw_step_against_reference():
    """Loss and every gradient leaf of the float32 model against
    ``jax.value_and_grad`` of the reference's whole-model loss (the
    selection biases' is 0 on both sides); the reference's
    layer-at-a-time ``train_readings`` against both, and its parameters'
    change against one step of the example's optimizer (AdamW, the
    biases out of the decay) on the program's tree.  Near 40 s in the
    driver's run (three gradient programs at rehearsal size): the only
    case that holds every leaf's gradient and update, the rotation's
    and the balance loss a sequence's among them, against the reference
    ``correct`` is decided by."""
    import optax

    from chainermn_tpu.models.moe_transformer import moe_lm_loss

    cfg, opt_cfg = SHARE, {"lr": 1e-3, "weight_decay": 0.01}
    weights = ref.init_weights(ref.seed_key(5), cfg)
    tokens = _tokens(cfg, seed=1)
    tree = train_moonlight.program_tree(ref, weights, cfg)
    model = _model(cfg)
    # an auxiliary loss heavy enough for its gradient to be seen
    cfg = dict(cfg, aux_loss_coef=0.1)
    loss_of = lambda p: moe_lm_loss(_apply(model, p, tokens), tokens,
                                    aux_coef=cfg["aux_loss_coef"])
    loss, grads = jax.jit(jax.value_and_grad(loss_of))(tree)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(weights)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = train_moonlight.keyed_leaves(ref, grads, cfg)
    assert set(got) == set(want) == set(ref.leaf_keys(cfg))
    for name in got:
        if name.startswith("r_bias"):
            assert float(jnp.abs(got[name]).max()) \
                == float(jnp.abs(want[name]).max()) == 0.0
        else:
            assert _max_rel(got[name], want[name]) < 2e-3, name

    readings = ref.train_readings(5, cfg, tokens[None], opt_cfg)
    assert abs(readings["losses"][0] - float(want_loss)) \
        < 1e-5 * float(want_loss)
    for name, norm in readings["grad_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(want[name]))) \
            < 1e-3 * max(norm, 1e-6), name
    assert set(readings["grad_small"]) == {
        k for k, x in weights.items() if x.size <= ref.SMALL}
    assert len(readings["routes"][0]) == 2  # the expert layers'
    opt = optax.adamw(
        opt_cfg["lr"], weight_decay=opt_cfg["weight_decay"],
        mask=lambda t: jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key != "router_bias", t))
    updates, _ = opt.update(grads, opt.init(tree), tree)
    deltas = train_moonlight.keyed_leaves(ref, updates, cfg)
    for name, norm in readings["delta_norms"].items():
        if name.startswith("r_bias"):  # a constant of the run: rounding
            # between two programs that draw it, no more
            assert float(jnp.linalg.norm(deltas[name])) == 0.0
            assert norm < 1e-7 * float(jnp.linalg.norm(weights[name]))
        else:
            assert abs(norm - float(jnp.linalg.norm(deltas[name]))) \
                < 2e-3 * norm, name


def test_reference_follows_a_programs_routes_inside_its_window():
    """Handed the program's routes the reference takes those inside its
    tie window and refuses the rest; handed its own it follows none."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(11), cfg)
    tokens = _tokens(cfg, seed=4)
    _, reports = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, report=True))(weights)
    own = jnp.stack([r["chosen"] for r in reports])
    assert own.shape == (2, tokens.size, 3)
    _, again = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, routes=own, report=True))(weights)
    assert sum(float(r["followed"]) + float(r["refused"])
               for r in again) == 0.0
    # a program that sent every token's last route to expert 0
    other = own.at[:, :, -1].set(0)
    _, forced = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, routes=other, report=True))(weights)
    assert sum(float(r["refused"]) for r in forced) > 0.0


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    """``remat_blocks`` with a plan that keeps the dense layer's
    ``mlp_in`` and every layer's un-rotated queries (``latent_in``): one
    parameter tree, the same loss and gradients as without, so the
    rotation is made again from what was kept."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(6), cfg)
    tokens = _tokens(cfg, seed=2)
    tree = train_moonlight.program_tree(ref, weights, cfg)

    def loss_and_grads(options):
        model = _model(cfg, options=options, return_hidden=True)
        return jax.jit(jax.value_and_grad(lambda p: (
            _apply(model, p, tokens)[0] ** 2).mean()))(tree)

    plain = loss_and_grads(_options(cfg))
    kept = _options(cfg, remat_blocks=True, remat_budget_bytes=1 << 30)
    assert _model(cfg, options=kept).remat_plan(tokens.size) == (
        ("mlp_in", "latent_in"), ("latent_in",), ("latent_in",))
    again = loss_and_grads(kept)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(again[1]),
                    jax.tree_util.tree_leaves(plain[1])):
        np.testing.assert_allclose(a, b, atol=1e-6 + 1e-4 * float(
            jnp.abs(b).max()))


def test_the_scopes_are_on_the_models_operations():
    cfg = SHARE
    tokens = _tokens(cfg, rows=1, s=32)
    model = _model(cfg)
    # lowered from the parameters' shapes: nothing runs
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0), tokens)["params"]}
    text = jax.jit(lambda p: _apply(model, p, tokens)).lower(
        params).as_text(debug_info=True)
    for scope in ("latent_proj", "latent_rope", "gated_mlp", "moe_route",
                  "moe_experts", "moe_shared"):
        assert scope in text, scope
    assert "kda_mixer" not in text and "attn_proj" not in text


# -- the example and the cell ---------------------------------------------------
def test_the_examples_flags_come_with_the_general_block():
    from cellbench.runners.common import load_example

    main = load_example("moe_lm/train_moe_lm.py").main
    for argv in (["--seq-aux"], ["--rope-theta", "5e4", "--no-positions"]):
        with pytest.raises(SystemExit):
            main(argv)


def test_the_runners_command_line_is_the_issues():
    """The example's flags for the published widths."""
    spec = type("Spec", (), {"config": CONFIG, "sizes": {
        k: v for k, v in CONFIG.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)}})
    from cellbench.flops_moonlight import sizes_of

    argv = train_moonlight.example_argv(
        ref, sizes_of(spec), {"seq_len": 8192}, CONFIG["optimizer"], 2)
    said = " ".join(argv + CONFIG["argv"])
    for flags in ("--rope-theta 50000.0", "--layer-types latent_attention",
                  "--latent-kv-rank 512", "--first-dense 1",
                  "--dense-d-ff 11264", "--gated-mlp", "--d-ff 1408",
                  "--shared-d-ff 2816", "--shared-ungated",
                  "--router-score sigmoid", "--router-bias",
                  "--routed-scale 2.446", "--top-k 6", "--n-experts 64",
                  "--dropless", "--held 0,8", "--chunked-ce 8", "--seq-aux",
                  "--d-model 2048", "--n-heads 16", "--n-layers 6",
                  "--vocab 20480", "--seq-len 8192", "--batchsize 2",
                  "--flash", "--remat-blocks"):
        assert flags in said, flags
    assert "--no-positions" not in said


def test_the_cells_files_say_what_the_issue_asked_for():
    """Published widths, the cut and the cell's traffic, as files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"]
            if w["name"] == "moonlight16b_train_s8192"]
    assert len(cell) == 1 and cell[0]["chips"] == 1 \
        and cell[0]["config"] == "moonlight-16b-a3b" \
        and cell[0]["traffic"] == "train_mla_s8192"
    # the eighth cell and the seventh configuration: later PRs append
    assert bench["workloads"][7] is cell[0]
    entry = bench["configs"][6]
    assert entry["name"] == "moonlight-16b-a3b" \
        and entry["file"] == "cellbench/configs/moonlight-16b-a3b.json" \
        and entry["source"] == CONFIG["source"] \
        == "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 27,
                                   "n_routed_experts": 64,
                                   "vocab_size": 163840}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = [r for r in rows if r["name"] == "Moonlight-16B-A3B"][0]
    for key, value in catalog["config"].items():  # every width as published
        assert CONFIG[key] == (value if key not in CONFIG["reduced"]
                               else CONFIG[key]), key
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["router_experts"],
            CONFIG["first_expert"]) == (6, 8, 20480, 64, 0)
    assert CONFIG["runner"] == "train_moonlight" \
        and CONFIG["reference"] == "moonlight" \
        and CONFIG["argv"] == ["--flash", "--remat-blocks"]
    assert set(CONFIG["correct"]) == {"loss_rel", "grad_norm_gap",
                                      "grad_small_diff", "delta_norm_gap"}
    with open(os.path.join(ROOT, "cellbench", "traffic",
                           "train_mla_s8192.json")) as f:
        traffic = json.load(f)
    assert (traffic["seq_len"], traffic["per_chip_batch"]) == (8192, 2)
    assert traffic["rehearse"] == {"seq_len": 96, "per_chip_batch": 2}
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "tokens_per_s_per_chip"][0]
    assert rate["workloads"][6] == cell[0]["name"]
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".moonlight")]
    assert [m["name"].split(".")[0] for m in mine] == [
        "mfu_pct", "mla_proj_ms", "mla_rope_ms", "flash_ms",
        "flash_roofline_pct", "gated_mlp_ms", "moe_route_ms",
        "moe_experts_ms", "moe_experts_roofline_pct", "moe_shared_ms",
        "moe_held_share", "head_ce_ms", "peak_hbm_gib", "launch_gap_ms"]
    for m in mine:
        assert m["workloads"] == [cell[0]["name"]] \
            and m["moves"] == "tokens_per_s_per_chip"
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "layer_metrics", m["name"] + ".json"))
