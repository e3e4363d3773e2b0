"""Qwen3-Next on the training path: ``MoeTransformerLM`` with Gated
DeltaNet layers, a gated attention layer and a gated shared expert
against ``cellbench/reference/qwen3_next.py`` (the recurrence a token,
a loop over the held experts), the shares against the uncut layer, the
new attention options against hand-written forms, the plan of the
blocks' recomputation with the new kind, and what the benchmark's files
say of the cell."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import flops_qwen3next  # noqa: E402
from cellbench.reference import qwen3_next as ref  # noqa: E402
from cellbench.runners import train_qwen3next  # noqa: E402
from chainermn_tpu.models import transformer  # noqa: E402
from chainermn_tpu.models.moe_transformer import (  # noqa: E402
    COUNTERS,
    ROUTES,
    MoeMlp,
    MoeTransformerLM,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    LAYER_KINDS,
    REMAT_NAMES,
    BlockOptions,
    SelfAttention,
    TransformerLM,
    apply_rope,
    remat_kept,
    remat_plan,
)
from chainermn_tpu.ops.gated_delta import gated_delta_census  # noqa: E402
from chainermn_tpu.parallel import expert_parallel  # noqa: E402

with open(os.path.join(ROOT, "cellbench", "configs",
                       "qwen3-next-80b-a3b.json")) as _f:
    CONFIG = json.load(_f)

#: the configuration's rehearsal sizes, uncut (all 16 experts, 256 rows)
UNCUT = {k: v for k, v in {**CONFIG, **CONFIG["rehearse"]}.items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}
UNCUT.update(num_experts=16, first_expert=0)
#: one chip's share of it: experts 4..7, the first 64 rows
SHARE = dict(UNCUT, num_experts=4, first_expert=4, vocab_size=64)


@pytest.fixture
def small_blocks(monkeypatch):
    """Buffer blocks of 8 rows, so that a few dozen tokens fill and pad
    the sorted buffer."""
    monkeypatch.setattr(expert_parallel, "HELD_BLOCK_ROWS", 8)


def _options(cfg, **kw):
    return BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], qk_norm=True,
        rotary_fraction=cfg["partial_rotary_factor"],
        attn_output_gate=True, zero_centered_norm=True,
        layer_types=ref.layer_kinds(cfg),
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg["linear_conv_kernel_dim"],
        gdn_chunk=cfg["linear_chunk_size"], **kw)


def _model(cfg, dtype=jnp.float32, options=None, **kw):
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=1,
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=options or _options(cfg), routing="dropless",
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        tie_head=False, **kw)


def _tokens(cfg, rows=2, s=80, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, s), dtype=np.int32)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _apply(model, tree, tokens):
    return model.apply(tree, tokens, mutable=[COUNTERS, ROUTES])[0]


# -- the whole model against the reference ---------------------------------
def test_model_logits_against_reference():
    weights = ref.init_weights(ref.seed_key(3), SHARE)
    tokens = _tokens(SHARE)
    tree = train_qwen3next.program_tree(ref, weights, SHARE)
    logits, _ = jax.jit(lambda p: _apply(_model(SHARE), p, tokens))(tree)
    want = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE))(weights)
    assert _max_rel(logits, want) < 1e-4
    # the control is the same mathematics in scaled float8: apart
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE, lowp=True))(
        weights)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)


def test_model_loss_gradients_and_an_adamw_step_against_reference():
    """Loss and every gradient leaf of the float32 model against
    ``jax.value_and_grad`` of the reference's whole-model loss; the
    reference's layer-at-a-time ``train_readings`` against both, and
    its parameters' change against one ``optax.adamw`` step of the
    program's tree.  Over 40 s in the driver's run (three gradient
    programs at rehearsal size): the only case that holds every leaf's
    gradient and update against the reference ``correct`` is decided
    by."""
    import optax

    from chainermn_tpu.models.moe_transformer import moe_lm_loss

    cfg, opt_cfg = SHARE, {"lr": 1e-3, "weight_decay": 0.01}
    weights = ref.init_weights(ref.seed_key(5), cfg)
    tokens = _tokens(cfg, seed=1)
    tree = train_qwen3next.program_tree(ref, weights, cfg)
    model = _model(cfg)
    loss_of = lambda p: moe_lm_loss(_apply(model, p, tokens), tokens,
                                    aux_coef=cfg["aux_loss_coef"])
    loss, grads = jax.jit(jax.value_and_grad(loss_of))(tree)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(weights)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = train_qwen3next.keyed_leaves(ref, grads, cfg)
    assert set(got) == set(want) == set(ref.leaf_keys(cfg))
    for name in got:
        assert _max_rel(got[name], want[name]) < 2e-3, name

    readings = ref.train_readings(5, cfg, tokens[None], opt_cfg)
    assert abs(readings["losses"][0] - float(want_loss)) \
        < 1e-5 * float(want_loss)
    for name, norm in readings["grad_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(want[name]))) \
            < 1e-3 * max(norm, 1e-6), name
    assert set(readings["grad_small"]) == {
        k for k, x in weights.items() if x.size <= ref.SMALL}
    opt = optax.adamw(opt_cfg["lr"], weight_decay=opt_cfg["weight_decay"])
    updates, _ = opt.update(grads, opt.init(tree), tree)
    deltas = train_qwen3next.keyed_leaves(ref, updates, cfg)
    for name, norm in readings["delta_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(deltas[name]))) \
            < 2e-3 * norm, name


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    """``remat_blocks`` with a plan that keeps ``gdn_in``: one parameter
    tree, the same loss and gradients as without."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(6), cfg)
    tokens = _tokens(cfg, seed=2)
    tree = train_qwen3next.program_tree(ref, weights, cfg)

    def loss_and_grads(options):
        model = _model(cfg, options=options, return_hidden=True)
        return jax.jit(jax.value_and_grad(lambda p: (
            _apply(model, p, tokens)[0] ** 2).mean()))(tree)

    plain = loss_and_grads(_options(cfg))
    kept = _options(cfg, remat_blocks=True, remat_budget_bytes=1 << 30)
    assert _model(cfg, options=kept).remat_plan(tokens.size) \
        == (("gdn_in",), ())
    again = loss_and_grads(kept)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(again[1]),
                    jax.tree_util.tree_leaves(plain[1])):
        np.testing.assert_allclose(a, b, atol=1e-6 + 1e-4 * float(
            jnp.abs(b).max()))


# -- the shares -------------------------------------------------------------
def _layer_weights(weights, layer=0):
    return {n: weights[f"{n}.{layer}"] for n in ref.LAYER_LEAVES}


def _mlp_params(w):
    return {"params": {
        "router": w["router"], "expert_wg": w["w_gate"],
        "expert_wu": w["w_up"], "expert_wd": w["w_down"],
        "shared_wg": w["s_gate"], "shared_wu": w["s_up"],
        "shared_wd": w["s_down"], "shared_gate": w["s_mix"]}}


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer(
        small_blocks):
    """Each of 16 shares (one expert each) computes its own experts'
    routed part, each route counted once; those and the shared expert
    counted **once** are the uncut layer, in the reference and in the
    program (whose every share adds the shared expert: sixteen sums
    hold it sixteen times).  Over 40 s in the driver's run (sixteen
    layers with sixteen held ranges, each traced by itself): the only
    case that holds the cut of the deployment's experts into shares."""
    ein = ref._ein(False)
    weights = ref.init_weights(ref.seed_key(21), UNCUT)
    layer = _layer_weights(weights)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64))
    routed, want_aux, _ = ref.routed_part(u[0], layer, UNCUT, ein)
    shared = ref.shared_part(u[0], layer, ein)
    uncut = MoeMlp(16, 32, k=4, routing="dropless", shared_d_ff=32,
                   dtype=jnp.float32)
    (whole, _), _ = uncut.apply(_mlp_params(layer), u,
                                mutable=[COUNTERS, ROUTES])
    np.testing.assert_allclose(whole[0], routed + shared, atol=2e-5)

    total_ref, total, rows = 0.0, 0.0, 0
    for first in range(16):
        share = _layer_weights(ref.share_of(weights, UNCUT, first, 1, 0,
                                            256))
        cfg = dict(UNCUT, num_experts=1, first_expert=first)
        part, aux, _ = ref.routed_part(u[0], share, cfg, ein)
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        total_ref = total_ref + part
        mlp = MoeMlp(16, 32, k=4, routing="dropless", held=(first, 1),
                     shared_d_ff=32, dtype=jnp.float32)
        (y, _), sown = mlp.apply(_mlp_params(share), u,
                                 mutable=[COUNTERS, ROUTES])
        assert int(sown[COUNTERS]["moe_dropped"][0]) == 0
        rows += int(sown[COUNTERS]["moe_rows_routed"][0])
        total = total + y[0]
    assert rows == 96 * 4  # every route on exactly one share
    np.testing.assert_allclose(total_ref + shared, routed + shared,
                               atol=2e-5)
    np.testing.assert_allclose(total - 15 * shared, whole[0], atol=1e-4)


def test_expert_axis_adds_the_shared_expert_once(small_blocks):
    """Over an 8-chip expert axis each chip holds 2 of 16 experts and
    computes the shared expert alike; the sum over the axis holds it
    once: the uncut layer."""
    from jax.sharding import Mesh, PartitionSpec as P

    ein = ref._ein(False)
    layer = _layer_weights(ref.init_weights(ref.seed_key(23), UNCUT))
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 64))
    want = ref.routed_part(u[0], layer, UNCUT, ein)[0] \
        + ref.shared_part(u[0], layer, ein)
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("experts",))
    mlp = MoeMlp(16, 32, k=4, routing="dropless", expert_axis="experts",
                 shared_d_ff=32, dtype=jnp.float32)
    specs = {"params": {k: P("experts") if k.startswith("expert_w")
                        else P() for k in _mlp_params(layer)["params"]}}
    got = jax.jit(jax.shard_map(
        lambda p, u: mlp.apply(p, u, mutable=[COUNTERS, ROUTES])[0][0],
        mesh=mesh, in_specs=(specs, P()), out_specs=P()))(
        _mlp_params(layer), u)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_shared_expert_comes_with_dropless_routing():
    with pytest.raises(ValueError, match="dropless"):
        MoeMlp(4, 8, shared_d_ff=8).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# -- the attention options against hand-written forms ----------------------
def test_partial_rotation_turns_the_first_quarter_of_a_head():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 6, 2, 32)), jnp.float32)
    pos, theta = jnp.arange(6), 1e7
    got = apply_rope(x, pos, theta, 0.25)
    # rotate_half among channels 0..7: pairs (i, i + 4), i < 4
    freq = theta ** (-np.arange(4) / 4.0)
    ang = np.arange(6)[:, None] * freq[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    np.testing.assert_allclose(got[..., :4], a * cos - b * sin, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:8], b * cos + a * sin, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(apply_rope(x, pos, theta, 1.0),
                               apply_rope(x, pos, theta), atol=0)


def test_output_gate_is_the_second_half_of_a_heads_q_columns():
    """``[q | gate] = W_q x`` a head; ``W_o (attention * sigmoid(gate))``
    with zero-centred q/k norms, against a hand-written dense form."""
    b, s, d, hq, hkv, dh = 1, 12, 16, 4, 2, 8
    o = BlockOptions(n_kv_heads=hkv, head_dim=dh, qk_norm=True,
                     no_positions=True, attn_output_gate=True,
                     zero_centered_norm=True)
    attn = SelfAttention(hq, dtype=jnp.float32, options=o)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d))
    params = attn.init(jax.random.PRNGKey(1), x)["params"]
    assert params["q_proj"]["kernel"].shape == (d, 2 * hq * dh)
    assert float(jnp.abs(params["q_norm"]).max()) == 0.0  # 1 + w, w = 0
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                              p.shape), params)
    got = attn.apply({"params": params}, x)

    rms = lambda t, w: t * jax.lax.rsqrt(
        (t * t).mean(-1, keepdims=True) + 1e-6) * (1.0 + w)
    qg = (x @ params["q_proj"]["kernel"]).reshape(b, s, hq, 2 * dh)
    q, gate = rms(qg[..., :dh], params["q_norm"]), qg[..., dh:]
    k = rms((x @ params["k_proj"]["kernel"]).reshape(b, s, hkv, dh),
            params["k_norm"])
    v = (x @ params["v_proj"]["kernel"]).reshape(b, s, hkv, dh)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    want = (out * jax.nn.sigmoid(gate)).reshape(b, s, hq * dh) \
        @ params["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- kinds, plan, trees ------------------------------------------------------
def test_layer_kinds_are_one_tuple():
    assert LAYER_KINDS[:3] == ("attention", "mamba", "linear_attention")
    o = BlockOptions(layer_types=("linear_attention", "attention"))
    assert [o.layer_type(i) for i in range(3)] == [
        "linear_attention", "attention", "linear_attention"]
    with pytest.raises(ValueError) as err:
        BlockOptions(layer_types=("window",)).layer_type(0)
    assert all(kind in str(err.value) for kind in LAYER_KINDS)
    # the names a block can keep, each of every layer or of the kinds
    # that have the result
    assert {kind for kinds in transformer._REMAT_KIND.values()
            for kind in kinds} <= set(LAYER_KINDS)
    assert set(transformer._REMAT_KIND) <= set(REMAT_NAMES)


def test_remat_plan_with_the_new_kind():
    o = _options({**CONFIG, "linear_chunk_size": 64})
    widths = o.remat_widths(512)
    assert widths == {"gdn_in": 2 * 2048 + 2 * 4096}  # no gated MLP
    kinds = [o.layer_type(i) for i in range(4)]
    assert kinds == ["linear_attention"] * 3 + ["attention"]
    one = 8192 * 12288 * 2
    assert remat_plan(kinds, 8192, widths, 0) == ((),) * 4
    assert remat_plan(kinds, 8192, widths, 2 * one) \
        == (("gdn_in",), ("gdn_in",), (), ())
    full = remat_plan(kinds, 8192, widths, 10 * one)
    assert full == (("gdn_in",),) * 3 + ((),)  # never the attention layer
    assert remat_kept(full, 8192, widths) == ("gdn_in x3", 3 * one)
    # beside the other kinds' names, in REMAT_NAMES' order
    mixed = BlockOptions(
        layer_types=("mamba", "linear_attention"), gated_mlp=True,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8)
    widths = mixed.remat_widths(64)
    assert list(widths) == ["mlp_in", "ssm_in", "gdn_in"]
    assert remat_plan(["mamba", "linear_attention"], 10, widths, 1 << 30) \
        == (("mlp_in", "ssm_in"), ("mlp_in", "gdn_in"))


def _paths(model, tokens):
    """Path -> shape of the parameters ``init`` would make (traced by
    ``jax.eval_shape``: a shape needs no arithmetic)."""
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    return {"/".join(k.key for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree["params"])}


def test_default_options_leave_both_lms_trees_as_they_are():
    tokens = jnp.zeros((1, 8), jnp.int32)
    lm = TransformerLM(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                       max_len=8)
    assert _paths(lm, tokens) == {
        "embed/embedding": (32, 16), "pos_embed": (8, 16),
        "LayerNorm_0/scale": (16,), "LayerNorm_0/bias": (16,),
        "TransformerBlock_0/LayerNorm_0/scale": (16,),
        "TransformerBlock_0/LayerNorm_0/bias": (16,),
        "TransformerBlock_0/LayerNorm_1/scale": (16,),
        "TransformerBlock_0/LayerNorm_1/bias": (16,),
        "TransformerBlock_0/SelfAttention_0/Dense_0/kernel": (16, 48),
        "TransformerBlock_0/SelfAttention_0/Dense_1/kernel": (16, 16),
        "TransformerBlock_0/MlpBlock_0/Dense_0/kernel": (16, 64),
        "TransformerBlock_0/MlpBlock_0/Dense_0/bias": (64,),
        "TransformerBlock_0/MlpBlock_0/Dense_1/kernel": (64, 16),
        "TransformerBlock_0/MlpBlock_0/Dense_1/bias": (16,)}
    moe = MoeTransformerLM(vocab_size=32, d_model=16, n_heads=2,
                           n_layers=2, n_experts=2, max_len=8)
    got = _paths(moe, tokens)
    block = "MoeTransformerBlock_0/"
    assert {k: v for k, v in got.items() if k.startswith(block)} == {
        block + "LayerNorm_0/scale": (16,),
        block + "LayerNorm_0/bias": (16,),
        block + "LayerNorm_1/scale": (16,),
        block + "LayerNorm_1/bias": (16,),
        block + "SelfAttention_0/Dense_0/kernel": (16, 48),
        block + "SelfAttention_0/Dense_1/kernel": (16, 16),
        block + "MoeMlp_0/router": (16, 2),
        block + "MoeMlp_0/expert_w1": (2, 16, 64),
        block + "MoeMlp_0/expert_w2": (2, 64, 16)}
    assert sorted(k.split("/")[0] for k in got
                  if "/" in k and not k.startswith(block)) == sorted(
        ["embed"] + ["LayerNorm_0"] * 2 + ["TransformerBlock_0"] * 10)
    # and the general path's tree gains nothing it was not asked for
    rotary = MoeTransformerLM(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, n_experts=2,
        moe_every=1, d_ff=8, options=BlockOptions(
            norm="rmsnorm", rope_theta=1e4, qk_norm=True),
        routing="dropless", tie_head=False)
    got = _paths(rotary, tokens)
    assert set(got) == {
        "embed/embedding", "lm_head", "RMSNorm_0/scale",
        "MoeTransformerBlock_0/RMSNorm_0/scale",
        "MoeTransformerBlock_0/RMSNorm_1/scale",
        "MoeTransformerBlock_0/SelfAttention_0/q_proj/kernel",
        "MoeTransformerBlock_0/SelfAttention_0/k_proj/kernel",
        "MoeTransformerBlock_0/SelfAttention_0/v_proj/kernel",
        "MoeTransformerBlock_0/SelfAttention_0/o_proj/kernel",
        "MoeTransformerBlock_0/SelfAttention_0/q_norm",
        "MoeTransformerBlock_0/SelfAttention_0/k_norm",
        "MoeTransformerBlock_0/MoeMlp_0/router",
        "MoeTransformerBlock_0/MoeMlp_0/expert_wg",
        "MoeTransformerBlock_0/MoeMlp_0/expert_wu",
        "MoeTransformerBlock_0/MoeMlp_0/expert_wd"}


def test_transformer_lm_takes_the_new_kind_too():
    """``TransformerLM`` with ``linear_attention`` layers under a plan:
    the mixer is the block's, not one LM's."""
    o = BlockOptions(
        norm="rmsnorm", rope_theta=1e4,
        layer_types=("linear_attention", "attention"), gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, gdn_chunk=16,
        remat_blocks=True, remat_budget_bytes=1 << 20)
    lm = TransformerLM(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                       max_len=24, dtype=jnp.float32, options=o)
    tokens = jnp.arange(48).reshape(2, 24) % 32
    # one program each, not op by op
    params = jax.jit(lm.init)(jax.random.PRNGKey(0), tokens)
    mixer = params["params"]["TransformerBlock_0"]["GatedDeltaMixer_0"]
    assert sorted(mixer) == ["A_log", "conv_kernel", "dt_bias",
                             "in_proj_ba", "in_proj_qkvz", "norm",
                             "out_proj"]
    assert mixer["conv_kernel"].shape == (4, 2 * 16 + 32)
    assert lm.remat_plan(48) == (("gdn_in",), ())
    loss, grads = jax.jit(jax.value_and_grad(lambda p: transformer.lm_loss(
        lm.apply(p, tokens), tokens)))(params)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_the_new_scopes_are_on_the_operations():
    cfg = SHARE
    tokens = _tokens(cfg, rows=1, s=32)
    model = _model(cfg)
    # lowered from the parameters' shapes: nothing runs
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0), tokens)["params"]}
    text = jax.jit(lambda p: _apply(model, p, tokens)).lower(
        params).as_text(debug_info=True)
    for scope in ("gdn_mixer", "gdn_conv", "gdn_scan", "moe_shared",
                  "moe_route", "moe_experts", "attn_proj"):
        assert scope in text, scope
    assert "ssm_conv" not in text


# -- the cell ----------------------------------------------------------------
def test_the_cell_rehearses_correct_with_its_counters():
    """``cellbench.run --rehearse`` of the cell, in a process of its own
    (one CPU device, as the cell has one chip).  Over 40 s in the driver's
    run (a process start, the program's compile and the reference's at
    rehearsal size): the one tier-1 hold on the cell's own runner,
    example and comparison end to end, which no in-process case is."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "qwen3next80b_train_s8192", "--seed", str(2**31 + 7), "--seconds",
         "0.5", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    line = [l for l in lines if l.startswith("counters over")]
    assert len(line) == 1 and "moe_dropped total 0" in line[0]
    assert any(l.startswith("routes: the reference took") for l in lines)


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    published = dict(
        hidden_size=2048, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4,
        num_attention_heads=16, num_key_value_heads=2, head_dim=256,
        partial_rotary_factor=0.25, rope_theta=10000000,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts_per_tok=10, full_attention_interval=4,
        decoder_sparse_step=1, rms_norm_eps=1e-6, intermediate_size=5120,
        max_position_embeddings=262144)
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["router_experts"] == CONFIG["published"]["num_experts"] \
        == 512
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512,
                                   "vocab_size": 151936}
    entry = [c for c in bench["configs"]
             if c["name"] == "qwen3-next-80b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 151936 // 8)
    assert ref.layer_kinds(CONFIG) == ("linear_attention",) * 3 \
        + ("attention",)
    assert ref.n_parameters(CONFIG) == 625_667_136
    cell = [w for w in bench["workloads"]
            if w["name"] == "qwen3next80b_train_s8192"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "train_moe_s8192", 1)


# -- the benchmark's operation counts, by hand -------------------------------
def test_flops_qwen3next_against_hand_worked_values():
    cfg = {**CONFIG}
    assert flops_qwen3next.delta_weights(cfg) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048
    assert flops_qwen3next.attention_weights(cfg) == 2048 * 8192 \
        + 2 * 2048 * 512 + 4096 * 2048
    assert flops_qwen3next.dense_moe_weights(cfg) == 2048 * 512 \
        + 3 * 2048 * 512 + 2048
    # the scan's parts are the program's census, line for line
    census = gated_delta_census(8192, 64, 32, 128, 128, key_heads=16)
    assert flops_qwen3next.gdn_parts(cfg, 8192) == census["flops"]
    assert flops_qwen3next.gdn_flops(cfg, 8192, "fwd") \
        == census["flops_forward"]
    assert flops_qwen3next.gdn_flops(cfg, 8192, "bwd") \
        == census["flops_backward"]
    assert flops_qwen3next.gdn_bytes(cfg, 8192, "fwd") \
        == census["bytes_forward"]
    assert flops_qwen3next.attention_model_flops(cfg, 8192) == \
        12 * (8192 * 8193 // 2) * 16 * 256
    # a token forward, with balanced routing (10 x 32 / 512 of an expert
    # layer's routes land here): the issue's 469 MFLOP
    routed = 4 * 8192 * 10 * 32 / 512
    step = flops_qwen3next.step_model_flops(cfg, 8192, 1, routed)
    per_token = 2 * (3 * flops_qwen3next.delta_weights(cfg)
                     + flops_qwen3next.attention_weights(cfg)
                     + 4 * flops_qwen3next.dense_moe_weights(cfg)
                     + 2048 * 18992)
    want = 3 * 8192 * per_token \
        + 9 * flops_qwen3next.gdn_flops(cfg, 8192, "fwd") \
        + flops_qwen3next.attention_model_flops(cfg, 8192) \
        + 6 * 3 * 2048 * 512 * routed
    assert step == want
    assert step / 3 / 8192 == pytest.approx(469e6, rel=0.02)
    assert flops_qwen3next.step_model_flops(cfg, 8192, 2, 2 * routed) \
        == 2 * step
