"""The Mamba-2 / attention hybrid's files: its plain reference against
the system at rehearsal size on the CPU (``test_reference.py``'s
manner), and ``flops_granite.py`` against hand-worked values and XLA's
own count for one Mamba-2 layer at the published widths."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import flops_granite

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "cellbench", "configs",
                       "granite-4.0-h-micro.json")) as _f:
    CONFIG = json.load(_f)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _options(cfg, **kw):
    from chainermn_tpu.models.transformer import BlockOptions

    return BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"],
        attention_scale=cfg["attention_multiplier"],
        layer_types=tuple(cfg["layer_types"]),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], gated_mlp=True,
        no_positions=True,
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], **kw)


def test_granite_reference_matches_transformer_lm():
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss
    from cellbench.reference import granite_hybrid as ref
    from cellbench.runners.train_hybrid import keyed_leaves, program_tree

    cfg = {k: v for k, v in {**CONFIG, **CONFIG["rehearse"]}.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
    cfg["layer_types"] = tuple(CONFIG["rehearse"]["layer_types"])
    w = ref.init_weights(ref.seed_key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                               (2, 96), dtype=np.int32)
    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=96, dtype=jnp.float32, options=_options(cfg))
    tree = program_tree(ref, w, cfg)
    logits_of = jax.jit(lambda w, lowp: jax.vmap(
        lambda t: ref.logits_fn(w, t, cfg, lowp))(tokens),
        static_argnums=1)
    logits = jax.jit(model.apply)(tree, tokens)
    want = logits_of(w, False)
    assert _max_rel(logits, want) < 1e-4

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(model.apply(p, tokens), tokens)))(tree)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(w)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = keyed_leaves(ref, grads, cfg)
    assert set(got) == set(ref_grads)
    for name in got:
        assert _max_rel(got[name], ref_grads[name]) < 2e-3, name
    # the control is the same mathematics in scaled float8: apart from both
    assert _max_rel(logits_of(w, True), want) > 30 * _max_rel(logits, want)


def test_flops_granite_hand_worked():
    cfg = {**CONFIG, "layer_types": tuple(CONFIG["layer_types"])}
    # a Mamba-2 mixer: 2048 x 8512 in, 4096 x 2048 out; attention: q, o
    # of 2048 x 2048 and k, v of 2048 x 512; the MLP 3 x 2048 x 8192
    assert flops_granite.mamba_weights(cfg) == 17_432_576 + 8_388_608
    assert flops_granite.attention_weights(cfg) == 10_485_760
    assert flops_granite.mlp_weights(cfg) == 50_331_648
    # the scan at 8192: 32 chunks; scores 0.54, inside 17.2, states and
    # carried 8.6 GFLOP each, forward
    parts = flops_granite.ssd_parts(cfg, 8192)
    assert parts["scores"] == 2 * 32 * 256 * 256 * 128
    assert parts["inside"] == 2 * 32 * 256 * 256 * 4096
    assert parts["states"] == parts["carried"] == 2 * 32 * 256 * 4096 * 128
    assert flops_granite.ssd_flops(cfg, 8192, "fwd") == pytest.approx(
        34.9e9, rel=0.01)
    assert flops_granite.ssd_flops(cfg, 8192, "bwd") == \
        2 * flops_granite.ssd_flops(cfg, 8192, "fwd") + parts["scores"]
    assert flops_granite.ssd_bytes(cfg, 8192, "fwd") == 8192 * (
        2 * 4096 * 2 + 2 * 128 * 2 + 4 * 64)
    # attention's causal half, exactly
    assert flops_granite.attention_model_flops(cfg, 8192) == \
        12 * (8192 * 8193 // 2) * 2048
    # the issue's 3 x 8192 x (9 x 156.6 + 155.2 + 51.4) MFLOP
    per_token = 2 * (9 * (25_821_184 + 50_331_648)
                     + 10_485_760 + 50_331_648 + 2048 * 12544)
    want = 3 * 8192 * per_token + 9 * 3 * flops_granite.ssd_flops(
        cfg, 8192, "fwd") + flops_granite.attention_model_flops(cfg, 8192)
    assert flops_granite.step_model_flops(cfg, 8192, 1) == want
    assert want == pytest.approx(39.7e12, rel=0.01)
    assert flops_granite.step_model_flops(cfg, 8192, 2) == 2 * want


def test_mamba_layer_flops_against_cost_analysis():
    """One Mamba-2 layer of the published widths, forward: XLA's count
    of the compiled layer against 2 a weight a position and the scan's
    four parts (XLA also counts the element-wise chains, and a loop's
    body once: within a few per cent)."""
    from chainermn_tpu.models.transformer import TransformerBlock

    cfg = {**CONFIG, "layer_types": ("mamba",)}
    s = 1024  # 4 chunks: one pass of the scan's loop
    block = TransformerBlock(
        cfg["num_attention_heads"], cfg["intermediate_size"],
        options=_options(cfg), kind="mamba")
    x = jax.ShapeDtypeStruct((1, s, cfg["hidden_size"]), jnp.bfloat16)
    variables = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)
    compiled = jax.jit(block.apply).lower(variables, x).compile()
    want = 2.0 * s * (flops_granite.mamba_weights(cfg)
                      + flops_granite.mlp_weights(cfg)) \
        + flops_granite.ssd_flops(cfg, s, "fwd")
    assert compiled.cost_analysis()["flops"] == pytest.approx(want, rel=0.05)
