"""The KDA / latent-attention hybrid's files: its plain reference against
the system at rehearsal size on the CPU (``test_reference.py``'s
manner), the control and a broken timed path coming out not ``correct``
(``test_correct.py``'s), the cell's rehearsal, and
``flops_kimilinear.py`` against hand-worked values, the program's census
and XLA's own count for one KDA layer at the published widths."""

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import compare, flops_kimilinear, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "kimilinear48b_train_s8192"
with open(os.path.join(ROOT, "cellbench", "configs",
                       "kimi-linear-48b-a3b.json")) as _f:
    CONFIG = json.load(_f)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _model(cfg, ref, dtype=jnp.float32):
    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        RouterOptions,
    )
    from chainermn_tpu.models.transformer import BlockOptions

    lin = cfg["linear_attn_config"]
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=cfg["moe_layer_freq"],
        k=cfg["num_experts_per_token"], dtype=dtype,
        options=BlockOptions(
            norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], no_positions=True,
            layer_types=ref.mixer_kinds(cfg)[:lin["full_attn_layers"][0]],
            gdn_value_heads=lin["num_heads"], gdn_key_dim=lin["head_dim"],
            gdn_value_dim=lin["head_dim"],
            gdn_conv=lin["short_conv_kernel_size"],
            gdn_chunk=cfg["linear_chunk_size"],
            latent_kv_rank=cfg["kv_lora_rank"],
            latent_nope_dim=cfg["qk_nope_head_dim"],
            latent_shared_dim=cfg["qk_rope_head_dim"],
            latent_value_dim=cfg["v_head_dim"], gated_mlp=True),
        routing="dropless", held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"],
        router_options=RouterOptions(
            score=cfg["moe_router_activation_func"], selection_bias=True,
            routed_scale=cfg["routed_scaling_factor"], shared_gated=False),
        first_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], tie_head=False)


def test_kimilinear_reference_matches_moe_transformer_lm():
    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        ROUTES,
        moe_lm_loss,
    )
    from cellbench.reference import kimi_linear as ref
    from cellbench.runners.train_kimilinear import keyed_leaves, \
        program_tree

    spec, _ = run.load_spec(WORKLOAD, 3, True, False)
    cfg = flops_kimilinear.sizes_of(spec)
    w = ref.init_weights(ref.seed_key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                               (2, 96), dtype=np.int32)
    model, tree = _model(cfg, ref), program_tree(ref, w, cfg)
    apply = lambda p: model.apply(p, tokens, mutable=[COUNTERS, ROUTES])[0]
    logits = jax.jit(lambda p: apply(p)[0])(tree)
    want = jax.jit(lambda w: ref.logits_fn(w, tokens, cfg))(w)
    assert _max_rel(logits, want) < 1e-4

    loss, grads = jax.jit(jax.value_and_grad(lambda p: moe_lm_loss(
        apply(p), tokens, aux_coef=cfg["aux_loss_coef"])))(tree)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(w)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = keyed_leaves(ref, grads, cfg)
    assert set(got) == set(ref_grads)
    for name in got:
        if name.startswith("r_bias"):  # no gradient on either side
            assert float(jnp.abs(got[name]).max()) == 0.0
        else:
            assert _max_rel(got[name], ref_grads[name]) < 2e-3, name
    # the control is the same mathematics in scaled float8: apart from both
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, cfg, lowp=True))(w)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)


def test_control_is_not_correct_at_the_rehearsals_limits():
    """The float8 control in the program's place fails at least one of
    the rehearsal's limits on every seed; the bfloat16 program passes
    them all."""
    spec, _ = run.load_spec(WORKLOAD, 11, True, False)
    cell = importlib.import_module(
        f"cellbench.runners.{spec.config['runner']}").build(spec)
    for seed in (11, 12, 13):
        cell.reseed(seed)
        program, inputs = cell.first_steps(), cell.first_inputs()
        reference = cell.reference(inputs)
        control = cell.reference(inputs, lowp=True)
        limits = spec.config["correct"]
        assert compare.decide(program, reference, limits)["correct"]
        assert not compare.decide(control, reference, limits)["correct"]


def _run(build=None):
    args = argparse.Namespace(workload=WORKLOAD, seed=21, seconds=1.0,
                              trace=0, rehearse=True)
    return run.run_cell(args, check_chip=False, build=build)


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"] is True and result["failed"] == 0


def test_a_scan_that_forgets_its_channels_decays_is_not_correct(monkeypatch):
    """The timed path broken underneath: inside a chunk the channel-wise
    rule's products lose their decays (every key channel's factor 1
    wherever a pair is live), while the state between chunks still
    decays."""
    from chainermn_tpu.ops import gated_delta

    sound = gated_delta._channel_decays

    def forgetful(run, block):
        to_rows, from_cols, within = sound(run, block)
        live = lambda t: jnp.where(t > 0, 1.0, 0.0)
        return jnp.ones_like(to_rows), live(from_cols), live(within)

    monkeypatch.setattr(gated_delta, "_channel_decays", forgetful)
    jax.clear_caches()
    try:
        assert _run()["correct"] is False
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_flops_by_hand_and_against_the_programs_census():
    from chainermn_tpu.ops.gated_delta import gated_delta_census

    census = gated_delta_census(8192, 64, 32, 128, 128, channel_decay=True)
    assert flops_kimilinear.kda_parts(CONFIG, 8192) == census["flops"]
    assert flops_kimilinear.kda_flops(CONFIG, 8192, "fwd") \
        == census["flops_forward"] == pytest.approx(43.0e9, rel=0.01)
    assert flops_kimilinear.kda_flops(CONFIG, 8192, "bwd") \
        == census["flops_backward"]
    assert flops_kimilinear.kda_bytes(CONFIG, 8192, "fwd") \
        == census["bytes_forward"] == 8192 * 32 * (4 * 128 * 2 + 4 * 129)
    # the weights every position goes through, by hand
    assert flops_kimilinear.kda_weights(CONFIG) \
        == 39_518_368 - (4 * 12288 + 32 + 4096 + 4096 + 128)
    assert flops_kimilinear.latent_weights(CONFIG) == 29_114_880 - 512
    assert flops_kimilinear.dense_mlp_weights(CONFIG) == 63_700_992
    assert flops_kimilinear.dense_moe_weights(CONFIG) \
        == 2304 * 256 + 7_077_888
    assert flops_kimilinear.layer_kinds(CONFIG) == (
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("latent_attention", "experts"), ("kda", "experts"))
    # the causal launches at 192 / 128: forward 2 (192 + 128) a live pair
    pairs = 8192 * 8193 // 2
    assert flops_kimilinear.flash_call_flops(CONFIG, "fwd", 2, 8192) \
        == 2 * (192 + 128) * 2 * 32 * pairs
    assert flops_kimilinear.flash_call_flops(CONFIG, "dq", 2, 8192) \
        == 2 * (2 * 192 + 128) * 2 * 32 * pairs
    assert flops_kimilinear.flash_call_flops(CONFIG, "dkv", 2, 8192) \
        == 2 * (2 * 192 + 2 * 128) * 2 * 32 * pairs
    assert flops_kimilinear.flash_call_bytes(CONFIG, "fwd", 2, 8192) \
        == 2 * 8192 * 32 * (2 * 192 + 2 * 128) * 2 + 4 * 2 * 32 * 8192
    assert flops_kimilinear.attention_model_flops(CONFIG, 8192) \
        == 3 * 640 * 32 * pairs
    # the step: 38 TFLOP at two sequences and balanced routing
    step = flops_kimilinear.step_model_flops(
        CONFIG, 8192, 2, 4 * 2 * 8192 * 8 * 8 / 256)
    weights = 4 * flops_kimilinear.kda_weights(CONFIG) \
        + flops_kimilinear.latent_weights(CONFIG) + 63_700_992 \
        + 4 * (2304 * 256 + 7_077_888) + 2304 * 20480
    assert step == 2 * (6.0 * weights * 8192 + 12 * census["flops_forward"]
                        + 3 * 640 * 32 * pairs) \
        + 6.0 * 7_077_888 * 16384
    assert step == pytest.approx(38.2e12, rel=0.01)


def test_kda_layer_flops_against_cost_analysis():
    """One KDA mixer of the published widths, forward: XLA's count of
    the compiled mixer against 2 a weight a position and the scan's
    parts (XLA counts the nilpotent series where the census counts a
    forward substitution, the blocks' own sums and the element-wise
    chains besides, and a loop's body once: within the stated band)."""
    from chainermn_tpu.models.transformer import BlockOptions, KdaMixer

    s = 512  # 8 chunks: one pass of the stages outside the recurrence
    mixer = KdaMixer(BlockOptions(
        gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128, gdn_conv=4,
        gdn_chunk=64))
    x = jax.ShapeDtypeStruct((1, s, 2304), jnp.bfloat16)
    variables = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)
    compiled = jax.jit(mixer.apply).lower(variables, x).compile()
    parts = flops_kimilinear.kda_parts(CONFIG, s)
    chunks = s // 64
    # what the recurrence's loop runs a chunk is counted once, not 8 x
    in_loop = (parts["read"] + parts["from_state"] + parts["state"])
    series = 10 * 2.0 * chunks * 32 * 64 ** 3  # ten 64^3 products
    # inside a block of 16 the sums run on the vector unit: counted
    # beside the matmul between blocks, which spans the whole chunk
    near = 2 * 3.0 * chunks * 32 * 4 * 16 * 16 * 128
    want = 2.0 * s * flops_kimilinear.kda_weights(CONFIG) \
        + sum(parts.values()) - parts["solve"] + series + near \
        - in_loop * (1 - 1 / chunks)
    assert compiled.cost_analysis()["flops"] == pytest.approx(want, rel=0.15)
