"""The window-and-full-attention configuration's files: its plain
reference against the system at rehearsal size on the CPU
(``test_reference.py``'s manner), the control and broken timed paths
coming out not ``correct`` (``test_correct.py``'s), the cell's
rehearsal, ``flops_laguna.py`` against hand-worked values and the new
readers on a made-up trace.  (``test_cells.py`` rehearses both new cells
with every other.)"""

import argparse
import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import compare, flops, flops_laguna, flops_sdar, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "lagunas21_train_s8192"
with open(os.path.join(ROOT, "cellbench", "configs",
                       "laguna-s-2.1.json")) as _f:
    CONFIG = json.load(_f)
SIZES = {**{k: v for k, v in CONFIG.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)},
         **{k: CONFIG[k] for k in flops_laguna._SHAPE_KEYS}}


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _model(cfg, ref, dtype=jnp.float32):
    """The model the runner's command line builds, from the same
    fields."""
    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        RouterOptions,
    )
    from chainermn_tpu.models.transformer import BlockOptions, YarnScaling
    from cellbench.runners.train_laguna import mixer_fields

    m = mixer_fields(ref, cfg)
    full, window = m["full"], m["window"]
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=m["heads"], n_layers=cfg["num_hidden_layers"],
        n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"],
        moe_every=cfg["decoder_sparse_step"],
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=BlockOptions(
            norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], head_gate=True,
            rope_theta=float(full["rope_theta"]),
            rotary_fraction=full["partial_rotary_factor"],
            rope_yarn=YarnScaling(
                float(full["factor"]),
                full["original_max_position_embeddings"],
                float(full["beta_fast"]), float(full["beta_slow"]),
                full["attention_factor"]),
            layer_types=tuple(m["types"]), window=cfg["sliding_window"],
            window_heads=m["window_heads"],
            window_rope_theta=float(window["rope_theta"]),
            window_rotary_fraction=float(window["partial_rotary_factor"]),
            gated_mlp=True),
        routing="dropless", held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        router_options=RouterOptions(
            routed_scale=cfg["moe_routed_scaling_factor"]),
        first_dense=m["first_dense"], dense_d_ff=cfg["intermediate_size"],
        tie_head=False)


def test_laguna_reference_matches_moe_transformer_lm():
    """Logits, loss and every gradient leaf of the float32 model against
    ``jax.value_and_grad`` of the reference's whole-model loss; the
    reference's layer-at-a-time ``train_readings`` against both, and its
    parameters' change against one step of the example's optimizer."""
    import optax

    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        ROUTES,
        moe_lm_loss,
    )
    from cellbench.reference import laguna as ref
    from cellbench.runners.train_laguna import keyed_leaves, program_tree

    spec, _ = run.load_spec(WORKLOAD, 3, True, False)
    cfg = dict(flops_laguna.sizes_of(spec), aux_loss_coef=0.1)
    w = ref.init_weights(ref.seed_key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                               (2, 64), dtype=np.int32)
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
    assert set(got) == set(ref_grads) == set(ref.leaf_keys(cfg))
    for name in got:
        assert _max_rel(got[name], ref_grads[name]) < 2e-3, name
    # the control is the same mathematics in scaled float8: apart from both
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, cfg, lowp=True))(w)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)

    opt_cfg = {"lr": 1e-3, "weight_decay": 0.01}
    readings = ref.train_readings(3, cfg, tokens[None], opt_cfg)
    assert abs(readings["losses"][0] - float(ref_loss)) \
        < 1e-5 * float(ref_loss)
    for name, norm in readings["grad_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(ref_grads[name]))) \
            < 1e-3 * max(norm, 1e-6), name
    assert set(readings["grad_small"]) == {
        k for k, x in w.items() if x.size <= ref.SMALL}
    assert len(readings["routes"][0]) == 4  # the expert layers'
    opt = optax.adamw(opt_cfg["lr"], weight_decay=opt_cfg["weight_decay"])
    updates, _ = opt.update(grads, opt.init(tree), tree)
    deltas = keyed_leaves(ref, updates, cfg)
    for name, norm in readings["delta_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(deltas[name]))) \
            < 2e-3 * norm, name


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


def _broken(monkeypatch, module, name, replacement):
    monkeypatch.setattr(module, name, replacement)
    jax.clear_caches()
    try:
        return _run()["correct"]
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_a_window_twice_as_long_is_not_correct(monkeypatch):
    """The timed path broken underneath: the window launches see 32 keys
    where the model has 16."""
    from chainermn_tpu.ops import pallas_attention as pa

    assert _broken(monkeypatch, pa, "_live_window",
                   lambda window, s: None if window is None
                   or 2 * window >= s else 2 * window) is False


def test_a_full_layer_without_yarn_is_not_correct(monkeypatch):
    """The timed path broken underneath: the full layers turn by the
    plain frequencies, no ramp and no factor on cos and sin."""
    from chainermn_tpu.models import transformer

    plain = transformer.apply_rope
    assert _broken(monkeypatch, transformer, "apply_rope",
                   lambda x, pos, theta, fraction=1.0, yarn=None: plain(
                       x, pos, theta, fraction)) is False


def test_flops_by_hand():
    """The mask's pairs, the weights every position goes through, the
    launches of both families and the step at one sequence and balanced
    routing."""
    assert flops_laguna.live_pairs(8192) == 8192 * 8193 // 2
    assert flops_laguna.live_pairs(8192, 512) == sum(
        min(i + 1, 512) for i in range(8192)) == 4_063_488
    assert flops_laguna.live_pairs(256, 512) == 256 * 257 // 2
    assert flops_laguna.mixer_weights(SIZES, 48) == 44_187_648
    assert flops_laguna.mixer_weights(SIZES, 72) == 63_135_744
    assert flops_laguna.dense_mlp_weights(SIZES) == 113_246_208
    assert flops_laguna.dense_moe_weights(SIZES) \
        == 3072 * 256 + 9_437_184 + 3072
    assert flops_laguna.expert_layers(SIZES) == 4
    assert flops_laguna.flash_call_flops(SIZES, "fwd", 1, 8192, 72, 512) \
        == 2 * 2 * 128 * 72 * 4_063_488
    assert flops_laguna.flash_call_flops(SIZES, "dq", 1, 8192, 48) \
        == 2 * 3 * 128 * 48 * (8192 * 8193 // 2)
    assert flops_laguna.flash_call_flops(SIZES, "dkv", 1, 8192, 72, 512) \
        == 2 * 4 * 128 * 72 * 4_063_488
    # q and o of 72 heads, k and v of 8, and the lse
    assert flops_laguna.flash_call_bytes(SIZES, "fwd", 1, 8192, 72) \
        == 8192 * 128 * 2 * (2 * 72 + 2 * 8) + 4 * 72 * 8192
    assert flops_laguna.flash_call_bytes(SIZES, "dkv", 1, 8192, 48) \
        == 8192 * 128 * 2 * (2 * 48 + 4 * 8) + 2 * 4 * 48 * 8192
    routed = 4 * 8192 * 10 * 8 / 256  # balanced: a 32nd of all routes
    weights = 2 * 44_187_648 + 3 * 63_135_744 + 113_246_208 \
        + 4 * (3072 * 256 + 9_437_184 + 3072) + 3072 * 12544
    # the issue's 482 M active matmul parameters, 278 M of them the mixers'
    assert 2 * 44_187_648 + 3 * 63_135_744 == pytest.approx(278e6, rel=0.01)
    active = weights + 4 * 10 * 9_437_184 / 32
    assert active == pytest.approx(482e6, rel=0.01)
    attention = 3 * 2 * 2 * 128 * (
        2 * 48 * (8192 * 8193 // 2) + 3 * 72 * 4_063_488)
    assert attention == pytest.approx(6.3e12, rel=0.02)
    step = flops_laguna.step_model_flops(SIZES, 8192, 1, routed)
    assert step == 6.0 * weights * 8192 + attention \
        + 6.0 * 9_437_184 * routed
    assert step == pytest.approx(30.0e12, rel=0.02)
    assert flops_laguna.sizes_of(types.SimpleNamespace(
        config=CONFIG, sizes={"hidden_size": 64, "layer_types": ["x"]})) \
        == {"hidden_size": 64, "layer_types": ["x"],
            "mlp_layer_types": CONFIG["mlp_layer_types"],
            "num_attention_heads_per_layer":
                CONFIG["num_attention_heads_per_layer"],
            "rope_parameters": CONFIG["rope_parameters"]}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    """``mfu_laguna``, ``flash_roofline_laguna`` (both families, and a
    kernel alone), ``moe_experts_roofline_laguna`` and
    ``swa_live_block_share`` on hand-made launches and counters, and each
    finding nothing to read in a configuration that lacks this one's
    keys or in a program that lacks the window launches."""
    from cellbench.readers import (
        flash_roofline_laguna,
        mfu_laguna,
        moe_experts_roofline_laguna,
        scope_ms,
        swa_live_block_share,
    )

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sizes = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    routed = 4 * 8192 * 10 / 32.0
    spec = types.SimpleNamespace(
        config=CONFIG, sizes=sizes, chips=1, rehearse=False,
        traffic={"per_chip_batch": 1, "seq_len": 8192})
    ops = {"%_swaflash_forward.3 = fusion": (0.030, 9),
           "%_swaflash_backward_dq = fusion": (0.030, 9),
           "%_swaflash_backward_dkdv.1 = fusion": (0.045, 9),
           "%_bdflash_forward = fusion": (0.060, 6),
           "%_bdflash_backward_dq.2 = fusion": (0.075, 6),
           "%_bdflash_backward_dkdv = fusion": (0.100, 6),
           "%fusion.7 = fusion": (9.0, 100)}
    blocks = {"visited": 288, "live": 279, "executed": 279,
              "below_window": 0}
    ctx = types.SimpleNamespace(
        spec=spec, trace={"ops": ops}, peaks=lambda: peaks,
        telemetry={"counters": {"moe_rows_routed": np.array(
            [routed, routed])}, "swa_blocks": blocks},
        samples_per_step=8192, untraced_rate_per_chip=lambda: 20480.0)

    def least(kinds, heads, window):
        return sum(n * flops.roofline_seconds(
            flops_laguna.flash_call_flops(SIZES, kind, 1, 8192, heads,
                                          window),
            flops_laguna.flash_call_bytes(SIZES, kind, 1, 8192, heads),
            peaks)[0] for kind, n in kinds)

    swa = flash_roofline_laguna.read(ctx, "swa")
    assert swa == pytest.approx(100 * least(
        (("fwd", 9), ("dq", 9), ("dkv", 9)), 72, 512) / 0.105)
    assert flash_roofline_laguna.read(ctx, "swa", "dq") == pytest.approx(
        100 * least((("dq", 9),), 72, 512) / 0.030)
    full = flash_roofline_laguna.read(ctx, "bd")
    assert full == pytest.approx(100 * least(
        (("fwd", 6), ("dq", 6), ("dkv", 6)), 48, None) / 0.235)
    assert 0 < swa < 100 and 0 < full < 100
    # 2.5 steps a second of 30 TFLOP
    assert mfu_laguna.read(ctx) == pytest.approx(
        100 * 2.5 * flops_laguna.step_model_flops(
            SIZES, 8192, 1, routed) / 197e12)
    assert 35 < mfu_laguna.read(ctx) < 40
    monkeypatch.setattr(scope_ms, "read", lambda ctx, scope: 20.0)
    want, _ = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, 3072, 1024),
        flops_sdar.expert_bytes(routed, 3072, 1024, 8, 4), peaks)
    assert moe_experts_roofline_laguna.read(ctx) == pytest.approx(
        100 * want * 1e3 / 20.0)
    assert 0 < moe_experts_roofline_laguna.read(ctx) < 100
    assert swa_live_block_share.read(ctx) == pytest.approx(
        100 * 279 / 288)
    # another configuration's cell, or a program without the window
    # launches: nothing to read, nothing raised
    other = types.SimpleNamespace(
        spec=types.SimpleNamespace(config={}, sizes={}, traffic={},
                                   rehearse=False),
        trace={"ops": ops}, telemetry=None)
    assert swa_live_block_share.read(other) is None
    for reader, args in ((mfu_laguna, ()), (flash_roofline_laguna, ("swa",)),
                         (moe_experts_roofline_laguna, ())):
        assert reader.read(other, *args) is None
    ctx.trace = {"ops": {"%fusion.7 = fusion": (9.0, 100)}}
    assert flash_roofline_laguna.read(ctx, "swa") is None
    assert flash_roofline_laguna.read(ctx, "bd", "fwd") is None
