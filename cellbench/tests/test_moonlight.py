"""The latent-attention configuration's files: its plain reference
against the system at rehearsal size on the CPU (``test_reference.py``'s
manner), the control and two broken timed paths coming out not
``correct`` (``test_correct.py``'s), the cell's rehearsal, and
``flops_moonlight.py`` against hand-worked values."""

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import compare, flops_moonlight, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "moonlight16b_train_s8192"
with open(os.path.join(ROOT, "cellbench", "configs",
                       "moonlight-16b-a3b.json")) as _f:
    CONFIG = json.load(_f)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _model(cfg, ref, dtype=jnp.float32):
    from chainermn_tpu.models.moe_transformer import (
        MoeTransformerLM,
        RouterOptions,
    )
    from chainermn_tpu.models.transformer import BlockOptions

    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=cfg["moe_layer_freq"],
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=BlockOptions(
            norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
            rope_theta=float(cfg["rope_theta"]), layer_types=(ref.MIXER,),
            latent_kv_rank=cfg["kv_lora_rank"],
            latent_nope_dim=cfg["qk_nope_head_dim"],
            latent_shared_dim=cfg["qk_rope_head_dim"],
            latent_value_dim=cfg["v_head_dim"], gated_mlp=True),
        routing="dropless",
        held=(cfg["first_expert"], cfg["n_routed_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        router_options=RouterOptions(
            score=cfg["scoring_func"], selection_bias=True,
            routed_scale=cfg["routed_scaling_factor"], shared_gated=False,
            seq_aux=True),
        first_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], tie_head=False)


def test_moonlight_reference_matches_moe_transformer_lm():
    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        ROUTES,
        moe_lm_loss,
    )
    from cellbench.reference import moonlight as ref
    from cellbench.runners.train_moonlight import keyed_leaves, \
        program_tree

    spec, _ = run.load_spec(WORKLOAD, 3, True, False)
    cfg = flops_moonlight.sizes_of(spec)
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


def _broken(monkeypatch, module, name, replacement):
    monkeypatch.setattr(module, name, replacement)
    jax.clear_caches()
    try:
        return _run()["correct"]
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_a_rotation_that_pairs_halves_is_not_correct(monkeypatch):
    """The timed path broken underneath: the shared channels are turned
    by halves as they come (``apply_rope``), so channel ``i`` is paired
    with ``i + turned / 2`` and not with its neighbour."""
    from chainermn_tpu.models import transformer

    def by_halves(x, positions, theta, turned):
        lead = x.shape[-1] - turned
        return jnp.concatenate([x[..., :lead], transformer.apply_rope(
            x[..., lead:], positions, theta)], axis=-1)

    assert _broken(monkeypatch, transformer, "rotate_pairs",
                   by_halves) is False


def test_a_layer_that_forgets_its_rotation_is_not_correct(monkeypatch):
    """The timed path broken underneath: no channel is turned at all
    (the sibling's unrotated layer under this model's name)."""
    from chainermn_tpu.models import transformer

    assert _broken(monkeypatch, transformer, "rotate_pairs",
                   lambda x, *a, **kw: x) is False


def test_flops_by_hand():
    """The weights every position goes through, the launches at 192 /
    128 and 16 heads, and the step at two sequences and balanced
    routing."""
    assert flops_moonlight.latent_weights(CONFIG) == 13_763_072 - 512
    assert flops_moonlight.dense_mlp_weights(CONFIG) == 69_206_016
    assert flops_moonlight.dense_moe_weights(CONFIG) \
        == 2048 * 64 + 17_301_504
    assert flops_moonlight.expert_layers(CONFIG) == 5
    pairs = 8192 * 8193 // 2
    assert flops_moonlight.flash_call_flops(CONFIG, "fwd", 2, 8192) \
        == 2 * (192 + 128) * 2 * 16 * pairs
    assert flops_moonlight.flash_call_flops(CONFIG, "dq", 2, 8192) \
        == 2 * (2 * 192 + 128) * 2 * 16 * pairs
    assert flops_moonlight.flash_call_flops(CONFIG, "dkv", 2, 8192) \
        == 2 * (2 * 192 + 2 * 128) * 2 * 16 * pairs
    assert flops_moonlight.flash_call_bytes(CONFIG, "fwd", 2, 8192) \
        == 2 * 8192 * 16 * (2 * 192 + 2 * 128) * 2 + 4 * 2 * 16 * 8192
    assert flops_moonlight.attention_model_flops(CONFIG, 8192) \
        == 3 * 640 * 16 * pairs
    routed = 5 * 2 * 8192 * 6 * 8 / 64  # balanced: an eighth of all routes
    step = flops_moonlight.step_model_flops(CONFIG, 8192, 2, routed)
    weights = 6 * (13_763_072 - 512) + 69_206_016 \
        + 5 * (2048 * 64 + 17_301_504) + 2048 * 20480
    assert weights == pytest.approx(281e6, rel=0.01)  # the issue's 281 M
    assert step == 2 * (6.0 * weights * 8192 + 6 * 3 * 640 * 16 * pairs) \
        + 6.0 * 8_650_752 * routed
    assert step == pytest.approx(43.2e12, rel=0.01)
    # the rotation has no matmul: it is in no count
    assert flops_moonlight.sizes_of(type("Spec", (), {
        "config": CONFIG, "sizes": {"hidden_size": 2048}})) \
        == {"scoring_func": "sigmoid", "hidden_size": 2048}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    """``mfu_moonlight``, ``flash_roofline_moonlight`` and
    ``moe_experts_roofline_moonlight`` on hand-made launches and
    counters: each divides the least time of ``flops_moonlight``'s
    counts by the time taken, and each finds nothing to read in a
    configuration that lacks this one's keys."""
    import types

    from cellbench import flops, flops_sdar
    from cellbench.readers import (
        flash_roofline_moonlight,
        mfu_moonlight,
        moe_experts_roofline_moonlight,
        scope_ms,
    )

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sizes = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    routed = 5 * 2 * 8192 * 6 / 8.0
    spec = types.SimpleNamespace(
        config=CONFIG, sizes=sizes, chips=1,
        traffic={"per_chip_batch": 2, "seq_len": 8192})
    ops = {"%_bdflash_forward.3 = fusion": (0.120, 12),
           "%_bdflash_backward_dq = fusion": (0.090, 6),
           "%_bdflash_backward_dkdv.1 = fusion": (0.110, 6),
           "%fusion.7 = fusion": (9.0, 100)}
    ctx = types.SimpleNamespace(
        spec=spec, trace={"ops": ops}, peaks=lambda: peaks,
        telemetry={"counters": {"moe_rows_routed": np.array(
            [routed, routed])}},
        samples_per_step=16384, untraced_rate_per_chip=lambda: 24576.0)
    least = sum(n * flops.roofline_seconds(
        flops_moonlight.flash_call_flops(CONFIG, kind, 2, 8192),
        flops_moonlight.flash_call_bytes(CONFIG, kind, 2, 8192), peaks)[0]
        for kind, n in (("fwd", 12), ("dq", 6), ("dkv", 6)))
    assert flash_roofline_moonlight.read(ctx) == pytest.approx(
        100 * least / 0.320)
    assert 0 < flash_roofline_moonlight.read(ctx) < 100
    # 1.5 steps a second of 43.2 TFLOP: a third of the peak
    assert mfu_moonlight.read(ctx) == pytest.approx(
        100 * 1.5 * flops_moonlight.step_model_flops(
            sizes, 8192, 2, routed) / 197e12)
    assert 30 < mfu_moonlight.read(ctx) < 36
    monkeypatch.setattr(scope_ms, "read", lambda ctx, scope: 60.0)
    want, _ = flops.roofline_seconds(
        flops_sdar.expert_flops(routed, 2048, 1408),
        flops_sdar.expert_bytes(routed, 2048, 1408, 8, 5), peaks)
    assert moe_experts_roofline_moonlight.read(ctx) == pytest.approx(
        100 * want * 1e3 / 60.0)
    assert 0 < moe_experts_roofline_moonlight.read(ctx) < 100
    # another configuration's cell: nothing to read, nothing raised
    other = types.SimpleNamespace(
        spec=types.SimpleNamespace(config={}, sizes={}, traffic={}),
        trace={"ops": ops}, telemetry=None)
    for reader in (mfu_moonlight, flash_roofline_moonlight,
                   moe_experts_roofline_moonlight):
        assert reader.read(other) is None
