"""The Gated DeltaNet / gated-attention hybrid's files: its plain
reference against the system at rehearsal size on the CPU
(``test_reference.py``'s manner), the control and a broken timed path
coming out not ``correct`` (``test_correct.py``'s), the cell's
rehearsal, and ``flops_qwen3next.py`` against the program's census and
XLA's own count for one Gated DeltaNet layer at the published widths."""

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import compare, flops_qwen3next, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "qwen3next80b_train_s8192"
with open(os.path.join(ROOT, "cellbench", "configs",
                       "qwen3-next-80b-a3b.json")) as _f:
    CONFIG = json.load(_f)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _model(cfg, ref, dtype=jnp.float32):
    from chainermn_tpu.models.moe_transformer import MoeTransformerLM
    from chainermn_tpu.models.transformer import BlockOptions

    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=1,
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=BlockOptions(
            norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
            qk_norm=True, rotary_fraction=cfg["partial_rotary_factor"],
            attn_output_gate=True, zero_centered_norm=True,
            layer_types=ref.layer_kinds(cfg),
            gdn_key_heads=cfg["linear_num_key_heads"],
            gdn_value_heads=cfg["linear_num_value_heads"],
            gdn_key_dim=cfg["linear_key_head_dim"],
            gdn_value_dim=cfg["linear_value_head_dim"],
            gdn_conv=cfg["linear_conv_kernel_dim"],
            gdn_chunk=cfg["linear_chunk_size"]),
        routing="dropless", held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"], tie_head=False)


def test_qwen3next_reference_matches_moe_transformer_lm():
    from chainermn_tpu.models.moe_transformer import (
        COUNTERS,
        ROUTES,
        moe_lm_loss,
    )
    from cellbench.reference import qwen3_next as ref
    from cellbench.runners.train_qwen3next import keyed_leaves, \
        program_tree

    cfg = {k: v for k, v in {**CONFIG, **CONFIG["rehearse"]}.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
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


def test_a_scan_that_forgets_its_state_is_not_correct(monkeypatch):
    """The timed path broken underneath: the recurrence over chunks
    starts every chunk from an empty state."""
    from chainermn_tpu.ops import gated_delta

    sound = gated_delta._carry_on

    def forgetful(state, chunk_of, dtype):
        return sound(jnp.zeros_like(state), chunk_of, dtype)

    monkeypatch.setattr(gated_delta, "_carry_on", forgetful)
    jax.clear_caches()
    try:
        assert _run()["correct"] is False
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_flops_against_the_programs_census():
    from chainermn_tpu.ops.gated_delta import gated_delta_census

    census = gated_delta_census(8192, 64, 32, 128, 128, key_heads=16)
    assert flops_qwen3next.gdn_parts(CONFIG, 8192) == census["flops"]
    assert flops_qwen3next.gdn_flops(CONFIG, 8192, "bwd") \
        == census["flops_backward"]
    assert flops_qwen3next.gdn_bytes(CONFIG, 8192, "fwd") \
        == census["bytes_forward"]


def test_delta_layer_flops_against_cost_analysis():
    """One Gated DeltaNet mixer of the published widths, forward: XLA's
    count of the compiled mixer against 2 a weight a position and the
    scan's parts (XLA counts the nilpotent series where the census
    counts a forward substitution, the element-wise chains, and a
    loop's body once: within the stated band)."""
    from chainermn_tpu.models.transformer import BlockOptions, \
        GatedDeltaMixer

    s = 2048  # 32 chunks: one pass of the stages outside the recurrence
    mixer = GatedDeltaMixer(BlockOptions(
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
        gdn_value_dim=128, gdn_conv=4, gdn_chunk=64))
    x = jax.ShapeDtypeStruct((1, s, 2048), jnp.bfloat16)
    variables = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)
    compiled = jax.jit(mixer.apply).lower(variables, x).compile()
    parts = flops_qwen3next.gdn_parts(CONFIG, s)
    # what the recurrence's loop runs a chunk is counted once, not 32 x
    in_loop = (parts["read"] + parts["from_state"] + parts["state"])
    series = 10 * 2.0 * 32 * 32 * 64 ** 3  # ten 64^3 products a head, chunk
    want = 2.0 * s * flops_qwen3next.delta_weights(CONFIG) \
        + sum(parts.values()) - parts["solve"] + series \
        - in_loop * (1 - 1 / 32)
    assert compiled.cost_analysis()["flops"] == pytest.approx(want, rel=0.1)
