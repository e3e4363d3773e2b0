"""``moonlight16b_train_s8192``'s whole step, compiled ahead of time for
a described v5e under the plan its example would choose.

The step is ``cellbench/configs/moonlight-16b-a3b.json`` and
``cellbench/traffic/train_mla_s8192.json`` through
``examples/moe_lm/train_moe_lm.py``'s options, with what its blocks keep
chosen as the example chooses it on a v5e.  It is compiled ONCE, in the
module's fixture (the file's whole cost: nothing cheaper holds the
depth's fit, the plan and the kernels of the step the chip runs); the
tests below are the holds on that one compiled step.  Nothing executes
and nothing is timed.
"""

import dataclasses
import json
import os
import re
import types

import pytest

import jax

from conftest import V5E_BYTES_LIMIT


@pytest.fixture(scope="module")
def moonlight_step(moe_step_builder):
    """The cell's step, compiled: the widths a block may keep, the plan
    the example states, and the compiled program's memory analysis and
    text."""
    from chainermn_tpu.models.moe_transformer import RouterOptions
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "moonlight-16b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_mla_s8192.json")) as f:
        traffic = json.load(f)
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        layer_types=("latent_attention",),
        latent_kv_rank=cfg["kv_lora_rank"],
        latent_nope_dim=cfg["qk_nope_head_dim"],
        latent_shared_dim=cfg["qk_rope_head_dim"],
        latent_value_dim=cfg["v_head_dim"], gated_mlp=True,
        use_flash=True, remat_blocks=True)
    n_layers, dense_layers = (cfg["num_hidden_layers"],
                              cfg["first_k_dense_replace"])
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=n_layers,
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        held=(cfg["first_expert"], cfg["n_routed_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        seq_len=seq, per_chip_batch=rows, chunked_ce=cfg["head_chunks"],
        lr=cfg["optimizer"]["lr"], aux_coef=cfg["aux_loss_coef"],
        router_options=RouterOptions(
            score=cfg["scoring_func"], selection_bias=True,
            routed_scale=cfg["routed_scaling_factor"], shared_gated=False,
            seq_aux=cfg["seq_aux"]),
        first_dense=dense_layers, dense_d_ff=cfg["intermediate_size"])
    tokens = rows * seq
    widths = options.remat_widths(cfg["intermediate_size"],
                                  cfg["num_attention_heads"])
    with pytest.MonkeyPatch.context() as patch:
        # the program asks the backend whether its kernels are compiled
        # or interpreted
        patch.setattr(jax, "default_backend", lambda: "tpu")
        _, state = moe_step_builder(options=options, **sizes)
        budget = remat_budget(
            types.SimpleNamespace(
                memory_stats=lambda: {"bytes_limit": V5E_BYTES_LIMIT}),
            state[:2], tokens, widths)
        options = dataclasses.replace(options, remat_budget_bytes=budget)
        plan = remat_plan(
            [options.layer_type(i) for i in range(n_layers)], tokens,
            widths, budget,
            dense=[i < dense_layers for i in range(n_layers)])
        said, kept_bytes = remat_kept(plan, tokens, widths)
        step, abstract = moe_step_builder(options=options, **sizes)
        compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    return types.SimpleNamespace(
        tokens=tokens, widths=widths, said=said, kept_bytes=kept_bytes,
        n_layers=n_layers, memory=compiled.memory_analysis(),
        text=compiled.as_text())


def test_the_plan_is_what_the_example_chooses(moonlight_step):
    """Six latent-attention layers, one of them behind a dense MLP: the
    budget left beside 8.03 GB of state keeps every layer's attention
    result (first: it saves the recomputation a kernel launch), the
    dense layer's ``mlp_in`` and every layer's un-rotated queries.  (This case's junit
    time is the module's one compile: it alone holds that six layers at
    2 x 8192 tokens fit the chip.)"""
    assert moonlight_step.n_layers == 6
    assert moonlight_step.widths == {"attn_out": 2080, "mlp_in": 22528,
                                     "latent_in": 3072}
    assert moonlight_step.said == "attn_out x6, mlp_in x1, latent_in x6"
    assert moonlight_step.kept_bytes == moonlight_step.tokens * 2 * (
        6 * 2080 + 22528 + 6 * 3072)


def test_the_step_fits_the_chip(moonlight_step):
    """Arguments (12 bytes a parameter: 8.03 GB) and temporaries (6.00
    GB counted ahead of time, 0.41 of them the six kept attention
    results) stay 1 GB under the limit the chip reports (14.03 of 16.91
    GB): six layers fit, so the configuration's floor of five is not
    taken."""
    memory = moonlight_step.memory
    assert memory.argument_size_in_bytes == pytest.approx(
        668_890_432 * 12, rel=1e-3)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 1.0e9 <= V5E_BYTES_LIMIT, (held, V5E_BYTES_LIMIT)
    # kept for real
    assert memory.temp_size_in_bytes > moonlight_step.kept_bytes


def test_the_kernels_and_the_rotation_are_in_the_step(moonlight_step):
    """The causal kernels at 192 / 128 in every layer (a forward and
    the backward's two: the blocks keep the forward's result, ``attn_out``,
    so their recomputation launches none), the grouped products, and
    the rotation under a scope of its own beside the projections'."""
    text = moonlight_step.text
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv", "_grouped_matmul",
                   "_grouped_matmul_dw"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    launches = [n for n in op_names if "LatentAttention" in n
                and n.endswith("/pallas_call")]
    # a layer: forward, dq, dkdv
    assert len(launches) == 3 * moonlight_step.n_layers, sorted(launches)
    rope = [n for n in op_names if "/latent_rope/" in n]
    assert rope and not [n for n in rope if "/latent_proj/" in n]
    for scope in ("latent_proj", "latent_rope", "moe_route", "moe_experts",
                  "moe_shared", "gated_mlp", "head_ce"):
        assert scope in text, scope
