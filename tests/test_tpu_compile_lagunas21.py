"""``lagunas21_train_s8192``'s whole step, compiled ahead of time for a
described v5e under the plan its example would choose.

The step is ``cellbench/configs/laguna-s-2.1.json`` and
``cellbench/traffic/train_swa_s8192.json`` through
``examples/moe_lm/train_moe_lm.py``'s options (the command line
``cellbench/runners/train_laguna.py`` writes), with what its blocks keep
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

PARAMETERS = 811_029_504


@pytest.fixture(scope="module")
def laguna_step(moe_step_builder):
    """The cell's step, compiled: the widths a block may keep, the plan
    the example states, and the compiled program's memory analysis and
    text."""
    from chainermn_tpu.models.moe_transformer import RouterOptions
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        YarnScaling,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "laguna-s-2.1.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_swa_s8192.json")) as f:
        traffic = json.load(f)
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    full = cfg["rope_parameters"]["full_attention"]
    window = cfg["rope_parameters"]["sliding_attention"]
    kinds = {"full_attention": "attention",
             "sliding_attention": "window_attention"}
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        head_gate=True, rope_theta=float(full["rope_theta"]),
        rotary_fraction=full["partial_rotary_factor"],
        rope_yarn=YarnScaling(
            float(full["factor"]),
            full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]),
            full["attention_factor"]),
        # one period of the pattern: full, window, window, window
        layer_types=tuple(kinds[k] for k in cfg["layer_types"][:4]),
        window=cfg["sliding_window"],
        window_heads=cfg["num_attention_heads_per_layer"][1],
        window_rope_theta=float(window["rope_theta"]),
        window_rotary_fraction=float(window["partial_rotary_factor"]),
        gated_mlp=True, use_flash=True, remat_blocks=True)
    n_layers, dense_layers = (cfg["num_hidden_layers"],
                              len(cfg["mlp_only_layers"]))
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=n_layers,
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        seq_len=seq, per_chip_batch=rows, chunked_ce=cfg["head_chunks"],
        lr=cfg["optimizer"]["lr"], aux_coef=cfg["aux_loss_coef"],
        router_options=RouterOptions(
            routed_scale=cfg["moe_routed_scaling_factor"]),
        first_dense=dense_layers, dense_d_ff=cfg["intermediate_size"])
    tokens = rows * seq
    widths = options.remat_widths(cfg["intermediate_size"],
                                  cfg["num_attention_heads"],
                                  d_model=cfg["hidden_size"])
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
        budget=budget, n_layers=n_layers, memory=compiled.memory_analysis(),
        text=compiled.as_text())


def test_the_plan_is_what_the_example_chooses(laguna_step):
    """Five layers, the first behind a dense MLP: ``attn_out`` is the
    widest layer's (72 heads of 128 and their log-sum-exps), ``mlp_in``
    the dense layer's ``[g | u]``.  (This case's junit time is the
    module's one compile: it alone holds that five layers at 1 x 8192
    tokens fit the chip.)"""
    assert laguna_step.n_layers == 5
    assert laguna_step.widths == {"attn_out": 72 * 128 + 2 * 72,
                                  "mlp_in": 2 * 12288}
    print(laguna_step.said, laguna_step.kept_bytes, laguna_step.budget)
    assert laguna_step.said == "attn_out x5, mlp_in x1"


def test_the_step_fits_the_chip(laguna_step):
    """Arguments (12 bytes a parameter: 9.73 GB) and temporaries stay
    under the limit the chip reports."""
    memory = laguna_step.memory
    assert memory.argument_size_in_bytes == pytest.approx(
        PARAMETERS * 12, rel=1e-3)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("argument", memory.argument_size_in_bytes, "temp",
          memory.temp_size_in_bytes, "held", held)
    assert held + 0.5e9 <= V5E_BYTES_LIMIT, (held, V5E_BYTES_LIMIT)


def test_the_kernels_and_the_scopes_are_in_the_step(laguna_step):
    """The window kernels in the three window layers and the causal
    ones in the two full layers (a forward and the backward's two: the
    blocks keep the forward's result, ``attn_out``, so their
    recomputation launches none), the grouped products, and the
    rotation and the gate a head under scopes of their own inside the
    projections'."""
    text = laguna_step.text
    for kernel in ("_swaflash_forward", "_swaflash_backward_dq",
                   "_swaflash_backward_dkdv", "_bdflash_forward",
                   "_bdflash_backward_dq", "_bdflash_backward_dkdv",
                   "_grouped_matmul", "_grouped_matmul_dw"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    launches = [n for n in op_names if "SelfAttention" in n
                and n.endswith("/pallas_call")]
    assert len([n for n in launches if "_swaflash_" in n]) == 3 * 3, \
        sorted(launches)
    assert len([n for n in launches if "_bdflash_" in n]) == 3 * 2, \
        sorted(launches)
    for scope in ("attn_proj/attn_rope", "attn_proj/head_gate"):
        assert [n for n in op_names if f"/{scope}/" in n], scope
    for scope in ("attn_proj", "moe_route", "moe_experts", "moe_shared",
                  "gated_mlp", "head_ce"):
        assert scope in text, scope
