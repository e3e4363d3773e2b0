"""``qwen3next80b_train_s8192``'s whole step, compiled ahead of time
for a described v5e under the plan its example would choose.

The step is ``cellbench/configs/qwen3-next-80b-a3b.json`` and
``cellbench/traffic/train_moe_s8192.json`` through
``examples/moe_lm/train_moe_lm.py``'s options, with what its blocks keep
chosen as the example chooses it on a v5e.  It is compiled ONCE, in the
module's fixture (15 s to trace, 70-130 s to compile, the file's whole
cost: nothing cheaper holds the plan, the memory and the kernels of the
step the chip runs); the tests below are the holds on that one compiled
step.  Nothing executes and nothing is timed.
"""

import dataclasses
import json
import os
import re
import types

import pytest

import jax

from conftest import V5E_BYTES_LIMIT

_KINDS = ("linear_attention",) * 3 + ("attention",)


@pytest.fixture(scope="module")
def qwen3next_step(moe_step_builder):
    """The cell's step, compiled: the plan the example states, and the
    compiled program's memory analysis and text."""
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_moe_s8192.json")) as f:
        traffic = json.load(f)
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        rotary_fraction=cfg["partial_rotary_factor"],
        attn_output_gate=True, zero_centered_norm=True, layer_types=_KINDS,
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg["linear_conv_kernel_dim"],
        gdn_chunk=cfg["linear_chunk_size"], use_flash=True,
        remat_blocks=True)
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"], seq_len=seq,
        per_chip_batch=rows, chunked_ce=cfg["head_chunks"],
        lr=cfg["optimizer"]["lr"], aux_coef=cfg["aux_loss_coef"])
    tokens = rows * seq
    with pytest.MonkeyPatch.context() as patch:
        # the program asks the backend which form of the scan to trace
        patch.setattr(jax, "default_backend", lambda: "tpu")
        widths = options.remat_widths(cfg["moe_intermediate_size"],
                                      cfg["num_attention_heads"])
        _, state = moe_step_builder(options=options, **sizes)
        budget = remat_budget(
            types.SimpleNamespace(
                memory_stats=lambda: {"bytes_limit": V5E_BYTES_LIMIT}),
            state[:2], tokens, widths)
        options = dataclasses.replace(options, remat_budget_bytes=budget)
        kept = remat_kept(remat_plan(_KINDS, tokens, widths, budget),
                          tokens, widths)
        step, abstract = moe_step_builder(options=options, **sizes)
        compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    return types.SimpleNamespace(
        tokens=tokens, kept=kept, memory=compiled.memory_analysis(),
        text=compiled.as_text())


def test_the_plan_is_what_the_example_chooses(qwen3next_step):
    """The full-attention layer's result first (16 heads of 256 and
    their log-sum-exps), then the three mixers' in-projections, then
    the three delta-rule launches' results (``o``, the entering states
    and ``T``: 805 MB a layer).  (This case's junit time is the
    module's one compile.)"""
    assert qwen3next_step.kept == (
        "attn_out x1, gdn_in x3, scan_out x3",
        qwen3next_step.tokens * (4128 + 3 * 12288 + 3 * 24576) * 2)


def test_the_step_holds_what_the_chip_has_room_for(qwen3next_step):
    """The arguments are the 7.51 GB of float32 state.
    ``memory_analysis()`` counted 11.16 GB of temporaries where the chip
    reserved 8.61 while the delta rule ran in XLA (``PERF.md`` section
    6, PR 41); with its kernels (PR 42) it counts 7.09 GB where the
    chip reserves 6.11; 6.46 GB with the convolution's kernel (PR 45)
    and the attention layer's result kept (PR 48: 0.14 GB of them);
    8.34 GB with the three delta-rule launches' results kept (PR 49:
    2.42 GB of them, 1.88 GB more than without: the peak held one
    block's entering states and ``T`` already)."""
    memory = qwen3next_step.memory
    assert memory.argument_size_in_bytes == pytest.approx(
        625_667_136 * 12, rel=1e-3)
    # not above the parent's temporaries (11.16 GB with the XLA form;
    # the kernels keep no (chunk, chunk) tensor or (c, b, h, ...) copy)
    assert memory.temp_size_in_bytes <= 11_164_387_328
    assert memory.temp_size_in_bytes <= 8.5e9
    # kept for real, and 1 GB under the limit the chip reports
    assert memory.temp_size_in_bytes > qwen3next_step.kept[1]
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 1.0e9 <= V5E_BYTES_LIMIT, (held, V5E_BYTES_LIMIT)


def test_the_kernels_are_in_the_step(qwen3next_step):
    """The causal kernels at head width 256 (a forward and the
    backward's two: the block keeps ``attn_out`` and recomputes no
    launch), the grouped products and
    the delta rule's kernels, a forward and a backward launch a layer
    (the blocks keep ``scan_out``: nine launches in such a compile mean
    the name is not reaching the policy); every ``pallas_call`` of the
    mixers lies under ``gdn_scan`` or ``gdn_conv`` and no ``while`` is
    left under the scan."""
    text = qwen3next_step.text
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv", "_grouped_matmul",
                   "_grouped_matmul_dw", "_gdn_forward", "_gdn_backward"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    attention = [name for name in op_names if "/_bdflash_" in name
                 and name.endswith("/pallas_call")]
    assert len(attention) == 3, sorted(attention)
    kernels = [name for name in op_names if "gdn_mixer" in name
               and name.endswith("/pallas_call")]
    # three layers: the convolution's backward (PR 45) under its scope
    conv = [name for name in kernels if "/gdn_conv/" in name]
    assert len(conv) == 3 and all(
        name.endswith("/gdn_conv/_conv_backward/pallas_call")
        for name in conv), conv
    delta_rule = sorted(set(kernels) - set(conv))
    assert all("/gdn_scan/_gdn_" in name for name in delta_rule), delta_rule
    assert [sum(f"/{kernel}/" in name for name in delta_rule)
            for kernel in ("_gdn_forward", "_gdn_backward")] == [3, 3], \
        delta_rule
    assert not [name for name in op_names
                if "gdn_scan" in name and "while" in name]
    for scope in ("gdn_mixer", "gdn_conv", "gdn_scan", "moe_shared"):
        assert scope in text, scope
