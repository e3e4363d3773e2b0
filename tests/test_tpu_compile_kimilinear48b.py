"""``kimilinear48b_train_s8192``'s whole step, compiled ahead of time
for a described v5e under the plan its example would choose.

The step is ``cellbench/configs/kimi-linear-48b-a3b.json`` and
``cellbench/traffic/train_kda_s8192.json`` through
``examples/moe_lm/train_moe_lm.py``'s options, with what its blocks keep
chosen as the example chooses it on a v5e.  It is compiled ONCE, in the
module's fixture (15 s to trace, 70-100 s to compile, the file's whole
cost: nothing cheaper holds the plan, the fit and the kernels of the
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


@pytest.fixture(scope="module")
def kimilinear_step(moe_step_builder):
    """The cell's step, compiled: the widths a block may keep off a TPU
    and on one, the plan the example states, and the compiled program's
    memory analysis and text."""
    from chainermn_tpu.models.moe_transformer import RouterOptions
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_kda_s8192.json")) as f:
        traffic = json.load(f)
    lin = cfg["linear_attn_config"]
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    kinds = ("kda", "kda", "kda", "latent_attention")
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], layer_types=kinds,
        gdn_value_heads=lin["num_heads"], gdn_key_dim=lin["head_dim"],
        gdn_value_dim=lin["head_dim"],
        gdn_conv=lin["short_conv_kernel_size"],
        gdn_chunk=cfg["linear_chunk_size"],
        latent_kv_rank=cfg["kv_lora_rank"],
        latent_nope_dim=cfg["qk_nope_head_dim"],
        latent_shared_dim=cfg["qk_rope_head_dim"],
        latent_value_dim=cfg["v_head_dim"], gated_mlp=True,
        no_positions=True, use_flash=True, remat_blocks=True)
    n_layers, dense_layers = (cfg["num_hidden_layers"],
                              cfg["first_k_dense_replace"])
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=n_layers,
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_token"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"], seq_len=seq, per_chip_batch=rows,
        chunked_ce=cfg["head_chunks"], lr=cfg["optimizer"]["lr"],
        aux_coef=cfg["aux_loss_coef"],
        router_options=RouterOptions(
            score=cfg["moe_router_activation_func"], selection_bias=True,
            routed_scale=cfg["routed_scaling_factor"], shared_gated=False),
        first_dense=dense_layers, dense_d_ff=cfg["intermediate_size"])
    tokens = rows * seq
    widths_of = lambda: options.remat_widths(cfg["intermediate_size"],
                                             cfg["num_attention_heads"])
    widths_off_tpu = widths_of()
    with pytest.MonkeyPatch.context() as patch:
        # the program asks the backend which form of the scan to trace
        patch.setattr(jax, "default_backend", lambda: "tpu")
        widths = widths_of()
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
        tokens=tokens, widths_off_tpu=widths_off_tpu, widths=widths,
        plan=plan, said=said, kept_bytes=kept_bytes,
        memory=compiled.memory_analysis(), text=compiled.as_text())


def test_the_plan_is_what_the_example_chooses(kimilinear_step):
    """With the channel-wise rule's kernels ``remat_widths`` holds no
    ``KDA_WORK`` and the plan is ``attn_out x1, mlp_in x1, kda_in x4,
    latent_in x1`` (2.56 GB, the latent layer's attention result first;
    with the XLA form nothing could be kept: ``PERF.md`` section 6,
    PR 43) and, of the 1.21 GB the budget has left, ``scan_out x1``:
    one of the four delta-rule launches' results (805 MB), the last
    layer's.  (This case's junit time is the module's one compile.)"""
    from chainermn_tpu.models.transformer import KDA_WORK

    # off the TPU the XLA form runs, and its reserve with it
    assert KDA_WORK in kimilinear_step.widths_off_tpu
    assert "scan_out" not in kimilinear_step.widths_off_tpu
    assert kimilinear_step.widths == {
        "attn_out": 4160, "mlp_in": 18432, "kda_in": 12288,
        "latent_in": 6144, "scan_out": 24576}
    assert kimilinear_step.said \
        == "attn_out x1, mlp_in x1, kda_in x4, latent_in x1, scan_out x1"
    assert kimilinear_step.kept_bytes == kimilinear_step.tokens * 2 * (
        4160 + 18432 + 4 * 12288 + 6144 + 24576)
    assert [i for i, names in enumerate(kimilinear_step.plan)
            if "scan_out" in names] == [4]


def test_the_step_fits_the_chip(kimilinear_step):
    """Arguments and temporaries (15.07 GB counted ahead of time with
    the last KDA layer's ``scan_out`` among the kept: 15.13 without it,
    15.98 with the first layer's in its place, which this line
    refuses) stay 1 GB under the limit the chip reports."""
    memory = kimilinear_step.memory
    assert memory.argument_size_in_bytes == pytest.approx(
        602_450_816 * 12, rel=1e-3)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 1.0e9 <= V5E_BYTES_LIMIT, (held, V5E_BYTES_LIMIT)
    # kept for real
    assert memory.temp_size_in_bytes > kimilinear_step.kept_bytes


def test_the_kernels_are_in_the_step(kimilinear_step):
    """The causal kernels at 192 / 128 (a forward and the backward's
    two: the block keeps ``attn_out`` and recomputes no launch), the
    grouped products and the delta rule's kernels; every ``pallas_call``
    of the mixers lies under ``kda_scan`` or ``kda_conv`` and no
    ``while`` is left under the scan."""
    text = kimilinear_step.text
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv", "_grouped_matmul",
                   "_grouped_matmul_dw", "_kda_forward", "_kda_backward"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    attention = [name for name in op_names if "LatentAttention" in name
                 and name.endswith("/pallas_call")]
    assert len(attention) == 3, sorted(attention)
    kernels = [name for name in op_names if "kda_mixer" in name
               and name.endswith("/pallas_call")]
    # four layers: the convolution's backward (PR 45) under its scope,
    conv = [name for name in kernels if "/kda_conv/" in name]
    assert len(conv) == 4 and all(
        name.endswith("/kda_conv/_conv_backward/pallas_call")
        for name in conv), conv
    # and a forward and a backward of the rule each, with the forward's
    # recomputation in the three layers that do not keep ``scan_out``
    delta_rule = sorted(set(kernels) - set(conv))
    assert [sum(f"/{kernel}/" in name for name in delta_rule)
            for kernel in ("_kda_forward", "_kda_backward")] == [7, 4], \
        delta_rule
    assert all("/kda_scan/" in name and "/gdn_scan/_kda_" in name
               for name in delta_rule), delta_rule
    assert not [name for name in op_names
                if "kda_scan" in name and "while" in name]
    for scope in ("kda_mixer", "kda_conv", "kda_scan", "latent_proj",
                  "moe_shared", "gated_mlp"):
        assert scope in text, scope
