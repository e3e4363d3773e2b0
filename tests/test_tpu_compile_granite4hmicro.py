"""``granite4hmicro_train_s8192``'s whole step, compiled ahead of time
for a described v5e under the plan its example would choose.

The step is ``cellbench/configs/granite-4.0-h-micro.json`` through
``examples/lm/train_lm.py``'s options, with what its blocks keep across
their recomputation chosen as the example chooses it on a v5e
(``remat_budget`` of the reported limit and the abstract state,
``remat_plan``).  Each plan is compiled ONCE, in the module's fixture
(60-80 s, the file's whole cost: nothing cheaper holds the plan, the
fit and the kernels of the step the chip runs); the tests below are the
holds on that one compiled step.  Nothing executes and nothing is
timed.
"""

import dataclasses
import re
import types

import pytest

import jax

from conftest import V5E_BYTES_LIMIT

#: a Mamba-2 layer of the cell: 76 182 976 float32 parameters with ``mu``
#: and ``nu`` beside them
_MAMBA_LAYER_STATE = 76_182_976 * 12
_CELL_LAYERS = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
#: plan -> (sequences a step, layers, what the example says it keeps)
_PLANS = {
    # the cell: one 8192-token sequence, all ten layers, every result
    "cell": (1, _CELL_LAYERS, "attn_out x1, mlp_in x10, ssm_in x9"),
    # two sequences a step, a shorter plan from the same code; five of
    # the layers, on a device that reports the other five's state less
    "two_sequences": (2, _CELL_LAYERS[3:8], "attn_out x1, mlp_in x4"),
}

#: HLO ``copy`` instructions under the ``ssm_mixer`` scope in the cell's
#: compiled step (jax 0.9.0, libtpu 0.0.34; 324 at PR 45's parent)
_MIXER_LAYOUT_COPIES = 288


@pytest.fixture(scope="module", params=list(_PLANS))
def hybrid_step(request, lm_step_builder):
    """The plan's step, compiled: what the example says it keeps, the
    bytes that is, the limit it was planned against, and the compiled
    program's memory analysis and text."""
    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    rows, layer_types, _ = _PLANS[request.param]
    limit = V5E_BYTES_LIMIT \
        - (len(_CELL_LAYERS) - len(layer_types)) * _MAMBA_LAYER_STATE
    options = BlockOptions(
        norm="rmsnorm", norm_eps=1e-5, n_kv_heads=8,
        attention_scale=1 / 64, layer_types=layer_types, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_conv=4, ssm_chunk=256,
        gated_mlp=True, no_positions=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, use_flash=True,
        remat_blocks=True)
    sizes = dict(n_layers=len(layer_types), d_model=2048, n_heads=32,
                 vocab=12544, seq_len=8192, per_chip_batch=rows, d_ff=8192,
                 chunked_ce=7, lr=1e-4)
    tokens, widths = rows * 8192, options.remat_widths(
        8192, sizes["n_heads"], d_model=sizes["d_model"])
    with pytest.MonkeyPatch.context() as patch:
        # the program asks the backend which form of the scan to trace
        patch.setattr(jax, "default_backend", lambda: "tpu")
        _, state = lm_step_builder(1, options=options, **sizes)
        options = dataclasses.replace(
            options, remat_budget_bytes=remat_budget(
                types.SimpleNamespace(
                    memory_stats=lambda: {"bytes_limit": limit}),
                state[:2], tokens, widths))
        step, abstract = lm_step_builder(1, options=options, **sizes)
        said, kept_bytes = remat_kept(
            remat_plan(layer_types, tokens, widths,
                       options.remat_budget_bytes), tokens, widths)
        compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    return types.SimpleNamespace(
        plan=request.param, said=said, kept_bytes=kept_bytes, limit=limit,
        memory=compiled.memory_analysis(), text=compiled.as_text())


#: for the holds that read the cell's plan alone: the fixture's first
#: parameter, so the step compiled for the others is the one they read
only_the_cell = pytest.mark.parametrize("hybrid_step", ["cell"], indirect=True)


def test_the_plan_is_what_the_example_chooses(hybrid_step):
    """(This case's junit time is its plan's one compile.)"""
    assert hybrid_step.said == _PLANS[hybrid_step.plan][2]


def test_the_step_fits_the_chip(hybrid_step):
    """Arguments and temporaries stay 0.8 GB under the limit the chip
    reports."""
    memory = hybrid_step.memory
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 0.8e9 <= hybrid_step.limit, (held, hybrid_step.limit)
    # kept for real: the temporaries hold them
    assert memory.temp_size_in_bytes > hybrid_step.kept_bytes


def test_the_kernels_are_in_the_step(hybrid_step):
    """The scan's, the attention's and the convolution's kernels; the
    attention layer keeps ``attn_out``, so its three launches are a
    forward and the backward's two, none recomputed."""
    for kernel in ("_ssd_forward", "_ssd_backward", "_bdflash_forward",
                   "ssm_conv/_conv_backward"):
        assert f"{kernel}/pallas_call" in hybrid_step.text, kernel
    attention = {name for name in re.findall(
        r'op_name="([^"]*/pallas_call)"', hybrid_step.text)
        if "/_bdflash_" in name}
    assert len(attention) == 3, sorted(attention)


@only_the_cell
def test_each_in_projection_is_computed_once_and_kept(hybrid_step):
    """One forward ``in_proj`` product a layer, each kept."""
    for width, layers in ((16384, 10), (8512, 9)):
        assert len(re.findall(rf"= bf16\[1,8192,{width}\]\S* fusion\(",
                              hybrid_step.text)) == layers


@only_the_cell
def test_the_in_projections_result_is_row_major(hybrid_step):
    """The layout PR 45's gain hangs on (``ROADMAP.md`` Design #19): the
    convolution kernel's row-major operand makes XLA emit the mixers'
    in-projection row-major, ``bf16[1,8192,8512]{2,1,0}``, and the
    copies into the scan's kernels' layout go with the ``{1,2,0}`` form
    (3 % of the cell's step on the chip, ``PERF.md`` section 6, PR 45).
    A libtpu or a kernel that hands XLA back the other form fails here;
    the count of the mixers' layout copies is what this tree compiles
    to, pinned so that a change shows."""
    produced = re.findall(
        r"= bf16\[1,8192,8512\](\{[\d,]*)\S* fusion\(", hybrid_step.text)
    assert produced == ["{2,1,0"] * 9, produced
    assert "bf16[1,8192,8512]{1,2,0" not in hybrid_step.text
    copies = [line for line in hybrid_step.text.splitlines()
              if re.search(r" copy\(", line) and "ssm_mixer" in line]
    assert len(copies) == _MIXER_LAYOUT_COPIES, len(copies)

