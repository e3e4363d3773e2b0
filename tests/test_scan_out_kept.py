"""The delta rule's kernels' result kept across a block's recomputation.

``ops/gated_delta_kernels.py``'s and ``ops/kda_kernels.py``'s forward
rules name the three arrays a differentiated launch writes (``o``, the
state entering every chunk, every chunk's ``T``) ``scan_out``; a block
whose plan lists the name (``models/transformer.py``: ``REMAT_NAMES``,
``remat_plan``) keeps them, and its recomputation holds no forward
launch.  Interpreted kernels at tiny shapes; nothing is timed.
"""

import dataclasses
import functools
import re
import types

import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import transformer
from chainermn_tpu.models.transformer import (
    REMAT_NAMES,
    BlockOptions,
    TransformerBlock,
    block_under_plan,
    remat_budget,
    remat_kept,
    remat_plan,
)
from chainermn_tpu.ops import gated_delta
from chainermn_tpu.ops import gated_delta_kernels as scalar_rule

from test_attn_out_kept import _gradients_and_launches, _same_bits

_D_MODEL, _D_FF, _TOKENS = 64, 128, (1, 256)
#: a layer of each rule at the sizes the kernels tile: two value heads
#: of 128 (behind one key head under the scalar rule), a chunk of 64
_OPTIONS = {
    "linear_attention": BlockOptions(
        norm="rmsnorm", gated_mlp=True, layer_types=("linear_attention",),
        gdn_key_heads=1, gdn_value_heads=2),
    "kda": BlockOptions(
        norm="rmsnorm", gated_mlp=True, layer_types=("kda",),
        gdn_value_heads=2),
}
_KERNELS = {"linear_attention": "_gdn", "kda": "_kda"}
#: the scan's body without its own ``jit``, which would hand a second
#: lowering the first one's trace
_SCAN_BODY = gated_delta.gated_delta_scan.__wrapped__
_RULES = list(_OPTIONS)


@pytest.fixture(scope="module")
def interpreted():
    """The mixers' scan with its kernels interpreted (left to itself it
    runs its XLA form off the TPU)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gated_delta, "gated_delta_scan", functools.partial(
            gated_delta.gated_delta_scan, interpret=True))
        yield


@pytest.fixture(scope="module", params=_RULES)
def block_gradients(request, interpreted):
    """``(kind, {form: (gradients, launches)})`` of one block of the
    kind, gradients in parameters and input: as it is, recomputed with
    nothing kept, and recomputed with ``scan_out`` kept."""
    kind = request.param
    fields = dict(n_heads=2, d_ff=_D_FF, dtype=jnp.float32,
                  options=_OPTIONS[kind], kind=kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (*_TOKENS, _D_MODEL))
    forms = {
        "unrecomputed": TransformerBlock(**fields),
        "recomputed": block_under_plan(TransformerBlock, (), 0)(**fields),
        "kept": block_under_plan(TransformerBlock, ("scan_out",), 0)(
            **fields),
    }
    params = forms["unrecomputed"].init(jax.random.PRNGKey(1), x)
    return kind, {
        form: _gradients_and_launches(
            lambda p, x: jnp.sum(block.apply(p, x) ** 2), params, x)
        for form, block in forms.items()}


def test_a_kept_result_changes_no_bit_of_a_blocks_gradient(block_gradients):
    """The same kernels on the same operands, one of them run once."""
    kept, recomputed, plain = (block_gradients[1][form][0] for form in (
        "kept", "recomputed", "unrecomputed"))
    _same_bits(kept, recomputed)
    _same_bits(kept, plain)
    assert all(bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(kept))


def test_a_kept_result_takes_the_forward_launch_off_the_recomputation(
        block_gradients):
    """Gone from the gradient's jaxpr, not merely unused: a forward and
    the backward launch with the name kept; the forward twice without
    it."""
    kind, forms = block_gradients
    once = {f"{_KERNELS[kind]}_forward": 1, f"{_KERNELS[kind]}_backward": 1}
    assert forms["unrecomputed"][1] == once
    assert forms["recomputed"][1] \
        == {**once, f"{_KERNELS[kind]}_forward": 2}
    assert forms["kept"][1] == once


# -- the name -----------------------------------------------------------------
def _scan_operands(kind, dtype=jnp.float32, s=256):
    """``q, k, v, g, beta`` of one sequence at :data:`_OPTIONS`' heads."""
    hk = 2 if kind == "kda" else 1
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (jax.random.normal(key, (1, s, hk, 128), dtype) * 0.1
            for key in keys[:2])
    v = jax.random.normal(keys[2], (1, s, 2, 128), dtype)
    g = -jax.random.uniform(
        keys[3], (1, s, 2, 128) if kind == "kda" else (1, s, 2))
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, 2)))


@pytest.mark.parametrize("kind", _RULES)
def test_the_name_is_one_for_all_three_results(kind):
    """A policy cannot keep ``o`` without the states or ``T``: a launch
    with one result not kept still runs.  The residuals keep their
    float32."""
    assert scalar_rule.SCAN_OUT == "scan_out" == REMAT_NAMES[-1]
    q, k, v, g, beta = _scan_operands(kind, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v: gated_delta.gated_delta_scan(
            q, k, v, g, beta, interpret=True).astype(jnp.float32).sum()))(v)
    named = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                named.append((eqn.params["name"],
                              eqn.outvars[0].aval.dtype.name,
                              eqn.outvars[0].aval.size))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    # o (s, h dv); a state (dk, dv) and a T (64, 64) a head and chunk
    assert sorted(named) == sorted([
        ("scan_out", "bfloat16", 256 * 2 * 128),
        ("scan_out", "float32", 4 * 2 * 128 * 128),
        ("scan_out", "float32", 4 * 2 * 64 * 64)])


@pytest.mark.parametrize("kind", _RULES)
def test_outside_a_policy_the_name_lowers_to_nothing(kind, monkeypatch):
    """A step that recomputes no block lowers its gradient to the text
    it lowered to without the name (but for the ordinals JAX numbers
    its private functions with)."""
    q, k, v, g, beta = _scan_operands(kind)
    scan = functools.partial(_SCAN_BODY, q, k, g=g, beta=beta,
                             dtype=jnp.float32, interpret=True)

    def text():
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.grad(
            lambda v: scan(v=v).sum())).lower(v).as_text())

    named = text()
    unnamed = []
    monkeypatch.setattr(scalar_rule, "named",
                        lambda *results: unnamed.append(1) or results)
    assert named == text() and unnamed


# -- the plan -----------------------------------------------------------------
#: the options of the two cells with such layers, their heads, and their
#: ``remat_widths`` on a TPU: ``scan_out`` is ``h dv + h dk dv 2 / chunk
#: + h 2 chunk`` two-byte units a token, 4096 + 16 384 + 4096
_CELLS = {
    "qwen3next80b": (BlockOptions(
        use_flash=True, head_dim=256, n_kv_heads=2,
        layer_types=("linear_attention",) * 3 + ("attention",),
        gdn_key_heads=16, gdn_value_heads=32), 512, 16,
        {"attn_out": 4128, "gdn_in": 12288, "scan_out": 24576}),
    "kimilinear48b": (BlockOptions(
        use_flash=True, gated_mlp=True,
        layer_types=("kda",) * 3 + ("latent_attention",),
        gdn_value_heads=32, latent_value_dim=128), 9216, 32,
        {"attn_out": 4160, "mlp_in": 18432, "kda_in": 12288,
         "latent_in": 6144, "scan_out": 24576}),
}


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the program asks to learn which form of the scan it runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("cell", list(_CELLS))
def test_the_width_is_there_only_where_the_kernels_run(cell, monkeypatch):
    options, d_ff, heads, widths = _CELLS[cell]
    of = lambda o=options, dtype=jnp.bfloat16: o.remat_widths(
        d_ff, heads, dtype)
    # off the TPU the XLA form runs: nothing of that name to keep
    assert "scan_out" not in of()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert of() == widths and list(of())[-1] == "scan_out"
    # float32 products, heads of 64, a chunk of 128: the XLA form
    assert "scan_out" not in of(dtype=jnp.float32)
    for field in ({"gdn_key_dim": 64}, {"gdn_value_dim": 64},
                  {"gdn_chunk": 128}):
        assert "scan_out" not in of(dataclasses.replace(options, **field))
    # no such layer, no such width
    assert "scan_out" not in of(dataclasses.replace(
        options, layer_types=("attention",)))


@pytest.mark.parametrize("cell", list(_CELLS))
def test_the_reserve_is_what_it_was_without_the_name(cell, on_a_tpu):
    """``remat_budget`` reserves ``REMAT_TEMPORARIES`` tensors of the
    widest *other* result: ``scan_out`` is wide by its float32
    residuals, and the step's temporaries do not grow with it."""
    options, d_ff, heads, _ = _CELLS[cell]
    widths = options.remat_widths(d_ff, heads)
    others = {k: w for k, w in widths.items() if k != "scan_out"}
    assert widths["scan_out"] > max(others.values())
    device = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": 16_909_336_064})
    state = [jax.ShapeDtypeStruct((1_000_000, 1000), jnp.float32,
                                  sharding=jax.sharding.SingleDeviceSharding(
                                      jax.devices()[0]))]
    budget = remat_budget(device, state, 16384, widths)
    assert budget == remat_budget(device, state, 16384, others) \
        == 16_909_336_064 - 4_000_000_000 - (1 << 30) \
        - transformer.REMAT_TEMPORARIES * 16384 * max(others.values()) * 2


_QWEN_KINDS = ("linear_attention",) * 3 + ("attention",)
_QWEN_WIDTHS = _CELLS["qwen3next80b"][3]
_REST = 16384 * (4128 + 3 * 12288) * 2   # attn_out x1, gdn_in x3
_LAYER = 16384 * 24576 * 2               # 805 MB: one layer's scan_out


@pytest.mark.parametrize("budget,layers", [
    (5_100_000_000, 3),              # the cell's budget: 3.76 GB spent
    (_REST + 3 * _LAYER, 3),
    (_REST + 3 * _LAYER - 1, 2),     # a byte short of the third layer's
    (_REST + 2 * _LAYER - 1, 1),
    (_REST + _LAYER - 1, 0),
], ids=["the_cells", "all", "two", "one", "none"])
def test_scan_out_is_spent_last_and_a_layer_at_a_time(budget, layers):
    """After every other name, and on the last layers first: the
    backward pass starts there, with every kept result still held."""
    plan = remat_plan(_QWEN_KINDS, 16384, _QWEN_WIDTHS, budget)
    assert [i for i, names in enumerate(plan) if "scan_out" in names] \
        == list(range(3 - layers, 3))
    # what the other names keep does not depend on it
    assert tuple(tuple(n for n in names if n != "scan_out")
                 for names in plan) == remat_plan(
        _QWEN_KINDS, 16384, _QWEN_WIDTHS, _REST)
    said, nbytes = remat_kept(plan, 16384, _QWEN_WIDTHS)
    assert nbytes == _REST + layers * _LAYER <= budget
    assert said == "attn_out x1, gdn_in x3" + (
        f", scan_out x{layers}" if layers else "")


def test_scan_out_displaces_no_other_name():
    """``kimilinear48b``'s layers (a dense one first) under a budget
    that holds the other names and one layer's ``scan_out``: the last
    KDA layer's, and every other name as without it."""
    options, d_ff, heads, widths = _CELLS["kimilinear48b"]
    kinds = [options.layer_type(i) for i in range(5)]
    dense = [True] + [False] * 4
    rest = 16384 * (4160 + 18432 + 4 * 12288 + 6144) * 2
    plan = remat_plan(kinds, 16384, widths, rest + 2 * _LAYER - 1,
                      dense=dense)
    assert remat_kept(plan, 16384, widths) == (
        "attn_out x1, mlp_in x1, kda_in x4, latent_in x1, scan_out x1",
        rest + _LAYER)
    assert plan[0] == ("mlp_in", "kda_in")
    assert plan[4] == ("kda_in", "scan_out")
    assert remat_kept(remat_plan(kinds, 16384, widths, rest + _LAYER - 1,
                                 dense=dense), 16384, widths) == (
        "attn_out x1, mlp_in x1, kda_in x4, latent_in x1", rest)


@pytest.mark.parametrize("kind", _RULES)
def test_a_model_keeps_scan_out_in_its_delta_rule_layers(kind, on_a_tpu):
    """``TransformerLM.remat_plan`` through ``model_remat_widths``."""
    from chainermn_tpu.models.transformer import TransformerLM

    options = dataclasses.replace(
        _OPTIONS[kind], layer_types=(kind, "attention"), remat_blocks=True)
    model = TransformerLM(
        vocab_size=64, d_model=_D_MODEL, n_heads=2, n_layers=2,
        d_ff=_D_FF, max_len=256, dtype=jnp.bfloat16, options=options)
    widths = transformer.model_remat_widths(model)
    assert widths["scan_out"] == 2 * 128 + 2 * 128 * 128 * 2 // 64 \
        + 2 * 2 * 64
    others = {k: w for k, w in widths.items() if k != "scan_out"}
    rest = remat_kept(remat_plan((kind, "attention"), 256, others, 1 << 40),
                      256, others)[1]
    enough = rest + 256 * widths["scan_out"] * 2
    plan = model.clone(options=dataclasses.replace(
        options, remat_budget_bytes=enough)).remat_plan(256)
    assert "scan_out" in plan[0] and "scan_out" not in plan[1]
    short = model.clone(options=dataclasses.replace(
        options, remat_budget_bytes=enough - 1)).remat_plan(256)
    assert not any("scan_out" in names for names in short)
