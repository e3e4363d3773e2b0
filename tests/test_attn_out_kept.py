"""The attention kernels' result kept across a block's recomputation.

``ops/pallas_attention.py``'s forward rules name the pair the backward
kernels read (``out`` and its log-sum-exp) ``attn_out``; a block whose
plan lists the name (``models/transformer.py``: ``REMAT_NAMES``,
``remat_plan``) keeps the pair, and its recomputation holds no forward
launch.  Interpreted kernels at tiny shapes; nothing is timed.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import transformer
from chainermn_tpu.models.transformer import (
    REMAT_NAMES,
    BlockOptions,
    TransformerBlock,
    block_under_plan,
    remat_kept,
    remat_plan,
)
from chainermn_tpu.ops import pallas_attention as pa

_HEADS, _D_MODEL, _D_FF, _TOKENS = 4, 64, 128, (2, 128)
_OPTIONS = {
    # grouped-query heads of 16 with rotary positions
    "attention": BlockOptions(
        norm="rmsnorm", n_kv_heads=2, rope_theta=10000.0, gated_mlp=True,
        use_flash=True),
    # keys of 32 + 16, values of 32, the shared channels rotated
    "latent_attention": BlockOptions(
        norm="rmsnorm", rope_theta=10000.0, gated_mlp=True, use_flash=True,
        layer_types=("latent_attention",), latent_kv_rank=32,
        latent_nope_dim=32, latent_shared_dim=16, latent_value_dim=32),
}


def _launches(jaxpr, found=None) -> dict:
    """``pallas_call``s of ``jaxpr`` (every level of it) by kernel name."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _launches(sub, found)
    return found


def _gradients_and_launches(f, *args):
    """The gradient of ``f`` in both arguments, traced once: its value
    (compiled) and the launches its jaxpr holds."""
    traced = jax.jit(jax.grad(f, argnums=(0, 1))).trace(*args)
    return (traced.lower().compile()(*args),
            _launches(traced.jaxpr.jaxpr))


def _same_bits(a, b):
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(a)) == len(leaves(b))
    for x, y in zip(leaves(a), leaves(b)):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module", params=list(_OPTIONS))
def block_gradients(request):
    """Gradients (parameters and input) and the gradient's launches of
    one block of the kind: as it is, recomputed with nothing kept, and
    recomputed with ``attn_out`` kept."""
    kind = request.param
    fields = dict(n_heads=_HEADS, d_ff=_D_FF, dtype=jnp.float32,
                  options=_OPTIONS[kind], kind=kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (*_TOKENS, _D_MODEL))
    forms = {
        "unrecomputed": TransformerBlock(**fields),
        "recomputed": block_under_plan(TransformerBlock, (), 0)(**fields),
        "kept": block_under_plan(TransformerBlock, ("attn_out",), 0)(
            **fields),
    }
    params = forms["unrecomputed"].init(jax.random.PRNGKey(1), x)
    out = {}
    for form, block in forms.items():
        out[form] = _gradients_and_launches(
            lambda p, x: jnp.sum(block.apply(p, x) ** 2), params, x)
    return out


def test_a_kept_result_changes_no_bit_of_a_blocks_gradient(block_gradients):
    """The same kernels on the same operands, one of them run once."""
    kept, recomputed, plain = (block_gradients[form][0] for form in (
        "kept", "recomputed", "unrecomputed"))
    _same_bits(kept, recomputed)
    _same_bits(kept, plain)
    assert all(bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(kept))


def test_a_kept_result_takes_the_forward_launch_off_the_recomputation(
        block_gradients):
    """Gone from the gradient's jaxpr, not merely unused: a forward, dq
    and dk/dv with the name kept; the forward twice without it."""
    once = {"_bdflash_forward": 1, "_bdflash_backward_dq": 1,
            "_bdflash_backward_dkdv": 1}
    assert block_gradients["unrecomputed"][1] == once
    assert block_gradients["recomputed"][1] \
        == {**once, "_bdflash_forward": 2}
    assert block_gradients["kept"][1] == once


# -- the causal family --------------------------------------------------------
_CAUSAL = {
    "flash_attention": lambda q, k, v: pa.flash_attention(
        q, k, v, True, interpret=True),
    "flash_attention_with_lse": lambda q, k, v: (
        lambda out, lse: out + lse[..., None])(
        *pa.flash_attention_with_lse(q, k, v, True, interpret=True)),
}


@pytest.fixture(scope="module", params=list(_CAUSAL))
def causal_gradients(request):
    """``(gradients, launches)`` of the causal family's function behind
    a projection (so that something is recomputed): under no
    ``jax.checkpoint``, under one with no policy, under one whose
    policy saves ``attn_out``."""
    attend = _CAUSAL[request.param]
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(keys[0], (1, 128, 2 * 32))
    w = jax.random.normal(keys[1], (2 * 32, 3 * 2 * 32)) * 0.1

    def layer(x, w):
        q, k, v = jnp.split((x @ w).reshape(1, 128, 2, 3 * 32), 3, axis=-1)
        return jnp.sum(jnp.tanh(attend(q, k, v)) ** 2)

    policy = jax.checkpoint_policies.save_only_these_names("attn_out")
    forms = {"unrecomputed": layer, "recomputed": jax.checkpoint(layer),
             "kept": jax.checkpoint(layer, policy=policy)}
    return {form: _gradients_and_launches(f, x, w)
            for form, f in forms.items()}


def test_the_causal_familys_kept_result_changes_no_bit(causal_gradients):
    _same_bits(causal_gradients["kept"][0],
               causal_gradients["recomputed"][0])
    _same_bits(causal_gradients["kept"][0],
               causal_gradients["unrecomputed"][0])


def test_the_causal_familys_forward_launches_once_with_the_name_kept(
        causal_gradients):
    once = {"_flash_forward": 1, "_flash_backward_dq": 1,
            "_flash_backward_dkdv": 1}
    assert causal_gradients["unrecomputed"][1] == once
    assert causal_gradients["recomputed"][1] \
        == {**once, "_flash_forward": 2}
    assert causal_gradients["kept"][1] == once


@pytest.mark.parametrize("width,kept_as", [
    (32, (1, 128, 64)),        # narrower than the lanes: heads side by side
    (128, (1, 128, 2, 128)),
], ids=["narrow_heads", "whole_lanes"])
def test_the_name_is_one_for_both_results(width, kept_as):
    """A policy cannot keep the output without its log-sum-exp; heads
    narrower than 128 lanes are kept unpadded."""
    assert pa.ATTN_OUT == "attn_out" == REMAT_NAMES[0]
    q = jnp.ones((1, 128, 2, width))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q: pa.block_causal_attention_with_lse(
            q, q, q, 1, interpret=True)[0].sum()))(q)
    named = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "name"]
    assert [eqn.params["name"] for eqn in named] == ["attn_out"] * 2
    assert sorted((eqn.outvars[0].aval.shape for eqn in named), key=len) \
        == [(2, 128), kept_as]


@pytest.mark.parametrize("attend", [
    lambda q: pa.flash_attention(q, q, q, True, interpret=True),
    lambda q: pa.block_causal_attention_with_lse(
        q, q, q, 4, interpret=True)[0],
], ids=["causal", "block_causal"])
def test_outside_a_policy_the_name_lowers_to_nothing(attend, monkeypatch):
    """``sdar30b`` and the two ``cgpt590m`` cells recompute no block:
    their gradient lowers to the text it lowered to without the name
    (but for the ordinals JAX numbers its private functions with), at
    their heads of 128."""
    import re

    q = jnp.ones((1, 256, 2, 128), jnp.bfloat16)

    def text():
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.grad(
            lambda q: attend(q).astype(jnp.float32).sum())).lower(
            q).as_text())

    named = text()
    monkeypatch.setattr(pa, "_named", lambda out, lse: (out, lse))
    assert named == text()


# -- the plan -----------------------------------------------------------------
#: ``remat_widths(d_ff, n_heads, d_model=...)`` of the four cells that
#: recompute their blocks -> ``attn_out``'s width (``h dv + 2 h``)
_CELLS = {
    "moonlight16b": (BlockOptions(
        use_flash=True, layer_types=("latent_attention",),
        latent_value_dim=128), 16, 2048, 2080),
    "kimilinear48b": (BlockOptions(
        use_flash=True, layer_types=("kda",) * 3 + ("latent_attention",),
        gdn_value_heads=32, latent_value_dim=128), 32, 2304, 4160),
    "qwen3next80b": (BlockOptions(
        use_flash=True, head_dim=256, n_kv_heads=2,
        layer_types=("linear_attention",) * 3 + ("attention",),
        gdn_key_heads=16, gdn_value_heads=32), 16, 2048, 4128),
    # no head_dim: d_model / n_heads
    "granite4hmicro": (BlockOptions(
        use_flash=True, n_kv_heads=8, ssm_heads=64,
        layer_types=("mamba",) * 5 + ("attention",)), 32, 2048, 2112),
}


@pytest.mark.parametrize("cell", list(_CELLS))
def test_the_width_is_the_heads_values_and_their_log_sum_exps(cell):
    options, heads, d_model, width = _CELLS[cell]
    widths = options.remat_widths(512, heads, d_model=d_model)
    assert list(widths)[0] == "attn_out" and widths["attn_out"] == width
    # no kernel, no heads known, or two launches under the one name:
    # nothing to keep
    for other in (dataclasses.replace(options, use_flash=False),
                  dataclasses.replace(options, block_diffusion=4)):
        assert "attn_out" not in other.remat_widths(512, heads,
                                                    d_model=d_model)
    assert "attn_out" not in options.remat_widths(512)


def test_a_model_without_attention_layers_has_no_attn_out():
    o = BlockOptions(use_flash=True, layer_types=("mamba",), ssm_heads=4)
    assert "attn_out" not in o.remat_widths(64, 4, d_model=64)


_KINDS = ("kda", "latent_attention", "mamba", "attention",
          "linear_attention", "latent_attention")
_WIDTHS = {"attn_out": 2080, "mlp_in": 4096, "latent_in": 3072}
_LAYER = 16384 * 2080 * 2  # tokens x (h dv + 2 h) x 2 bytes


@pytest.mark.parametrize("budget,layers", [
    (0, []),
    (_LAYER - 1, []),            # a byte short of one layer's result
    (_LAYER, [1]),
    (3 * _LAYER - 1, [1, 3]),    # ... of the third's: its forward recomputed
    (3 * _LAYER, [1, 3, 5]),
], ids=["none", "a_byte_short", "one", "two_and_a_byte_short", "all"])
def test_attn_out_is_spent_first_and_on_attention_layers_only(budget, layers):
    plan = remat_plan(_KINDS, 16384, _WIDTHS, budget)
    assert [i for i, names in enumerate(plan) if "attn_out" in names] \
        == layers
    # nothing else while attn_out has not had its fill
    assert all(set(names) <= {"attn_out"} for names in plan)
    said, nbytes = remat_kept(plan, 16384, _WIDTHS)
    assert nbytes == len(layers) * _LAYER <= budget
    assert said == (f"attn_out x{len(layers)}" if layers else "")


def test_the_other_names_follow_in_their_order():
    cost = lambda name: 16384 * _WIDTHS[name] * 2
    budget = 3 * _LAYER + cost("mlp_in") + cost("latent_in")
    plan = remat_plan(_KINDS, 16384, _WIDTHS, budget)
    assert plan == (("mlp_in",), ("attn_out", "latent_in"), (),
                    ("attn_out",), (), ("attn_out",))
    assert remat_kept(plan, 16384, _WIDTHS) == (
        "attn_out x3, mlp_in x1, latent_in x1", budget)
    # one policy object a set of names, as for the other names
    assert transformer._keep(plan[1]) is transformer._keep(
        ("attn_out", "latent_in"))


def test_a_model_keeps_attn_out_in_its_attention_layers():
    """``TransformerLM.remat_plan`` through ``model_remat_widths``: the
    model's heads and width reach the plan."""
    from chainermn_tpu.models.transformer import TransformerLM

    options = dataclasses.replace(
        _OPTIONS["attention"], layer_types=("mamba", "attention"),
        ssm_heads=4, ssm_head_dim=16, ssm_state=16, remat_blocks=True,
        remat_budget_bytes=256 * (_D_MODEL + 2 * _HEADS) * 4)
    model = TransformerLM(
        vocab_size=64, d_model=_D_MODEL, n_heads=_HEADS, n_layers=2,
        d_ff=_D_FF, max_len=128, dtype=jnp.float32, options=options)
    assert transformer.model_remat_widths(model)["attn_out"] \
        == _D_MODEL + 2 * _HEADS
    assert model.remat_plan(256) == ((), ("attn_out",))
    short = dataclasses.replace(
        options, remat_budget_bytes=options.remat_budget_bytes - 1)
    assert model.clone(options=short).remat_plan(256) == ((), ())
