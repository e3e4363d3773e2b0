"""Mamba-2 / attention hybrid on ``TransformerLM``: the chunked scan
against the position-by-position recurrence, the causal convolution, the
block-causal kernels at head width 64 with a given scale, the model
against the plain reference (``cellbench/reference/granite_hybrid.py``),
the cut's tie to the model, the configuration's widths and the
benchmark's operation counts.  CPU, seeded, small; Pallas interpreted
only where a kernel is under test."""

import json
import os
import sys

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import compare, flops_granite  # noqa: E402
from cellbench.reference import granite_hybrid as ref  # noqa: E402
from cellbench.runners import train_hybrid  # noqa: E402
from chainermn_tpu.models import transformer  # noqa: E402
from chainermn_tpu.models.transformer import (  # noqa: E402
    BlockOptions,
    GatedMlp,
    Mamba2Mixer,
    TransformerLM,
    lm_loss,
    remat_kept,
    remat_plan,
)
from chainermn_tpu.ops import chunked_lm_loss  # noqa: E402
from chainermn_tpu.ops import pallas_attention as pa  # noqa: E402
from chainermn_tpu.ops import ssd_scan as ssd  # noqa: E402

with open(os.path.join(ROOT, "cellbench", "configs",
                       "granite-4.0-h-micro.json")) as _f:
    CONFIG = json.load(_f)
#: the configuration's rehearsal sizes
SMALL = {k: v for k, v in {**CONFIG, **CONFIG["rehearse"]}.items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}
SMALL["layer_types"] = tuple(CONFIG["rehearse"]["layer_types"])


def options_of(cfg, **kw) -> BlockOptions:
    """The block's options the example builds from ``cfg``'s sizes."""
    return BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"],
        attention_scale=cfg["attention_multiplier"],
        layer_types=tuple(cfg["layer_types"]),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], gated_mlp=True,
        no_positions=True,
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], **kw)


def model_of(cfg, dtype=jnp.float32, **kw) -> TransformerLM:
    return TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=128, dtype=dtype, options=options_of(cfg, **kw))


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# -- the scan ----------------------------------------------------------------
def _scan_inputs(s, rate, b=2, h=4, p=8, n=16, seed=0):
    """Inputs whose decay a position, ``exp(dt A)``, lies near ``rate``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.random.uniform(k[1], (b, s, h), jnp.float32, 0.5, 1.5)
    A = jnp.log(rate) * jax.random.uniform(k[2], (h,), jnp.float32, 0.8,
                                           1.2)
    return (jax.random.normal(k[0], (b, s, h, p)), dt, A,
            jax.random.normal(k[3], (b, s, n)),
            jax.random.normal(k[4], (b, s, n)),
            jax.random.normal(k[5], (h,)))


def _recurrence(x, dt, A, B, C, D):
    """The reference's position-by-position recurrence, a sequence of
    the batch at a time."""
    return jax.vmap(ref.recurrence, in_axes=(0, 0, None, 0, 0, None))(
        x, dt, A, B, C, D)


def _scan32(*args):
    return ssd.ssd_scan(*args, chunk=32, dtype=jnp.float32)


@jax.jit
def _values_and_gradients(weigh, *args):
    """Both forms' results and all their gradients (of a weighted sum)."""
    return tuple(
        (f(*args), jax.grad(lambda *a: (f(*a) * weigh).sum(),
                            argnums=range(6))(*args))
        for f in (_scan32, _recurrence))


@pytest.mark.parametrize("rate", [0.999, 0.5, 1e-4],
                         ids=["decay_near_1", "decay_half", "decay_near_0"])
@pytest.mark.parametrize("s", [64, 40, 100],
                         ids=["on_boundary", "under_a_chunk", "over_a_chunk"])
def test_chunked_scan_is_the_recurrence_value_and_gradients(s, rate):
    args = _scan_inputs(s, rate)
    weigh = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)
    (got, grads), (want, wanted) = _values_and_gradients(weigh, *args)
    assert _max_rel(got, want) < 2e-5
    for name, g, w in zip("x dt A B C D".split(), grads, wanted):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        assert _max_rel(g, w) < 1e-3, name


def test_chunked_scan_in_bfloat16_stays_near_the_recurrence():
    args = _scan_inputs(96, 0.9)
    x16 = args[0].astype(jnp.bfloat16)
    got = ssd.ssd_scan(x16, *args[1:], chunk=32)
    assert got.dtype == jnp.bfloat16
    want = _recurrence(x16.astype(jnp.float32), *args[1:])
    assert _max_rel(got.astype(jnp.float32), want) < 0.02


def test_padding_rows_leave_every_state_as_it_was():
    """A sequence cut short gives the rows of the whole one: what the
    padding adds (``dt = 0``: decay 1, no input) changes no state."""
    args = _scan_inputs(64, 0.9)
    whole = _scan32(*args)
    cut = _scan32(*(a[:, :50] if a.ndim > 1 else a for a in args))
    np.testing.assert_allclose(cut, whole[:, :50], rtol=1e-5, atol=1e-5)


def test_scan_census_counts_the_algorithms_matmuls():
    got = ssd.ssd_census(8192, 256, 64, 64, 128)
    assert (got["chunks"], got["padded"]) == (32, 8192)
    assert got["flops"]["scores"] == 2 * 32 * 256 * 256 * 128
    assert got["flops"]["inside"] == 2 * 32 * 256 * 256 * 4096
    assert got["flops"]["states"] == got["flops"]["carried"] \
        == 2 * 32 * 256 * 4096 * 128
    assert got["flops_backward"] == 2 * got["flops_forward"] \
        + got["flops"]["scores"]
    assert got["bytes_forward"] == 8192 * (2 * 4096 * 2 + 2 * 128 * 2
                                           + 4 * 64)
    assert ssd.ssd_census(100, 32, 4, 8, 16)["padded"] == 128
    # the benchmark's count, made apart from the program, is the same
    cfg = {k: CONFIG[k] for k in ("mamba_chunk_size", "mamba_d_state",
                                  "mamba_n_heads", "mamba_d_head")}
    assert flops_granite.ssd_parts(cfg, 8192) == got["flops"]
    assert flops_granite.ssd_flops(cfg, 8192, "bwd") == got["flops_backward"]
    assert flops_granite.ssd_bytes(cfg, 8192, "fwd") == got["bytes_forward"]


# -- the convolution ---------------------------------------------------------
def test_causal_convolution_is_the_shifted_sum_and_xlas_grouped_one():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (2, 37, 12))
    taps = jax.random.normal(k[1], (4, 12))
    bias = jax.random.normal(k[2], (12,))
    got = ssd.causal_conv1d(x, taps, bias)
    want = np.zeros(x.shape, np.float64) + np.asarray(bias)
    for j in range(4):  # tap j meets x shifted 3 - j positions later
        shift = 3 - j
        want[:, shift:] += np.asarray(taps[j]) * np.asarray(
            x[:, :x.shape[1] - shift])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grouped = lax.conv_general_dilated(
        x, taps[:, None, :], (1,), [(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=12,
        precision=lax.Precision.HIGHEST) + bias
    np.testing.assert_allclose(got, grouped, rtol=1e-5, atol=1e-5)
    # causal: a later position changes no earlier result
    moved = ssd.causal_conv1d(x.at[:, 20].add(1.0), taps, bias)
    np.testing.assert_array_equal(moved[:, :20], got[:, :20])
    # with the activation inside (PR 45), and over a column range of a
    # wider x: the same values, bit for bit
    np.testing.assert_array_equal(
        ssd.causal_conv1d(x, taps, bias, silu=True), jax.nn.silu(got))
    wide = jnp.concatenate([x[..., :5] + 1.0, x, x[..., :3] - 1.0], axis=-1)
    np.testing.assert_array_equal(
        ssd.causal_conv1d(wide, taps, bias, silu=True, first_column=5),
        jax.nn.silu(got))
    np.testing.assert_array_equal(
        ssd.causal_conv1d(wide, taps, bias, first_column=5), got)


# -- the kernels at head width 64 --------------------------------------------
def test_block_causal_kernels_at_head_width_64_with_a_given_scale():
    """4 query heads a key/value head, ``scale = 1 / 64`` (not ``64 **
    -0.5``): forward and all three gradients against the dense form."""
    b, s, hq, hkv, d, scale = 1, 128, 8, 2, 64, 1.0 / 64
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(k[0], (b, s, hq, d)) * 3
    kk = jax.random.normal(k[1], (b, s, hkv, d)) * 3
    v = jax.random.normal(k[2], (b, s, hkv, d))
    weigh = jax.random.normal(k[3], (b, s, hq, d))

    def dense(q, kk, v):
        rep = lambda t: jnp.repeat(t, hq // hkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, rep(kk)) * scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                          rep(v))

    def kernel(q, kk, v):
        return pa.block_causal_attention_with_lse(
            q, kk, v, 1, scale=scale, block_size=64, interpret=True)[0]

    assert _max_rel(kernel(q, kk, v), dense(q, kk, v)) < 1e-5
    # the default scale is another function: the argument is taken
    assert _max_rel(pa.block_causal_attention_with_lse(
        q, kk, v, 1, block_size=64, interpret=True)[0],
        dense(q, kk, v)) > 1e-2
    got = jax.grad(lambda *a: (kernel(*a) * weigh).sum(), (0, 1, 2))(
        q, kk, v)
    want = jax.grad(lambda *a: (dense(*a) * weigh).sum(), (0, 1, 2))(
        q, kk, v)
    for name, g, w in zip("q k v".split(), got, want):
        assert _max_rel(g, w) < 1e-4, name


# -- the model against the reference -----------------------------------------
OPTIMIZER = {"lr": 1e-4, "weight_decay": 0.01}


@pytest.fixture(scope="module")
def seeded():
    cfg = SMALL
    weights = ref.init_weights(ref.seed_key(3), cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 96), dtype=np.int32)
    return cfg, weights, train_hybrid.program_tree(ref, weights, cfg), tokens


@pytest.fixture(scope="module")
def whole(seeded):
    """Loss and gradient of the whole reference model by autodiff."""
    cfg, weights, _, tokens = seeded
    return jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(weights)


@pytest.fixture(scope="module")
def sound(seeded):
    """What the reference reads over the first step of ``seeded``."""
    cfg, _, _, tokens = seeded
    return ref.train_readings(3, cfg, tokens[None], OPTIMIZER)


def test_reference_matches_the_model_in_float32(seeded, whole):
    """Logits, loss and every gradient leaf, the program's model in
    float32 with the dense attention; the float8 control apart."""
    cfg, weights, tree, tokens = seeded
    model = model_of(cfg)
    logits_of = jax.jit(lambda w, lowp: jax.vmap(
        lambda t: ref.logits_fn(w, t, cfg, lowp))(tokens),
        static_argnums=1)
    logits = jax.jit(model.apply)(tree, tokens)
    want = logits_of(weights, False)
    assert _max_rel(logits, want) < 1e-4
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(model.apply(p, tokens), tokens)))(tree)
    ref_loss, ref_grads = whole
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = train_hybrid.keyed_leaves(ref, grads, cfg)
    assert set(got) == set(ref_grads) == set(ref.leaf_keys(cfg))
    for name in got:
        assert _max_rel(got[name], ref_grads[name]) < 2e-3, name
    assert _max_rel(logits_of(weights, True), want) \
        > 30 * _max_rel(logits, want)


def test_the_step_as_the_cell_runs_it_is_correct_and_the_control_is_not(
        seeded, sound):
    """bfloat16, the flash kernels, per-block recomputation and the
    chunked head, as the example builds them: loss, first gradient and
    first AdamW step against ``train_readings`` through the comparison
    that decides ``correct``, at the rehearsal's limits; the float8
    control fails it.  Over 40 s in the driver's run (the step's program
    and the reference's control, a layer at a time): the only in-process
    case on the step as the cell builds it, and the only one that shows
    the comparison can fail."""
    cfg, _, tree, tokens = seeded
    model = model_of(cfg, dtype=jnp.bfloat16, use_flash=True,
                     remat_blocks=True)
    opt = optax.adamw(OPTIMIZER["lr"],
                      weight_decay=OPTIMIZER["weight_decay"])
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: chunked_lm_loss(model, p, tokens,
                                  cfg["head_chunks"])))(tree)
    updates, _ = opt.update(grads, opt.init(tree), tree)
    keyed = lambda t: train_hybrid.keyed_leaves(ref, t, cfg)
    norm = lambda t: {k: float(jnp.linalg.norm(x))
                      for k, x in keyed(t).items()}
    program = {"losses": [float(loss)], "grad_norms": norm(grads),
               "grad_small": {k: np.asarray(x) for k, x in
                              keyed(grads).items() if x.size <= ref.SMALL},
               "delta_norms": norm(updates)}
    limits = CONFIG["rehearse"]["correct"]
    verdict = compare.decide(program, sound, limits)
    assert verdict["correct"], verdict["lines"]
    control = ref.train_readings(3, cfg, tokens[None], OPTIMIZER,
                                 lowp=True)
    assert not compare.decide(control, sound, limits)["correct"]


def test_train_readings_a_layer_at_a_time_is_the_whole_models_gradient(
        seeded, whole, sound):
    cfg, weights, _, _ = seeded
    loss, g = whole
    new, _, _ = ref._adamw(weights, *(jax.tree_util.tree_map(
        jnp.zeros_like, weights),) * 2, g, 1.0, **{
            "lr": OPTIMIZER["lr"], "wd": OPTIMIZER["weight_decay"]})
    assert sound["losses"][0] == pytest.approx(float(loss), rel=1e-6)
    assert set(sound["grad_norms"]) == set(g)
    for k in g:
        assert sound["grad_norms"][k] == pytest.approx(
            float(jnp.linalg.norm(g[k])), rel=1e-4), k
        assert sound["delta_norms"][k] == pytest.approx(
            float(jnp.linalg.norm(new[k] - weights[k])), rel=1e-3), k
    assert set(sound["grad_small"]) == {k for k in g
                                        if g[k].size <= ref.SMALL}
    np.testing.assert_allclose(sound["grad_small"]["a_log.0"],
                               g["a_log.0"], rtol=1e-4, atol=1e-9)


# -- the options on TransformerLM --------------------------------------------
def test_default_options_give_the_parents_parameter_tree():
    """GPT-2's block: a position table, fused qkv, GELU MLP with biases,
    LayerNorm; the same leaves with and without the field."""
    tokens = jnp.zeros((1, 16), jnp.int32)
    kw = dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2, max_len=16)
    plain = jax.eval_shape(TransformerLM(**kw).init,
                           jax.random.PRNGKey(0), tokens)
    shapes = jax.tree_util.tree_map(lambda x: x.shape, plain)["params"]
    block = {"LayerNorm_0": {"bias": (16,), "scale": (16,)},
             "LayerNorm_1": {"bias": (16,), "scale": (16,)},
             "SelfAttention_0": {"Dense_0": {"kernel": (16, 48)},
                                 "Dense_1": {"kernel": (16, 16)}},
             "MlpBlock_0": {"Dense_0": {"kernel": (16, 64), "bias": (64,)},
                            "Dense_1": {"kernel": (64, 16),
                                        "bias": (16,)}}}
    assert shapes == {"embed": {"embedding": (32, 16)},
                      "pos_embed": (16, 16),
                      "LayerNorm_0": {"bias": (16,), "scale": (16,)},
                      "TransformerBlock_0": block,
                      "TransformerBlock_1": block}
    given = jax.eval_shape(
        TransformerLM(options=BlockOptions(), **kw).init,
        jax.random.PRNGKey(0), tokens)
    assert jax.tree_util.tree_structure(given) \
        == jax.tree_util.tree_structure(plain)


def test_recomputation_changes_neither_the_tree_nor_the_result(seeded):
    cfg, _, tree, tokens = seeded
    plain, remat = model_of(cfg), model_of(cfg, remat_blocks=True)
    shape = lambda m: jax.eval_shape(m.init, jax.random.PRNGKey(0), tokens)
    assert jax.tree_util.tree_structure(shape(remat)) \
        == jax.tree_util.tree_structure(shape(plain))
    loss = lambda m: jax.jit(jax.value_and_grad(
        lambda p: lm_loss(m.apply(p, tokens), tokens)))(tree)
    (l0, g0), (l1, g1) = loss(plain), loss(remat)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-8)


# -- what a block keeps across its recomputation -----------------------------
#: the cell's sizes: one 8192-token sequence, ``[g | u]`` 2 x 8192 wide,
#: ``[z | xBC | dt]`` 2 x 4096 + 2 x 128 + 64
_CELL_WIDTHS = {"mlp_in": 16384, "ssm_in": 8512}
_MAMBA = [i for i, kind in enumerate(CONFIG["layer_types"])
          if kind == "mamba"]


@pytest.mark.parametrize("tokens,budget,mlp_layers,ssm_layers", [
    (8192, 0, [], []),
    (8192, 139_460_607, [], []),           # a byte short of one ssm_in
    (8192, 268_435_455, [], [0]),          # ... of one mlp_in: one ssm_in
    (8192, 2_700_000_000, range(10), []),  # 10 x 268 MB, 15.6 MB over
    (8192, 3_600_000_000, range(10), _MAMBA[:6]),  # + 6 x 139 MB
    (8192, 4_000_000_000, range(10), _MAMBA),      # all 19: 3.94 GB
    (16384, 2_700_000_000, range(5), []),  # two sequences: half of them
    (16384, 4_100_000_000, range(7), [0]),  # 7 x 537 MB, then one 279 MB
], ids=["none", "a_byte_short", "one_mixer", "mlp", "mlp_and_six", "all",
        "batch2", "batch2_more"])
def test_remat_plan_by_hand(tokens, budget, mlp_layers, ssm_layers):
    plan = remat_plan(CONFIG["layer_types"], tokens, _CELL_WIDTHS, budget)
    assert [i for i, names in enumerate(plan) if "mlp_in" in names] \
        == list(mlp_layers)
    assert [i for i, names in enumerate(plan) if "ssm_in" in names] \
        == list(ssm_layers)
    assert all(set(names) <= {"mlp_in", "ssm_in"} for names in plan)
    assert plan[5] in ((), ("mlp_in",))  # the attention layer has no ssm_in
    said, nbytes = remat_kept(plan, tokens, _CELL_WIDTHS)
    assert nbytes == tokens * 2 * (16384 * len(mlp_layers)
                                   + 8512 * len(ssm_layers)) <= budget
    assert said == ", ".join(
        f"{name} x{len(layers)}" for name, layers in
        (("mlp_in", mlp_layers), ("ssm_in", ssm_layers)) if len(layers))


def test_remat_plan_names_only_what_the_model_has():
    """No gated MLP: no ``mlp_in``; no mamba layer: nothing at all."""
    kinds = ["mamba", "attention"]
    assert remat_plan(kinds, 64, {"ssm_in": 100}, 1 << 30) \
        == (("ssm_in",), ())
    assert remat_plan(["attention"] * 2, 64, {}, 1 << 30) == ((), ())
    assert BlockOptions(gated_mlp=True).remat_widths(32) == {"mlp_in": 64}
    assert BlockOptions().remat_widths(32) == {}
    assert options_of(CONFIG).remat_widths(CONFIG["intermediate_size"]) \
        == _CELL_WIDTHS
    # layers that keep the same names share one policy object (JAX caches
    # a block's derived inner programs under it: a fresh one a layer
    # cost 3.2 s of the cell's set-up), and no names is no policy
    assert transformer._keep(("mlp_in", "ssm_in")) \
        is transformer._keep(("mlp_in", "ssm_in"))
    assert transformer._keep(()) is None


def _loss_and_gradient(model, tree, tokens):
    """Operation by operation: a compiled program's fusions, and with
    them its float32 roundings, depend on what else is in it."""
    with jax.disable_jit():
        return jax.value_and_grad(
            lambda p: lm_loss(model.apply(p, tokens), tokens))(tree)


@pytest.fixture(scope="module")
def plainly_recomputed(seeded):
    cfg, _, tree, tokens = seeded
    return _loss_and_gradient(model_of(cfg, remat_blocks=True), tree,
                              tokens)


def _in_proj_products(model, tree, tokens) -> dict:
    """``dot_general``s of the gradient's jaxpr (every level of it)
    under the gated MLP's and the mixer's scopes with a ``(b, s, width
    of the in_proj result)`` result: the forward ``in_proj`` products,
    each twice where a block's forward is computed again."""
    widths = model.options.remat_widths(model.d_ff)
    scopes = {"mlp_in": transformer.GATED_MLP_SCOPE,
              "ssm_in": transformer.SSM_MIXER_SCOPE}
    found = dict.fromkeys(widths, 0)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            shape = eqn.outvars[0].aval.shape
            for name, width in widths.items():
                found[name] += eqn.primitive.name == "dot_general" \
                    and len(shape) == 3 and shape[-1] == width \
                    and scopes[name] in str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(
        lambda p: lm_loss(model.apply(p, tokens), tokens)))(tree).jaxpr)
    return found


#: ``seeded``'s sizes: 192 tokens in float32, ``mlp_in`` 256 wide in all
#: three layers, ``ssm_in`` 328 wide in the two mamba layers
_MLP_IN, _SSM_IN = 192 * 256 * 4, 192 * 328 * 4


@pytest.mark.parametrize("budget,plan", [
    (_MLP_IN, (("mlp_in",), (), ())),
    (3 * _MLP_IN + _SSM_IN, (("mlp_in", "ssm_in"), ("mlp_in",),
                             ("mlp_in",))),
    (1 << 30, (("mlp_in", "ssm_in"), ("mlp_in",), ("mlp_in", "ssm_in"))),
], ids=["one_layer", "mlp_and_one_mixer", "all"])
def test_kept_results_change_no_bit_and_save_their_products(
        seeded, plainly_recomputed, budget, plan):
    """With a plan that keeps something: the parameter tree, the loss
    and every gradient are the plain recomputation's to the last bit
    (the same operations on the same values, run one by one), and the
    gradient runs one ``in_proj`` product a kept layer where the plain
    recomputation runs two.  (The first case pays ``plainly_recomputed``
    for all three, over 40 s in the driver's run: bit identity needs
    both sides run one operation at a time, which no compiled form
    gives.)"""
    cfg, _, tree, tokens = seeded
    plain = model_of(cfg, remat_blocks=True)
    kept = model_of(cfg, remat_blocks=True, remat_budget_bytes=budget)
    assert kept.remat_plan(tokens.size) == plan
    shape = lambda m: jax.eval_shape(m.init, jax.random.PRNGKey(0), tokens)
    assert jax.tree_util.tree_structure(shape(kept)) \
        == jax.tree_util.tree_structure(shape(plain))
    (l0, g0), (l1, g1) = plainly_recomputed, _loss_and_gradient(
        kept, tree, tokens)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(a, b)
    assert _in_proj_products(plain, tree, tokens) \
        == {"mlp_in": 2 * 3, "ssm_in": 2 * 2}
    assert _in_proj_products(kept, tree, tokens) == {
        name: 2 * n - sum(name in names for names in plan)
        for name, n in (("mlp_in", 3), ("ssm_in", 2))}


def test_no_budget_lowers_to_the_plain_recomputation(seeded, monkeypatch):
    """Budget 0 hands ``nn.remat`` no policy, and a name is an identity
    outside one: the lowered text is that of a model without the names
    (but for the ordinals JAX numbers its private functions with), with
    recomputation and without it."""
    import re

    cfg, _, tree, tokens = seeded

    def texts():
        return [re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.grad(
            lambda p: lm_loss(model_of(cfg, **kw).apply(p, tokens), tokens)
        )).lower(tree).as_text()) for kw in ({"remat_blocks": True}, {})]

    named = texts()
    monkeypatch.setattr(transformer, "checkpoint_name",
                        lambda x, name: x)
    assert named == texts()
    assert named[0] != named[1]


def test_the_mixer_and_the_gated_mlp_hold_the_published_leaves():
    """A Mamba-2 layer of the published sizes: 76 182 976 parameters
    with its MLP and two norms, the issue's count."""
    o = options_of({**CONFIG, "layer_types": ("mamba",)})
    x = jax.ShapeDtypeStruct((1, 8, 2048), jnp.bfloat16)
    count = lambda m: sum(int(np.prod(v.shape)) for v in
                          jax.tree_util.tree_leaves(jax.eval_shape(
                              m.init, jax.random.PRNGKey(0), x)))
    mixer = count(Mamba2Mixer(o))
    assert mixer == 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 \
        + 4096 * 2048
    assert count(GatedMlp(8192)) == 3 * 2048 * 8192
    assert mixer + 3 * 2048 * 8192 + 2 * 2048 == 76_182_976
    assert flops_granite.mamba_weights(CONFIG) == 2048 * 8512 + 4096 * 2048


@pytest.mark.parametrize("field", ["seq_axis", "tp_axis", "decode"])
def test_the_mixer_is_single_device(field):
    o = options_of(SMALL)
    mixer = Mamba2Mixer(o, **{field: True if field == "decode" else "x"})
    with pytest.raises(ValueError, match="single-device"):
        mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


# -- the tie of the cut to the model -----------------------------------------
def test_the_loss_over_a_slice_of_the_vocabulary_is_the_references_on_it():
    """A chip that holds the first rows of the table computes, on ids
    from those rows, the loss the uncut reference gives with its softmax
    held to the same rows; the layers do not see the cut."""
    uncut = dict(SMALL, vocab_size=4 * SMALL["vocab_size"])
    rows = SMALL["vocab_size"]
    whole = ref.init_weights(ref.seed_key(11), uncut)
    share = dict(whole, wte=whole["wte"][:rows])
    tokens = np.random.default_rng(1).integers(0, rows, (96,),
                                               dtype=np.int32)

    @jax.jit
    def both(whole, share):
        logits = ref.logits_fn(whole, tokens, uncut)[:-1, :rows]
        held = (jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tokens[1:, None], -1)[:, 0]).mean()
        return held, ref.row_loss(share, tokens, SMALL)

    want, got = both(whole, share)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    tree = train_hybrid.program_tree(ref, share, SMALL)
    got = jax.jit(lambda p: lm_loss(
        model_of(SMALL).apply(p, tokens[None]), tokens[None]))(tree)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_logits_scaling_is_applied_once_on_what_the_head_reads(seeded):
    """The ``return_hidden`` twin hands over hidden states already
    divided, so the chunked head (which knows nothing of the options)
    gives the loss of the model's own logits."""
    cfg, _, tree, tokens = seeded
    model = model_of(cfg)
    assert model.options.logits_scaling == cfg["logits_scaling"] != 1.0
    logits = model.apply(tree, tokens)
    hidden = model.clone(return_hidden=True).apply(tree, tokens)
    table = tree["params"]["embed"]["embedding"]
    np.testing.assert_allclose(hidden @ table.T, logits, rtol=1e-5,
                               atol=1e-5)
    undivided = model_of(dict(cfg, logits_scaling=1.0)).apply(tree, tokens)
    np.testing.assert_allclose(undivided / cfg["logits_scaling"], logits,
                               rtol=1e-5, atol=1e-5)
    assert float(chunked_lm_loss(model, tree, tokens, 2)) == pytest.approx(
        float(lm_loss(logits, tokens)), rel=2e-3)


def test_a_first_carry_varies_as_the_operands_it_is_like():
    """``vary_alike(..., like=)`` types a scan's zero carry as the
    operands vary inside a vma-checked ``shard_map`` and leaves the
    operands alone; outside one it is the identity."""
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu.ops.grouped_matmul import vary_alike

    zero = jnp.zeros((4,))
    assert vary_alike(zero, like=(jnp.ones((4,)),))[0] is zero
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def body(x):
        first, = vary_alike(jnp.zeros(x.shape[1:]), like=(x,))
        assert jax.typeof(first).vma == jax.typeof(x).vma == {"d"}
        total, _ = lax.scan(lambda c, row: (c + row, None), first, x)
        return total[None]

    x = jnp.arange(24.0).reshape(2, 3, 4)
    got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                                out_specs=P("d")))(x.reshape(6, 4))
    np.testing.assert_allclose(got, x.sum(1))


def test_the_cell_rehearses_correct():
    """``cellbench.run --rehearse`` of the cell, in a process of its own
    (one CPU device, as the cell has one chip): the example's ``main``
    under the runner's flags, three steps against the reference.  Over 40 s in the driver's
    run (a process start, the program's compile and the reference's at
    rehearsal size): the one tier-1 hold on the cell's own runner,
    example and comparison end to end, which no in-process case is."""
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "granite4hmicro_train_s8192", "--seed", str(2**31 + 7),
         "--seconds", "0.5", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    published = dict(
        hidden_size=2048, intermediate_size=8192,
        shared_intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_d_conv=4, mamba_chunk_size=256,
        mamba_expand=2, mamba_n_groups=1, num_attention_heads=32,
        num_key_value_heads=8, attention_multiplier=1 / 64,
        embedding_multiplier=12, residual_multiplier=0.22,
        logits_scaling=8, rms_norm_eps=1e-5,
        position_embedding_type="nope", tie_word_embeddings=True)
    assert {k: CONFIG[k] for k in published} == published
    entry = [c for c in bench["configs"]
             if c["name"] == "granite-4.0-h-micro"][0]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    # layers 0-9 of the published pattern, a whole period of it
    whole = CONFIG["published"]["layer_types"]
    assert len(whole) == CONFIG["published"]["num_hidden_layers"] == 40
    assert CONFIG["layer_types"] == whole[:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert all(whole[i:i + 10] == whole[:10] for i in range(0, 40, 10))
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["vocab_size"] % CONFIG["head_chunks"] == 0
    cell = [w for w in bench["workloads"]
            if w["config"] == "granite-4.0-h-micro"]
    assert [(w["name"], w["chips"]) for w in cell] == [
        ("granite4hmicro_train_s8192", 1)]


def test_the_programs_parameter_count_at_the_cells_size():
    cfg = {**CONFIG, "layer_types": tuple(CONFIG["layer_types"])}
    model = model_of(cfg, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 256), jnp.int32))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == 772_160_448
    leaves = jax.eval_shape(lambda k: ref.init_weights(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in leaves.values()) == count


# -- the benchmark's operation counts ----------------------------------------
def test_flops_granite_against_hand_worked_values():
    cfg = dict(hidden_size=4, intermediate_size=6, vocab_size=7,
               num_attention_heads=2, num_key_value_heads=1,
               mamba_n_heads=2, mamba_d_head=4, mamba_d_state=3,
               mamba_chunk_size=4,
               layer_types=("mamba", "attention", "mamba"))
    # W_in to 8 | 8 + 6 | 2 and W_out from 8; q, o of 4 and k, v of 2
    assert flops_granite.mamba_weights(cfg) == 4 * 24 + 8 * 4
    assert flops_granite.attention_weights(cfg) == 2 * 4 * 4 + 2 * 4 * 2
    assert flops_granite.mlp_weights(cfg) == 3 * 4 * 6
    parts = flops_granite.ssd_parts(cfg, 10)  # 3 chunks of 4
    assert parts == {"scores": 2 * 3 * 16 * 3, "inside": 2 * 3 * 16 * 8,
                     "states": 2 * 3 * 4 * 8 * 3,
                     "carried": 2 * 3 * 4 * 8 * 3}
    fwd = sum(parts.values())
    assert flops_granite.ssd_flops(cfg, 10, "fwd") == fwd
    assert flops_granite.ssd_flops(cfg, 10, "bwd") == 2 * fwd + 2 * 3 * 16 * 3
    assert flops_granite.attention_model_flops(cfg, 10) == 12 * 55 * 2 * 2
    weights = 2 * 128 + 48 + 3 * 72 + 4 * 7
    assert flops_granite.step_model_flops(cfg, 10, 3) == 3 * (
        6 * weights * 10 + 3 * 2 * fwd + 12 * 55 * 2 * 2)
    # the cell: the issue's 39.7 TFLOP a step
    cell = {**CONFIG, "layer_types": tuple(CONFIG["layer_types"])}
    assert flops_granite.step_model_flops(cell, 8192, 1) \
        == pytest.approx(39.7e12, rel=0.01)
