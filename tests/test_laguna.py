"""Laguna on the training path: the window kernels (interpreted) against
the dense mask forward and in dq, dk, dv, their census against what they
sweep, a window layer and a full layer of the one attention module
against ``cellbench/reference/laguna.py`` (a dense mask, the rotation's
frequencies as ``transformers`` computes them, a loop over the held
experts), each named breakage coming out apart, the shares against the
uncut layer, and the raises that keep such a model off the paths that
cannot run it."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import flops_laguna  # noqa: E402
from cellbench.reference import laguna as ref  # noqa: E402
from cellbench.runners import train_laguna  # noqa: E402
from chainermn_tpu.models.moe_transformer import (  # noqa: E402
    COUNTERS,
    ROUTES,
    MoeMlp,
    MoeTransformerLM,
    RouterOptions,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    BlockOptions,
    SelfAttention,
    TransformerLM,
    YarnScaling,
    generate,
    make_mixer,
    yarn_frequencies,
)
from chainermn_tpu.ops import pallas_attention as pa  # noqa: E402
from chainermn_tpu.parallel import expert_parallel  # noqa: E402

with open(os.path.join(ROOT, "cellbench", "configs",
                       "laguna-s-2.1.json")) as _f:
    CONFIG = json.load(_f)

_SHAPE_KEYS = ("layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "rope_parameters")
#: the configuration's rehearsal sizes, uncut (all 16 experts, 256 rows)
UNCUT = {**{k: v for k, v in CONFIG.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)},
         **{k: CONFIG[k] for k in _SHAPE_KEYS},
         **CONFIG["rehearse"], "num_experts": 16, "first_expert": 0}
UNCUT.pop("correct")
#: one chip's share of it: experts 4..7, the first 64 rows
SHARE = dict(UNCUT, num_experts=4, first_expert=4, vocab_size=64)
FULL, WINDOW = ("full_attention", "sparse", 4), \
    ("sliding_attention", "sparse", 6)


@pytest.fixture
def small_blocks(monkeypatch):
    """Buffer blocks of 8 rows, so that a few dozen tokens fill and pad
    the sorted buffer."""
    monkeypatch.setattr(expert_parallel, "HELD_BLOCK_ROWS", 8)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# -- the window kernels --------------------------------------------------------
def _dense(q, k, v, window):
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, s, hkv, hq // hkv, d),
                    k) * d ** -0.5
    sc = jnp.where(pa.block_causal_mask(s, 1, window=window), sc, -jnp.inf)
    return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, -1),
                      v).reshape(b, s, hq, d)


@pytest.mark.parametrize("tile", [None, 8], ids=["whole", "tiled"])
@pytest.mark.parametrize("s", [64, 8], ids=["four-windows", "half-a-window"])
@pytest.mark.parametrize("group", [6, 9])
def test_window_kernels_against_the_dense_mask(group, s, tile):
    """Forward, dq, dk and dv of the window launch at 6 and 9 query heads
    a key/value head (the model's two), over a sequence of four windows
    (blocks of a window: the diagonal block under the causal mask, the
    one before it under its mirror image) and over one shorter than a
    window (the launch then runs without one); with a compute tile the
    masked blocks are walked in strips.  One key more or one fewer in the
    window is apart."""
    window, hkv, d = 16, 2, 16
    q, k, v, w = (_normal(i, 2, s, h, d) for i, h in enumerate(
        (hkv * group, hkv, hkv, hkv * group)))
    kernel = lambda q, k, v: pa.block_causal_attention_with_lse(
        q, k, v, 1, interpret=True, tile=tile, window=window)[0]
    np.testing.assert_allclose(kernel(q, k, v), _dense(q, k, v, window),
                               atol=3e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense(*a, window) * w).sum(),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-5)
    if s > window:
        for off in (window - 1, window + 1):
            assert float(jnp.abs(kernel(q, k, v)
                                 - _dense(q, k, v, off)).max()) > 1e-2


def test_a_window_launch_has_kernels_of_its_own_and_the_others_none():
    """A launch without a window traces the kernels it always did; with
    one, kernels named apart (a device trace tells them by name)."""
    q = jax.ShapeDtypeStruct((1, 2048, 12, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)

    def names(**kw):
        text = str(jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v: pa.block_causal_attention_with_lse(
                q, k, v, 1, interpret=False, **kw)[0].astype(
                    jnp.float32).sum(), (0, 1, 2)))(q, k, k))
        return {n for n in ("_bdflash_forward", "_bdflash_backward_dq",
                            "_bdflash_backward_dkdv", "_swaflash_forward",
                            "_swaflash_backward_dq",
                            "_swaflash_backward_dkdv")
                if n in text}

    bd = {"_bdflash_forward", "_bdflash_backward_dq",
          "_bdflash_backward_dkdv"}
    assert names() == bd
    assert names(window=2048) == bd  # reaches the whole sequence: none
    assert names(window=512) == {n.replace("_bd", "_swa") for n in bd}
    with pytest.raises(ValueError, match="whole number"):
        pa.block_causal_attention_with_lse(
            jnp.zeros((1, 512, 2, 128)), jnp.zeros((1, 512, 2, 128)),
            jnp.zeros((1, 512, 2, 128)), 1, interpret=False, window=192)


@pytest.mark.parametrize("s,bs,window", [(64, 16, 16), (96, 16, 32),
                                         (128, 16, 64), (8192, 512, 512)])
def test_census_is_what_the_window_kernels_sweep(s, bs, window):
    """The census against the definition, block by block: every (q
    block, k block) that holds a live pair of the dense mask is swept by
    the forward / dq grid and by the dk/dv grid and classed interior
    (all pairs live) or masked (some); nothing swept lies wholly below
    the window; the points swept and not live are the few a sweep near
    the sequence's start runs past the diagonal."""
    n, n_t = s // bs, window // bs + 1
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    live = ((j <= i) & (j > i - window)).reshape(n, bs, n, bs)
    some, every = live.any((1, 3)), live.all((1, 3))
    fwd = {(q, pa._swept_k(q, t, n_t, window))
           for q in range(n) for t in range(n_t)}
    dkv = {(int(pa._swept_q(kb, jnp.int32(t), n_t, window)), kb)
           for kb in range(n) for t in range(n_t)}
    want = {(q, kb) for q in range(n) for kb in range(n) if some[q, kb]}
    assert want <= fwd and want == {p for p in dkv if p[0] < n}
    assert len(fwd) == len(dkv) == n * n_t
    for q, kb in fwd:
        interior, masked = pa._bc_class(q, kb, bs, s, window)
        assert bool(interior) == bool(kb < n and every[q, kb])
        assert bool(masked) == bool(kb < n and some[q, kb]
                                    and not every[q, kb])
    for kind in ("fwd", "bwd"):
        census = pa.block_census(s, s, bs, bs, True, kind, window=window)
        assert census["visited"] == n * n_t
        assert census["live"] == len(want) \
            == census["interior"] + census["masked"]
        assert census["interior"] == int(every.sum())
        assert census["dead"] == n * n_t - len(want)
        assert census["below_window"] == 0
    # the launch without a window visits every block of the square
    assert pa.block_census(s, s, bs, bs, True)["dead"] == n * (n - 1) // 2
    if s == 8192:  # the cell's launch: 31 live points of 32 swept, a head
        assert (len(want), n * n_t) == (31, 32)
        assert int(live.sum()) == flops_laguna.live_pairs(8192, 512) \
            == 512 * 513 // 2 + (8192 - 512) * 512


# -- the rotation --------------------------------------------------------------
def test_yarn_frequencies_are_transformers():
    """The program's and the reference's frequencies of the full
    layers' rotation against ``transformers``' own function, at the
    published sizes: the ramp between interpolated and extrapolated
    channels over the 64 rotated ones, and the factor on cos and sin."""
    torch = pytest.importorskip("torch")
    utils = pytest.importorskip("transformers.modeling_rope_utils")
    full = CONFIG["rope_parameters"]["full_attention"]
    hf = type("Config", (), dict(
        rope_theta=full["rope_theta"], head_dim=CONFIG["head_dim"],
        partial_rotary_factor=full["partial_rotary_factor"],
        hidden_size=CONFIG["hidden_size"],
        num_attention_heads=CONFIG["num_attention_heads"],
        max_position_embeddings=CONFIG["max_position_embeddings"],
        rope_scaling=full))()
    want, factor = utils._compute_yarn_parameters(hf, torch.device("cpu"))
    yarn = YarnScaling(full["factor"],
                       full["original_max_position_embeddings"],
                       full["beta_fast"], full["beta_slow"], None)
    assert yarn.cos_sin_factor == pytest.approx(full["attention_factor"])
    assert factor == full["attention_factor"]
    np.testing.assert_allclose(
        yarn_frequencies(float(full["rope_theta"]), 64, yarn),
        want.numpy(), rtol=1e-6)
    freq, on_cos_sin, turned = ref.rotation(CONFIG, "full_attention")
    np.testing.assert_allclose(freq, want.numpy(), rtol=1e-6)
    assert (on_cos_sin, turned) == (full["attention_factor"], 64)
    # 32 pairs: the fastest as trained, the slowest interpolated 128-fold
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert freq[0] == pytest.approx(1.0) and float(freq[-1]) \
        == pytest.approx(plain[-1] / 128, rel=1e-6)
    plain_w, one, all_of_it = ref.rotation(CONFIG, "sliding_attention")
    assert (one, all_of_it) == (1.0, 128)
    np.testing.assert_allclose(plain_w, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-6)


# -- a layer's mixer -------------------------------------------------------------
def _options(cfg=SHARE, **kw):
    full = cfg["rope_parameters"]["full_attention"]
    window = cfg["rope_parameters"]["sliding_attention"]
    base = dict(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        head_gate=True, rope_theta=float(full["rope_theta"]),
        rotary_fraction=full["partial_rotary_factor"],
        rope_yarn=YarnScaling(
            float(full["factor"]),
            full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]),
            full["attention_factor"]),
        layer_types=("attention", "window_attention", "window_attention",
                     "window_attention"),
        window=cfg["sliding_window"], window_heads=6,
        window_rope_theta=float(window["rope_theta"]),
        window_rotary_fraction=float(window["partial_rotary_factor"]),
        gated_mlp=True)
    return BlockOptions(**{**base, **kw})


def _mixer_weights(kinds, seed=7, cfg=SHARE):
    shapes = ref._shapes(cfg, kinds[2])
    return {n: 0.3 * _normal(seed + i, *shapes[n])
            for i, n in enumerate(("w_q", "w_k", "w_v", "w_g", "w_o"))}


def _mixer_params(w):
    return {"params": {"q_proj": {"kernel": w["w_q"]},
                       "k_proj": {"kernel": w["w_k"]},
                       "v_proj": {"kernel": w["w_v"]},
                       "g_proj": {"kernel": w["w_g"]},
                       "o_proj": {"kernel": w["w_o"]}}}


def _mix(kinds, options, x, w):
    kind = {"full_attention": "attention",
            "sliding_attention": "window_attention"}[kinds[0]]
    return make_mixer(kind, 4, options, jnp.float32).apply(
        _mixer_params(w), x)


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "kernels"])
@pytest.mark.parametrize("kinds", [FULL, WINDOW], ids=["full", "window"])
def test_a_layers_attention_against_the_reference(kinds, flash):
    """One module, a layer kind's own head count, window and rotation:
    4 heads with YaRN over half a head and no window, or 6 heads with
    the plain rotation over the whole head and 16 keys, each under a
    gate a head; through the kernels (interpreted) and through the
    dense form."""
    x, w = _normal(1, 2, 48, 64), _mixer_weights(kinds)
    got = _mix(kinds, _options(use_flash=flash), x, w)
    want = jax.vmap(lambda row: ref.attention(
        row, w, SHARE, ref._ein(False), kinds))(x)
    assert _max_rel(got, want) < 2e-5


def _pairs(x, pos, freq, on_cos_sin, turned):
    """The rotation by neighbouring pairs, what the family does not do."""
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = (f(ang)[:, None, :] * on_cos_sin for f in (jnp.cos, jnp.sin))
    a, b = x[..., 0:turned:2], x[..., 1:turned:2]
    return jnp.concatenate([jnp.stack(
        [a * cos - b * sin, a * sin + b * cos], -1).reshape(
            *x.shape[:-1], turned), x[..., turned:]], -1)


#: name -> (layer kinds, the program's options changed, the reference's
#: configuration changed, a replacement for the reference's ``rotate``)
BREAKAGES = {
    "window one key longer": (WINDOW, dict(window=17), {}, None),
    "window one key shorter": (WINDOW, dict(window=15), {}, None),
    "window layer run as a full one": (
        WINDOW, dict(window=48, window_rope_theta=500000.0), {}, None),
    "full layer run as a window one": (
        FULL, {}, {"layer_types": ["sliding_attention"] * 5}, None),
    "no ramp: the plain frequencies under the factor": (
        FULL, {}, {"rope_parameters": dict(
            SHARE["rope_parameters"], full_attention=dict(
                SHARE["rope_parameters"]["full_attention"], factor=1.0,
                attention_factor=1.4852030263919618))}, None),
    "no attention_factor": (FULL, dict(rope_yarn=YarnScaling(
        128.0, 8192, 32.0, 1.0, 1.0)), {}, None),
    "rotation over the whole head": (
        FULL, dict(rotary_fraction=1.0), {}, None),
    "rotation by pairs": (FULL, {}, {}, _pairs),
    "gate left out": (WINDOW, dict(head_gate=False), {}, None),
}


@pytest.mark.parametrize("name", BREAKAGES)
def test_a_broken_layer_is_apart(name, monkeypatch):
    """Each thing the issue names, broken on one side: the program's
    layer then lies 30 times further from the reference's than the
    sound layer does."""
    kinds, changed, cfg_changed, rotate = BREAKAGES[name]
    x, w = _normal(1, 2, 48, 64), _mixer_weights(kinds)

    def reference(cfg, kinds):
        return jax.vmap(lambda row: ref.attention(
            row, w, cfg, ref._ein(False), kinds))(x)

    sound = _max_rel(_mix(kinds, _options(), x, w), reference(SHARE, kinds))
    if rotate is not None:
        monkeypatch.setattr(ref, "rotate", rotate)
    # the reference's layer takes its type from the changed lists
    as_read = (cfg_changed.get("layer_types", kinds[:1])[0],) + kinds[1:]
    broken = _max_rel(_mix(kinds, _options(**changed), x, w),
                      reference(dict(SHARE, **cfg_changed), as_read))
    assert sound < 2e-5 and broken > 30 * sound, (sound, broken)


def test_swapped_head_counts_and_an_elementwise_gate_do_not_fit():
    """The window layers' 6 heads in a full layer's place, or the
    element-wise gate (a ``q_proj`` twice as wide) in the head gate's,
    do not take the reference's weights at all."""
    x, w = _normal(1, 2, 48, 64), _mixer_weights(FULL)
    from flax.errors import ScopeParamShapeError

    with pytest.raises(ScopeParamShapeError):
        make_mixer("attention", 6, _options(), jnp.float32).apply(
            _mixer_params(w), x)
    with pytest.raises(ScopeParamShapeError):
        make_mixer("attention", 4, _options(
            head_gate=False, attn_output_gate=True), jnp.float32).apply(
            _mixer_params(w), x)


# -- the expert layer ------------------------------------------------------------
ROUTER = RouterOptions(routed_scale=2.5)


def _layer_weights(weights, cfg, layer=1):
    return {n: weights[k] for n, k in ref.layer_leaves(
        ref.layer_kinds(cfg)[layer], layer).items()}


def _mlp_params(w):
    return {"params": {
        "router": w["router"], "expert_wg": w["w_gate"],
        "expert_wu": w["w_up"], "expert_wd": w["w_down"],
        "shared_wg": w["s_gate"], "shared_wu": w["s_up"],
        "shared_wd": w["s_down"], "shared_gate": w["s_mix"]}}


def _mlp(held=None, router_options=ROUTER):
    return MoeMlp(16, 32, k=3, routing="dropless", shared_d_ff=32,
                  dtype=jnp.float32, router_options=router_options,
                  held=held)


def test_shares_and_the_shared_expert_once_are_the_uncut_layer(
        small_blocks):
    """The guide's test at a small size: 4 shares of 4 of the 16 experts
    (the deployment's 32 of 8 of 256).  Each computes its own experts'
    routed part under the router all share (top 3, renormalised, times
    2.5), each route counted once; those and the gated shared expert
    counted **once** are the uncut layer, in the reference and in the
    program (whose every share adds the shared expert: four sums hold it
    four times); the balance term is the same on every share; without
    the scaling factor the layer is apart."""
    ein = ref._ein(False)
    weights = ref.init_weights(ref.seed_key(21), UNCUT)
    layer = _layer_weights(weights, UNCUT)
    u = _normal(16, 2, 24, 64)
    flat = u.reshape(-1, 64)
    routed, want_aux, _ = ref.routed_part(flat, layer, UNCUT, ein)
    shared = ref.shared_part(flat, layer, ein)
    (whole, aux), _ = _mlp().apply(_mlp_params(layer), u,
                                   mutable=[COUNTERS, ROUTES])
    whole = whole.reshape(-1, 64)
    np.testing.assert_allclose(whole, routed + shared, atol=2e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
    (unscaled, _), _ = _mlp(router_options=RouterOptions()).apply(
        _mlp_params(layer), u, mutable=[COUNTERS, ROUTES])
    assert _max_rel(unscaled.reshape(-1, 64), routed + shared) > 0.1

    total_ref, total, rows = 0.0, 0.0, 0
    for first in range(0, 16, 4):
        share = _layer_weights(
            ref.share_of(weights, UNCUT, first, 4, 0, 256), UNCUT)
        cfg = dict(UNCUT, num_experts=4, first_expert=first)
        part, aux, _ = ref.routed_part(flat, share, cfg, ein)
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        total_ref = total_ref + part
        (y, _), sown = _mlp(held=(first, 4)).apply(
            _mlp_params(share), u, mutable=[COUNTERS, ROUTES])
        assert int(sown[COUNTERS]["moe_dropped"][0]) == 0
        rows += int(sown[COUNTERS]["moe_rows_routed"][0])
        total = total + y.reshape(-1, 64)
    assert rows == 48 * 3  # every route on exactly one share
    np.testing.assert_allclose(total_ref + shared, routed + shared,
                               atol=2e-5)
    np.testing.assert_allclose(total - 3 * shared, whole, atol=1e-4)


# -- the whole model -------------------------------------------------------------
def _model(cfg, dtype=jnp.float32, options=None, **kw):
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"],
        moe_every=cfg["decoder_sparse_step"],
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=options or _options(cfg), routing="dropless",
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        router_options=ROUTER, first_dense=1,
        dense_d_ff=cfg["intermediate_size"], tie_head=False, **kw)


def _tokens(cfg, rows=2, s=48, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, s), dtype=np.int32)


def _apply(model, tree, tokens):
    return model.apply(tree, tokens, mutable=[COUNTERS, ROUTES])[0]


def test_model_logits_against_reference():
    """Five layers (full behind a dense MLP, three window layers, full)
    through the kernels against the reference; with the window and the
    full layers swapped the same weights do not fit, and the float8
    control is apart."""
    weights = ref.init_weights(ref.seed_key(3), SHARE)
    tokens = _tokens(SHARE)
    tree = train_laguna.program_tree(ref, weights, SHARE)
    model = _model(SHARE, options=_options(use_flash=True))
    logits, _ = jax.jit(lambda p: _apply(model, p, tokens))(tree)
    want = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE))(weights)
    assert _max_rel(logits, want) < 1e-4
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE, lowp=True))(
        weights)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)
    from flax.errors import ScopeParamShapeError

    swapped = _model(SHARE, options=_options(layer_types=(
        "window_attention", "attention", "attention", "attention")))
    with pytest.raises(ScopeParamShapeError):
        _apply(swapped, tree, tokens)


def test_parameter_count_at_the_published_widths_by_hand():
    """The issue's table: 811 029 504 in chip 0's five layers."""
    sizes = {**{k: v for k, v in CONFIG.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)},
             **{k: CONFIG[k] for k in _SHAPE_KEYS}}
    full = 3072 * 6144 * 2 + 2 * 3072 * 1024 + 3072 * 48
    window = 3072 * 9216 * 2 + 2 * 3072 * 1024 + 3072 * 72
    sparse = 3072 * 256 + 3 * 3072 * 1024 + 3072 + 8 * 3 * 3072 * 1024
    assert (full, window) == (44_187_648, 63_135_744)
    assert ref.n_parameters(sizes) == 811_029_504 \
        == full + 3 * 3072 * 12288 + 6144 + 3 * (window + sparse + 6144) \
        + full + sparse + 6144 + 2 * 12544 * 3072 + 3072
    assert [k[0] for k in ref.layer_kinds(sizes)] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert flops_laguna.mixer_weights(sizes, 48) == full
    assert flops_laguna.mixer_weights(sizes, 72) == window


# -- what keeps such a model off the paths that cannot run it -------------------
@pytest.mark.parametrize("field", [
    dict(head_gate=True), dict(window=16), dict(window_heads=6),
    dict(window_rope_theta=1e4), dict(window_rotary_fraction=1.0),
    dict(rope_yarn=YarnScaling(2.0, 64)),
    dict(layer_types=("window_attention",), window=16)])
def test_the_new_fields_take_the_general_path_and_its_raises(field):
    """A window, a head gate, a head count of a layer's own and a scaled
    rotation count in ``general_attention``: under ``seq_axis``,
    ``tp_axis`` or ``decode`` the module raises instead of silently
    leaving them out, and ``generate`` says it cannot serve the model."""
    options = BlockOptions(**field)
    assert options.general_attention and options.layer_attention
    x = jnp.zeros((1, 8, 32))
    for axes in (dict(seq_axis="mn_seq"), dict(tp_axis="mn_model"),
                 dict(decode=True, cache_len=8)):
        with pytest.raises(ValueError, match="no seq_axis, tp_axis or "
                                             "decode"):
            SelfAttention(4, options=options, **axes).init(
                jax.random.PRNGKey(0), x)
    model = TransformerLM(vocab_size=32, d_model=32, n_heads=4, n_layers=1,
                          max_len=16, options=dataclasses.replace(
                              options, rope_theta=1e4))
    with pytest.raises(ValueError, match="cannot serve this model"):
        generate(model, None, jnp.zeros((1, 4), jnp.int32), 4)
    assert not BlockOptions().layer_attention


def test_a_window_layer_without_a_window_is_refused():
    with pytest.raises(ValueError, match="options.window > 0"):
        make_mixer("window_attention", 4, BlockOptions(rope_theta=1e4),
                   jnp.float32).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8, 32)))


def test_remat_widths_take_the_widest_layer():
    """``attn_out`` is the widest kind's: 72 heads of 128 and their
    log-sum-exps at the published sizes, and the attention layers' own
    where no window layer is there; the plan spends it on window layers
    too."""
    from chainermn_tpu.models.transformer import remat_plan

    options = _options(CONFIG, use_flash=True, window_heads=72)
    widths = options.remat_widths(12288, 48, d_model=3072)
    assert widths == {"attn_out": 72 * 128 + 2 * 72, "mlp_in": 2 * 12288}
    alone = dataclasses.replace(options, layer_types=("attention",))
    assert alone.remat_widths(12288, 48, d_model=3072)["attn_out"] \
        == 48 * 128 + 2 * 48
    kinds = [options.layer_type(i) for i in range(5)]
    assert kinds == ["attention"] + ["window_attention"] * 3 + ["attention"]
    plan = remat_plan(kinds, 8192, widths, 1 << 40,
                      dense=[True] + [False] * 4)
    assert plan == (("attn_out", "mlp_in"),) + (("attn_out",),) * 4


def test_the_runners_command_line_is_the_issues():
    """The example's command line the runner writes from the
    configuration's lists a layer and its rotations."""
    sizes = {**{k: v for k, v in CONFIG.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)},
             **{k: CONFIG[k] for k in _SHAPE_KEYS}}
    argv = train_laguna.example_argv(
        ref, sizes, {"seq_len": 8192}, CONFIG["optimizer"], 1)
    flags = dict(zip(argv[::1], argv[1::1]))
    assert flags["--layer-types"] == ("attention,window_attention,"
                                      "window_attention,window_attention")
    assert (flags["--n-heads"], flags["--window-heads"],
            flags["--n-kv-heads"], flags["--head-dim"]) \
        == ("48", "72", "8", "128")
    assert (flags["--window"], flags["--window-rope-theta"],
            flags["--window-rotary-fraction"]) == ("512", "10000.0", "1.0")
    assert flags["--rope-yarn"] == "128.0,8192.0,32.0,1.0,1.4852030263919618"
    assert (flags["--rope-theta"], flags["--rotary-fraction"]) \
        == ("500000.0", "0.5")
    assert (flags["--n-experts"], flags["--top-k"], flags["--held"],
            flags["--routed-scale"]) == ("256", "10", "0,8", "2.5")
    assert (flags["--first-dense"], flags["--dense-d-ff"],
            flags["--d-ff"], flags["--shared-d-ff"]) \
        == ("1", "12288", "1024", "1024")
    assert "--head-gate" in argv and "--qk-norm" not in argv


def test_the_cells_files_say_what_the_issue_asked_for():
    """The catalog row's numbers under their keys, every cut in
    ``reduced`` with the published value beside it, and the two cells'
    entries."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["laguna-s-2.1"]
    assert entry["file"] == "cellbench/configs/laguna-s-2.1.json"
    assert set(entry["reduced"]) == set(CONFIG["reduced"]) \
        == set(CONFIG["published"])
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["head_dim"],
            CONFIG["num_experts_per_tok"], CONFIG["sliding_window"],
            CONFIG["max_position_embeddings"]) \
        == (3072, 12288, 1024, 128, 10, 512, 1048576)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"], CONFIG["router_experts"]) \
        == (5, 8, 12544, 256)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells["lagunas21_train_s8192"]["chips"],
            cells["cgpt590m_dpwire4_s2048"]["chips"]) == (1, 4)
    assert len(cells) == 10 and sum(
        w["chips"] == 4 for w in cells.values()) == 2
    rate = {m["name"]: m for m in bench["end_to_end"]}[
        "tokens_per_s_per_chip"]
    assert rate["workloads"][-2:] == ["cgpt590m_dpwire4_s2048",
                                      "lagunas21_train_s8192"]
    for metric in bench["per_layer"]:
        if metric["name"].endswith((".laguna", ".lmwire")):
            assert len(metric["workloads"]) == 1
            assert os.path.exists(os.path.join(
                ROOT, "cellbench", "layer_metrics",
                metric["name"] + ".json")), metric["name"]
