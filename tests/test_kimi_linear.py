"""Kimi Linear on the training path: the delta rule with a decay a key
channel (``ops/gated_delta.py``) against the recurrence, the causal
kernels at a key width apart from the value width, ``KdaMixer`` and
``LatentAttention`` behind ``make_mixer``, the router's other forms
(sigmoid scores, a selection bias outside the weights, a scaling
factor, an ungated shared expert), leading dense layers, the shares
against the uncut layer, and ``MoeTransformerLM`` against
``cellbench/reference/kimi_linear.py`` (the recurrence a token, a dense
masked softmax, a loop over the held experts)."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench.reference import kimi_linear as ref  # noqa: E402
from cellbench.runners import train_kimilinear  # noqa: E402
from chainermn_tpu.models.moe_transformer import (  # noqa: E402
    COUNTERS,
    ROUTES,
    MoeMlp,
    MoeTransformerLM,
    RouterOptions,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    LAYER_KINDS,
    REMAT_NAMES,
    BlockOptions,
    KdaMixer,
    LatentAttention,
    make_mixer,
    remat_plan,
)
from chainermn_tpu.ops import gated_delta  # noqa: E402
from chainermn_tpu.ops import pallas_attention as pa  # noqa: E402
from chainermn_tpu.ops.gated_delta import (  # noqa: E402
    gated_delta_census,
    gated_delta_scan,
)
from chainermn_tpu.parallel import expert_parallel  # noqa: E402

with open(os.path.join(ROOT, "cellbench", "configs",
                       "kimi-linear-48b-a3b.json")) as _f:
    CONFIG = json.load(_f)

#: the configuration's rehearsal sizes, uncut (all 16 experts, 256 rows)
UNCUT = {**{k: v for k, v in CONFIG.items()
            if isinstance(v, (int, float, str)) and not isinstance(v, bool)},
         **CONFIG["rehearse"], "num_experts": 16, "first_expert": 0}
UNCUT.pop("correct")
#: one chip's share of it: experts 4..7, the first 64 rows
SHARE = dict(UNCUT, num_experts=4, first_expert=4, vocab_size=64)


@pytest.fixture
def small_blocks(monkeypatch):
    """Buffer blocks of 8 rows, so that a few dozen tokens fill and pad
    the sorted buffer."""
    monkeypatch.setattr(expert_parallel, "HELD_BLOCK_ROWS", 8)


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# -- the delta rule with a decay a key channel ------------------------------
def recurrence(q, k, v, g, beta):
    """``S <- Diag(e^g) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T
    q``, a position after another; ``g (b, s, h, dk)``."""
    b, s, hk, dk = k.shape
    h, dv = v.shape[2:]
    q, k = (jnp.repeat(t, h // hk, axis=2) for t in (q, k))

    def one(S, at):
        q, k, v, g, beta = at
        S = jnp.exp(g)[..., None] * S
        delta = (v - jnp.einsum("bhkv,bhk->bhv", S, k)) * beta[..., None]
        S = S + k[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    _, o = lax.scan(one, jnp.zeros((b, h, dk, dv)),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _operands(s, seed=0, b=2, hk=4, h=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(n(b, s, hk, dk)) * dk ** -0.5, unit(n(b, s, hk, dk)),
            n(b, s, h, dv), -0.3 * jnp.exp(n(b, s, h, dk)),
            jax.nn.sigmoid(n(b, s, h)))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("s", [128, 150], ids=["whole_chunks", "off_boundary"])
def test_channel_decay_scan_is_the_recurrence(s, chunk):
    """Values and all five gradients (``g``'s a key channel's), at
    lengths on and off a chunk boundary and two chunk sizes (one block
    of 16 a chunk, and four), float32 products."""
    args = _operands(s)
    got = gated_delta_scan(*args, chunk=chunk, dtype=jnp.float32)
    want = recurrence(*args)
    assert got.shape == want.shape == (2, s, 4, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)
    weight = jnp.cos(jnp.arange(8.0))
    grads = jax.grad(lambda *a: (gated_delta_scan(
        *a, chunk=chunk, dtype=jnp.float32) * weight).sum(),
        argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert g.shape == w.shape
        assert float(jnp.abs(g - w).max()) \
            < 2e-5 * float(jnp.abs(w).max()), name


def test_channel_decay_scan_serves_value_heads_through_key_heads():
    """Two value heads a key head, each under decays of its own."""
    args = _operands(96, seed=2, hk=2, h=4)
    np.testing.assert_allclose(
        gated_delta_scan(*args, chunk=32, dtype=jnp.float32),
        recurrence(*args), atol=2e-6)


def test_one_decay_for_all_channels_is_the_scalar_rule():
    """``g`` the same in every key channel: the scalar form's result,
    from the other code path."""
    q, k, v, g, beta = _operands(128, seed=4)
    scalar = g[..., 0]
    got = gated_delta_scan(q, k, v, jnp.broadcast_to(
        scalar[..., None], g.shape), beta, chunk=64, dtype=jnp.float32)
    want = gated_delta_scan(q, k, v, scalar, beta, chunk=64,
                            dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_strong_channel_decays_stay_finite_and_right():
    """``g = -80`` a position and channel (a softplus of 5 under ``A_log
    = log 16``): ``e^{-G}`` of the split form ``(K e^G)(K e^-G)^T``
    overflows float32 at the second position of a chunk; the blocked
    form evaluates no exponent above 0, and agrees with the recurrence.
    A mix of strong and no decay in one head likewise."""
    q, k, v, g, beta = _operands(128, seed=5)
    assert not bool(jnp.isfinite(jnp.exp(80.0 * 2)))  # what it avoids
    mixed = jnp.where(jnp.arange(16) % 2 == 0, -80.0, -1e-4) \
        * jnp.ones_like(g)
    for strong in (jnp.full_like(g, -80.0), mixed):
        got = gated_delta_scan(q, k, v, strong, beta, chunk=64,
                               dtype=jnp.float32)
        grads = jax.grad(lambda *a: gated_delta_scan(
            *a, chunk=64, dtype=jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4))(q, k, v, strong, beta)
        assert all(bool(jnp.isfinite(t).all()) for t in (got, *grads))
        want = recurrence(q, k, v, strong, beta)
        assert float(jnp.abs(got - want).max()) \
            < 1e-5 * float(jnp.abs(want).max())


def test_channel_decay_bfloat16_products_stay_near_the_recurrence():
    args = _operands(192, seed=3)
    got = gated_delta_scan(*args, chunk=64, dtype=jnp.bfloat16)
    want = recurrence(*args)
    assert float(jnp.abs(got - want).max()) \
        < 0.02 * float(jnp.abs(want).max())


def test_channel_decay_runs_kernels_of_its_own(monkeypatch):
    """The scalar rule's kernels take ``G`` as one row a head and chunk:
    a decay a channel never reaches them; asked for interpreted at the
    sizes that tile it runs its own (``ops/kda_kernels.py``), left to
    itself off the TPU its XLA form; ``g`` of another shape is
    refused."""
    from chainermn_tpu.ops import gated_delta_kernels, kda_kernels

    def refuse(*args, **kw):
        raise AssertionError("the scalar rule's kernels ran")

    ran = []
    sound = kda_kernels.kda_chunks

    def counted(*args):
        ran.append(args[3].shape)
        return sound(*args)

    q, k, v, g, beta = _operands(256, b=1, hk=2, h=2, dk=128, dv=128)
    assert gated_delta._use_kernels(k, v, 64, jnp.float32, True)
    assert gated_delta._use_kernels(k, v, 64, jnp.float32, True, True)
    monkeypatch.setattr(gated_delta_kernels, "gated_delta_chunks", refuse)
    monkeypatch.setattr(kda_kernels, "kda_chunks", counted)
    jax.clear_caches()
    want = recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(
        gated_delta_scan(q, k, v, g, beta, chunk=64, dtype=jnp.float32),
        want, atol=2e-6)
    assert not ran
    np.testing.assert_allclose(
        gated_delta_scan(q, k, v, g, beta, chunk=64, dtype=jnp.float32,
                         interpret=True), want, atol=2e-6)
    assert ran == [(1, 256, 2 * 128)]
    with pytest.raises(AssertionError, match="kernels ran"):
        gated_delta_scan(q, k, v, g[..., 0], beta, chunk=64,
                         dtype=jnp.float32, interpret=True)
    with pytest.raises(ValueError, match="g is"):
        gated_delta_scan(q, k, v, g[..., :64], beta)


def test_channel_decay_census_at_the_cells_shape_by_hand():
    """8192 positions, chunk 64, 32 heads of 128, a key head each."""
    census = gated_delta_census(8192, 64, 32, 128, 128, channel_decay=True)
    parts = census["flops"]
    assert parts["kk"] == parts["qk"] == 2 * 128 * 32 * 64 * 64 * 128
    assert parts["solve"] == 128 * 32 * 64 * 64 * 256
    assert parts["read"] == 2 * 128 * 32 * 64 * 128 * 128
    assert census["flops_forward"] == pytest.approx(43.0e9, rel=0.01)
    # g: as many bytes as q, k, v and o together
    assert census["bytes_forward"] == 8192 * 32 * (4 * 128 * 2
                                                   + 4 * 128 + 4)
    # the kernel path's account: tests/test_kda_kernels.py
    assert census["kernels"]["forward"]["grid"] == (1, 32, 32)
    # 16 x 16 x 128 in each of 4 blocks, 4 + 1 factors of 64 x 128
    # between them, e^G and e^{G_C - G}: 23 x 64 x 128 a head and chunk
    assert census["exponentials"] == 128 * 32 * 64 * 128 * (16 + 4 + 3)
    # the scalar rule's census is as it was
    scalar = gated_delta_census(8192, 64, 32, 128, 128, key_heads=16)
    assert "exponentials" not in scalar and scalar["kernels"] is not None


# -- keys wider than values in the causal kernels ---------------------------
def _dense_attention(q, k, v, scale):
    s, rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)


@pytest.mark.parametrize("dk,dv,hq,hkv", [
    pytest.param(192, 128, 2, 2, id="192_128"),
    pytest.param(24, 16, 4, 2, id="24_16_grouped"),
    pytest.param(96, 32, 2, 1, id="96_32_grouped"),
])
def test_causal_kernels_take_a_key_width_apart_from_the_value_width(
        dk, dv, hq, hkv):
    """Forward and all three gradients against a dense masked softmax,
    each as wide as its operand."""
    rng = np.random.default_rng(0)
    n = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = n(1, 256, hq, dk), n(1, 256, hkv, dk), n(1, 256, hkv, dv)
    scale = dk ** -0.5
    attend = lambda q, k, v: pa.block_causal_attention_with_lse(
        q, k, v, 1, scale=scale, block_size=128, interpret=True)[0]
    got = attend(q, k, v)
    assert got.shape == (1, 256, hq, dv)
    np.testing.assert_allclose(got, _dense_attention(q, k, v, scale),
                               atol=5e-6)
    weight = n(1, 256, hq, dv)
    grads = jax.grad(lambda *a: (attend(*a) * weight).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (_dense_attention(*a, scale)
                                 * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(grads, wants, (q, k, v)):
        assert g.shape == like.shape
        assert _max_rel(g, w) < 1e-5


# -- the mixers --------------------------------------------------------------
def _options(cfg, **kw):
    lin = cfg["linear_attn_config"]
    return BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], no_positions=True,
        layer_types=ref.mixer_kinds(cfg)[:lin["full_attn_layers"][0]],
        gdn_value_heads=lin["num_heads"], gdn_key_dim=lin["head_dim"],
        gdn_value_dim=lin["head_dim"],
        gdn_conv=lin["short_conv_kernel_size"],
        gdn_chunk=cfg["linear_chunk_size"],
        latent_kv_rank=cfg["kv_lora_rank"],
        latent_nope_dim=cfg["qk_nope_head_dim"],
        latent_shared_dim=cfg["qk_rope_head_dim"],
        latent_value_dim=cfg["v_head_dim"], gated_mlp=True, **kw)


def _mixer_params(weights, cfg, layer):
    """One layer's mixer leaves in the program's names."""
    tree = train_kimilinear.program_tree(ref, weights, cfg)["params"]
    block = dict(train_kimilinear._paths(ref, cfg))[f"norm1_g.{layer}"][0]
    return tree[block][
        train_kimilinear._MIXER[ref.layer_kinds(cfg)[layer][0]]]


@pytest.mark.parametrize("layer,kind", [(0, "kda"), (3, "latent_attention")])
def test_mixers_against_the_reference(layer, kind):
    """``KdaMixer`` and ``LatentAttention`` (dense and through the
    interpreted kernels) on the reference's seeded leaves against the
    reference's ``mix`` of the same layer."""
    cfg = SHARE
    assert ref.layer_kinds(cfg)[layer][0] == kind
    weights = ref.init_weights(ref.seed_key(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, cfg["hidden_size"]))
    w = {n: weights[k] for n, k in ref.layer_leaves(
        ref.layer_kinds(cfg)[layer], layer).items()}
    want = jax.vmap(lambda row: ref._MIX[kind](
        row, w, cfg, ref._ein(False), False))(x)
    params = {"params": _mixer_params(weights, cfg, layer)}
    for flash in (False, True):
        mixer = make_mixer(kind, cfg["num_attention_heads"],
                           _options(cfg, use_flash=flash), jnp.float32)
        assert isinstance(mixer, KdaMixer if kind == "kda"
                          else LatentAttention)
        got = mixer.apply(params, x)
        assert _max_rel(got, want) < 2e-5, flash
    assert set(params["params"]) == set(
        mixer.init(jax.random.PRNGKey(0), x)["params"])


def test_new_mixers_are_single_device_and_latent_attention_unrotated():
    """Under ``no_positions`` the latent-attention layer holds no
    rotation (it takes one with ``rope_theta``:
    ``tests/test_moonlight.py``), and both mixers still refuse a
    sequence axis, a head axis and a cache."""
    x = jnp.zeros((1, 16, 64))
    for kind in ("kda", "latent_attention"):
        for refused in (dict(tp_axis="mn_model"), dict(seq_axis="mn_seq"),
                        dict(decode=True)):
            with pytest.raises(ValueError, match="single-device"):
                make_mixer(kind, 4, _options(SHARE), jnp.float32,
                           **refused).init(jax.random.PRNGKey(0), x)
    options = _options(SHARE)
    assert options.no_positions and options.rope_theta is None
    mixer = make_mixer("latent_attention", 4, options, jnp.float32)
    variables = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)
    text = jax.jit(mixer.apply).lower(variables, x).as_text(debug_info=True)
    assert "latent_proj" in text and "latent_rope" not in text
    assert "sine" not in text


def test_kinds_and_what_their_blocks_keep():
    assert LAYER_KINDS == ("attention", "mamba", "linear_attention", "kda",
                           "latent_attention", "window_attention")
    assert REMAT_NAMES == ("attn_out", "mlp_in", "ssm_in", "gdn_in",
                           "kda_in", "latent_in", "scan_out")
    o = _options(CONFIG)
    # with the kernels the latent layer has a result to keep: 32 heads of
    # 128 and their float32 log-sum-exps, spent first
    flash = dataclasses.replace(o, use_flash=True).remat_widths(9216, 32)
    assert list(flash)[0] == "attn_out" and flash["attn_out"] == 4160
    widths = o.remat_widths(9216, 32)
    # kda_work is no result: it widens what remat_budget leaves the step
    assert widths == {"mlp_in": 18432, "kda_in": 12288, "latent_in": 6144,
                      "kda_work": 2 * 5 * 4096}
    kinds = [o.layer_type(i) for i in range(5)]
    assert kinds == ["kda", "kda", "kda", "latent_attention", "kda"]
    dense = [True, False, False, False, False]
    tokens = 16384
    cost = lambda name: tokens * widths[name] * 2
    everything = remat_plan(kinds, tokens, widths, 1 << 40, dense=dense)
    assert everything == (("mlp_in", "kda_in"), ("kda_in",), ("kda_in",),
                          ("latent_in",), ("kda_in",))
    # an expert layer has no mlp_in to keep: the budget goes on
    assert remat_plan(kinds, tokens, widths,
                      cost("mlp_in") + cost("kda_in"), dense=dense) \
        == (("mlp_in", "kda_in"), (), (), (), ())
    # without the flags every layer's MLP is the dense one (the other LM)
    assert remat_plan(kinds, tokens, widths, 2 * cost("mlp_in"))[:3] \
        == (("mlp_in",), ("mlp_in",), ())


def test_budget_leaves_the_scans_working_set_its_room():
    """On a v5e beside the cell's 7.23 GB of state: two 8192-token
    sequences leave nothing to keep (ahead of time the step compiles
    with nothing kept and with any one result kept does not), one
    sequence keeps every result."""
    import types

    from chainermn_tpu.models.transformer import remat_budget, remat_kept

    o = _options(CONFIG)
    widths = o.remat_widths(9216, 32)
    kinds = [o.layer_type(i) for i in range(5)]
    dense = [True, False, False, False, False]
    device = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": 16_911_433_728})
    state = [jax.ShapeDtypeStruct(
        (602_450_816 * 3,), jnp.float32,
        sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))]
    kept = lambda rows: remat_kept(remat_plan(
        kinds, rows * 8192, widths, remat_budget(
            device, state, rows * 8192, widths), dense=dense),
        rows * 8192, widths)[0]
    assert kept(2) == ""
    assert kept(1) == "mlp_in x1, kda_in x4, latent_in x1"


# -- the router's other forms ------------------------------------------------
def _layer_weights(weights, cfg, layer=1):
    return {n: weights[k] for n, k in ref.layer_leaves(
        ref.layer_kinds(cfg)[layer], layer).items()}


def _mlp_params(w):
    return {"params": {
        "router": w["router"], "router_bias": w["r_bias"],
        "expert_wg": w["w_gate"], "expert_wu": w["w_up"],
        "expert_wd": w["w_down"], "shared_wg": w["s_gate"],
        "shared_wu": w["s_up"], "shared_wd": w["s_down"]}}


ROUTER = RouterOptions(score="sigmoid", selection_bias=True,
                       routed_scale=2.446, shared_gated=False)


def _mlp(held=None, **kw):
    return MoeMlp(16, 32, k=4, routing="dropless", shared_d_ff=32,
                  dtype=jnp.float32, router_options=ROUTER, held=held, **kw)


def test_the_bias_moves_the_selection_and_not_the_weights(small_blocks):
    """A large bias on expert 3 puts it among every token's four; the
    weights of the routes are the scores' alone (those of the chosen
    over their sum, times the factor), the bias gets no gradient, and
    the counter says how many routes it changed."""
    layer = _layer_weights(ref.init_weights(ref.seed_key(9), UNCUT), UNCUT)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 64))
    scores = jax.nn.sigmoid(u[0] @ layer["router"])

    def run(bias):
        (y, _), sown = _mlp().apply(
            _mlp_params(dict(layer, r_bias=bias)), u,
            mutable=[COUNTERS, ROUTES])
        return y[0], sown[ROUTES]["chosen"][0], \
            int(sown[COUNTERS]["moe_routes_biased"][0])

    _, plain, changed = run(jnp.zeros(16))
    assert changed == 0
    np.testing.assert_array_equal(
        np.sort(plain, -1), np.sort(lax.top_k(scores, 4)[1], -1))
    pushed = jnp.zeros(16).at[3].set(10.0)
    y, chosen, changed = run(pushed)
    assert bool((chosen == 3).any(-1).all())
    own = lax.top_k(scores, 4)[1]
    assert changed == int(((chosen[:, :, None] != own[:, None, :])
                           .all(-1)).sum()) > 0
    # the reference's layer under the same bias: the same result
    w = dict(layer, r_bias=pushed)
    ein = ref._ein(False)
    want = ref.routed_part(u[0], w, UNCUT, ein)[0] \
        + ref._gated(u[0], w["s_gate"], w["s_up"], w["s_down"], ein)
    np.testing.assert_allclose(y, want, atol=2e-5)
    vals = jnp.take_along_axis(scores, chosen, -1)
    _, _, weights = ref.route(u[0], w["router"], pushed, UNCUT)
    np.testing.assert_allclose(
        jnp.sort(weights, -1),
        jnp.sort(2.446 * vals / vals.sum(-1, keepdims=True), -1), rtol=1e-6)
    grads = jax.grad(lambda p: _mlp().apply(
        p, u, mutable=[COUNTERS, ROUTES])[0][0].sum())(_mlp_params(w))
    assert float(jnp.abs(grads["params"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["params"]["router"]).max()) > 0.0


def test_shared_expert_without_a_gate_has_no_gate_parameter():
    x = jnp.zeros((1, 8, 16))
    # the trees' shapes: traced, nothing runs
    plain = jax.eval_shape(
        MoeMlp(4, 8, routing="dropless", shared_d_ff=8).init,
        jax.random.PRNGKey(0), x)["params"]
    bare = jax.eval_shape(
        MoeMlp(4, 8, routing="dropless", shared_d_ff=8,
               router_options=ROUTER).init,
        jax.random.PRNGKey(0), x)["params"]
    assert "shared_gate" in plain and "router_bias" not in plain
    assert "shared_gate" not in bare and bare["router_bias"].shape == (4,)


def test_router_options_come_with_dropless_routing():
    """The capacity-queue layer refuses them, and says which."""
    x = jnp.zeros((1, 4, 8))
    for kw in (dict(shared_d_ff=8), dict(router_options=ROUTER),
               dict(router_options=RouterOptions(routed_scale=2.0))):
        with pytest.raises(ValueError) as err:
            MoeMlp(4, 8, **kw).init(jax.random.PRNGKey(0), x)
        for word in ("ungated", "selection bias", "scaling factor",
                     "dropless"):
            assert word in str(err.value)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        MoeMlp(4, 8, routing="dropless",
               router_options=RouterOptions(score="tanh")).init(
            jax.random.PRNGKey(0), x)


# -- the shares ---------------------------------------------------------------
def test_shares_and_the_shared_expert_once_are_the_uncut_layer(
        small_blocks):
    """The guide's test at a small size: 8 shares of 2 of the 16 experts
    (the deployment's 32 of 8 of 256).  Each computes its own experts'
    routed part under the router all share (sigmoid scores, the bias in
    the choice, the factor on the weights), each route counted once;
    those and the ungated shared expert counted **once** are the uncut
    layer, in the reference and in the program (whose every share adds
    the shared expert: eight sums hold it eight times)."""
    ein = ref._ein(False)
    weights = ref.init_weights(ref.seed_key(21), UNCUT)
    weights["r_bias.1"] = 20.0 * weights["r_bias.1"]  # a bias that bites
    layer = _layer_weights(weights, UNCUT)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64))
    routed, want_aux, _ = ref.routed_part(u[0], layer, UNCUT, ein)
    shared = ref._gated(u[0], layer["s_gate"], layer["s_up"],
                        layer["s_down"], ein)
    (whole, aux), _ = _mlp().apply(_mlp_params(layer), u,
                                   mutable=[COUNTERS, ROUTES])
    np.testing.assert_allclose(whole[0], routed + shared, atol=2e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)

    total_ref, total, rows = 0.0, 0.0, 0
    for first in range(0, 16, 2):
        share = _layer_weights(
            ref.share_of(weights, UNCUT, first, 2, 0, 256), UNCUT)
        cfg = dict(UNCUT, num_experts=2, first_expert=first)
        part, aux, _ = ref.routed_part(u[0], share, cfg, ein)
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        total_ref = total_ref + part
        (y, _), sown = _mlp(held=(first, 2)).apply(
            _mlp_params(share), u, mutable=[COUNTERS, ROUTES])
        assert int(sown[COUNTERS]["moe_dropped"][0]) == 0
        rows += int(sown[COUNTERS]["moe_rows_routed"][0])
        total = total + y[0]
    assert rows == 96 * 4  # every route on exactly one share
    np.testing.assert_allclose(total_ref + shared, routed + shared,
                               atol=2e-5)
    np.testing.assert_allclose(total - 7 * shared, whole[0], atol=1e-4)


# -- the whole model ----------------------------------------------------------
def _model(cfg, dtype=jnp.float32, options=None, **kw):
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=cfg["moe_layer_freq"],
        k=cfg["num_experts_per_token"], dtype=dtype,
        options=options or _options(cfg), routing="dropless",
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"], router_options=ROUTER,
        first_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], tie_head=False, **kw)


def _tokens(cfg, rows=2, s=80, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, s), dtype=np.int32)


def _apply(model, tree, tokens):
    return model.apply(tree, tokens, mutable=[COUNTERS, ROUTES])[0]


def test_the_first_layer_is_dense_and_the_rest_route():
    """``first_dense=1`` under ``moe_every=1``: layer 0 is the other
    LM's block with a gated MLP of ``dense_d_ff``, layers 1..4 expert
    layers; four layers sow routes; the tree is the reference's leaves,
    the parameters its count."""
    cfg = SHARE
    model = _model(cfg)
    assert [model.sparse_layer(i) for i in range(5)] \
        == [False, True, True, True, True]
    tokens = _tokens(cfg)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    params = variables["params"]
    assert sorted(b for b in params if "Block" in b) == [
        "MoeTransformerBlock_0", "MoeTransformerBlock_1",
        "MoeTransformerBlock_2", "MoeTransformerBlock_3",
        "TransformerBlock_0"]
    assert params["TransformerBlock_0"]["GatedMlp_0"]["in_proj"][
        "kernel"].shape == (64, 2 * 128)
    assert "KdaMixer_0" in params["TransformerBlock_0"]
    assert "LatentAttention_0" in params["MoeTransformerBlock_2"]
    assert len(jax.tree_util.tree_leaves(variables[ROUTES])) == 4
    assert "pos_embed" not in params
    got = train_kimilinear.keyed_leaves(ref, {"params": params}, cfg)
    shapes = ref._shapes(cfg)
    assert {k: v.shape for k, v in got.items()} == {
        key: shapes[name] for key, name, _ in ref.leaves(cfg)}
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == ref.n_parameters(cfg)
    # the other path refuses what it cannot build
    with pytest.raises(ValueError, match="leading dense"):
        MoeTransformerLM(vocab_size=64, first_dense=1).init(
            jax.random.PRNGKey(0), tokens)


def test_parameter_count_at_the_published_widths_by_hand():
    """The cut the configuration's file describes, and the whole
    model."""
    cut = CONFIG
    assert ref.n_parameters(cut) == 602_450_816
    shapes = ref._shapes(cut)
    count = lambda names: sum(int(np.prod(shapes[n])) for n in names)
    assert count(ref.KIND_LEAVES["kda"]) == 39_518_368
    assert count(ref.KIND_LEAVES["latent_attention"]) == 29_114_880
    assert count(ref.KIND_LEAVES["dense"]) == 63_700_992
    assert count(("router", "r_bias")) == 590_080
    assert count(("s_gate", "s_up", "s_down")) == 7_077_888
    whole = dict(cut, **cut["published"], router_experts=256)
    assert ref.n_parameters(whole) == pytest.approx(49.12e9, rel=1e-3)
    kinds = ref.layer_kinds(whole)
    assert sum(m == "kda" for m, _ in kinds) == 20
    assert [i + 1 for i, (m, _) in enumerate(kinds)
            if m == "latent_attention"] == [4, 8, 12, 16, 20, 24, 27]


def test_model_logits_against_reference():
    weights = ref.init_weights(ref.seed_key(3), SHARE)
    tokens = _tokens(SHARE)
    tree = train_kimilinear.program_tree(ref, weights, SHARE)
    logits, _ = jax.jit(lambda p: _apply(_model(SHARE), p, tokens))(tree)
    want = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE))(weights)
    assert _max_rel(logits, want) < 1e-4
    # the control is the same mathematics in scaled float8: apart
    low = jax.jit(lambda w: ref.logits_fn(w, tokens, SHARE, lowp=True))(
        weights)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)


def test_model_loss_gradients_and_an_adamw_step_against_reference():
    """Loss and every gradient leaf of the float32 model against
    ``jax.value_and_grad`` of the reference's whole-model loss (the
    selection biases' is 0 on both sides); the reference's
    layer-at-a-time ``train_readings`` against both, and its parameters'
    change against one step of the example's optimizer (AdamW, the
    biases out of the decay) on the program's tree.  Over 40 s in the
    driver's run (three gradient programs at rehearsal size, five
    layers of two mixer kinds with experts): the only case that holds
    every leaf's gradient and update against the reference ``correct``
    is decided by."""
    import optax

    from chainermn_tpu.models.moe_transformer import moe_lm_loss

    cfg, opt_cfg = SHARE, {"lr": 1e-3, "weight_decay": 0.01}
    weights = ref.init_weights(ref.seed_key(5), cfg)
    tokens = _tokens(cfg, seed=1)
    tree = train_kimilinear.program_tree(ref, weights, cfg)
    model = _model(cfg)
    loss_of = lambda p: moe_lm_loss(_apply(model, p, tokens), tokens,
                                    aux_coef=cfg["aux_loss_coef"])
    loss, grads = jax.jit(jax.value_and_grad(loss_of))(tree)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: ref.batch_loss(w, tokens, cfg)))(weights)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = train_kimilinear.keyed_leaves(ref, grads, cfg)
    assert set(got) == set(want) == set(ref.leaf_keys(cfg))
    for name in got:
        if name.startswith("r_bias"):
            assert float(jnp.abs(got[name]).max()) \
                == float(jnp.abs(want[name]).max()) == 0.0
        else:
            assert _max_rel(got[name], want[name]) < 2e-3, name

    readings = ref.train_readings(5, cfg, tokens[None], opt_cfg)
    assert abs(readings["losses"][0] - float(want_loss)) \
        < 1e-5 * float(want_loss)
    for name, norm in readings["grad_norms"].items():
        assert abs(norm - float(jnp.linalg.norm(want[name]))) \
            < 1e-3 * max(norm, 1e-6), name
    assert set(readings["grad_small"]) == {
        k for k, x in weights.items() if x.size <= ref.SMALL}
    assert len(readings["routes"][0]) == 4  # the expert layers'
    opt = optax.adamw(
        opt_cfg["lr"], weight_decay=opt_cfg["weight_decay"],
        mask=lambda t: jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key != "router_bias", t))
    updates, _ = opt.update(grads, opt.init(tree), tree)
    deltas = train_kimilinear.keyed_leaves(ref, updates, cfg)
    for name, norm in readings["delta_norms"].items():
        if name.startswith("r_bias"):  # a constant of the run: rounding
            # between two programs that draw it, no more
            assert float(jnp.linalg.norm(deltas[name])) == 0.0
            assert norm < 1e-7 * float(jnp.linalg.norm(weights[name]))
        else:
            assert abs(norm - float(jnp.linalg.norm(deltas[name]))) \
                < 2e-3 * norm, name


def test_reference_follows_a_programs_routes_inside_its_window():
    """Handed the program's routes the reference takes those inside its
    tie window and refuses the rest; handed its own it follows none."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(11), cfg)
    tokens = _tokens(cfg, seed=4)
    _, reports = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, report=True))(weights)
    own = jnp.stack([r["chosen"] for r in reports])
    assert own.shape == (4, tokens.size, 4)
    _, again = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, routes=own, report=True))(weights)
    assert sum(float(r["followed"]) + float(r["refused"])
               for r in again) == 0.0
    # a program that sent every token's last route to expert 0
    other = own.at[:, :, -1].set(0)
    _, forced = jax.jit(lambda w: ref.batch_loss(
        w, tokens, cfg, routes=other, report=True))(weights)
    assert sum(float(r["refused"]) for r in forced) > 0.0


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    """``remat_blocks`` with a plan that keeps the dense layer's
    ``mlp_in``, ``kda_in`` and ``latent_in``: one parameter tree, the
    same loss and gradients as without."""
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(6), cfg)
    tokens = _tokens(cfg, seed=2)
    tree = train_kimilinear.program_tree(ref, weights, cfg)

    def loss_and_grads(options):
        model = _model(cfg, options=options, return_hidden=True)
        return jax.jit(jax.value_and_grad(lambda p: (
            _apply(model, p, tokens)[0] ** 2).mean()))(tree)

    plain = loss_and_grads(_options(cfg))
    kept = _options(cfg, remat_blocks=True, remat_budget_bytes=1 << 30)
    assert _model(cfg, options=kept).remat_plan(tokens.size) == (
        ("mlp_in", "kda_in"), ("kda_in",), ("kda_in",), ("latent_in",),
        ("kda_in",))
    again = loss_and_grads(kept)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(again[1]),
                    jax.tree_util.tree_leaves(plain[1])):
        np.testing.assert_allclose(a, b, atol=1e-6 + 1e-4 * float(
            jnp.abs(b).max()))


def test_the_cells_files_say_what_the_issue_asked_for():
    """Published widths, the cut and the cell's traffic, as files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"]
            if w["name"] == "kimilinear48b_train_s8192"]
    assert len(cell) == 1 and cell[0]["chips"] == 1 \
        and cell[0]["config"] == "kimi-linear-48b-a3b" \
        and cell[0]["traffic"] == "train_kda_s8192"
    entry = [c for c in bench["configs"]
             if c["name"] == "kimi-linear-48b-a3b"][0]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in dict(
            hidden_size=2304, intermediate_size=9216, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            moe_intermediate_size=1024, num_experts_per_token=8,
            routed_scaling_factor=2.446, first_k_dense_replace=1,
            num_attention_heads=32, num_hidden_layers=5, num_experts=8,
            vocab_size=20480, router_experts=256).items():
        assert CONFIG[key] == value, key
    lin = CONFIG["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    with open(os.path.join(ROOT, "cellbench", "traffic",
                           "train_kda_s8192.json")) as f:
        traffic = json.load(f)
    assert traffic["seq_len"] == 8192
    mine = [m for m in bench["per_layer"]
            if m["name"].endswith(".kimilinear")]
    # PR 43's sixteen and the convolution's time (PR 45)
    assert len(mine) == 17 and all(
        m["workloads"] == ["kimilinear48b_train_s8192"] for m in mine)
    for m in mine:
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "layer_metrics", m["name"] + ".json"))
