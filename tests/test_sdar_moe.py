"""SDAR-MoE block-diffusion training: the block-causal grouped-query
kernels against dense masks, the model against the plain reference
(``cellbench/reference/sdar_moe.py``), one chip's share against the
uncut model, routing without drops, the cell's rehearsal and the
benchmark's operation counts.  CPU, seeded, small; Pallas interpreted."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import flops_sdar  # noqa: E402
from cellbench.reference import sdar_moe as ref  # noqa: E402
from cellbench.runners import train_sdar  # noqa: E402
from chainermn_tpu.models.moe_transformer import (  # noqa: E402
    COUNTERS,
    ROUTES,
    MoeMlp,
    MoeTransformerLM,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    BlockOptions,
    block_diffusion_loss,
    noised_copy,
)
from chainermn_tpu.ops import grouped_matmul as gm  # noqa: E402
from chainermn_tpu.ops import pallas_attention as pa  # noqa: E402
from chainermn_tpu.parallel import expert_parallel  # noqa: E402
from chainermn_tpu.parallel.expert_parallel import (  # noqa: E402
    held_experts_moe,
)

#: the configuration's rehearsal sizes, uncut (all 8 experts, 256 rows)
UNCUT = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    router_experts=8, num_experts=8, first_expert=0,
    num_experts_per_tok=2, vocab_size=256, mask_token_id=255,
    rope_theta=1e6, rms_norm_eps=1e-6, block_length=4,
    aux_loss_coef=1e-3, t_min=1e-3, route_tie_window=0.03,
)
#: one chip's share of it: experts 2..5, the first 64 rows
SHARE = dict(UNCUT, num_experts=4, first_expert=2, vocab_size=64,
             mask_token_id=63)


@pytest.fixture
def small_blocks(monkeypatch):
    """Buffer blocks of 8 rows, so that a few dozen tokens fill, pad and
    overflow the sorted buffer."""
    monkeypatch.setattr(expert_parallel, "HELD_BLOCK_ROWS", 8)


def _model(cfg, dtype=jnp.float32, **kw):
    return MoeTransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        n_experts=cfg["router_experts"],
        d_ff=cfg["moe_intermediate_size"], moe_every=1,
        k=cfg["num_experts_per_tok"], dtype=dtype,
        options=BlockOptions(
            norm="rmsnorm", n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
            qk_norm=True, block_diffusion=cfg["block_length"],
            use_flash=True),
        routing="dropless",
        held=(cfg["first_expert"], cfg["num_experts"]),
        tie_head=False, **kw)


def _batch(cfg, rows=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg["mask_token_id"], (rows, s),
                          dtype=np.int32)
    t = np.repeat(rng.uniform(0.2, 1.0, (rows, s // 4)), 4, axis=1)
    mask = rng.uniform(size=(rows, s)) < t
    return tokens, mask, np.where(mask, 1.0 / t, 0.0).astype(np.float32)


# -- the kernels against dense masks ---------------------------------------
def _dense(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * q.shape[-1] ** -0.5
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), vv)


@pytest.mark.parametrize("geometry", [
    dict(), dict(block_size=32, tile=8), dict(block_size=16),
], ids=["whole", "tiled", "blocks16"])
@pytest.mark.parametrize("strict", [False, True],
                         ids=["inclusive", "strict"])
def test_block_causal_kernels_against_dense_mask(strict, geometry):
    """Forward, dq, dk and dv (summed over the group) of both forms."""
    b, s, hq, hkv, d, block = 2, 64, 4, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    # a strict launch's first block sees no key: its rows are weighed 0
    w = jax.random.normal(ks[3], (b, s, hq, d)) * (
        jnp.arange(s) >= (block if strict else 0))[None, :, None, None]
    mask = pa.block_causal_mask(s, block, strict)
    mask = mask.at[:block, 0].set(True) if strict else mask

    def kernel(q, k, v):
        out, _ = pa.block_causal_attention_with_lse(
            q, k, v, block, strict, **geometry)
        return jnp.sum(out * w)

    got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(_dense(q, k, v, mask) * w),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=2e-5)


def test_block_causal_lse_and_its_gradient():
    b, s, hq, hkv, d = 1, 32, 4, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v = (jax.random.normal(ks[i], (b, s, h, d))
               for i, h in enumerate((hq, hkv, hkv)))
    w = jax.random.normal(ks[3], (b, s, hq))

    def dense_lse(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bqhk", q,
                        jnp.repeat(k, hq, axis=2)) * d ** -0.5
        sc = jnp.where(pa.block_causal_mask(s, 4)[None, :, None, :], sc,
                       -jnp.inf)
        return jnp.sum(jax.nn.logsumexp(sc, axis=-1) * w)

    got = jax.grad(lambda q, k, v: jnp.sum(
        pa.block_causal_attention_with_lse(q, k, v, 4)[1] * w),
        (0, 1, 2))(q, k, v)
    for g, r in zip(got, jax.grad(dense_lse, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-5)


@pytest.mark.parametrize("geometry", [dict(), dict(block_size=32, tile=8)],
                         ids=["whole", "tiled"])
def test_block_diffusion_attention_against_its_dense_mask(geometry):
    b, s, hq, hkv, d, block = 2, 64, 4, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, 2 * s, hq, d))
    k = jax.random.normal(ks[1], (b, 2 * s, hkv, d))
    v = jax.random.normal(ks[2], (b, 2 * s, hkv, d))
    w = jax.random.normal(ks[3], (b, 2 * s, hq, d))
    got = jax.value_and_grad(lambda *a: jnp.sum(
        pa.block_diffusion_attention(*a, block, **geometry) * w),
        (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(
        _dense(*a, pa.block_diffusion_mask(s, block)) * w),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=3e-5)


def test_diffusion_mask_of_reference_and_program_agree():
    s, block = 16, 4
    want = np.asarray(ref.diffusion_mask(jnp.arange(2 * s), s, block))
    np.testing.assert_array_equal(
        np.asarray(pa.block_diffusion_mask(s, block)), want)
    # a clean query of block 1 sees clean blocks 0..1; a noised one the
    # clean block 0 and its own noised block
    assert want[5].nonzero()[0].tolist() == list(range(8))
    assert want[s + 5].nonzero()[0].tolist() == [0, 1, 2, 3] + [
        s + 4, s + 5, s + 6, s + 7]


def test_block_causal_launch_keeps_the_causal_census():
    """The block-causal family classifies blocks and tiles as the causal
    kernels do, and the causal equal-heads launch is what it was."""
    cell = pa.launch_census(2048, 2048, 128)
    assert (cell["fwd"]["tile"], cell["fwd"]["executed_units"]) == (512, 2.5)
    assert (cell["bwd"]["tile"], cell["bwd"]["executed_units"]) == (256, 2.25)
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    for kind, tile in (("fwd", 512), ("bwd", 256)):
        group, bs, t = pa._bc_geometry(q, k, 4, None, False, kind, None)
        assert (group, bs, t) == (8, 1024, tile)
        census = pa.block_census(8192, 8192, bs, bs, True, kind)
        assert (census["dead"], census["interior"], census["masked"]) \
            == (28, 28, 8)


def test_block_causal_refuses_what_it_cannot_tile():
    q = jnp.zeros((1, 48, 4, 8))
    with pytest.raises(ValueError, match="whole number"):
        pa.block_causal_attention_with_lse(q, q[:, :, :2], q[:, :, :2], 4,
                                           block_size=32)
    with pytest.raises(ValueError, match="hq % hkv"):
        pa.block_causal_attention_with_lse(q, q[:, :, :3], q[:, :, :3], 4)


# -- the grouped product -------------------------------------------------------
@pytest.mark.parametrize("transpose", [False, True])
def test_grouped_matmul_kernel_against_xla(transpose):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (48, 16))
    w = jax.random.normal(ks[1], (3, 16, 24))
    cot = jax.random.normal(ks[2], (48, 24))
    groups = jnp.array([0, 0, 1, 2, 2, 2], jnp.int32)
    if transpose:
        got = gm._rows_product(cot, w, groups, 8, True, True)
        want = gm._rows_product(cot, w, groups, 8, True, None)
        np.testing.assert_allclose(got, want, atol=1e-5)
        return
    f = lambda interpret: jax.value_and_grad(
        lambda x, w: jnp.sum(gm.grouped_matmul(x, w, groups, 8, interpret)
                             * cot), (0, 1))(x, w)
    got, want = f(True), f(None)
    by_hand = sum(jnp.sum((x[8 * b:8 * b + 8] @ w[g])
                          * cot[8 * b:8 * b + 8])
                  for b, g in enumerate([0, 0, 1, 2, 2, 2]))
    np.testing.assert_allclose(got[0], by_hand, rtol=1e-5)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-5)


# -- the model against the plain reference ---------------------------------
def _program_params(weights, cfg):
    return train_sdar.program_tree(weights, cfg["num_hidden_layers"])


def test_model_logits_of_both_copies_against_reference():
    weights = ref.init_weights(ref.seed_key(11), SHARE)
    tokens, mask, _ = _batch(SHARE)
    both = noised_copy(jnp.asarray(tokens), jnp.asarray(mask),
                       SHARE["mask_token_id"])
    want, want_aux = ref.logits_fn(weights, both, SHARE)
    (got, got_aux), sown = _model(SHARE).apply(
        _program_params(weights, SHARE), both, mutable=[COUNTERS, ROUTES])
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(got_aux, want_aux, rtol=1e-5)
    dropped = jax.tree_util.tree_leaves(sown[COUNTERS])
    assert len(dropped) == 3 * SHARE["num_hidden_layers"]


def test_model_loss_and_every_gradient_leaf_against_reference():
    cfg = SHARE
    weights = ref.init_weights(ref.seed_key(12), cfg)
    batch = tuple(jnp.asarray(x) for x in _batch(cfg, seed=1))
    model = _model(cfg, return_hidden=True)

    def loss(params):
        tokens, mask, w = batch
        (hidden, aux), _ = model.apply(
            params, noised_copy(tokens, mask, cfg["mask_token_id"]),
            mutable=[COUNTERS, ROUTES])
        return block_diffusion_loss(
            hidden, params["params"]["lm_head"], tokens, w,
            dtype=jnp.float32) + cfg["aux_loss_coef"] * aux

    got, grads = jax.value_and_grad(loss)(_program_params(weights, cfg))
    want, want_grads = jax.value_and_grad(ref.batch_loss)(weights, batch,
                                                          cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    got_leaves = train_sdar.keyed_leaves(grads, cfg["num_hidden_layers"])
    assert set(got_leaves) == set(ref.leaf_keys(cfg))
    for key, g in got_leaves.items():
        name, _, layer = key.partition(".")
        r = want_grads[name][int(layer)] if layer else want_grads[name]
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(g, r, atol=2e-4 * scale + 1e-8,
                                   err_msg=key)


# -- one chip's share against the uncut model ------------------------------
def test_expert_layer_shares_add_up_to_the_uncut_layer(small_blocks):
    """Each of the 8 shares computes its own experts' part, each route
    counted once; together they are the uncut reference's layer."""
    uncut = dict(UNCUT, num_experts_per_tok=3)
    weights = ref.init_weights(ref.seed_key(21), uncut)
    layer = {n: weights[n][0] for n in ref.LAYER_LEAVES}
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64))
    want, want_aux = ref.expert_layer(u[0], layer, uncut, ref._ein(False))
    total, routed = 0.0, 0
    for first in range(8):
        mlp = MoeMlp(8, 32, k=3, routing="dropless", held=(first, 1),
                     dtype=jnp.float32)
        share = ref.share_of(weights, first, 1, 0, 256)
        params = {"params": {
            "router": share["router"][0], "expert_wg": share["w_gate"][0],
            "expert_wu": share["w_up"][0], "expert_wd": share["w_down"][0]}}
        (y, aux), sown = mlp.apply(params, u, mutable=[COUNTERS, ROUTES])
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        assert int(sown[COUNTERS]["moe_dropped"][0]) == 0
        routed += int(sown[COUNTERS]["moe_rows_routed"][0])
        total = total + y[0]
    assert routed == 96 * 3  # every route on exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_vocabulary_slices_give_the_uncut_logits_columns():
    weights = ref.init_weights(ref.seed_key(22), UNCUT)
    rows = 32  # 8 slices of 32 rows
    cfg = dict(UNCUT, vocab_size=rows)
    rng = np.random.default_rng(4)
    uncut = jax.jit(lambda tokens: ref.logits_fn(weights, tokens, UNCUT)[0])
    sliced = jax.jit(lambda params, tokens: _model(cfg).apply(
        params, tokens, mutable=[COUNTERS, ROUTES])[0][0])
    for c in range(8):
        local = rng.integers(0, rows, (1, 2 * 32), dtype=np.int32)
        share = ref.share_of(weights, 0, 8, c * rows, rows)
        got = sliced(_program_params(share, cfg), jnp.asarray(local))
        want = uncut(jnp.asarray(local + c * rows))
        np.testing.assert_allclose(
            got, want[..., c * rows:(c + 1) * rows], atol=2e-4)


def test_expert_axis_runs_the_same_layer_on_every_chip(small_blocks):
    """Over a 4-chip expert axis each chip holds 2 of 8 experts; the sum
    over the axis is the uncut layer."""
    from jax.sharding import Mesh, PartitionSpec as P

    weights = ref.init_weights(ref.seed_key(23), UNCUT)
    layer = {n: weights[n][0] for n in ref.LAYER_LEAVES}
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 64))
    want, _ = ref.expert_layer(u[0], layer, UNCUT, ref._ein(False))
    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("experts",))
    mlp = MoeMlp(8, 32, k=2, routing="dropless", expert_axis="experts",
                 dtype=jnp.float32)
    params = {"params": {
        "router": layer["router"], "expert_wg": layer["w_gate"],
        "expert_wu": layer["w_up"], "expert_wd": layer["w_down"]}}
    specs = {"params": {"router": P(), "expert_wg": P("experts"),
                        "expert_wu": P("experts"),
                        "expert_wd": P("experts")}}
    got = jax.jit(jax.shard_map(
        lambda p, u: mlp.apply(p, u, mutable=[COUNTERS, ROUTES])[0][0],
        mesh=mesh, in_specs=(specs, P()), out_specs=P()))(params, u)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# -- routing without drops -------------------------------------------------
def test_no_route_is_dropped_when_every_route_lands_on_held_experts(small_blocks):
    """A router that sends all k routes of every token to the held
    experts: 8 times the balanced load, past any buffer, so the exact
    path runs; the result is the reference's and nothing is dropped."""
    cfg = dict(router_experts=16, num_experts=4, first_expert=4,
               num_experts_per_tok=4)
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    t, d, f = 64, 32, 16
    x = jnp.abs(jax.random.normal(ks[0], (t, d))) + 0.1
    held = (jnp.arange(16) >= 4) & (jnp.arange(16) < 8)
    router = jnp.where(held[None, :], 1.0, -1.0) \
        + 0.05 * jax.random.normal(ks[1], (d, 16))
    wg, wu = (0.2 * jax.random.normal(k, (4, d, f)) for k in ks[2:4])
    wd = 0.2 * jax.random.normal(ks[4], (4, f, d))
    cot = jax.random.normal(ks[5], (t, d))

    def program(x, router, wg, wu, wd):
        y, aux, counters, _ = held_experts_moe(
            x, router, wg, wu, wd, num_experts=16, k=4, first=4)
        return jnp.sum(y * cot) + 0.1 * aux, counters

    def reference(x, router, wg, wu, wd):
        y, aux = ref.expert_layer(
            x, dict(router=router, w_gate=wg, w_up=wu, w_down=wd), cfg,
            ref._ein(False))
        return jnp.sum(y * cot) + 0.1 * aux

    args = (x, router, wg, wu, wd)
    (got, counters), grads = jax.value_and_grad(
        program, (0, 1, 2, 3, 4), has_aux=True)(*args)
    want, want_grads = jax.value_and_grad(reference, (0, 1, 2, 3, 4))(*args)
    assert int(counters["moe_rows_routed"]) == t * 4
    assert int(counters["moe_dropped"]) == 0
    assert int(counters["moe_rows_computed"]) >= t * 4
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, r in zip(grads, want_grads):
        np.testing.assert_allclose(g, r, atol=1e-5 * float(jnp.abs(r).max())
                                   + 1e-7)


@pytest.mark.parametrize("buffer_factor", [2.0, 0.1],
                         ids=["buffer", "exact_path"])
def test_both_paths_of_the_held_layer_agree_with_reference(
        buffer_factor, small_blocks, monkeypatch):
    monkeypatch.setattr(expert_parallel, "HELD_BUFFER_FACTOR", buffer_factor)
    cfg = dict(router_experts=8, num_experts=4, first_expert=2,
               num_experts_per_tok=2)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    t, d, f = 96, 32, 16
    x = jax.random.normal(ks[0], (t, d))
    router = 0.5 * jax.random.normal(ks[1], (d, 8))
    wg, wu = (0.2 * jax.random.normal(k, (4, d, f)) for k in ks[2:4])
    wd = 0.2 * jax.random.normal(ks[4], (4, f, d))
    y, aux, counters, _ = held_experts_moe(
        x, router, wg, wu, wd, num_experts=8, k=2, first=2)
    want, want_aux = ref.expert_layer(
        x, dict(router=router, w_gate=wg, w_up=wu, w_down=wd), cfg,
        ref._ein(False))
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
    assert int(counters["moe_dropped"]) == 0
    assert int(counters["moe_rows_computed"]) == (
        4 * t if buffer_factor < 1 else 8 * (
            int(np.ceil(buffer_factor * t * 2 * 4 / 8 / 8)) + 4))


# -- the movement from buffer rows to token rows ---------------------------
def _handmade_routes():
    """64 tokens' top 8 of 128, experts 16..31 held: token 0 has all 8
    routes held, tokens 1..9 none, held expert 31 gets no route (an
    empty run), expert 17 exactly 8 (its run ends on a block edge)."""
    t, k = 64, 8
    chosen = np.tile(64 + np.arange(k), (t, 1))  # held elsewhere
    chosen[0] = 16 + np.arange(k)
    chosen[10:17, 0] = 17
    rng = np.random.default_rng(5)
    for p in range(20, t):
        n = rng.integers(0, 4)
        chosen[p, rng.choice(k, n, replace=False)] = rng.choice(
            np.arange(18, 31), n, replace=False)
    return chosen, 128, 16, 16


def _random_routes(t, k, num_experts, seed):
    logits = np.random.default_rng(seed).normal(size=(t, num_experts))
    return np.argsort(-logits, axis=-1)[:, :k]


#: name -> (chosen, num_experts, first, count)
ROUTE_CASES = {
    "handmade": _handmade_routes(),
    "random": (_random_routes(64, 8, 128, 1), 128, 32, 16),
    "all_held": (_random_routes(32, 2, 8, 2), 8, 0, 8),
}
#: row width of the tests' buffers: up to 4 pieces of 128 columns
WIDTH = 512


@pytest.fixture(params=[1, 2, 4], ids=lambda n: f"pieces{n}")
def pieces(request, monkeypatch):
    """The buffer is gathered whole, or cut into 2 or 4 column pieces:
    the limit is set so that the cases' buffers (192 to 256 float32
    rows of ``WIDTH``) come to that number."""
    monkeypatch.setattr(expert_parallel, "GATHER_OPERAND_BYTES",
                        256 * WIDTH * 4 // request.param)
    return request.param


def _plan_of(case):
    """The plan of a case (8-row blocks: call under ``small_blocks``)
    and its dense form ``(tokens, k, rows)``: 1 where a route has a
    buffer row."""
    chosen, num_experts, first, count = ROUTE_CASES[case]
    t, k = chosen.shape
    n_blocks = expert_parallel.held_buffer_blocks(t, num_experts, k, count)
    plan = expert_parallel.plan_held_routes(
        jnp.asarray(chosen, jnp.int32), first, count, 8, n_blocks)
    assert not bool(plan.overflow)
    return plan, jax.nn.one_hot(plan.row, n_blocks * 8, dtype=jnp.float32)


def test_handmade_routes_hold_the_cases_they_name(small_blocks):
    chosen, _, first, count = ROUTE_CASES["handmade"]
    plan, dense = _plan_of("handmade")
    held = (chosen >= first) & (chosen < first + count)
    assert held[0].all() and not held[1:10].any()
    per_expert = [(chosen == first + e).sum() for e in range(count)]
    assert per_expert[1] == 8 and per_expert[15] == 0
    # every held route has one row, every row one route; expert 17's
    # run fills block 1 and the next block is the next expert's
    np.testing.assert_array_equal(dense.sum(-1), held)
    assert float(dense.sum((0, 1)).max()) == 1.0
    assert int(plan.block_group[1]) == 1 and int(plan.block_group[2]) == 2
    assert (np.asarray(plan.route_of_row[8:16]) < chosen.size).all()


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["gate_weighted", "weight_one"])
@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_sum_rows_by_token_against_one_hot(case, weighted, pieces,
                                           small_blocks):
    plan, dense = _plan_of(case)
    t, k, rows = dense.shape
    assert expert_parallel.gather_column_pieces(rows, WIDTH, 4) == pieces
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    rows_of = jax.random.normal(ks[0], (rows, WIDTH))
    weights = jax.random.uniform(ks[1], (t, k)) if weighted else None
    got = expert_parallel.sum_rows_by_token(rows_of, weights, plan)
    want = jnp.einsum("pjr,pj,rd->pd", dense,
                      weights if weighted else jnp.ones((t, k)), rows_of)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-5)
    low = expert_parallel.sum_rows_by_token(
        rows_of.astype(jnp.bfloat16), weights, plan)
    assert low.dtype == jnp.bfloat16  # float32 sum, one rounding
    np.testing.assert_allclose(low.astype(jnp.float32), want, atol=0.05)


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_dispatch_and_combine_gradients_against_one_hot(case, pieces,
                                                        small_blocks):
    plan, dense = _plan_of(case)
    t, k, rows = dense.shape
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (t, WIDTH))
    out = jax.random.normal(ks[1], (rows, WIDTH))
    weights = jax.random.uniform(ks[2], (t, k))
    cot_rows = jax.random.normal(ks[3], (rows, WIDTH))
    cot = jax.random.normal(ks[4], (t, WIDTH))

    def program(x, out, weights):
        return (jnp.sum(expert_parallel.dispatch_rows(x, plan) * cot_rows)
                + jnp.sum(expert_parallel.combine_rows(out, weights, plan)
                          * cot))

    def one_hot(x, out, weights):
        return (jnp.sum(jnp.einsum("pjr,pd->rd", dense, x) * cot_rows)
                + jnp.sum(jnp.einsum("pjr,pj,rd->pd", dense, weights, out)
                          * cot))

    np.testing.assert_allclose(program(x, out, weights),
                               one_hot(x, out, weights), rtol=1e-5)
    got = jax.grad(program, (0, 1, 2))(x, out, weights)
    want = jax.grad(one_hot, (0, 1, 2))(x, out, weights)
    for g, w in zip(got, want):  # sums over WIDTH columns in float32
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n_pieces", [1, 2], ids=["whole", "pieces2"])
def test_expert_axis_gradients_are_the_uncut_layers(n_pieces, small_blocks,
                                                    monkeypatch):
    """Under ``shard_map`` (2 of 8 experts a chip, the buffer gathered
    whole or in two column pieces) every gradient of the summed parts
    is the uncut layer's: the custom gradients' cotangents vary as
    their primals do."""
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = dict(UNCUT, hidden_size=256)
    rows = 8 * expert_parallel.held_buffer_blocks(64, 8, 2, 2)
    monkeypatch.setattr(expert_parallel, "GATHER_OPERAND_BYTES",
                        rows * 256 * 4 // n_pieces)
    assert expert_parallel.held_rows_moved(
        64, 8, 2, 2, width=256, itemsize=4)["pieces"] == n_pieces
    weights = ref.init_weights(ref.seed_key(24), cfg)
    layer = {n: weights[n][0] for n in ref.LAYER_LEAVES}
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    u = jax.random.normal(ks[0], (1, 64, 256))
    cot = jax.random.normal(ks[1], (64, 256))
    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("experts",))
    mlp = MoeMlp(8, 32, k=2, routing="dropless", expert_axis="experts",
                 dtype=jnp.float32)
    names = dict(router="router", expert_wg="w_gate", expert_wu="w_up",
                 expert_wd="w_down")
    params = {"params": {p: layer[r] for p, r in names.items()}}
    specs = {"params": {p: P() if p == "router" else P("experts")
                        for p in names}}
    part = jax.shard_map(
        lambda p, u: mlp.apply(p, u, mutable=[COUNTERS, ROUTES])[0][0],
        mesh=mesh, in_specs=(specs, P()), out_specs=P())
    got = jax.jit(jax.grad(
        lambda p, u: jnp.sum(part(p, u)[0] * cot), (0, 1)))(params, u)
    want = jax.grad(lambda layer, u: jnp.sum(ref.expert_layer(
        u[0], layer, cfg, ref._ein(False))[0] * cot), (0, 1))(layer, u)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    for p, r in names.items():
        np.testing.assert_allclose(got[0]["params"][p], want[0][r],
                                   atol=2e-5)


def test_rows_moved_at_the_cells_shapes():
    """The static census at ``sdar30b_train_bd4_s8192``'s layer: the
    rows gathered are the parent's 335 872; what changed is the operand
    a route's gather reads from, a quarter of the buffer's columns."""
    moved = expert_parallel.held_rows_moved(16384, 128, 8, 16, width=2048)
    assert moved["buffer_rows"] == 36864
    assert moved["pieces"] == 4 and moved["piece_bytes"] == 36 << 20
    assert moved["rows"] == {
        "dispatch": 36864, "combine": 131072, "combine_backward": 36864,
        "dispatch_backward": 131072}
    assert sum(moved["rows"].values()) == 335872
    assert moved["gathers"] == {
        "dispatch": 1, "combine": 32, "combine_backward": 1,
        "dispatch_backward": 32}


@pytest.mark.parametrize("shape, pieces", [
    ((64, 8, 2, 8, 64), 1),         # a small layer holding all 8 experts
    ((16384, 128, 8, 16, 128), 1),  # 128 columns are not cut
    ((8192, 128, 8, 16, 2048), 2),  # half the cell's tokens: 76 MiB
    ((16384, 128, 8, 128, 2048), 16),  # all 128 held: pieces of 128 columns
], ids=["small", "narrow", "half", "all_held"])
def test_gather_pieces_follow_the_buffers_bytes(shape, pieces):
    *layer, width = shape
    moved = expert_parallel.held_rows_moved(*layer, width=width)
    assert moved["pieces"] == pieces
    assert moved["gathers"]["combine"] == layer[2] * pieces
    assert moved["piece_bytes"] * pieces == moved["buffer_rows"] * width * 2
    assert pieces == 1 or width // pieces == 128 \
        or moved["piece_bytes"] <= expert_parallel.GATHER_OPERAND_BYTES


def test_reference_follows_a_programs_route_only_inside_the_tie_window():
    cfg = dict(num_experts_per_tok=2, route_tie_window=0.03)
    # one token, four experts: probabilities 0.4, 0.3, 0.295, 0.005
    logits = jnp.log(jnp.array([[0.4, 0.3, 0.295, 0.005]]))
    u, router = jnp.ones((1, 1)), logits
    own = ref.route(u, router, cfg)[1]
    assert sorted(np.asarray(own)[0].tolist()) == [0, 1]
    # expert 2 is within 3 % of expert 1: the program's route is followed,
    # with the reference's own probabilities as the gate weights
    _, near, weights = ref.route(u, router, cfg, prefer=jnp.array([[0, 2]]))
    assert sorted(np.asarray(near)[0].tolist()) == [0, 2]
    np.testing.assert_allclose(sorted(np.asarray(weights)[0]),
                               [0.295 / 0.695, 0.4 / 0.695], rtol=1e-5)
    # expert 3 is not: the reference keeps its own
    far = ref.route(u, router, cfg, prefer=jnp.array([[0, 3]]))[1]
    assert sorted(np.asarray(far)[0].tolist()) == [0, 1]


@pytest.mark.parametrize("prefer, followed, refused",
                         [([0, 2], 1, 0), ([0, 3], 0, 1), (None, 0, 0)],
                         ids=["near", "far", "own"])
def test_reference_reports_what_the_tie_window_did(prefer, followed, refused):
    """The routes taken from the program over the reference's own, and
    the program's routes it did not take (same token as above)."""
    cfg = dict(num_experts_per_tok=2, route_tie_window=0.03)
    probs = jnp.array([[0.4, 0.3, 0.295, 0.005]])
    prefer = None if prefer is None else jnp.array([prefer])
    chosen = ref.route(jnp.ones((1, 1)), jnp.log(probs), cfg, prefer)[1]
    report = ref._routing_report(probs, chosen, prefer, cfg)
    assert int(report["followed"]) == followed
    assert int(report["refused"]) == refused
    assert report["chosen"] is chosen


def test_router_control_rounds_both_operands_of_the_routers_product():
    cfg = dict(num_experts_per_tok=2, route_tie_window=0.03)
    u = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    want = ref.route(low(u), low(router), cfg)[0]
    got = ref.route(u, router, cfg, round_to="bfloat16")[0]
    np.testing.assert_array_equal(got, want)
    assert float(jnp.abs(got - ref.route(u, router, cfg)[0]).max()) > 0
    coarse = ref.route(u, router, cfg, round_to="float8")[0]
    assert float(jnp.abs(coarse - want).max()) > 0


# -- the cell ----------------------------------------------------------------
def test_the_cell_rehearses_correct_with_its_counters():
    """``cellbench.run --rehearse`` of the cell, in a process of its own
    (one CPU device, as the cell has one chip).  Over 40 s in the driver's
    run (a process start, the program's compile and the reference's at
    rehearsal size): the one tier-1 hold on the cell's own runner,
    example and comparison end to end, which no in-process case is."""
    import json
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "sdar30b_train_bd4_s8192", "--seed", str(2**31 + 7), "--seconds",
         "0.5", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    line = [l for l in lines if l.startswith("counters over")]
    assert len(line) == 1 and "moe_dropped total 0" in line[0]
    assert "moe_held_share mean 0." in line[0]


def test_the_configuration_keeps_every_published_width():
    import json

    with open(os.path.join(ROOT, "cellbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    published = dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, moe_intermediate_size=768, num_experts_per_tok=8,
        rope_theta=1000000, rms_norm_eps=1e-6, intermediate_size=6144,
        max_position_embeddings=32768)
    assert {k: cfg[k] for k in published} == published
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 128
    entry = [c for c in bench["configs"] if c["name"] == "sdar-30b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert 4 <= cfg["num_hidden_layers"] <= 6
    assert (cfg["num_experts"], cfg["vocab_size"]) == (16, 151936 // 8)


# -- the benchmark's operation counts, by hand -------------------------------
def test_flops_sdar_against_hand_worked_values():
    # s 8, block 4: clean 8*12/2 = 48, noised over clean 8*4/2 = 16,
    # own block 8*4 = 32
    assert flops_sdar.live_pairs(8, 4) == 48 + 16 + 32 == 8 * (8 + 4)
    assert flops_sdar.attention_model_flops(8, 4, 2, 16) == 12 * 96 * 2 * 16
    assert flops_sdar.bdflash_flops("fwd", 1, 2, 8, 16, 4) == 2 * 2 * 16 * 96 * 2
    assert flops_sdar.bdflash_flops("dkv", 3, 2, 8, 16, 4) == 4 * 2 * 16 * 96 * 2 * 3
    # fwd: q, o with 2 heads and k, v with 1 over 16 positions of 16
    # bf16 values, plus one float32 statistic a head and position
    assert flops_sdar.bdflash_bytes("fwd", 1, 2, 1, 8, 16) == (
        (2 * 2 + 2 * 1) * 16 * 16 * 2 + 4 * 2 * 16)
    assert flops_sdar.expert_flops(10, 4, 3) == 9 * 2 * 4 * 3 * 10
    cfg = dict(hidden_size=4, num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=1, head_dim=3, router_experts=5,
               moe_intermediate_size=3, vocab_size=7)
    per_position = 4 * 6 * 2 + 4 * 3 * 2 + 4 * 5
    assert flops_sdar.step_model_flops(cfg, 8, 1, 4, 10.0) == (
        6 * per_position * 16 * 2 + 6 * 3 * 4 * 3 * 10 + 6 * 4 * 7 * 8
        + 12 * 96 * 2 * 3 * 2)
    # the issue's form of the attention term at the cell's sizes
    assert flops_sdar.attention_model_flops(8192, 4, 32, 128) == \
        12 * 8192 * 8196 * 4096
