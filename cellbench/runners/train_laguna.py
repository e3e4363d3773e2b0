"""Runner: ``examples/moe_lm/train_moe_lm.py`` on the next-token loss as
a training cell of a model of window and full attention layers with
sparse experts behind a leading dense layer (one chip's share of a
Laguna-S-2.1 layer: its experts, its rows of the embedding and of the
head, the dense layer and one period of the layer pattern).

The cell is ``runners/train_qwen3next.py``'s with another model in it:
the feed, the reseeding, the first-step readings, the dispatch with its
counters and routes and the reference's call are ``Qwen3NextCell``'s
(the router is that family's: a softmax, no selection bias).  What reads
this configuration or the tree is here: the example's command line (the
window layers' fields from the configuration's lists a layer and its
``rope_parameters``), where each leaf lies in the program's tree (a
block's name depends on whether its MLP is dense and on how many of its
kind came before), set-up, and the counters, the window launches' block
census among them.
"""

from __future__ import annotations

import numpy as np

from ..flops_laguna import WINDOW_LAYER, sizes_of
from .common import find_state, load_example, load_reference
from .train_lm import _get, _set
from .train_qwen3next import COUNTERS, Qwen3NextCell

_MIXER = "SelfAttention_0"
_MLP = {"dense": ("TransformerBlock", "GatedMlp_0"),
        "sparse": ("MoeTransformerBlock", "MoeMlp_0")}
#: the program's name of a layer type of the configuration
_KIND = {"full_attention": "attention", WINDOW_LAYER: "window_attention"}
#: reference leaf name -> path below its block (a layer's) or below
#: ``params['params']`` (the top's)
_TOP = {"wte": ("embed", "embedding"), "head": ("lm_head",),
        "normf_g": ("RMSNorm_0", "scale")}
_IN_BLOCK = {"norm1_g": ("RMSNorm_0", "scale"),
             "norm2_g": ("RMSNorm_1", "scale")}
_IN_MIXER = {"w_q": ("q_proj", "kernel"), "w_k": ("k_proj", "kernel"),
             "w_v": ("v_proj", "kernel"), "w_g": ("g_proj", "kernel"),
             "w_o": ("o_proj", "kernel")}
_IN_MLP = {
    "d_in": ("in_proj", "kernel"), "d_out": ("out_proj", "kernel"),
    "router": ("router",), "w_gate": ("expert_wg",),
    "w_up": ("expert_wu",), "w_down": ("expert_wd",),
    "s_gate": ("shared_wg",), "s_up": ("shared_wu",),
    "s_down": ("shared_wd",), "s_mix": ("shared_gate",),
}


def _paths(ref, cfg: dict):
    """``(reference key, program path)`` of every leaf."""
    kinds = ref.layer_kinds(cfg)
    for key, name, layer in ref.leaves(cfg):
        if layer is None:
            yield key, _TOP[name]
            continue
        mlp = kinds[layer][1]
        cls, module = _MLP[mlp]
        # the serial a block of its class has: how many came before
        block = f"{cls}_{sum(k[1] == mlp for k in kinds[:layer])}"
        if name in _IN_BLOCK:
            yield key, (block, *_IN_BLOCK[name])
        elif name in _IN_MIXER:
            yield key, (block, _MIXER, *_IN_MIXER[name])
        else:
            yield key, (block, module, *_IN_MLP[name])


def program_tree(ref, weights: dict, cfg: dict) -> dict:
    """The reference's weights in the program's flax tree."""
    inner = {}
    for key, path in _paths(ref, cfg):
        _set(inner, path, weights[key])
    return {"params": inner}


def keyed_leaves(ref, tree: dict, cfg: dict) -> dict:
    """The program's leaves under the reference's ``leaf_keys`` names."""
    return {key: _get(tree["params"], path)
            for key, path in _paths(ref, cfg)}


def mixer_fields(ref, cfg: dict) -> dict:
    """What the configuration's lists a layer and its rotations come to
    in the program's terms: the period of layer kinds, each kind's head
    count, and the two rotations (the program has one of each a kind)."""
    kinds = ref.layer_kinds(cfg)
    heads = {t: {h for k, _, h in kinds if k == t} for t in _KIND}
    dense = [mlp == "dense" for _, mlp, _ in kinds]
    if any(len(h) > 1 for h in heads.values()) or dense != sorted(
            dense, reverse=True):
        raise ValueError("the program has one head count a layer kind and "
                         "its dense layers first")
    types = [_KIND[k] for k, _, _ in kinds]
    period = next(p for p in range(1, len(types) + 1)
                  if all(types[i] == types[i % p] for i in range(len(types))))
    full = cfg["rope_parameters"]["full_attention"]
    window = cfg["rope_parameters"][WINDOW_LAYER]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError("the full layers' rotation is YaRN's, the window "
                         "layers' the plain one")
    return {
        "types": types[:period], "first_dense": sum(dense),
        "heads": heads["full_attention"].pop(),
        "window_heads": heads[WINDOW_LAYER].pop(),
        "full": full, "window": window,
    }


def example_argv(ref, cfg: dict, traffic: dict, opt_cfg: dict, rows: int):
    """The example's command line for this configuration's sizes."""
    m = mixer_fields(ref, cfg)
    full, window = m["full"], m["window"]
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(m["heads"]),
        "--n-kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]), "--head-gate",
        "--rope-theta", repr(float(full["rope_theta"])),
        "--rotary-fraction", repr(float(full["partial_rotary_factor"])),
        "--rope-yarn", ",".join(repr(float(full[k])) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")),
        "--layer-types", ",".join(m["types"]),
        "--window", str(cfg["sliding_window"]),
        "--window-heads", str(m["window_heads"]),
        "--window-rope-theta", repr(float(window["rope_theta"])),
        "--window-rotary-fraction",
        repr(float(window["partial_rotary_factor"])),
        "--rmsnorm", "--norm-eps", repr(float(cfg["rms_norm_eps"])),
        "--first-dense", str(m["first_dense"]),
        "--dense-d-ff", str(cfg["intermediate_size"]), "--gated-mlp",
        "--d-ff", str(cfg["moe_intermediate_size"]),
        "--shared-d-ff", str(cfg["shared_expert_intermediate_size"]),
        "--n-experts", str(cfg["router_experts"]),
        "--top-k", str(cfg["num_experts_per_tok"]),
        "--held", f"{cfg['first_expert']},{cfg['num_experts']}",
        "--moe-every", str(cfg["decoder_sparse_step"]),
        "--routed-scale", repr(float(cfg["moe_routed_scaling_factor"])),
        "--dropless", "--return-routes", "--untied-head",
        "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--chunked-ce", str(cfg["head_chunks"]),
        "--lr", str(opt_cfg["lr"]),
        "--aux-coef", str(cfg["aux_loss_coef"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
    ]


class LagunaCell(Qwen3NextCell):
    """``Qwen3NextCell``'s feed, dispatch, first-step readings and
    reference call around this module's command line, tree and
    counters."""

    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        cfg = sizes_of(spec)
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.chips = cfg, spec.chips
        self.opt_cfg = spec.config["optimizer"]
        self.seq = int(spec.traffic["seq_len"])
        self.rows = int(spec.traffic["per_chip_batch"]) * spec.chips
        self.samples_per_step = self.rows * self.seq
        kinds = ref.layer_kinds(cfg)
        self.n_sparse = sum(mlp == "sparse" for _, mlp, _ in kinds)
        self.window_layers = sum(k == WINDOW_LAYER for k, _, _ in kinds)

        argv = example_argv(ref, cfg, spec.traffic, self.opt_cfg,
                            self.rows) \
            + list(spec.config.get("argv", [])) \
            + list(spec.traffic.get("argv", []))
        if spec.rehearse:
            argv.append("--cpu-mesh")
        out = load_example("moe_lm/train_moe_lm.py").main(argv)
        self.step, self.comm = out["step"], out["comm"]
        if self.comm.size != spec.chips:
            raise RuntimeError(
                f"cell asks for {spec.chips} chips, the example's "
                f"communicator spans {self.comm.size}")

        # the benchmark's weights, from --seed, in the step's layout
        old = out.pop("params")
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, old)
        del old
        self._make_params = jax.jit(
            lambda key: program_tree(ref, ref.init_weights(key, cfg), cfg),
            out_shardings=shardings)
        # a zeroed optimizer state in the example's shapes and layout,
        # made from nothing (``runners/train_hybrid.py`` has why)
        state = out.pop("opt_state")
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        self._zeros = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes),
            out_shardings=jax.tree_util.tree_map(
                lambda x: x.sharding, state))
        self.params = self.opt_state = None
        del out, state

        def norms(tree):
            return {k: jnp.linalg.norm(x.astype(jnp.float32))
                    for k, x in keyed_leaves(ref, tree, cfg).items()}

        self._norms = jax.jit(norms)
        # the seeded weights are made again inside the program, so that
        # no second copy of the parameters is ever a live buffer
        self._deltas = jax.jit(lambda p, key: norms(
            jax.tree_util.tree_map(
                jnp.subtract, p,
                program_tree(ref, ref.init_weights(key, cfg), cfg))))
        self.reseed(spec.seed)

    def _swa_blocks(self) -> dict:
        """The (q block, k block) grid points the window launches of a
        step visit, hold a live pair in, execute and visit wholly below
        the window, from the program's census of the launch it runs at
        this sequence and window (forward, dq and dk/dv a window layer;
        the block is the launch's own: at most the window)."""
        from chainermn_tpu.ops import pallas_attention as pa

        launch = pa.window_launch_census(
            self.seq, int(self.cfg["sliding_window"]),
            int(self.cfg["head_dim"]))
        if not launch:  # the launch runs without a window
            return {}
        total = {"visited": 0, "live": 0, "executed": 0, "below_window": 0}
        for census in (launch["fwd"], launch["bwd"], launch["bwd"]):
            for name in total:
                total[name] += self.window_layers * (
                    census["interior"] + census["masked"]
                    if name == "executed" else census[name])
        return {**total, "block": launch["block"]}

    def telemetry(self):
        """The expert layers' counters of every step dispatched since
        the window opened (summed over layers), what a step's routes
        come to, and the window launches' block census; prints their
        summary."""
        import jax

        fetched = jax.device_get(self._counters)
        steps = {name: np.array([int(c[name]) for c in fetched])
                 for name in COUNTERS}
        routes = self.n_sparse * self.rows * self.seq \
            * self.cfg["num_experts_per_tok"]
        share = steps["moe_rows_routed"] / routes
        blocks = self._swa_blocks()
        print(f"counters over {len(share)} steps: moe_held_share mean "
              f"{share.mean():.6f} min {share.min():.6f} max "
              f"{share.max():.6f} (balanced "
              f"{self.cfg['num_experts'] / self.cfg['router_experts']:.6f})"
              f"; moe_rows_routed mean {steps['moe_rows_routed'].mean():.1f}"
              f"; moe_rows_computed min {steps['moe_rows_computed'].min()} "
              f"max {steps['moe_rows_computed'].max()}; moe_dropped total "
              f"{steps['moe_dropped'].sum()}; window launches a step and "
              f"head: {blocks}")
        return {"counters": steps, "routes_per_step": routes,
                "swa_blocks": blocks}

    def _first_gradient(self):
        """Per-leaf norms of the first gradient as the optimizer got it,
        and its small leaves whole: Adam's mu after one step is
        (1 - b1) g."""
        mu = find_state(self.opt_state, "mu")
        scale = 1.0 / (1.0 - self.ref.B1)
        small = {k: np.asarray(v, np.float32) * scale for k, v in
                 keyed_leaves(self.ref, mu, self.cfg).items()
                 if v.size <= self.ref.SMALL}
        return ({k: float(v) * scale
                 for k, v in self._norms(mu).items()}, small)


def build(spec) -> LagunaCell:
    return LagunaCell(spec)
