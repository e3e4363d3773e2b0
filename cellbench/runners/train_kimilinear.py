"""Runner: ``examples/moe_lm/train_moe_lm.py`` on the next-token loss as
a training cell of a Kimi-Delta-Attention / latent-attention hybrid
with sparse experts behind a leading dense layer (one chip's share of a
Kimi Linear layer: its experts, its rows of the embedding and of the
head, the dense layer and the layers that follow it).

The cell is ``runners/train_qwen3next.py``'s with another model in it:
``main(argv)`` builds and warms the step, the runner lays the seeded
weights of ``reference/kimi_linear.py`` into the returned tree, zeroes
the optimizer state and feeds seeded token batches through the
example's own ``step.place_batch`` + ``step(...)`` pair; the step's
``metrics["aux"]`` carries the expert layers' counters (the routes the
selection bias changed among them) and every expert layer's routing
decisions, which the reference follows inside its tie window.  The
feed, the reseeding and the reference's call are ``Qwen3NextCell``'s;
what reads the configuration or the tree is here: the example's command
line, where each leaf lies in the program's tree (a block's name
depends on whether its MLP is dense and on how many of its kind came
before), set-up, and the counters.
"""

from __future__ import annotations

import numpy as np

from ..flops_kimilinear import sizes_of
from .common import find_state, load_example, load_reference
from .train_lm import _get, _set
from .train_qwen3next import COUNTERS, Qwen3NextCell

_MIXER = {"kda": "KdaMixer_0", "latent_attention": "LatentAttention_0"}
_MLP = {"dense": ("TransformerBlock", "GatedMlp_0"),
        "experts": ("MoeTransformerBlock", "MoeMlp_0")}
#: reference leaf name -> path below its block (a layer's) or below
#: ``params['params']`` (the top's)
_TOP = {"wte": ("embed", "embedding"), "head": ("lm_head",),
        "normf_g": ("RMSNorm_0", "scale")}
_IN_BLOCK = {"norm1_g": ("RMSNorm_0", "scale"),
             "norm2_g": ("RMSNorm_1", "scale")}
_IN_MIXER = {
    "k_in": ("in_proj_qkv", "kernel"), "conv_w": ("conv_kernel",),
    "f_a": ("f_a_proj", "kernel"), "f_b": ("f_b_proj", "kernel"),
    "a_log": ("A_log",), "dt_bias": ("dt_bias",),
    "b_w": ("b_proj", "kernel"), "g_a": ("g_a_proj", "kernel"),
    "g_b": ("g_b_proj", "kernel"), "g_bias": ("g_b_proj", "bias"),
    "knorm_g": ("norm",), "k_out": ("out_proj", "kernel"),
    "w_q": ("q_proj", "kernel"), "w_kva": ("kv_a_proj", "kernel"),
    "kvn_g": ("kv_a_norm",), "w_kvb": ("kv_b_proj", "kernel"),
    "w_o": ("o_proj", "kernel"),
}
_IN_MLP = {
    "d_in": ("in_proj", "kernel"), "d_out": ("out_proj", "kernel"),
    "router": ("router",), "r_bias": ("router_bias",),
    "w_gate": ("expert_wg",), "w_up": ("expert_wu",),
    "w_down": ("expert_wd",), "s_gate": ("shared_wg",),
    "s_up": ("shared_wu",), "s_down": ("shared_wd",),
}
BIASED = "moe_routes_biased"


def _paths(ref, cfg: dict):
    """``(reference key, program path)`` of every leaf."""
    kinds = ref.layer_kinds(cfg)
    for key, name, layer in ref.leaves(cfg):
        if layer is None:
            yield key, _TOP[name]
            continue
        mixer, mlp = kinds[layer]
        cls, module = _MLP[mlp]
        # the serial a block of its class has: how many came before
        block = f"{cls}_{sum(k[1] == mlp for k in kinds[:layer])}"
        if name in _IN_BLOCK:
            yield key, (block, *_IN_BLOCK[name])
        elif name in _IN_MIXER:
            yield key, (block, _MIXER[mixer], *_IN_MIXER[name])
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


def example_argv(ref, cfg: dict, traffic: dict, opt_cfg: dict, rows: int):
    """The example's command line for this configuration's sizes."""
    lin = cfg["linear_attn_config"]
    kinds = ref.mixer_kinds(cfg)
    period = lin["full_attn_layers"][0]  # the pattern repeats from here
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--no-positions", "--rmsnorm",
        "--norm-eps", repr(float(cfg["rms_norm_eps"])),
        "--layer-types", ",".join(kinds[:period]),
        "--gdn-value-heads", str(lin["num_heads"]),
        "--gdn-key-dim", str(lin["head_dim"]),
        "--gdn-value-dim", str(lin["head_dim"]),
        "--gdn-conv", str(lin["short_conv_kernel_size"]),
        "--gdn-chunk", str(cfg["linear_chunk_size"]),
        "--latent-kv-rank", str(cfg["kv_lora_rank"]),
        "--latent-nope-dim", str(cfg["qk_nope_head_dim"]),
        "--latent-shared-dim", str(cfg["qk_rope_head_dim"]),
        "--latent-value-dim", str(cfg["v_head_dim"]),
        "--first-dense", str(cfg["first_k_dense_replace"]),
        "--dense-d-ff", str(cfg["intermediate_size"]), "--gated-mlp",
        "--d-ff", str(cfg["moe_intermediate_size"]),
        "--shared-d-ff", str(cfg["moe_intermediate_size"]
                             * cfg["num_shared_experts"]),
        "--shared-ungated",
        "--n-experts", str(cfg["router_experts"]),
        "--top-k", str(cfg["num_experts_per_token"]),
        "--held", f"{cfg['first_expert']},{cfg['num_experts']}",
        "--moe-every", str(cfg["moe_layer_freq"]),
        "--router-score", cfg["moe_router_activation_func"],
        "--router-bias",
        "--routed-scale", repr(float(cfg["routed_scaling_factor"])),
        "--dropless", "--return-routes", "--untied-head",
        "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--chunked-ce", str(cfg["head_chunks"]),
        "--lr", str(opt_cfg["lr"]),
        "--aux-coef", str(cfg["aux_loss_coef"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
    ]


class KimiLinearCell(Qwen3NextCell):
    """``Qwen3NextCell``'s feed, first-step readings and reference call
    around this module's command line, tree and counters."""

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
        self.n_sparse = sum(mlp == "experts"
                            for _, mlp in ref.layer_kinds(cfg))

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

    def dispatch(self):
        batch = self.step.place_batch(self._next_batch())
        self.params, self.opt_state, metrics = self.step(
            self.params, self.opt_state, batch)
        aux = metrics["aux"]
        self._counters.append({name: aux[name]
                               for name in (*COUNTERS, BIASED)})
        if len(self._routes) < len(self._first):
            self._routes.append(np.asarray(aux["routes"]))
        return metrics["loss"]

    def telemetry(self):
        """The expert layers' counters of every step dispatched since
        the window opened (summed over layers), and what a step's routes
        come to; prints their summary."""
        import jax

        fetched = jax.device_get(self._counters)
        steps = {name: np.array([int(c[name]) for c in fetched])
                 for name in (*COUNTERS, BIASED)}
        routes = self.n_sparse * self.rows * self.seq \
            * self.cfg["num_experts_per_token"]
        share = steps["moe_rows_routed"] / routes
        print(f"counters over {len(share)} steps: moe_held_share mean "
              f"{share.mean():.6f} min {share.min():.6f} max "
              f"{share.max():.6f} (balanced "
              f"{self.cfg['num_experts'] / self.cfg['router_experts']:.6f})"
              f"; moe_rows_routed mean {steps['moe_rows_routed'].mean():.1f}"
              f"; moe_rows_computed min {steps['moe_rows_computed'].min()} "
              f"max {steps['moe_rows_computed'].max()}; moe_dropped total "
              f"{steps['moe_dropped'].sum()}; routes the selection bias "
              f"changed: mean share {(steps[BIASED] / routes).mean():.6f}")
        return {"counters": steps, "routes_per_step": routes}

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


def build(spec) -> KimiLinearCell:
    return KimiLinearCell(spec)
