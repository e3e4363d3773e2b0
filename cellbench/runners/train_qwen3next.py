"""Runner: ``examples/moe_lm/train_moe_lm.py`` on the next-token loss as
a training cell of a Gated DeltaNet / gated-attention hybrid with
sparse experts (one chip's share of a Qwen3-Next layer: its experts,
its rows of the embedding and of the head, one period of the layer
pattern).

``main(argv)`` builds the communicator, ``MoeTransformerLM`` from the
repo's block with its options, the multi-node optimizer and the
compiled step, and warms the step's one shape; the runner then lays the
benchmark's seeded weights into the returned tree, zeroes the optimizer
state, and feeds seeded token batches through the same
``step.place_batch`` + ``step(...)`` pair the example's loop uses.  The
step's ``metrics["aux"]`` carries the expert layers' counters and every
layer's routing decisions, as ``runners/train_sdar.py`` reads them: the
reference is handed the routes of the first steps and follows one
inside its tie window (``reference/qwen3_next.py::route``).  The leaves
of a layer depend on its kind (``full_attention_interval``).
"""

from __future__ import annotations

import numpy as np

from .common import TrainCell, batch_rng, find_state, load_example, \
    load_reference
from .train_lm import _get, _set

_BLOCK = "MoeTransformerBlock_{l}"
_MIXER = (_BLOCK, "GatedDeltaMixer_0")
_ATTN = (_BLOCK, "SelfAttention_0")
_MOE = (_BLOCK, "MoeMlp_0")
#: reference leaf name -> path below ``params['params']``; ``{l}`` is
#: the layer of a leaf keyed ``name.<layer>``
_PATHS = {
    "wte": ("embed", "embedding"),
    "head": ("lm_head",),
    "normf_g": ("RMSNorm_0", "scale"),
    "norm1_g": (_BLOCK, "RMSNorm_0", "scale"),
    "norm2_g": (_BLOCK, "RMSNorm_1", "scale"),
    "router": (*_MOE, "router"),
    "w_gate": (*_MOE, "expert_wg"),
    "w_up": (*_MOE, "expert_wu"),
    "w_down": (*_MOE, "expert_wd"),
    "s_gate": (*_MOE, "shared_wg"),
    "s_up": (*_MOE, "shared_wu"),
    "s_down": (*_MOE, "shared_wd"),
    "s_mix": (*_MOE, "shared_gate"),
    "l_in": (*_MIXER, "in_proj_qkvz", "kernel"),
    "l_ba": (*_MIXER, "in_proj_ba", "kernel"),
    "conv_w": (*_MIXER, "conv_kernel"),
    "a_log": (*_MIXER, "A_log"),
    "dt_bias": (*_MIXER, "dt_bias"),
    "lnorm_g": (*_MIXER, "norm"),
    "l_out": (*_MIXER, "out_proj", "kernel"),
    "w_q": (*_ATTN, "q_proj", "kernel"),
    "w_k": (*_ATTN, "k_proj", "kernel"),
    "w_v": (*_ATTN, "v_proj", "kernel"),
    "w_o": (*_ATTN, "o_proj", "kernel"),
    "qn_g": (*_ATTN, "q_norm"),
    "kn_g": (*_ATTN, "k_norm"),
}
COUNTERS = ("moe_rows_routed", "moe_rows_computed", "moe_dropped")


def _paths(ref, cfg: dict):
    """``(reference key, program path)`` of every leaf."""
    for key, name, layer in ref.leaves(cfg):
        yield key, tuple(p.format(l=layer) for p in _PATHS[name])


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
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--n-kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]),
        "--rope-theta", str(float(cfg["rope_theta"])),
        "--rotary-fraction", repr(float(cfg["partial_rotary_factor"])),
        "--qk-norm", "--attn-output-gate",
        "--rmsnorm", "--zero-centered-norm",
        "--norm-eps", repr(float(cfg["rms_norm_eps"])),
        "--layer-types", ",".join(ref.layer_kinds(cfg)),
        "--gdn-key-heads", str(cfg["linear_num_key_heads"]),
        "--gdn-value-heads", str(cfg["linear_num_value_heads"]),
        "--gdn-key-dim", str(cfg["linear_key_head_dim"]),
        "--gdn-value-dim", str(cfg["linear_value_head_dim"]),
        "--gdn-conv", str(cfg["linear_conv_kernel_dim"]),
        "--gdn-chunk", str(cfg["linear_chunk_size"]),
        "--d-ff", str(cfg["moe_intermediate_size"]),
        "--shared-d-ff", str(cfg["shared_expert_intermediate_size"]),
        "--n-experts", str(cfg["router_experts"]),
        "--top-k", str(cfg["num_experts_per_tok"]),
        "--held", f"{cfg['first_expert']},{cfg['num_experts']}",
        "--moe-every", str(cfg["decoder_sparse_step"]),
        "--dropless", "--return-routes", "--untied-head",
        "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--chunked-ce", str(cfg["head_chunks"]),
        "--lr", str(opt_cfg["lr"]),
        "--aux-coef", str(cfg["aux_loss_coef"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
    ]


class Qwen3NextCell(TrainCell):
    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        cfg = dict(spec.sizes)
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.chips = cfg, spec.chips
        self.opt_cfg = spec.config["optimizer"]
        self.seq = int(spec.traffic["seq_len"])
        self.rows = int(spec.traffic["per_chip_batch"]) * spec.chips
        self.samples_per_step = self.rows * self.seq
        self.n_layer = cfg["num_hidden_layers"]

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

    def reseed(self, seed: int):
        """Seeded weights, a zeroed optimizer and the feed at batch 0."""
        self.seed, self._index = seed, 0
        self._first = []  # the first steps' batches, for the reference
        self._routes = []  # and the routing decisions taken on them
        self._counters = []  # every step's, as the step returned them
        self.params = self.opt_state = None  # freed before the new
        self.params = self._make_params(self.ref.seed_key(seed))
        self.opt_state = self._zeros()

    # -- the window's call and feed ------------------------------------
    def _next_batch(self) -> np.ndarray:
        """Ids uniform over the vocabulary rows held here."""
        toks = batch_rng(self.seed, self._index).integers(
            0, self.cfg["vocab_size"], (self.rows, self.seq),
            dtype=np.int32)
        if self._index < self.first_n:
            self._first.append(toks)
        self._index += 1
        return toks

    def dispatch(self):
        batch = self.step.place_batch(self._next_batch())
        self.params, self.opt_state, metrics = self.step(
            self.params, self.opt_state, batch)
        aux = metrics["aux"]
        self._counters.append({name: aux[name] for name in COUNTERS})
        if len(self._routes) < len(self._first):
            self._routes.append(np.asarray(aux["routes"]))
        return metrics["loss"]

    def start_window(self):
        self._counters = []

    def telemetry(self):
        """The expert layers' counters of every step dispatched since
        the window opened (summed over layers), and what a step's routes
        come to; prints their summary."""
        import jax

        fetched = jax.device_get(self._counters)
        steps = {name: np.array([int(c[name]) for c in fetched])
                 for name in COUNTERS}
        routes = self.n_layer * self.rows * self.seq \
            * self.cfg["num_experts_per_tok"]
        share = steps["moe_rows_routed"] / routes
        print(f"counters over {len(share)} steps: moe_held_share mean "
              f"{share.mean():.6f} min {share.min():.6f} max "
              f"{share.max():.6f} (balanced "
              f"{self.cfg['num_experts'] / self.cfg['router_experts']:.6f})"
              f"; moe_rows_routed mean {steps['moe_rows_routed'].mean():.1f}"
              f"; moe_rows_computed min {steps['moe_rows_computed'].min()} "
              f"max {steps['moe_rows_computed'].max()}; moe_dropped total "
              f"{steps['moe_dropped'].sum()}")
        return {"counters": steps, "routes_per_step": routes}

    # -- what correct reads --------------------------------------------
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

    def _delta_norms(self) -> dict:
        return {k: float(v) for k, v in self._deltas(
            self.params, self.ref.seed_key(self.seed)).items()}

    def free(self):
        self.params = self.opt_state = self.step = None
        self._counters = []

    def first_inputs(self) -> dict:
        return {"seed": self.seed, "batches": np.stack(self._first),
                "routes": list(self._routes)}

    def reference(self, inputs: dict, lowp: bool = False) -> dict:
        got = self.ref.train_readings(
            inputs["seed"], self.cfg, inputs["batches"], self.opt_cfg,
            lowp=lowp, routes=inputs["routes"])
        print(f"routes: the {'control' if lowp else 'reference'} took "
              f"{got['routes_followed']:.6f} of its routes from the "
              f"program over its own (tie window "
              f"{self.cfg['route_tie_window']:g}) and refused "
              f"{got['routes_refused']:.6f} of the program's")
        return got


def build(spec) -> Qwen3NextCell:
    return Qwen3NextCell(spec)
