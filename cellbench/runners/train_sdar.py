"""Runner: ``examples/moe_lm/train_moe_lm.py`` on the block-diffusion
objective as a training cell (one chip's share of an SDAR-MoE layer).

``main(argv)`` builds the communicator, the model from the repo's block
with its options, the multi-node optimizer and the compiled step, and
warms the step's one shape; the runner then lays the benchmark's seeded
weights into the returned tree, zeroes the optimizer state, and feeds
seeded batch trees ``(tokens, mask, weights)`` through the same
``step.place_batch`` + ``step(...)`` pair the example's loop uses.  The
step's ``metrics["aux"]`` carries the expert layers' counters; the
runner keeps every step's and prints their window summary.  It also
carries every layer's routing decisions, which the reference is handed
for the first steps: a top-8 of 128 flips on rounding wherever the
eighth and ninth probabilities nearly tie, so the reference takes the
program's choice inside its tie window and its own outside
(``reference/sdar_moe.py::route``).
"""

from __future__ import annotations

import numpy as np

from .common import TrainCell, batch_rng, find_state, load_example, \
    load_reference
from .train_lm import _get, _per_layer, _set

_BLOCK = "MoeTransformerBlock_{l}"
#: reference leaf -> path below ``params['params']``; a path with
#: ``{l}`` is a leaf of every layer, stacked in the reference
_PATHS = {
    "wte": ("embed", "embedding"),
    "head": ("lm_head",),
    "normf_g": ("RMSNorm_0", "scale"),
    "norm1_g": (_BLOCK, "RMSNorm_0", "scale"),
    "w_q": (_BLOCK, "SelfAttention_0", "q_proj", "kernel"),
    "w_k": (_BLOCK, "SelfAttention_0", "k_proj", "kernel"),
    "w_v": (_BLOCK, "SelfAttention_0", "v_proj", "kernel"),
    "qn_g": (_BLOCK, "SelfAttention_0", "q_norm"),
    "kn_g": (_BLOCK, "SelfAttention_0", "k_norm"),
    "w_o": (_BLOCK, "SelfAttention_0", "o_proj", "kernel"),
    "norm2_g": (_BLOCK, "RMSNorm_1", "scale"),
    "router": (_BLOCK, "MoeMlp_0", "router"),
    "w_gate": (_BLOCK, "MoeMlp_0", "expert_wg"),
    "w_up": (_BLOCK, "MoeMlp_0", "expert_wu"),
    "w_down": (_BLOCK, "MoeMlp_0", "expert_wd"),
}
COUNTERS = ("moe_rows_routed", "moe_rows_computed", "moe_dropped")


def _leaves(n_layer: int):
    """``(reference name, layer or None, program path)`` of every leaf."""
    for name, path in _PATHS.items():
        if not _per_layer(path):
            yield name, None, path
            continue
        for l in range(n_layer):
            yield name, l, tuple(p.format(l=l) for p in path)


def program_tree(weights: dict, n_layer: int) -> dict:
    """The reference's stacked weights in the program's flax tree."""
    inner = {}
    for name, layer, path in _leaves(n_layer):
        _set(inner, path,
             weights[name] if layer is None else weights[name][layer])
    return {"params": inner}


def keyed_leaves(tree: dict, n_layer: int) -> dict:
    """The program's leaves under the reference's ``leaf_keys`` names."""
    return {name if layer is None else f"{name}.{layer}":
            _get(tree["params"], path)
            for name, layer, path in _leaves(n_layer)}


def example_argv(cfg: dict, traffic: dict, opt_cfg: dict, rows: int):
    """The example's command line for this configuration's sizes."""
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--n-kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]),
        "--d-ff", str(cfg["moe_intermediate_size"]),
        "--n-experts", str(cfg["router_experts"]),
        "--top-k", str(cfg["num_experts_per_tok"]),
        "--held", f"{cfg['first_expert']},{cfg['num_experts']}",
        "--moe-every", "1", "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--rope-theta", str(float(cfg["rope_theta"])), "--rmsnorm",
        "--qk-norm", "--untied-head", "--dropless", "--return-routes",
        "--block-diffusion", str(traffic["block_len"]),
        "--lr", str(opt_cfg["lr"]),
        "--aux-coef", str(cfg["aux_loss_coef"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
    ]


class SdarCell(TrainCell):
    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        cfg = dict(spec.sizes, block_length=int(spec.traffic["block_len"]))
        if cfg["mask_token_id"] != cfg["vocab_size"] - 1:
            raise ValueError("the example's mask id is the vocabulary's "
                             "last row")
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.chips = cfg, spec.chips
        self.opt_cfg = spec.config["optimizer"]
        self.seq = int(spec.traffic["seq_len"])
        self.rows = int(spec.traffic["per_chip_batch"]) * spec.chips
        self.samples_per_step = self.rows * self.seq
        self.n_layer = n_layer = cfg["num_hidden_layers"]

        argv = example_argv(cfg, spec.traffic, self.opt_cfg, self.rows) \
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
            lambda key: program_tree(ref.init_weights(key, cfg), n_layer),
            out_shardings=shardings)
        self._zero = jax.jit(
            lambda s: jax.tree_util.tree_map(jnp.zeros_like, s),
            donate_argnums=0)
        self.params, self.opt_state = None, out.pop("opt_state")
        del out

        def norms(tree):
            return {k: jnp.linalg.norm(x.astype(jnp.float32))
                    for k, x in keyed_leaves(tree, n_layer).items()}

        self._norms = jax.jit(norms)
        # the seeded weights are made again inside the program, so that
        # no second copy of the parameters is ever a live buffer
        self._deltas = jax.jit(lambda p, key: norms(
            jax.tree_util.tree_map(
                jnp.subtract, p,
                program_tree(ref.init_weights(key, cfg), n_layer))))
        self.reseed(spec.seed)

    def reseed(self, seed: int):
        """Seeded weights, a zeroed optimizer and the feed at batch 0."""
        self.seed, self._index = seed, 0
        self._first = []  # the first steps' batches, for the reference
        self._routes = []  # and the routing decisions taken on them
        self._counters = []  # every step's, as the step returned them
        self.params = None
        self.params = self._make_params(self.ref.seed_key(seed))
        self.opt_state = self._zero(self.opt_state)

    # -- the window's call and feed ------------------------------------
    def _next_batch(self):
        """Tokens uniform over the slice but the mask id; one noise level
        a block, the mask drawn from it; loss weights 1 / t_b."""
        cfg, rng = self.cfg, batch_rng(self.seed, self._index)
        shape, block = (self.rows, self.seq), cfg["block_length"]
        tokens = rng.integers(0, cfg["mask_token_id"], shape,
                              dtype=np.int32)
        t = 1.0 - rng.uniform(0.0, 1.0 - cfg["t_min"],
                              (self.rows, self.seq // block))
        t = np.repeat(t, block, axis=1)
        mask = rng.uniform(size=shape) < t
        batch = (tokens, mask, np.where(mask, 1.0 / t, 0.0).astype(
            np.float32))
        if self._index < self.first_n:
            self._first.append(batch)
        self._index += 1
        return batch

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
        routes = self.n_layer * self.rows * 2 * self.seq \
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
                 keyed_leaves(mu, self.n_layer).items()
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
        return {"seed": self.seed, "batches": list(self._first),
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


def build(spec) -> SdarCell:
    return SdarCell(spec)
