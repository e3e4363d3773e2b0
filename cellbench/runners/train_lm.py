"""Runner: ``examples/lm/train_lm.py`` as a training cell.

``main(argv)`` builds the communicator, the model, the multi-node
optimizer and the compiled step, and warms the step's one shape; the
runner then lays the benchmark's seeded weights into the returned tree,
zeroes the optimizer state, and feeds seeded token batches through the
same ``step.place_batch`` + ``step(...)`` pair the example's loop uses.
"""

from __future__ import annotations

import numpy as np

from .common import TrainCell, batch_rng, find_state, load_example, \
    load_reference

#: reference leaf -> path below ``params['params']``; a path with
#: ``{l}`` is a leaf of every layer, stacked in the reference
_PATHS = {
    "wte": ("embed", "embedding"),
    "wpe": ("pos_embed",),
    "lnf_g": ("LayerNorm_0", "scale"),
    "lnf_b": ("LayerNorm_0", "bias"),
    "ln1_g": ("TransformerBlock_{l}", "LayerNorm_0", "scale"),
    "ln1_b": ("TransformerBlock_{l}", "LayerNorm_0", "bias"),
    "w_qkv": ("TransformerBlock_{l}", "SelfAttention_0", "Dense_0",
              "kernel"),
    "w_o": ("TransformerBlock_{l}", "SelfAttention_0", "Dense_1", "kernel"),
    "ln2_g": ("TransformerBlock_{l}", "LayerNorm_1", "scale"),
    "ln2_b": ("TransformerBlock_{l}", "LayerNorm_1", "bias"),
    "w_fc": ("TransformerBlock_{l}", "MlpBlock_0", "Dense_0", "kernel"),
    "b_fc": ("TransformerBlock_{l}", "MlpBlock_0", "Dense_0", "bias"),
    "w_proj": ("TransformerBlock_{l}", "MlpBlock_0", "Dense_1", "kernel"),
    "b_proj": ("TransformerBlock_{l}", "MlpBlock_0", "Dense_1", "bias"),
}


def _per_layer(path) -> bool:
    return any("{l}" in p for p in path)


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def program_tree(weights: dict, n_layer: int) -> dict:
    """The reference's stacked weights in the program's flax tree."""
    inner = {}
    for name, path in _PATHS.items():
        if not _per_layer(path):
            _set(inner, path, weights[name])
            continue
        for l in range(n_layer):
            _set(inner, tuple(p.format(l=l) for p in path),
                 weights[name][l])
    return {"params": inner}


def keyed_leaves(tree: dict, n_layer: int) -> dict:
    """The program's leaves under the reference's ``leaf_keys`` names."""
    inner, out = tree["params"], {}
    for name, path in _PATHS.items():
        if not _per_layer(path):
            out[name] = _get(inner, path)
            continue
        for l in range(n_layer):
            out[f"{name}.{l}"] = _get(
                inner, tuple(p.format(l=l) for p in path))
    return out


class LmCell(TrainCell):
    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        cfg = spec.sizes
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.chips = cfg, spec.chips
        self.opt_cfg = spec.config["optimizer"]
        self.seq = int(spec.traffic["seq_len"])
        self.rows = int(spec.traffic["per_chip_batch"]) * spec.chips
        self.samples_per_step = self.rows * self.seq

        argv = [
            "--d-model", str(cfg["n_embd"]), "--n-layers",
            str(cfg["n_layer"]), "--n-heads", str(cfg["n_head"]),
            "--vocab", str(cfg["vocab_size"]), "--seq-len", str(self.seq),
            "--batchsize", str(self.rows), "--lr", str(self.opt_cfg["lr"]),
            "--steps", "2", "--report-every", "1", "--generate", "0",
            "--serve", "0",
        ] + list(spec.config.get("argv", [])) \
          + list(spec.traffic.get("argv", []))
        if spec.rehearse:
            argv.append("--cpu-mesh")
        out = load_example("lm/train_lm.py").main(argv)
        self.step, self.comm = out["step"], out["comm"]
        if self.comm.size != spec.chips:
            raise RuntimeError(
                f"cell asks for {spec.chips} chips, the example's "
                f"communicator spans {self.comm.size}")

        # the benchmark's weights, from --seed, in the step's layout
        old = out.pop("params")
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, old)
        del old
        n_layer = cfg["n_layer"]
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
        self.params = None
        self.params = self._make_params(self.ref.seed_key(seed))
        self.opt_state = self._zero(self.opt_state)

    # -- the window's call and feed ------------------------------------
    def _next_batch(self) -> np.ndarray:
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
        return metrics["loss"]

    # -- what correct reads --------------------------------------------
    def _first_gradient(self):
        """Per-leaf norms of the first gradient as the optimizer got it,
        and its small leaves whole: Adam's mu after one step is
        (1 - b1) g."""
        mu = find_state(self.opt_state, "mu")
        scale = 1.0 / (1.0 - self.ref.B1)
        small = {k: np.asarray(v, np.float32) * scale for k, v in
                 keyed_leaves(mu, self.cfg["n_layer"]).items()
                 if v.size <= self.ref.SMALL}
        return ({k: float(v) * scale
                 for k, v in self._norms(mu).items()}, small)

    def _delta_norms(self) -> dict:
        return {k: float(v) for k, v in self._deltas(
            self.params, self.ref.seed_key(self.seed)).items()}

    def free(self):
        self.params = self.opt_state = self.step = None

    def first_inputs(self) -> dict:
        return {"seed": self.seed, "batches": np.stack(self._first).reshape(
            self.first_n, self.chips, -1, self.seq)}

    def reference(self, inputs: dict, lowp: bool = False) -> dict:
        return self.ref.train_readings(inputs["seed"], self.cfg,
                                  inputs["batches"], self.opt_cfg,
                                  lowp=lowp)


def build(spec) -> LmCell:
    return LmCell(spec)
