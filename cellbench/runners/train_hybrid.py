"""Runner: ``examples/lm/train_lm.py`` with the block's options as a
training cell of a Mamba-2 / attention hybrid (one pipeline stage's
layers and one chip's rows of the tied embedding).

``main(argv)`` builds the communicator, ``TransformerLM`` with its
options, the multi-node optimizer and the compiled step, and warms the
step's one shape; the runner then lays the benchmark's seeded weights
into the returned tree, zeroes the optimizer state, and feeds seeded
token batches through the same ``step.place_batch`` + ``step(...)`` pair
the example's loop uses, as ``runners/train_lm.py`` does for the GPT-2
block.  The leaves of a layer depend on its kind (``layer_types``).
"""

from __future__ import annotations

import numpy as np

from .. import flops_granite
from .common import TrainCell, batch_rng, find_state, load_example, \
    load_reference
from .train_lm import _get, _set

_BLOCK = "TransformerBlock_{l}"
_MIXER = (_BLOCK, "Mamba2Mixer_0")
_ATTN = (_BLOCK, "SelfAttention_0")
#: reference leaf name -> path below ``params['params']``; ``{l}`` is
#: the layer of a leaf keyed ``name.<layer>``
_PATHS = {
    "wte": ("embed", "embedding"),
    "normf_g": ("RMSNorm_0", "scale"),
    "norm1_g": (_BLOCK, "RMSNorm_0", "scale"),
    "norm2_g": (_BLOCK, "RMSNorm_1", "scale"),
    "w_in": (_BLOCK, "GatedMlp_0", "in_proj", "kernel"),
    "w_out": (_BLOCK, "GatedMlp_0", "out_proj", "kernel"),
    "m_in": (*_MIXER, "in_proj", "kernel"),
    "conv_w": (*_MIXER, "conv_kernel"),
    "conv_b": (*_MIXER, "conv_bias"),
    "a_log": (*_MIXER, "A_log"),
    "dt_bias": (*_MIXER, "dt_bias"),
    "d_skip": (*_MIXER, "D"),
    "mnorm_g": (*_MIXER, "norm"),
    "m_out": (*_MIXER, "out_proj", "kernel"),
    "w_q": (*_ATTN, "q_proj", "kernel"),
    "w_k": (*_ATTN, "k_proj", "kernel"),
    "w_v": (*_ATTN, "v_proj", "kernel"),
    "w_o": (*_ATTN, "o_proj", "kernel"),
}


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


def example_argv(cfg: dict, traffic: dict, opt_cfg: dict, rows: int):
    """The example's command line for this configuration's sizes."""
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--n-kv-heads", str(cfg["num_key_value_heads"]),
        "--attention-scale", repr(float(cfg["attention_multiplier"])),
        "--d-ff", str(cfg["intermediate_size"]),
        "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--rmsnorm", "--norm-eps", repr(float(cfg["rms_norm_eps"])),
        "--gated-mlp", "--no-positions",
        "--layer-types", ",".join(cfg["layer_types"]),
        "--ssm-heads", str(cfg["mamba_n_heads"]),
        "--ssm-head-dim", str(cfg["mamba_d_head"]),
        "--ssm-state", str(cfg["mamba_d_state"]),
        "--ssm-conv", str(cfg["mamba_d_conv"]),
        "--ssm-chunk", str(cfg["mamba_chunk_size"]),
        "--embedding-multiplier", repr(float(cfg["embedding_multiplier"])),
        "--residual-multiplier", repr(float(cfg["residual_multiplier"])),
        "--logits-scaling", repr(float(cfg["logits_scaling"])),
        "--chunked-ce", str(cfg["head_chunks"]),
        "--lr", str(opt_cfg["lr"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
        "--serve", "0",
    ]


class HybridCell(TrainCell):
    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        cfg = flops_granite.sizes_of(spec)
        if cfg["mamba_n_groups"] != 1 or cfg["mamba_n_heads"] \
                * cfg["mamba_d_head"] != cfg["mamba_expand"] \
                * cfg["hidden_size"]:
            raise ValueError("the mixer has one group of B and C, and "
                             "heads x head width = expand x hidden")
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.chips = cfg, spec.chips
        self.opt_cfg = spec.config["optimizer"]
        self.seq = int(spec.traffic["seq_len"])
        self.rows = int(spec.traffic["per_chip_batch"]) * spec.chips
        self.samples_per_step = self.rows * self.seq

        argv = example_argv(cfg, spec.traffic, self.opt_cfg, self.rows) \
            + list(spec.config.get("argv", [])) \
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
        self._make_params = jax.jit(
            lambda key: program_tree(ref, ref.init_weights(key, cfg), cfg),
            out_shardings=shardings)
        # a zeroed optimizer state in the example's shapes and layout,
        # made from nothing: a jit of ``zeros_like`` keeps its (dead)
        # argument beside its result, twice 6.18 GB at this cell's size
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
        return metrics["loss"]

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

    def first_inputs(self) -> dict:
        return {"seed": self.seed, "batches": np.stack(self._first)}

    def reference(self, inputs: dict, lowp: bool = False) -> dict:
        return self.ref.train_readings(inputs["seed"], self.cfg,
                                       inputs["batches"], self.opt_cfg,
                                       lowp=lowp)


def build(spec) -> HybridCell:
    return HybridCell(spec)
