"""Runner: ``examples/moe_lm/train_moe_lm.py`` on the next-token loss as
a training cell of a latent-attention model with sparse experts behind
a leading dense layer (one chip's share of a Moonlight layer: its
experts, its rows of the embedding and of the head, the dense layer and
the layers that follow it).

The cell is ``runners/train_kimilinear.py``'s with another model in it:
the feed, the reseeding, the first-step readings, the counters and the
reference's call are ``KimiLinearCell``'s, and so is where each leaf
lies in the program's tree (``program_tree`` / ``keyed_leaves``: the
reference names its leaves as the sibling's does).  What reads this
configuration is here: the example's command line (a rotation's base in
``--no-positions``' place, every layer latent attention, the balance
loss counted a sequence) and set-up.
"""

from __future__ import annotations

from ..flops_moonlight import sizes_of
from .common import load_example, load_reference
from .train_kimilinear import KimiLinearCell, keyed_leaves, program_tree


def example_argv(ref, cfg: dict, traffic: dict, opt_cfg: dict, rows: int):
    """The example's command line for this configuration's sizes."""
    return [
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--rope-theta", repr(float(cfg["rope_theta"])), "--rmsnorm",
        "--norm-eps", repr(float(cfg["rms_norm_eps"])),
        "--layer-types", ref.MIXER,
        "--latent-kv-rank", str(cfg["kv_lora_rank"]),
        "--latent-nope-dim", str(cfg["qk_nope_head_dim"]),
        "--latent-shared-dim", str(cfg["qk_rope_head_dim"]),
        "--latent-value-dim", str(cfg["v_head_dim"]),
        "--first-dense", str(cfg["first_k_dense_replace"]),
        "--dense-d-ff", str(cfg["intermediate_size"]), "--gated-mlp",
        "--d-ff", str(cfg["moe_intermediate_size"]),
        "--shared-d-ff", str(cfg["moe_intermediate_size"]
                             * cfg["n_shared_experts"]),
        "--shared-ungated",
        "--n-experts", str(cfg["router_experts"]),
        "--top-k", str(cfg["num_experts_per_tok"]),
        "--held", f"{cfg['first_expert']},{cfg['n_routed_experts']}",
        "--moe-every", str(cfg["moe_layer_freq"]),
        "--router-score", cfg["scoring_func"],
        "--router-bias",
        "--routed-scale", repr(float(cfg["routed_scaling_factor"])),
        "--seq-aux",
        "--dropless", "--return-routes", "--untied-head",
        "--vocab", str(cfg["vocab_size"]),
        "--seq-len", str(traffic["seq_len"]), "--batchsize", str(rows),
        "--chunked-ce", str(cfg["head_chunks"]),
        "--lr", str(opt_cfg["lr"]),
        "--aux-coef", str(cfg["aux_loss_coef"]),
        "--steps", "2", "--report-every", "1", "--generate", "0",
    ]


class MoonlightCell(KimiLinearCell):
    """``KimiLinearCell`` around this module's command line."""

    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        # ``KimiLinearCell.telemetry`` reads the sibling's names of the
        # experts held and of the experts a token
        cfg = sizes_of(spec)
        cfg.update(num_experts=cfg["n_routed_experts"],
                   num_experts_per_token=cfg["num_experts_per_tok"])
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


def build(spec) -> MoonlightCell:
    return MoonlightCell(spec)
