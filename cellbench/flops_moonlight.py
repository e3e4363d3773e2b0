"""Operations and bytes of a latent-attention model's training step with
sparse experts behind a leading dense layer (every layer's mixer latent
attention with a rotation on its shared channels), from shapes and from
the expert layers' counters: what ``mfu_pct.moonlight`` and the roofline
shares of the 192 / 128 attention launches and of the held experts'
products divide measured time into.  Counted from the definitions (6 a
matmul weight a position, attention by its causal half at its two
widths, the experts by the routes that landed on held ones), so the
same numbers whatever implements them; recomputation is never counted
as model work, and the rotation, which has no matmul, is none.

The latent mixer's weights and launches are ``flops_kimilinear``'s
functions: they read the keys both configurations spell alike
(``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``).
"""

from __future__ import annotations

from .flops_kimilinear import (  # noqa: F401 (readers use them)
    attention_model_flops,
    dense_mlp_weights,
    flash_call_bytes,
    flash_call_flops,
    latent_weights,
)

#: the configuration's keys that are no numbers and size the model
_SHAPE_KEYS = ("scoring_func",)


def sizes_of(spec) -> dict:
    """``spec.sizes`` (the configuration's numbers, a rehearsal's tiny
    ones over them) with the configuration's keys that are no numbers
    and size the model."""
    return {**{k: spec.config[k] for k in _SHAPE_KEYS}, **spec.sizes}


def expert_layers(cfg: dict) -> int:
    """The layers that have experts: all but the leading dense ones."""
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def dense_moe_weights(cfg: dict) -> int:
    """What every position goes through in an expert layer: the router
    and the shared experts."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] + 3 * d * cfg[
        "moe_intermediate_size"] * cfg["n_shared_experts"]


def step_model_flops(cfg: dict, s: int, rows: int,
                     rows_routed: float) -> float:
    """Training FLOPs one step requires: 6 a matmul weight a position (2
    forward, 4 backward) over every layer's mixer, the dense layers'
    MLP, the expert layers' router and shared experts and the head's
    rows held; the routed experts by the routes that landed on held
    experts (``rows_routed``, summed over layers); attention's causal
    half.  No recomputation."""
    n_layers, n_sparse = int(cfg["num_hidden_layers"]), expert_layers(cfg)
    weights = n_layers * latent_weights(cfg) \
        + (n_layers - n_sparse) * dense_mlp_weights(cfg) \
        + n_sparse * dense_moe_weights(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return rows * (6.0 * weights * s
                   + n_layers * attention_model_flops(cfg, s)) \
        + 6.0 * expert * rows_routed
