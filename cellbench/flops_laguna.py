"""Operations and bytes of the training step of a model of window and
full attention layers with sparse experts behind a leading dense layer,
from shapes and from the expert layers' counters: what
``mfu_pct.laguna`` and the roofline shares of the window launches, of
the full layers' launches and of the held experts' products divide
measured time into.  Counted from the definitions (6 a matmul weight a
position, attention by the pairs its mask has: ``sum_i min(i + 1,
window)`` a window head and ``s (s + 1) / 2`` a full one, whatever
blocks a kernel executes; the experts by the routes that landed on held
ones), so the same numbers whatever implements them; recomputation is
never counted as model work, and the rotation and the gates, which have
no matmul over the stream's width but the gate's small one, are none
beyond it.
"""

from __future__ import annotations

#: the configuration's keys that are no numbers and size the model (a
#: rehearsal's tiny sizes may replace a list)
_SHAPE_KEYS = ("layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "rope_parameters")
WINDOW_LAYER = "sliding_attention"

#: matmuls of a launch over the head's width, a live pair: fwd ``q k^T``
#: and ``p v``; dq those and ``do v^T``; dk/dv those and ``ds^T q``
_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}
#: tensors a launch reads or writes once, of ``(query heads, key/value
#: heads)``: fwd q o | k v; dq q do dq | k v; dkv q do | k v dk dv
_TENSORS = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4)}


def sizes_of(spec) -> dict:
    """``spec.sizes`` (the configuration's numbers, a rehearsal's tiny
    ones and lists over them) with the configuration's lists a layer
    and its rotations."""
    return {**{k: spec.config[k] for k in _SHAPE_KEYS}, **spec.sizes}


def layer_kinds(cfg: dict) -> tuple:
    """``(layer type, MLP kind, query heads)`` of each layer."""
    n = int(cfg["num_hidden_layers"])
    return tuple(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                     cfg["num_attention_heads_per_layer"][:n]))


def live_pairs(s: int, window=None) -> int:
    """(query, key) pairs of one head's mask over ``s`` positions:
    ``sum_i min(i + 1, window)``, the causal half without a window."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def window_of(cfg: dict, layer_type: str):
    return int(cfg["sliding_window"]) if layer_type == WINDOW_LAYER else None


def mixer_weights(cfg: dict, heads: int) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the gate a head."""
    d, dh, hkv = cfg["hidden_size"], cfg["head_dim"], \
        cfg["num_key_value_heads"]
    return 2 * d * heads * dh + 2 * d * hkv * dh + d * heads


def dense_mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def dense_moe_weights(cfg: dict) -> int:
    """What every position goes through in an expert layer: the router,
    the shared expert and its gate."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d


def expert_layers(cfg: dict) -> int:
    return sum(mlp == "sparse" for _, mlp, _ in layer_kinds(cfg))


def flash_call_flops(cfg: dict, kind: str, b: int, s: int, heads: int,
                     window=None) -> float:
    """FLOPs of one launch over ``b`` sequences: 2 a multiply-add over
    the head's width a live pair, head and product of ``kind``."""
    return 2.0 * _PRODUCTS[kind] * cfg["head_dim"] * b * heads \
        * live_pairs(s, window)


def flash_call_bytes(cfg: dict, kind: str, b: int, s: int, heads: int,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of a launch: every operand and result once (a
    key/value head once, however many query heads read it), plus the
    float32 row statistics (lse; delta in the backward)."""
    of_q, of_kv = _TENSORS[kind]
    stats = {"fwd": 1, "dq": 2, "dkv": 2}[kind] * 4.0 * b * heads * s
    return float(b * s * cfg["head_dim"]) * itemsize * (
        of_q * heads + of_kv * cfg["num_key_value_heads"]) + stats


def attention_model_flops(cfg: dict, s: int, heads: int,
                          window=None) -> float:
    """Training FLOPs of one layer's ``q k^T`` and ``p v`` on one
    sequence: forward and twice that backward, over the mask's pairs."""
    return 3.0 * flash_call_flops(cfg, "fwd", 1, s, heads, window)


def step_model_flops(cfg: dict, s: int, rows: int,
                     rows_routed: float) -> float:
    """Training FLOPs one step requires: 6 a matmul weight a position (2
    forward, 4 backward) over every layer's mixer at its own head
    count, the dense layers' MLP, the expert layers' router, shared
    expert and gate and the head's rows held; the routed experts by the
    routes that landed on held experts (``rows_routed``, summed over
    layers); attention by its mask's pairs, a layer its own.  No
    recomputation."""
    weights, attention = cfg["hidden_size"] * cfg["vocab_size"], 0.0
    for layer_type, mlp, heads in layer_kinds(cfg):
        weights += mixer_weights(cfg, heads) + (
            dense_mlp_weights(cfg) if mlp == "dense"
            else dense_moe_weights(cfg))
        attention += attention_model_flops(
            cfg, s, heads, window_of(cfg, layer_type))
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return rows * (6.0 * weights * s + attention) \
        + 6.0 * expert * rows_routed
