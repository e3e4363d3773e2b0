"""Operations and bytes of a Kimi-Delta-Attention / latent-attention
hybrid's training step with sparse experts behind a leading dense
layer, from shapes and from the expert layers' counters: what
``mfu_pct.kimilinear`` and the roofline shares of the channel-wise delta
rule's scan and of the 192 / 128 attention launches divide measured
time into.  Counted from the definitions (the chunked delta rule by the
matmuls of its algorithm, attention by its causal half, the experts by
the routes that landed on held ones), so the same numbers whatever
implements them; recomputation is never counted as model work, nor are
channels a kernel pads a key with.
"""

from __future__ import annotations

#: the configuration's keys that are no numbers and size the model
_SHAPE_KEYS = ("linear_attn_config", "moe_router_activation_func")


def sizes_of(spec) -> dict:
    """``spec.sizes`` (the configuration's numbers, a rehearsal's tiny
    ones over them) with the configuration's groups that size the
    model."""
    return {**{k: spec.config[k] for k in _SHAPE_KEYS}, **spec.sizes}


def layer_kinds(cfg: dict) -> tuple:
    """``(mixer kind, MLP kind)`` of each layer."""
    full = cfg["linear_attn_config"]["full_attn_layers"]  # counted from 1
    return tuple(
        ("latent_attention" if i + 1 in full else "kda",
         "dense" if i < int(cfg["first_k_dense_replace"]) else "experts")
        for i in range(int(cfg["num_hidden_layers"])))


def _kda(cfg: dict):
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"])


def kda_weights(cfg: dict) -> int:
    """Matmul weights of a KDA mixer: ``W_qkv``, ``W_fa``, ``W_fb``,
    ``W_b``, ``W_ga``, ``W_gb`` and ``W_o``."""
    d = cfg["hidden_size"]
    h, dk = _kda(cfg)
    return d * 3 * h * dk + 2 * (d * dk + dk * h * dk) + d * h + h * dk * d


def latent_weights(cfg: dict) -> int:
    """``W_q``, ``W_kva``, ``W_kvb``, ``W_o``."""
    d, hq, rank = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return d * hq * (dn + dr) + d * (rank + dr) + rank * hq * (dn + dv) \
        + hq * dv * d


def dense_mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def dense_moe_weights(cfg: dict) -> int:
    """What every position goes through in an expert layer: the router
    and the shared expert."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] + 3 * d * cfg[
        "moe_intermediate_size"] * cfg["num_shared_experts"]


def kda_parts(cfg: dict, s: int) -> dict:
    """The chunked channel-wise delta rule's forward matmul FLOPs for one
    sequence of ``s`` positions, by part (``chainermn_tpu.ops.
    gated_delta``'s docstring has the algorithm), every part a head's
    and a chunk's: ``kk`` and ``qk`` (``A`` and ``P``: ``chunk^2 dk``
    multiply-adds each, the decay inside the contraction), ``solve``
    (``T`` applied to ``[beta V | beta (K * e^G)]`` as a forward
    substitution would), ``read`` (``W S``), ``from_state`` (``(Q *
    e^G) S``), ``inside`` (``P V'``) and ``state`` (``(K * e^{G_C -
    G})^T V'``)."""
    c = cfg["linear_chunk_size"]
    h, dk = _kda(cfg)
    per_head = float(-(-s // c) * h)
    return {"kk": 2.0 * per_head * c * c * dk,
            "qk": 2.0 * per_head * c * c * dk,
            "solve": per_head * c * c * 2 * dk,
            "read": 2.0 * per_head * c * dk * dk,
            "from_state": 2.0 * per_head * c * dk * dk,
            "inside": 2.0 * per_head * c * c * dk,
            "state": 2.0 * per_head * c * dk * dk}


def kda_flops(cfg: dict, s: int, kind: str) -> float:
    """Matmul FLOPs of one layer's scan on one sequence: ``"fwd"`` the
    seven parts; ``"bwd"`` two products for each of the forward's and
    ``kk`` and ``qk`` once more (the backward computes them again)."""
    parts = kda_parts(cfg, s)
    forward = sum(parts.values())
    return forward if kind == "fwd" \
        else 2.0 * forward + parts["kk"] + parts["qk"]


def kda_bytes(cfg: dict, s: int, kind: str, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's scan on one sequence: ``q``,
    ``k``, ``v`` and ``o`` once each, ``g`` (a float32 a head and key
    channel: as many bytes as the four together in bfloat16) and
    ``beta`` in float32; the backward reads those and ``do`` and writes
    the five gradients."""
    h, dk = _kda(cfg)
    once = s * h * (4 * dk * itemsize + 4 * (dk + 1))
    return float(once) if kind == "fwd" else 2.0 * once


#: products of a live pair a launch runs, as (over the key width, over
#: the value width): fwd QK^T, PV; dq: QK^T, dQ | dP; dkv: QK^T, dK |
#: dV, dP
_FLASH_PRODUCTS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}
#: tensors a launch reads or writes once, as (of the key width, of the
#: value width): fwd q k | v o; dq: q k dq | v do; dkv: q k dk | v do dv
_FLASH_TENSORS = {"fwd": (2, 2), "dq": (3, 2), "dkv": (3, 3)}


def _latent(cfg: dict):
    return (int(cfg["num_attention_heads"]),
            int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def flash_call_flops(cfg: dict, kind: str, b: int, s: int) -> float:
    """FLOPs of one launch of the causal kernels over ``b`` sequences:
    2 a multiply-add over the key width (192) or the value width (128)
    a live pair and head (forward: ``2 (192 + 128)``)."""
    hq, dk, dv = _latent(cfg)
    over_keys, over_values = _FLASH_PRODUCTS[kind]
    return 2.0 * (over_keys * dk + over_values * dv) \
        * b * hq * (s * (s + 1) // 2)


def flash_call_bytes(cfg: dict, kind: str, b: int, s: int,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of a launch: every operand and result once at
    its own width, plus the float32 row statistics (lse; delta in the
    backward)."""
    hq, dk, dv = _latent(cfg)
    of_keys, of_values = _FLASH_TENSORS[kind]
    stats = {"fwd": 1, "dq": 2, "dkv": 2}[kind] * 4.0 * b * hq * s
    return float(b * s * hq) * (of_keys * dk + of_values * dv) * itemsize \
        + stats


def attention_model_flops(cfg: dict, s: int) -> float:
    """Training FLOPs of one latent-attention layer's ``q k^T`` and ``p
    v`` on one sequence: forward and twice that backward, over the
    causal mask's s(s + 1) / 2 pairs."""
    return 3.0 * flash_call_flops(cfg, "fwd", 1, s)


def step_model_flops(cfg: dict, s: int, rows: int,
                     rows_routed: float) -> float:
    """Training FLOPs one step requires: 6 a matmul weight a position (2
    forward, 4 backward) over every layer's mixer, the dense layers'
    MLP, the expert layers' router and shared expert and the head's
    rows held; the routed experts by the routes that landed on held
    experts (``rows_routed``, summed over layers); the scan's matmuls
    forward and twice backward; attention's causal half.  No
    recomputation."""
    kinds = layer_kinds(cfg)
    count = lambda i, kind: sum(k[i] == kind for k in kinds)
    n_kda, n_latent = count(0, "kda"), count(0, "latent_attention")
    weights = n_kda * kda_weights(cfg) + n_latent * latent_weights(cfg) \
        + count(1, "dense") * dense_mlp_weights(cfg) \
        + count(1, "experts") * dense_moe_weights(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return rows * (6.0 * weights * s
                   + 3.0 * n_kda * kda_flops(cfg, s, "fwd")
                   + n_latent * attention_model_flops(cfg, s)) \
        + 6.0 * expert * rows_routed
