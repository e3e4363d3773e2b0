"""Operations and bytes of a Gated DeltaNet / gated-attention hybrid's
training step with sparse experts, from shapes and from the expert
layers' counters: what ``mfu_pct.qwen3next`` and the delta-rule scan's
roofline share divide measured time into.  Counted from the definitions
(the chunked delta rule by the matmuls of its algorithm, attention by
its causal half, the experts by the routes that landed on held ones),
so the same numbers whatever implements them; recomputation is never
counted as model work.
"""

from __future__ import annotations


def layer_kinds(cfg: dict) -> tuple:
    every = int(cfg["full_attention_interval"])
    return tuple("attention" if (i + 1) % every == 0
                 else "linear_attention"
                 for i in range(int(cfg["num_hidden_layers"])))


def _keys_values(cfg: dict):
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def delta_weights(cfg: dict) -> int:
    """Matmul weights of a Gated DeltaNet mixer: ``W_qkvz``, ``W_ba``
    and ``W_o``."""
    d = cfg["hidden_size"]
    keys, values = _keys_values(cfg)
    return d * (2 * keys + 2 * values) \
        + d * 2 * cfg["linear_num_value_heads"] + values * d


def attention_weights(cfg: dict) -> int:
    """``W_q`` (query and gate a head), ``W_k``, ``W_v``, ``W_o``."""
    d, hq, hkv, dh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    return 3 * d * hq * dh + 2 * d * hkv * dh


def dense_moe_weights(cfg: dict) -> int:
    """What every position goes through in an expert layer: the router,
    the shared expert and its gate."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d


def gdn_parts(cfg: dict, s: int) -> dict:
    """The chunked delta rule's forward matmul FLOPs for one sequence of
    ``s`` positions, by part (``chainermn_tpu.ops.gated_delta``'s
    docstring has the algorithm): ``kk`` and ``qk`` a key head and
    chunk; ``solve`` (``T`` applied to ``[beta V | beta e^G K]`` as a
    forward substitution would: the least any way of solving needs),
    ``read`` (``W S``), ``from_state`` (``(Q e^G) S``), ``inside`` (the
    decayed ``Q K^T`` against ``V'``) and ``state`` (``(K e^{G_C -
    G})^T V'``) a value head and chunk."""
    c = cfg["linear_chunk_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    chunks = -(-s // c)
    per_head = float(chunks * hv)
    return {"kk": 2.0 * chunks * hk * c * c * dk,
            "qk": 2.0 * chunks * hk * c * c * dk,
            "solve": per_head * c * c * (dk + dv),
            "read": 2.0 * per_head * c * dk * dv,
            "from_state": 2.0 * per_head * c * dk * dv,
            "inside": 2.0 * per_head * c * c * dv,
            "state": 2.0 * per_head * c * dk * dv}


def gdn_flops(cfg: dict, s: int, kind: str) -> float:
    """Matmul FLOPs of one layer's scan on one sequence: ``"fwd"`` the
    seven parts; ``"bwd"`` two products for each of the forward's and
    ``kk`` and ``qk`` once more (the backward computes them again)."""
    parts = gdn_parts(cfg, s)
    forward = sum(parts.values())
    return forward if kind == "fwd" \
        else 2.0 * forward + parts["kk"] + parts["qk"]


def gdn_bytes(cfg: dict, s: int, kind: str, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's scan on one sequence: ``q``,
    ``k``, ``v`` and ``o`` once each, ``g`` and ``beta`` in float32; the
    backward reads those and ``do`` and writes the five gradients."""
    keys, values = _keys_values(cfg)
    once = s * ((2 * keys + 2 * values) * itemsize
                + 2 * 4 * cfg["linear_num_value_heads"])
    return float(once) if kind == "fwd" else 2.0 * once


def attention_model_flops(cfg: dict, s: int) -> float:
    """Training FLOPs of one attention layer's ``q k^T`` and ``p v`` on
    one sequence: 4 dh a live pair and head forward, twice that
    backward, over the causal mask's s(s + 1) / 2 pairs."""
    return 12.0 * (s * (s + 1) // 2) * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def step_model_flops(cfg: dict, s: int, rows: int,
                     rows_routed: float) -> float:
    """Training FLOPs one step requires: 6 a matmul weight a position (2
    forward, 4 backward) over every layer's mixer, router and shared
    expert and the head's rows held; the routed experts by the routes
    that landed on held experts (``rows_routed``, summed over layers);
    the scan's matmuls forward and twice backward; attention's causal
    half.  No recomputation."""
    kinds = layer_kinds(cfg)
    n_delta, n_attn = kinds.count("linear_attention"), \
        kinds.count("attention")
    sparse = sum((i + 1) % int(cfg["decoder_sparse_step"]) == 0
                 for i in range(len(kinds)))
    weights = n_delta * delta_weights(cfg) \
        + n_attn * attention_weights(cfg) \
        + sparse * dense_moe_weights(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return rows * (6.0 * weights * s
                   + 3.0 * n_delta * gdn_flops(cfg, s, "fwd")
                   + n_attn * attention_model_flops(cfg, s)) \
        + 6.0 * expert * rows_routed
