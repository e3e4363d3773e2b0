"""Operations and bytes of the SDAR-MoE block-diffusion step, from
shapes and from the expert layers' counters: what ``mfu_pct.sdar`` and
the roofline shares of the block-causal kernels and of the expert
products divide measured time into.  Counted from the definitions (the
mask's live pairs, the routes to held experts), so the same numbers
whatever implements them; recomputation is never counted as model work.
"""

from __future__ import annotations

#: matmuls of (pairs x dh) a kind runs: fwd QK^T, PV; dq: QK^T, dP, dQ;
#: dkv: QK^T, dV, dP, dK (the backward kinds recompute the scores)
_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def live_pairs(s: int, block: int) -> int:
    """Live (query, key) pairs a head of block-diffusion attention over
    one sequence of ``s`` tokens: clean queries over clean keys of their
    own and earlier blocks, s(s+B)/2; noised queries over clean keys of
    earlier blocks, s(s-B)/2; noised queries over their own block's
    noised keys, sB."""
    return s * (s + block) // 2 + s * (s - block) // 2 + s * block


def attention_model_flops(s: int, block: int, heads: int, dh: int) -> float:
    """Training FLOPs of one layer's attention on one sequence: QK^T and
    PV forward (4 dh a pair and head), twice that backward: 12 s(s+B)
    heads dh."""
    return 12.0 * live_pairs(s, block) * heads * dh


def step_model_flops(cfg: dict, s: int, rows: int, block: int,
                     rows_routed: float) -> float:
    """Training FLOPs one step of the cell requires: 6 a matmul weight a
    position over both copies (attention projections and router), the
    experts by the routes that landed on held experts (``rows_routed``,
    summed over layers), the head on the noised copy only, and
    attention by its live pairs."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    positions = 2 * s * rows
    per_position = d * hq * dh * 2 + d * hkv * dh * 2 \
        + d * cfg["router_experts"]
    experts = 3 * d * cfg["moe_intermediate_size"]
    return (6.0 * per_position * positions * L
            + 6.0 * experts * rows_routed
            + 6.0 * d * cfg["vocab_size"] * s * rows
            + attention_model_flops(s, block, hq, dh) * rows * L)


def bdflash_flops(kind: str, b: int, heads: int, s: int, dh: int,
                  block: int) -> float:
    """What the ``kind`` kernels of one layer need for ``b`` sequences:
    the live pairs of both launches (and of the own-block piece), times
    the kind's matmuls of 2 dh FLOPs a pair."""
    return _MATMULS[kind] * 2.0 * dh * live_pairs(s, block) * heads * b


def bdflash_bytes(kind: str, b: int, heads: int, kv_heads: int, s: int,
                  dh: int, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's ``kind`` kernels: q and the
    result (do, dq in the backward) over both copies with ``heads``
    heads, k and v (dk, dv) over both copies with ``kv_heads``, each
    once, plus the float32 row statistics."""
    q_like = {"fwd": 2, "dq": 3, "dkv": 2}[kind]  # q o | q do dq | q do
    kv_like = {"fwd": 2, "dq": 2, "dkv": 4}[kind]  # k v (| dk dv)
    stats = {"fwd": 1, "dq": 2, "dkv": 2}[kind] * 4.0 * b * heads * 2 * s
    return (q_like * heads + kv_like * kv_heads) * float(
        b * 2 * s * dh) * itemsize + stats


def expert_flops(rows_routed: float, d: int, f: int) -> float:
    """Three d x f products a routed row forward, their six backward."""
    return 9.0 * 2.0 * d * f * rows_routed


def expert_bytes(rows_routed: float, d: int, f: int, held: int,
                 layers: int, itemsize: int = 2) -> float:
    """The held experts' weights read once each way and their gradient
    written once (float32), and every routed row's input, hidden pair
    and output once each way."""
    weights = layers * held * 3.0 * d * f
    rows = rows_routed * (2 * d + 3 * f) * 2.0
    return weights * (2 * itemsize + 4) + rows * itemsize
