"""Operations and bytes of a Mamba-2 / attention hybrid's training step,
from shapes: what ``mfu_pct.granite`` and the scan's roofline share
divide measured time into.  Counted from the definitions (the chunked
scan by the matmuls of its algorithm, attention by its causal half), so
the same numbers whatever implements them; recomputation is never
counted as model work.
"""

from __future__ import annotations


def sizes_of(spec):
    """A cell's sizes with its ``layer_types`` (the one size that is a
    list: the rehearsal's own where it has one), or None for a
    configuration that is no Mamba-2 hybrid."""
    cfg = dict(spec.sizes)
    if "mamba_n_heads" not in cfg:
        return None
    cfg["layer_types"] = tuple(
        cfg.get("layer_types") or spec.config["layer_types"])
    return cfg


def _inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def mamba_weights(cfg: dict) -> int:
    """Matmul weights of a Mamba-2 mixer: ``W_in`` to ``z | xBC | dt``
    and ``W_out``."""
    d, inner = cfg["hidden_size"], _inner(cfg)
    return d * (2 * inner + 2 * cfg["mamba_d_state"]
                + cfg["mamba_n_heads"]) + inner * d


def attention_weights(cfg: dict) -> int:
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    dh = d // hq
    return 2 * d * hq * dh + 2 * d * hkv * dh


def mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def ssd_parts(cfg: dict, s: int) -> dict:
    """The chunked scan's forward matmul FLOPs for one sequence of ``s``
    positions, by part: ``scores`` (``C B^T``: one ``(chunk, chunk)``
    product a chunk for all heads, one group), ``inside`` (the masked
    scores against ``x``, a head), ``states`` (each chunk's own end
    state) and ``carried`` (``C`` against the state handed in)."""
    q, n, inner = cfg["mamba_chunk_size"], cfg["mamba_d_state"], _inner(cfg)
    chunks = -(-s // q)
    return {"scores": 2.0 * chunks * q * q * n,
            "inside": 2.0 * chunks * q * q * inner,
            "states": 2.0 * chunks * q * inner * n,
            "carried": 2.0 * chunks * q * inner * n}


def ssd_flops(cfg: dict, s: int, kind: str) -> float:
    """Matmul FLOPs of one layer's scan on one sequence: ``"fwd"`` the
    four parts; ``"bwd"`` two products for each of the forward's and the
    scores once more (the backward computes them again)."""
    parts = ssd_parts(cfg, s)
    forward = sum(parts.values())
    return forward if kind == "fwd" else 2.0 * forward + parts["scores"]


def ssd_bytes(cfg: dict, s: int, kind: str, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's scan on one sequence: ``x`` and
    ``y`` once each, ``B`` and ``C`` once, ``dt`` in float32; the
    backward reads those and ``dy`` and writes the four gradients."""
    once = s * ((2 * _inner(cfg) + 2 * cfg["mamba_d_state"]) * itemsize
                + 4 * cfg["mamba_n_heads"])
    return float(once) if kind == "fwd" else 2.0 * once


def attention_model_flops(cfg: dict, s: int) -> float:
    """Training FLOPs of one attention layer's ``q k^T`` and ``p v`` on
    one sequence: 4 dh a live pair and head forward, twice that
    backward, over the causal mask's s(s + 1) / 2 pairs."""
    hq = cfg["num_attention_heads"]
    return 12.0 * (s * (s + 1) // 2) * hq * (cfg["hidden_size"] // hq)


def step_model_flops(cfg: dict, s: int, rows: int) -> float:
    """Training FLOPs one step requires: 6 a matmul weight a position
    (2 forward, 4 backward) over every layer's mixer and MLP and the
    tied head's rows held, the scan's matmuls forward and twice
    backward, attention's causal half.  No recomputation."""
    kinds = list(cfg["layer_types"])
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    weights = n_mamba * mamba_weights(cfg) + n_attn * attention_weights(cfg) \
        + len(kinds) * mlp_weights(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    return rows * (6.0 * weights * s
                   + 3.0 * n_mamba * ssd_flops(cfg, s, "fwd")
                   + n_attn * attention_model_flops(cfg, s))
