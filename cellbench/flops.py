"""Operations and bytes from shapes: what the per-layer metrics divide
measured time into.  Two kinds, each named for what it counts:

* ``*_model_flops``: what the forward and backward passes of the model
  *require* (no recomputation counted) -- the numerator of ``mfu_pct``;
* ``flash_*_call_flops`` / ``flash_*_call_bytes``: what one call of a
  flash kernel needs for the algorithm it runs, *including* the scores
  the backward kernels recompute -- the numerator of a kernel's roofline
  share.  The causal half is taken exactly (s^2/2), so work a kernel
  spends on masked parts of diagonal blocks lowers its share.
"""

from __future__ import annotations


# -- model FLOPs (MFU) ---------------------------------------------------
def lm_model_flops_per_token(n_embd: int, n_layer: int, vocab_size: int,
                             seq_len: int, n_inner: int = 0) -> float:
    """Training FLOPs a token of a GPT-2-shaped LM requires: 6 per
    matmul weight (2 forward, 4 backward) over the blocks' 4 d^2 + 2 d f
    and the tied head's d V, plus causal attention's 12 s d a layer
    (QK^T and PV, forward and backward) halved."""
    f = n_inner or 4 * n_embd
    blocks = 6.0 * (4 * n_embd ** 2 + 2 * n_embd * f) * n_layer
    head = 6.0 * n_embd * vocab_size
    attention = 0.5 * 12.0 * seq_len * n_embd * n_layer
    return blocks + head + attention


def _conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def resnet50_forward_macs(image_size: int = 224, num_classes: int = 1000,
                          num_filters: int = 64,
                          stages=(3, 4, 6, 3)) -> int:
    """Multiply-accumulates of one image's forward pass through the
    convolutions and the classifier (the 3x3 strides in a down-sampling
    block, as the program has it)."""
    size = _conv_out(image_size, 7, 2, 3)
    macs = size * size * 7 * 7 * 3 * num_filters
    size = _conv_out(size, 3, 2, 1)  # max-pool
    c_in = num_filters
    for stage, count in enumerate(stages):
        f = num_filters * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out = _conv_out(size, 3, stride, 1)
            macs += size * size * c_in * f          # 1x1 at the input size
            macs += out * out * 9 * f * f           # 3x3, strided
            macs += out * out * f * 4 * f           # 1x1 expand
            if j == 0:
                macs += out * out * c_in * 4 * f    # projection shortcut
            size, c_in = out, 4 * f
    return macs + c_in * num_classes


def resnet50_model_flops_per_image(**sizes) -> float:
    """Training FLOPs an image requires: forward MACs x 2, x 3 for the
    backward pass's two products per forward one."""
    return 6.0 * resnet50_forward_macs(**sizes)


# -- flash-attention kernel calls (roofline) -------------------------------
#: matmuls of s x s x dh a call runs: fwd QK^T, PV; dq: QK^T, dP, dQ;
#: dkv: QK^T, dV, dP, dK
_FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
#: (b, s, h, dh) tensors a call reads or writes once: fwd q k v o;
#: dq: q k v do dq; dkv: q k v do dk dv
_FLASH_TENSORS = {"fwd": 4, "dq": 5, "dkv": 6}


def flash_call_flops(kind: str, b: int, h: int, s: int, dh: int,
                     causal: bool = True) -> float:
    full = _FLASH_MATMULS[kind] * 2.0 * b * h * s * s * dh
    return full / 2 if causal else full


def flash_call_bytes(kind: str, b: int, h: int, s: int, dh: int,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of a call: every operand and result once, plus
    the float32 row statistics (lse; delta in the backward)."""
    stats = {"fwd": 1, "dq": 2, "dkv": 2}[kind] * 4.0 * b * h * s
    return _FLASH_TENSORS[kind] * float(b * s * h * dh) * itemsize + stats


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take, and which bound applies."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(compute, memory), "compute" if compute >= memory else "memory"
