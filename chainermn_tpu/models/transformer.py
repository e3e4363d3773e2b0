"""Transformer language model, TPU-first and sequence-parallel-native.

The reference has no transformer (2017-era RNN/CNN zoo); this is the
model family its modern successors need, built directly on the
framework's sequence-parallel layer (SURVEY.md section 5.7: ring/Ulysses
over the reference's p2p/alltoall primitives).

Design:
* One module, two execution regimes.  With ``seq_axis=None`` it is an
  ordinary single-device causal LM.  Called inside ``shard_map`` with the
  token sequence sharded over ``seq_axis``, the SAME module becomes
  sequence-parallel: positional embeddings use global positions (axis
  index offset) and attention runs :func:`parallel.ring_attention` (or
  :func:`parallel.ulysses_attention` with ``sp_impl="ulysses"``) over
  the axis — everything else (LN, MLPs, embeddings) is position-local and
  needs no communication.
* ``attention_fn`` hook: the single-device core (default
  ``ops.multi_head_attention``; pass ``ops.flash_attention_fn()`` for the
  Pallas kernel).
* bfloat16 compute, fp32 params, fp32 LayerNorm/softmax; logits fp32.
* Pre-LN blocks; weight-tied output head (standard, halves embed params).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.ad_checkpoint import checkpoint_name

# Communication goes through the audited wrappers — raw lax collectives
# outside the sanctioned comm modules are a lint error (analysis.lint).
from chainermn_tpu.functions import collectives as _cc


class MlpBlock(nn.Module):
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = nn.Dense(self.d_ff, dtype=self.dtype)(x)
        h = nn.gelu(h)
        return nn.Dense(d, dtype=self.dtype)(h)


#: the kinds of sequence mixer a layer can have
#: (``BlockOptions.layer_types``; the examples' ``--layer-types`` and
#: :func:`remat_plan` read them here): :class:`SelfAttention`,
#: :class:`Mamba2Mixer`, :class:`GatedDeltaMixer`, :class:`KdaMixer`,
#: :class:`LatentAttention`, and :class:`SelfAttention` again under the
#: window layers' fields of :class:`BlockOptions`
LAYER_KINDS = ("attention", "mamba", "linear_attention", "kda",
               "latent_attention", "window_attention")


class YarnScaling(NamedTuple):
    """YaRN (arXiv:2309.00071) on a rotation's frequencies, as
    ``transformers``' ``_compute_yarn_parameters`` reads a config's
    ``rope_parameters``: positions interpolated by ``factor`` on the
    slow channels, left as they are on the fast ones, a linear ramp
    between (:func:`yarn_frequencies`), and cos and sin times
    ``attention_factor`` (``None``: ``0.1 ln(factor) + 1``)."""

    factor: float
    original_length: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def cos_sin_factor(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class BlockOptions:
    """What the one transformer block can be besides GPT-2's (the
    defaults): the options of its norms, of its attention, of its
    sequence mixer and MLP, and of the stream around them.  A module
    takes them as one hashable field, ``options``.

    ``norm``: ``"layernorm"`` or ``"rmsnorm"`` (gain only), epsilon
    ``norm_eps``.  ``n_kv_heads``: key/value heads, each shared by
    ``n_heads // n_kv_heads`` query heads.  ``head_dim``: width of a
    head where it is not ``d_model // n_heads``.  ``rope_theta``: rotary
    positions of that base on q and k (the model then holds no position
    table; in a ``"latent_attention"`` layer they turn the channels all
    heads share, in neighbouring pairs: :func:`rotate_pairs`).  ``qk_norm``: an RMSNorm over each head of q and k, before
    the rotation.  ``block_diffusion``: block length ``B`` of
    block-diffusion training; the sequence axis then holds the clean
    copy of every sequence followed by its noised copy, both at
    positions ``0..s-1``, under :func:`ops.pallas_attention.block_diffusion_mask`.
    ``attention_scale``: the factor on ``q k^T`` where it is not
    ``head_dim ** -0.5``, handed to the kernels as their ``scale``.
    ``use_flash``: the Pallas kernels instead of a dense masked softmax.
    ``rotary_fraction``: the leading share of each head's channels that
    the rotation turns (the rest pass unrotated).  ``attn_output_gate``:
    ``q_proj`` is twice as wide, a head's second half a gate, and the
    output projection reads ``attention * sigmoid(gate)``.
    ``zero_centered_norm``: every RMSNorm gain of the block and of the
    model (the q/k norms too, not a mixer's own gated norm) is ``1 +
    w`` with ``w`` initialised 0.  ``head_gate``: one gate a head,
    ``sigmoid(g_proj(x))`` with ``g_proj (d, heads)``, on the head's
    whole output before the output projection (arXiv:2505.06708's
    head-wise form; ``attn_output_gate`` is its element-wise one).
    ``rope_yarn``: a :class:`YarnScaling` on the ``"attention"``
    layers' rotation.  The ``"window_attention"`` layers are
    :class:`SelfAttention` too, each query seeing the last ``window``
    keys, its own among them (``i - window < j <= i``), with
    ``window_heads`` query heads (0: the model's) on the same
    ``n_kv_heads`` of ``head_dim``, and a rotation of base
    ``window_rope_theta`` over ``window_rotary_fraction`` of a head
    (``None``: the attention layers'), never YaRN-scaled: a layer's
    head count, window and rotation are its kind's.
    Any of the attention options takes :class:`SelfAttention` off its
    fused-qkv path onto separate ``q_proj`` / ``k_proj`` / ``v_proj`` /
    ``o_proj`` kernels; that path is single-device in the sequence and
    head axes (no ``seq_axis``, ``tp_axis`` or ``decode``).

    ``layer_types``: the kind of each layer's sequence mixer, one of
    :data:`LAYER_KINDS` (``"mamba"``: :class:`Mamba2Mixer`,
    ``"linear_attention"``: :class:`GatedDeltaMixer`), layer ``i``
    taking entry ``i % len(layer_types)``; ``None``: attention in every
    layer.  The Mamba-2 mixer's sizes: ``ssm_heads`` heads of
    ``ssm_head_dim`` (its inner width is their product), a state of
    ``ssm_state`` a head channel, ``ssm_conv`` taps of the causal
    convolution, the scan's ``ssm_chunk``.  The Gated DeltaNet mixer's:
    ``gdn_key_heads`` key heads of ``gdn_key_dim``, each serving
    ``gdn_value_heads / gdn_key_heads`` of the ``gdn_value_heads``
    value heads of ``gdn_value_dim``, ``gdn_conv`` taps, the scan's
    ``gdn_chunk``.  ``"kda"``: :class:`KdaMixer`, a delta rule with a
    decay a key channel, sized by the same fields (``gdn_value_heads``
    heads, a key head each).  ``"latent_attention"``:
    :class:`LatentAttention`, whose keys and values all heads expand
    from one compression of ``latent_kv_rank`` channels; a head's
    query and key are ``latent_nope_dim`` channels of its own beside
    ``latent_shared_dim`` that all heads share, its value
    ``latent_value_dim``.  ``gated_mlp``: ``W_out(SiLU(g) * u)`` with
    ``[g | u] = W_in x``, no biases (:class:`GatedMlp`), instead of GELU
    with biases.  ``no_positions``: the model holds no position table and
    attention sees no position at all.  The stream's multipliers:
    ``embedding_multiplier`` on the token embedding,
    ``residual_multiplier`` on what a mixer or an MLP adds to the
    stream, ``logits_scaling`` dividing the logits.  ``remat_blocks``:
    the backward pass computes each block's forward again from the
    block's input.  Besides that input a block keeps what
    :func:`remat_plan` fits into ``remat_budget_bytes`` of one device's
    memory: the gated MLP's ``in_proj`` result (``mlp_in``), the
    state-space mixer's (``ssm_in``), the widest tensors of a block,
    the Gated DeltaNet mixer's ``[q | k | v | z]`` (``gdn_in``), the
    KDA mixer's ``[q | k | v]`` (``kda_in``) and latent attention's
    queries (``latent_in``), whose matmuls the backward then does not
    run again, and before all of them the attention kernels' result
    (``attn_out``: the output and its log-sum-exp), whose forward
    launch the backward then does not run again, and after all of them
    the delta rule's kernels' (``scan_out``: ``o`` and the launch's
    float32 residuals, the state entering every chunk and every chunk's
    ``T``) in the ``linear_attention`` and ``kda`` layers, on the same
    terms.  The budget is
    the program's to fill from what it observes
    (:func:`remat_budget`: the device's memory less the state the step
    holds less a reserve); 0, the default, keeps the input alone."""

    norm: str = "layernorm"
    norm_eps: float = 1e-6
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rope_theta: Optional[float] = None
    qk_norm: bool = False
    block_diffusion: int = 0
    use_flash: bool = False
    attention_scale: Optional[float] = None
    rotary_fraction: float = 1.0
    attn_output_gate: bool = False
    head_gate: bool = False
    rope_yarn: Optional[YarnScaling] = None
    window: int = 0
    window_heads: int = 0
    window_rope_theta: Optional[float] = None
    window_rotary_fraction: Optional[float] = None
    zero_centered_norm: bool = False
    layer_types: Optional[Tuple[str, ...]] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = 64
    latent_kv_rank: int = 0
    latent_nope_dim: int = 128
    latent_shared_dim: int = 64
    latent_value_dim: int = 128
    gated_mlp: bool = False
    no_positions: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    remat_blocks: bool = False
    remat_budget_bytes: int = 0

    @property
    def general_attention(self) -> bool:
        return bool(self.n_kv_heads or self.head_dim or self.rope_theta
                    or self.qk_norm or self.block_diffusion
                    or self.attention_scale or self.no_positions
                    or self.attn_output_gate
                    or self.rotary_fraction != 1.0
                    # the general path's raises (no seq_axis, tp_axis or
                    # decode) cover these too
                    or self.layer_attention)

    @property
    def layer_attention(self) -> bool:
        """Whether attention here has what only the general path and no
        cache has: window layers (their fields), a gate a head, a
        scaled rotation."""
        return bool(self.head_gate or self.rope_yarn or self.window
                    or self.window_heads or self.window_rope_theta
                    or self.window_rotary_fraction is not None
                    or "window_attention" in (self.layer_types or ()))

    def attention_heads(self, kind: str, n_heads: int) -> int:
        """The query heads of a layer of ``kind`` in a model of
        ``n_heads``: the window layers have their own count."""
        return self.window_heads or n_heads \
            if kind == "window_attention" else n_heads

    def layer_type(self, layer: int) -> str:
        """The kind of layer ``layer``'s sequence mixer."""
        if not self.layer_types:
            return "attention"
        kind = self.layer_types[layer % len(self.layer_types)]
        if kind not in LAYER_KINDS:
            raise ValueError(f"layer_types holds {', '.join(LAYER_KINDS)}; "
                             f"got {kind!r}")
        return kind

    def remat_widths(self, d_ff: int, n_heads: int = 0,
                     dtype=jnp.bfloat16, d_model: int = 0) -> dict:
        """Width (last axis) of each result of :data:`REMAT_NAMES` that
        a model of these options and activations of ``dtype`` has: ``[g
        | u]`` of the gated MLP, ``[z | xBC | dt]`` of the state-space
        mixer, ``[q | k | v | z]`` of the Gated DeltaNet mixer, ``[q | k
        | v]`` of the KDA mixer, the ``n_heads`` queries of latent
        attention.  ``attn_out``, where the attention layers run the
        kernels (``use_flash``): a token's ``n_heads`` outputs of the
        values' width (``latent_value_dim`` in a latent layer; in an
        ``attention`` layer ``head_dim``, or ``d_model / n_heads``
        without it; a ``window_attention`` layer's ``window_heads``
        of them) and, in the plan's two-byte units, its ``n_heads``
        float32 log-sum-exps: the widest kind's (the plan has one width
        a name).  None under ``block_diffusion``, whose
        layer runs two launches under the one name: no cell recomputes
        such blocks, and a plan that does not list the name leaves
        their launches as they are.  ``scan_out``, only where the delta
        rule's layers run their kernels here
        (``ops.gated_delta.runs_kernels``; off the TPU and at sizes
        that do not tile nothing of that name is there to keep): in the
        plan's two-byte units a token's ``heads x dv`` of ``o``, its
        share of the float32 state entering its chunk (``heads x dk x
        dv x 2 / chunk``) and of the chunk's float32 ``T`` (``heads x 2
        x chunk``), 24 576 at 32 heads of 128 and a chunk of 64.  With
        KDA layers whose scan runs its XLA form here also
        :data:`KDA_WORK`, no result of a name: it only widens what
        :func:`remat_budget` leaves the step."""
        from chainermn_tpu.ops.gated_delta import runs_kernels

        widths = {}
        kinds = self.layer_types or ("attention",)
        heads, dk, dv = (self.gdn_value_heads, self.gdn_key_dim,
                         self.gdn_value_dim)
        # whether each kind of delta-rule layer here runs its kernels
        scan_kernels = {
            kind: runs_kernels(self.gdn_chunk, heads, key_heads, dk, dv,
                               dtype, channels)
            for kind, key_heads, channels in (
                ("linear_attention", self.gdn_key_heads, False),
                ("kda", heads, True)) if kind in kinds}
        if self.use_flash and n_heads and not self.block_diffusion:
            windowed = self.attention_heads("window_attention", n_heads)
            # a token's values and, as two units each, its log-sum-exps
            values = {"attention": (n_heads * self.head_dim
                                    if self.head_dim else d_model)
                      + 2 * n_heads,
                      "latent_attention": n_heads * (
                          self.latent_value_dim + 2),
                      "window_attention": windowed * (
                          (self.head_dim or d_model // n_heads) + 2)}
            widest = max((w for kind, w in values.items() if kind in kinds),
                         default=0)
            if widest:
                widths["attn_out"] = widest
        if self.gated_mlp:
            widths["mlp_in"] = 2 * d_ff
        if "mamba" in (self.layer_types or ()):
            widths["ssm_in"] = 2 * self.ssm_heads * self.ssm_head_dim \
                + 2 * self.ssm_state + self.ssm_heads
        if "linear_attention" in (self.layer_types or ()):
            widths["gdn_in"] = 2 * self.gdn_key_heads * self.gdn_key_dim \
                + 2 * self.gdn_value_heads * self.gdn_value_dim
        if "kda" in (self.layer_types or ()):
            widths["kda_in"] = heads * (2 * dk + dv)
            if not scan_kernels["kda"]:
                widths[KDA_WORK] = 2 * KDA_WORK_TENSORS * heads * dk
        if "latent_attention" in (self.layer_types or ()):
            widths["latent_in"] = n_heads * (
                self.latent_nope_dim + self.latent_shared_dim)
        if any(scan_kernels.values()):
            widths["scan_out"] = heads * dv + heads * dk * dv * 2 \
                // self.gdn_chunk + heads * 2 * self.gdn_chunk
        return widths


#: the results a block can keep across its recomputation
#: (``jax.ad_checkpoint.checkpoint_name``), in the order a budget is
#: spent on them.  First the attention kernels' result (``out`` and its
#: log-sum-exp, named in ``ops.pallas_attention``'s forward rules) in
#: the ``attention`` and ``latent_attention`` layers: it saves the
#: recomputation a kernel launch, not a matmul, and paid 113 ms a GB
#: kept where it was first read (7.67 ms for 68 MB a layer of 16 heads,
#: ``PERF.md`` section 6, PR 48) against the 7 and 14 ms a GB of the
#: next two.  Then
#: :class:`GatedMlp`'s ``in_proj`` result in every layer,
#: :class:`Mamba2Mixer`'s in the ``mamba`` layers, :class:`GatedDeltaMixer`'s
#: ``in_proj_qkvz`` result in the ``linear_attention`` layers,
#: :class:`KdaMixer`'s ``in_proj_qkv`` result in the ``kda`` layers,
#: :class:`LatentAttention`'s ``q_proj`` result in its layers (each saves
#: one matmul over ``d_model`` a layer; what the first two paid on the
#: chip: ``PERF.md`` section 6, PR 40).  Last the delta rule's kernels'
#: result (``o``, the state entering every chunk and every chunk's
#: ``T``, named in ``ops.gated_delta_kernels``' and ``ops.kda_kernels``'
#: forward rules) in the ``linear_attention`` and ``kda`` layers: a
#: launch again, but of 805 MB a layer of 32 heads at 16 384 tokens
#: (two of its three arrays are float32 residuals): it paid 7.2 ms a GB
#: kept where it was first read (17.4 ms of step for 2.42 GB in three
#: layers; 12.4 where a launch is 10.6 ms, ``PERF.md`` section 6,
#: PR 49), what the in-projections pay.  Last, so that it takes what
#: the other names leave and displaces none of them.
REMAT_NAMES = ("attn_out", "mlp_in", "ssm_in", "gdn_in", "kda_in",
               "latent_in", "scan_out")
#: the kinds of layer (:data:`LAYER_KINDS`) that have a result of that
#: name; a name not here is every layer's with a dense MLP
_REMAT_KIND = {"attn_out": (LAYER_KINDS[0], LAYER_KINDS[4], LAYER_KINDS[5]),
               "ssm_in": LAYER_KINDS[1:2], "gdn_in": LAYER_KINDS[2:3],
               "kda_in": LAYER_KINDS[3:4], "latent_in": LAYER_KINDS[4:5],
               "scan_out": LAYER_KINDS[2:4]}

#: a width among :meth:`BlockOptions.remat_widths` that is no kept
#: result's: what the channel-wise delta rule's XLA form holds a token in
#: float32 while a KDA block's backward runs (the log decays, their
#: copy cut into chunks, the running sums and the cotangents of the
#: first two: :data:`KDA_WORK_TENSORS` tensors of ``heads x dk``), in
#: units of the model's two-byte activations.  :func:`remat_budget` reserves :data:`REMAT_TEMPORARIES`
#: of the widest width, so this keeps the blocks of a model with such
#: layers from keeping results the scan's working set leaves no room
#: for (ahead of time, two 8192-token sequences of 32 heads of 128
#: beside 7.2 GB of state compile with nothing kept and with any one
#: result kept do not: ``PERF.md`` section 6, PR 43).  Goes with the
#: XLA form: where the scan runs its kernels (``ops.gated_delta.
#: runs_kernels``) :meth:`BlockOptions.remat_widths` leaves it out, and
#: what the kernels hold of a layer whose ``scan_out`` is not kept (the
#: entering states and ``T`` of one block's backward, 671 MB at that
#: shape) lies inside :data:`REMAT_TEMPORARIES` (``PERF.md`` section 6,
#: PR 44); a kept layer's are counted by the plan.
KDA_WORK = "kda_work"
KDA_WORK_TENSORS = 5

#: what :func:`remat_budget` leaves the step besides its state: its own
#: temporaries, as so many tensors of the widest kept result (the cell
#: of ``PERF.md`` reads 6.6 of them ahead of time at one 8192-token
#: sequence and 6.3 at two: the blocks' inputs, one block's backward,
#: the head's chunks; known from shapes only this roughly), and bytes
#: clear of the device's limit besides.  "Widest" leaves ``scan_out``
#: out: its width is twice the widest activation's only because two of
#: its three arrays are float32 residuals, the step's temporaries do
#: not grow with it, and a reserve of eight of it (6.44 GB where 3.22
#: and 4.83 stand in the two cells that have the name) would have the
#: plans keep less than without the name
REMAT_TEMPORARIES = 8
REMAT_CLEAR_BYTES = 1 << 30


def remat_plan(layer_kinds, tokens: int, widths: dict, budget_bytes: int,
               itemsize: int = 2, dense=None) -> Tuple[Tuple[str, ...], ...]:
    """What each block keeps besides its input under per-block
    recomputation, a tuple of :data:`REMAT_NAMES` a layer: names are
    added while their bytes (``tokens x widths[name] x itemsize`` a
    layer) fit into ``budget_bytes``, in :data:`REMAT_NAMES`' order, the
    first layers first; ``scan_out`` the last layers first (the backward
    pass starts at the last block with every kept result still held,
    and a block that keeps ``scan_out`` holds there the states and ``T``
    it would have computed again: ahead of time the last of four
    layers' 805 MB cost the step's peak nothing and the first's 0.85
    GB, ``PERF.md`` section 6, PR 49).  ``layer_kinds``: each layer's mixer
    (:meth:`BlockOptions.layer_type`); ``tokens``: the positions one
    device holds a step; ``widths``: :meth:`BlockOptions.remat_widths`;
    ``dense``: whether each layer's MLP is the dense one (``None``:
    every layer's; an expert layer has no ``mlp_in``).  No budget,
    nothing kept."""
    kept = [() for _ in layer_kinds]
    left = budget_bytes
    layers = list(enumerate(layer_kinds))
    for name in REMAT_NAMES:
        if name not in widths:
            continue
        cost = tokens * widths[name] * itemsize
        for i, kind in reversed(layers) if name == "scan_out" else layers:
            if kind not in _REMAT_KIND.get(name, (kind,)) or (
                    name == "mlp_in" and dense and not dense[i]):
                continue
            if cost > left:
                break
            kept[i] += (name,)
            left -= cost
    return tuple(kept)


def model_remat_widths(model) -> dict:
    """:meth:`BlockOptions.remat_widths` of ``model`` (either LM): its
    dense MLP's width (``dense_d_ff`` where the experts' ``d_ff`` is
    another) and its heads."""
    return model.options.remat_widths(
        getattr(model, "dense_d_ff", None) or model.d_ff
        or 4 * model.d_model, model.n_heads, model.dtype, model.d_model)


def model_remat_plan(model, tokens: int):
    """:func:`remat_plan` of the layers of ``model`` (either LM: its
    ``options``, ``n_layers``, ``n_heads``, ``d_ff`` / ``d_model`` and
    ``dtype``; an LM with expert layers says which are sparse,
    ``sparse_layer``) for ``tokens`` positions a device and step, under
    the options' budget."""
    o = model.options
    sparse = getattr(model, "sparse_layer", lambda i: False)
    return remat_plan(
        [o.layer_type(i) for i in range(model.n_layers)], tokens,
        model_remat_widths(model), o.remat_budget_bytes,
        jnp.dtype(model.dtype).itemsize,
        dense=[not sparse(i) for i in range(model.n_layers)])


@functools.lru_cache(maxsize=None)
def _keep(names: Tuple[str, ...]):
    """The ``jax.checkpoint`` policy that keeps the results called
    ``names`` (none: no policy).  One object a set of names: JAX caches
    what it derives from a block's inner programs under the policy it
    was given, and a fresh one a layer has every layer derive and trace
    them again (the scan's backward kernel nine times)."""
    return jax.checkpoint_policies.save_only_these_names(*names) \
        if names else None


def remat_kept(plan, tokens: int, widths: dict, itemsize: int = 2):
    """How far a :func:`remat_plan` engaged: ``(names x layers, bytes)``,
    e.g. ``("mlp_in x10, ssm_in x9", 3939500032)``; ``("", 0)`` for a
    plan that keeps nothing."""
    counts = {name: sum(name in names for names in plan)
              for name in REMAT_NAMES}
    return (", ".join(f"{name} x{n}" for name, n in counts.items() if n),
            sum(n * tokens * widths.get(name, 0) * itemsize
                for name, n in counts.items()))


def remat_budget(device, state, tokens: int, widths: dict,
                 itemsize: int = 2) -> int:
    """The bytes of ``device``'s memory that blocks may fill with kept
    results: the limit the device reports (``memory_stats()
    ["bytes_limit"]``) less the bytes it holds of ``state`` (a tree of
    arrays: parameters and the optimizer's state), less
    :data:`REMAT_TEMPORARIES` tensors of the widest result but
    ``scan_out`` for the step's own temporaries, less
    :data:`REMAT_CLEAR_BYTES`.  0 where the
    device reports no limit (off the TPU) or nothing is left."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit or not widths:
        return 0
    held = sum(
        math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state))
    widest = max(w for name, w in widths.items() if name != "scan_out")
    reserve = REMAT_TEMPORARIES * tokens * widest * itemsize \
        + REMAT_CLEAR_BYTES
    return max(0, int(limit) - held - reserve)


def rms_norm(x, scale, eps: float, dtype):
    """``x / rms(x) * scale`` over the last axis, statistics in float32,
    the result in ``dtype``."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def norm_gain(module: nn.Module, name: str, width: int,
              zero_centered: bool):
    """The learned gain ``name (width,)`` of an RMSNorm: the parameter
    itself, initialised 1, or zero-centred, ``1 + w`` with ``w``
    initialised 0."""
    if zero_centered:
        return 1.0 + module.param(name, nn.initializers.zeros, (width,),
                                  jnp.float32)
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


class RMSNorm(nn.Module):
    """:func:`rms_norm` with a learned gain (:func:`norm_gain`).  The
    backward pass computes the float32 intermediates again from ``x``
    (``jax.checkpoint``): none of them is kept beside the activation it
    normalises."""

    eps: float = 1e-6
    dtype: Any = jnp.float32
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x):
        scale = norm_gain(self, "scale", x.shape[-1], self.zero_centered)
        return jax.checkpoint(
            functools.partial(rms_norm, eps=self.eps, dtype=self.dtype)
        )(x, scale)


def make_norm(options: BlockOptions, dtype=jnp.float32, **kw):
    """The block's norm: flax ``LayerNorm`` (its epsilon 1e-6, as every
    model before the option) or :class:`RMSNorm`."""
    if options.norm == "rmsnorm":
        return RMSNorm(eps=options.norm_eps, dtype=dtype,
                       zero_centered=options.zero_centered_norm, **kw)
    if options.norm != "layernorm":
        raise ValueError(f"norm must be layernorm or rmsnorm, got "
                         f"{options.norm!r}")
    return nn.LayerNorm(dtype=dtype, **kw)


def yarn_frequencies(theta: float, turned: int, yarn: YarnScaling):
    """The ``turned // 2`` frequencies of a YaRN-scaled rotation over
    ``turned`` channels, as ``transformers``' ``_compute_yarn_parameters``
    computes them: channel pair ``i`` turns by ``theta ** (-2i /
    turned)`` a position where it completes ``beta_fast`` turns or more
    over ``original_length`` positions (left as trained), by that over
    ``factor`` where it completes ``beta_slow`` or fewer (positions
    interpolated), and by the linear blend between, the two ends cut to
    whole channel indices."""
    def index_of(turns):  # the channel pair that completes ``turns``
        return turned * math.log(yarn.original_length / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_of(yarn.beta_fast)), 0)
    high = min(math.ceil(index_of(yarn.beta_slow)), turned - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-jnp.arange(turned // 2, dtype=jnp.float32)
                      / (turned // 2))
    ramp = jnp.clip((jnp.arange(turned // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / yarn.factor * ramp + plain * (1.0 - ramp)


def apply_rope(x, positions, theta: float, fraction: float = 1.0,
               yarn: Optional[YarnScaling] = None):
    """Rotary positions on ``x (b, s, heads, dh)``, the halves
    convention (``rotate_half``), angles in float32.  ``fraction``: the
    leading ``fraction * dh`` channels of a head are rotated (among
    themselves), the rest pass as they are.  ``yarn``: the rotated
    channels' frequencies are :func:`yarn_frequencies`', their cos and
    sin times its ``cos_sin_factor`` (the channels that pass are not
    scaled)."""
    if fraction != 1.0:
        turned = int(x.shape[-1] * fraction)
        return jnp.concatenate(
            [apply_rope(x[..., :turned], positions, theta, yarn=yarn),
             x[..., turned:]], axis=-1)
    half = x.shape[-1] // 2
    if yarn is not None:
        freq = yarn_frequencies(theta, x.shape[-1], yarn)
    else:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.cos_sin_factor, sin * yarn.cos_sin_factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rotate_pairs(x, positions, theta: float, turned: int):
    """Rotary positions on the last ``turned`` channels of ``x (b, s,
    heads, dh)``, the neighbours convention: the pair ``(2i, 2i + 1)``
    of them is turned by ``positions * theta ** (-2i / turned)``, ``(a,
    b) -> (a cos - b sin, a sin + b cos)``, and the leading channels
    pass.  ``x`` is not taken apart (on a TPU a slice of a head's
    channels and the concatenation back cost more than the rotation):
    the result is ``x * cos + (x @ swap) * sin`` with ``swap`` the
    signed permutation that brings every turned channel its partner
    (exact in any dtype) and ``cos`` 1, ``sin`` 0 on the leading
    channels; angles and products float32."""
    s, dh = x.shape[1], x.shape[-1]
    lead = dh - turned
    freq = theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32) / turned)
    ang = jnp.repeat(
        positions.astype(jnp.float32)[:, None] * freq[None, :], 2, axis=-1)
    cos = jnp.concatenate([jnp.ones((s, lead)), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.zeros((s, lead)), jnp.sin(ang)], axis=-1)
    swap = np.zeros((dh, dh), np.float32)
    even = np.arange(lead, dh, 2)
    swap[even + 1, even], swap[even, even + 1] = -1.0, 1.0
    partner = jnp.einsum("bshd,de->bshe", x, jnp.asarray(swap, x.dtype),
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos[None, :, None, :]
            + partner * sin[None, :, None, :]).astype(x.dtype)


#: device scope of the attention projections (q, k, v, their norms and
#: rotation, the output gate and the output projection) on the general
#: path; inside it, of the rotation alone and of the gate a head
ATTN_PROJ_SCOPE = "attn_proj"
ATTN_ROPE_SCOPE = "attn_rope"
HEAD_GATE_SCOPE = "head_gate"


# Data-parallel mesh axis names this package's communicators bind
# (variants.py mesh factorizations).  Dropout folds bound ones into its
# rng so data shards draw independent masks.
_DATA_AXES = ("mn", "mn_data", "mn_inter", "mn_intra", "mn_x", "mn_y")


def _bound_axes(names, exclude=()):
    """The subset of ``names`` bound in the current trace (shard_map).
    ``lax.axis_index`` raises NameError on an unbound axis; anything
    else is a real error and propagates."""
    out = []
    for a in names:
        if a in exclude or a is None:
            continue
        try:
            lax.axis_index(a)
        except NameError:
            continue
        out.append(a)
    return out


def _stream_dropout(module: nn.Module, h, rate: float,
                    deterministic: bool, seq_axis, tp_axis=None):
    """Inverted dropout for the residual stream.  The 'dropout' rng
    collection is replicated across the mesh, so every *token-splitting*
    shard index is folded in — the sequence shard (SP) and any bound
    data axis (DP) — giving each shard an independent mask instead of
    one pattern correlated across the global batch.  The tensor axis is
    deliberately NOT folded: across TP shards the residual stream is
    replicated and the masks must agree."""
    if rate <= 0.0 or deterministic:
        return h
    rng = module.make_rng("dropout")
    fold = _bound_axes(_DATA_AXES, exclude=(tp_axis, seq_axis))
    if seq_axis is not None:
        fold.append(seq_axis)
    for a in fold:
        rng = jax.random.fold_in(rng, lax.axis_index(a))
    keep = jax.random.bernoulli(rng, 1.0 - rate, h.shape)
    return jnp.where(keep, h / (1.0 - rate), 0).astype(h.dtype)


class SelfAttention(nn.Module):
    """Causal self-attention; optionally tensor-parallel over ``tp_axis``
    (heads sharded Megatron-style: column-parallel q/k/v projections, one
    row-parallel psum on the output projection) and/or sequence-parallel
    over ``seq_axis``.  The two compose: each chip then holds its head
    shard of its sequence shard.

    ``sp_impl`` picks the sequence-parallel algorithm: ``"ring"``
    (ppermute K/V rotation — any head count, O(seq/chips) memory) or
    ``"ulysses"`` (two all_to_alls exchanging sequence- for
    head-sharding; local heads must divide by the seq-axis size, bulk
    ICI transposes instead of n ring hops).  Ulysses runs
    ``attention_fn`` on its gathered blocks (pass the flash kernel);
    ring uses its own flash tier automatically on TPU."""

    n_heads: int
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_impl: str = "ring"
    # KV-cache decode mode: keys/values accumulate in 'cache' variables
    # of length cache_len; each call appends its s positions and attends
    # against everything cached so far.  Causal only; composes with
    # tp_axis (head-sharded caches) but not seq_axis.
    decode: bool = False
    cache_len: int = 0
    attention_fn: Optional[Callable] = None
    options: BlockOptions = BlockOptions()
    # a "window_attention" layer: ``n_heads`` is then the window layers'
    # count, and the window and the rotation are ``options``' window
    # fields (make_mixer sets all three)
    windowed: bool = False

    def _general(self, x, causal: bool):
        """The path of :class:`BlockOptions`' attention options."""
        o = self.options
        if self.tp_axis is not None or self.seq_axis is not None \
                or self.decode:
            raise ValueError(
                "grouped-query / rotary / block-diffusion / window / "
                "head-gated attention and a head count of a layer's own "
                "are single-device in sequence and heads: no seq_axis, "
                "tp_axis or decode")
        from chainermn_tpu.ops import pallas_attention as pa

        b, s, d = x.shape
        hq = self.n_heads
        hkv = o.n_kv_heads or hq
        dh = o.head_dim or d // hq
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        # both copies of a block-diffusion pair sit at 0..s/2-1
        pos = jnp.arange(s) % (s // 2 if o.block_diffusion else s)
        # the layer kind's rotation and window
        theta, fraction, yarn, window = (
            o.rope_theta, o.rotary_fraction, o.rope_yarn, None)
        if self.windowed:
            if o.window <= 0 or o.block_diffusion or not causal:
                raise ValueError(
                    "a window_attention layer needs options.window > 0 "
                    "under the causal mask (no block_diffusion)")
            window, yarn = o.window, None
            theta = o.window_rope_theta or theta
            if o.window_rotary_fraction is not None:
                fraction = o.window_rotary_fraction

        def norm_and_rotate(t, gain):
            if gain is not None:
                t = rms_norm(t, gain, o.norm_eps, self.dtype)
            if theta:
                with jax.named_scope(ATTN_ROPE_SCOPE):
                    t = apply_rope(t, pos, theta, fraction, yarn)
            return t

        with jax.named_scope(ATTN_PROJ_SCOPE):
            gate = None
            if o.attn_output_gate:
                # a head's columns: its query, then its gate
                q, gate = jnp.split(dense(2 * hq * dh, name="q_proj")(
                    x).reshape(b, s, hq, 2 * dh), 2, axis=-1)
            else:
                q = dense(hq * dh, name="q_proj")(x).reshape(b, s, hq, dh)
            k = dense(hkv * dh, name="k_proj")(x).reshape(b, s, hkv, dh)
            v = dense(hkv * dh, name="v_proj")(x).reshape(b, s, hkv, dh)
            head_gate = dense(hq, name="g_proj")(x) if o.head_gate else None
            gains = [norm_gain(self, n, dh, o.zero_centered_norm)
                     if o.qk_norm else None for n in ("q_norm", "k_norm")]
            # float32 inside, recomputed in the backward pass: only the
            # projections' outputs and the kernels' inputs are kept
            q = jax.checkpoint(norm_and_rotate)(q, gains[0])
            k = jax.checkpoint(norm_and_rotate)(k, gains[1])
        if o.block_diffusion:
            attend = pa.block_diffusion_attention if o.use_flash \
                else pa.block_diffusion_attention_dense
            out = attend(q, k, v, o.block_diffusion,
                         scale=o.attention_scale)
        elif o.use_flash and causal:
            # causal is block-causal at block length 1; only a launch
            # with a window is handed the argument
            out, _ = pa.block_causal_attention_with_lse(
                q, k, v, 1, scale=o.attention_scale,
                **({"window": window} if window else {}))
        else:
            from chainermn_tpu.ops import multi_head_attention

            rep = lambda t: jnp.repeat(t, hq // hkv, axis=2)
            out = multi_head_attention(q, rep(k), rep(v), causal=causal,
                                       scale=o.attention_scale,
                                       window=window)
        with jax.named_scope(ATTN_PROJ_SCOPE):
            # the gates: float32 inside, recomputed in the backward pass
            gated = jax.checkpoint(lambda out, gate: (
                out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(out.dtype))
            if gate is not None:
                out = gated(out, gate)
            if head_gate is not None:
                with jax.named_scope(HEAD_GATE_SCOPE):
                    out = gated(out, head_gate[..., None])
            return dense(d, name="o_proj")(out.reshape(b, s, hq * dh))

    def _decode_attend(self, q, k, v, b, heads, dh, scale):
        """Append k/v to the cache and attend q against the filled
        prefix — exact causal attention at O(cache_len) per step.

        The dtype flow mirrors ``ops.multi_head_attention`` exactly
        (caches in compute dtype, QK einsum in compute dtype then fp32
        softmax, probs cast back for the PV einsum) so the KV-cache and
        recompute generate tiers stay token-for-token identical for
        bf16 models too."""
        ck = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, self.cache_len, heads, dh), q.dtype,
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, self.cache_len, heads, dh), q.dtype,
        )
        ci = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        idx = ci.value
        ck.value = lax.dynamic_update_slice(
            ck.value, k.astype(q.dtype), (0, idx, 0, 0)
        )
        cv.value = lax.dynamic_update_slice(
            cv.value, v.astype(q.dtype), (0, idx, 0, 0)
        )
        s = q.shape[1]
        ci.value = idx + s
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, ck.value
        ).astype(jnp.float32) * scale
        kpos = jnp.arange(self.cache_len)[None, :]
        qpos = idx + jnp.arange(s)[:, None]
        mask = kpos <= qpos  # causal AND only-written positions
        scores = jnp.where(
            mask[None, None], scores, jnp.finfo(jnp.float32).min
        )
        # Overflowing the cache would otherwise be silently clamped by
        # dynamic_update_slice (the failure the static max_len guard
        # prevents in training mode) — poison the logits loudly instead.
        scores = jnp.where(idx + s > self.cache_len, jnp.nan, scores)
        p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(q.dtype), cv.value
        )

    @nn.compact
    def __call__(self, x, *, causal: bool = True):
        if self.options.general_attention:
            return self._general(x, causal)
        b, s, d = x.shape
        if d % self.n_heads:
            raise ValueError(f"d_model ({d}) % n_heads ({self.n_heads})")
        dh = d // self.n_heads
        heads = self.n_heads
        if self.tp_axis is not None:
            from chainermn_tpu.parallel import (
                ColumnParallelDense,
                RowParallelDense,
            )

            ntp = lax.axis_size(self.tp_axis)
            if heads % ntp:
                raise ValueError(
                    f"n_heads ({heads}) not divisible by the "
                    f"'{self.tp_axis}' axis size ({ntp})"
                )
            heads = heads // ntp  # local heads
            # Auto-generated module names (ColumnParallelDense_0/1/2 =
            # q/k/v) keep the param tree spec-derivable without name
            # markers that could collide with user modules.
            col = functools.partial(
                ColumnParallelDense, axis_name=self.tp_axis,
                use_bias=False, dtype=self.dtype,
            )
            q = col(d)(x)
            k = col(d)(x)
            v = col(d)(x)
        else:
            qkv = nn.Dense(3 * d, use_bias=False, dtype=self.dtype)(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, heads, dh)
        k = k.reshape(b, s, heads, dh)
        v = v.reshape(b, s, heads, dh)
        if self.decode:
            if self.seq_axis is not None:
                raise ValueError(
                    "decode mode does not compose with sequence "
                    "parallelism (a decoded token needs its whole cache)"
                )
            if not causal:
                raise ValueError("decode mode implies causal attention")
            if self.cache_len <= 0:
                raise ValueError("decode mode needs cache_len > 0")
            # tp_axis composes: q/k/v hold this chip's LOCAL heads, the
            # cache shards with them, and the row-parallel output
            # projection below carries the one psum per step.
            out = self._decode_attend(q, k, v, b, heads, dh, dh**-0.5)
        elif self.seq_axis is not None:
            if self.sp_impl == "ring":
                from chainermn_tpu.parallel import ring_attention

                out = ring_attention(q, k, v, self.seq_axis, causal=causal)
            elif self.sp_impl == "ulysses":
                from chainermn_tpu.parallel import ulysses_attention

                out = ulysses_attention(
                    q, k, v, self.seq_axis, causal=causal,
                    attention_fn=self.attention_fn,
                )
            else:
                raise ValueError(
                    f"sp_impl must be 'ring' or 'ulysses', got "
                    f"{self.sp_impl!r}"
                )
        elif self.attention_fn is not None:
            out = self.attention_fn(q, k, v, causal, dh**-0.5)
        else:
            from chainermn_tpu.ops import multi_head_attention

            out = multi_head_attention(q, k, v, causal=causal)
        out = out.reshape(b, s, heads * dh)
        if self.tp_axis is not None:
            return RowParallelDense(
                d, axis_name=self.tp_axis, use_bias=False,
                dtype=self.dtype,
            )(out)
        return nn.Dense(d, use_bias=False, dtype=self.dtype)(out)


#: device scopes of the gated MLP, of the state-space mixer (the
#: convolution's and the scan's lie inside the mixer's: ops.ssd_scan)
#: and of the Gated DeltaNet mixer (its convolution's and, from
#: ops.gated_delta, its scan's inside it); of the KDA mixer, its
#: convolution and its scan (ops.gated_delta's own scope inside it), and
#: of latent attention's projections (the compression, its norm and
#: expansion, the queries and the output projection) and, beside them,
#: of the rotation of its shared channels
GATED_MLP_SCOPE = "gated_mlp"
SSM_MIXER_SCOPE = "ssm_mixer"
GDN_MIXER_SCOPE = "gdn_mixer"
GDN_CONV_SCOPE = "gdn_conv"
KDA_MIXER_SCOPE = "kda_mixer"
KDA_CONV_SCOPE = "kda_conv"
KDA_SCAN_SCOPE = "kda_scan"
LATENT_PROJ_SCOPE = "latent_proj"
LATENT_ROPE_SCOPE = "latent_rope"


class GatedMlp(nn.Module):
    """``W_out(SiLU(g) * u)`` with ``[g | u] = W_in x``, no biases: one
    ``in_proj`` kernel of ``(d, 2 d_ff)`` and ``out_proj`` of ``(d_ff,
    d)``."""

    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    @jax.named_scope(GATED_MLP_SCOPE)
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        # named for the block's recomputation (an identity elsewhere)
        gate, up = jnp.split(checkpoint_name(
            dense(2 * self.d_ff, name="in_proj")(x), "mlp_in"), 2, axis=-1)
        return dense(x.shape[-1], name="out_proj")(nn.silu(gate) * up)


def _ssm_rates(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a rate drawn uniformly from [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _ssm_taps(key, shape, dtype=jnp.float32):
    """The convolution's taps: uniform in +-1/2 (``k ** -0.5`` at the
    four taps every published Mamba-2 has)."""
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def _ssm_step_bias(key, shape, dtype=jnp.float32):
    """``dt_bias``: the inverse softplus of a step drawn log-uniformly
    from [1e-3, 1e-1]."""
    step = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


class Mamba2Mixer(nn.Module):
    """The sequence mixer of a Mamba-2 layer (arXiv:2405.21060; HF
    ``GraniteMoeHybridMambaLayer``), sized by ``options``' ``ssm_*``
    fields, one group of ``B`` and ``C`` for all heads.  On ``x (b, s,
    d)``:

        [z | xBC | dt] = in_proj(x)          widths inner | inner + 2n | h
        [x' | B | C] = SiLU(conv1d(xBC))     causal, depthwise, with bias
        y = ssd_scan(x', softplus(dt + dt_bias), -exp(A_log), B, C, D)
        out_proj(RMSNorm(y * SiLU(z)))       the gate goes in before the norm

    with ``inner = ssm_heads * ssm_head_dim`` and a learned gain on the
    norm.  Products in ``dtype``; the step, the rates, the scan's decays
    and states and the norm in float32.  Single-device in the sequence
    and the heads, as the scan is (:mod:`chainermn_tpu.ops.ssd_scan`):
    raises under ``seq_axis``, ``tp_axis`` or ``decode``."""

    options: BlockOptions
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    decode: bool = False

    @nn.compact
    @jax.named_scope(SSM_MIXER_SCOPE)
    def __call__(self, x):
        if self.tp_axis is not None or self.seq_axis is not None \
                or self.decode:
            raise ValueError(
                "the state-space mixer is single-device in sequence and "
                "heads: no seq_axis, tp_axis or decode")
        from chainermn_tpu.ops.ssd_scan import causal_conv1d, ssd_scan

        o = self.options
        b, s, d = x.shape
        h, p, n = o.ssm_heads, o.ssm_head_dim, o.ssm_state
        inner = h * p
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        f32 = lambda name, init, shape: self.param(name, init, shape,
                                                   jnp.float32)
        taps = f32("conv_kernel", _ssm_taps, (o.ssm_conv, inner + 2 * n))
        conv_bias = f32("conv_bias", nn.initializers.zeros,
                        (inner + 2 * n,))
        rates = -jnp.exp(f32("A_log", _ssm_rates, (h,)))
        step_bias = f32("dt_bias", _ssm_step_bias, (h,))
        skip = f32("D", nn.initializers.ones, (h,))
        gain = f32("norm", nn.initializers.ones, (inner,))

        # named for the block's recomputation (an identity elsewhere)
        proj = checkpoint_name(
            dense(2 * inner + 2 * n + h, name="in_proj")(x), "ssm_in")
        z, _, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
        xbc = causal_conv1d(proj, taps, conv_bias, silu=True,
                            first_column=inner)
        xs, B, C = jnp.split(xbc, [inner, inner + n], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + step_bias)
        y = ssd_scan(xs.reshape(b, s, h, p), dt, rates, B, C, skip,
                     chunk=o.ssm_chunk, dtype=self.dtype)
        gated = y.reshape(b, s, inner).astype(jnp.float32) \
            * nn.silu(z.astype(jnp.float32))
        return dense(d, name="out_proj")(
            rms_norm(gated, gain, o.norm_eps, self.dtype))


def _gdn_rates(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a rate drawn uniformly from (0, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def _unit_heads(t, scale: float, dtype):
    """Each head of ``t (..., dh)`` over its L2 norm (epsilon 1e-6 under
    the root), times ``scale``; float32 inside, the result in
    ``dtype``."""
    t32 = t.astype(jnp.float32)
    return (t32 * lax.rsqrt(jnp.sum(t32 * t32, axis=-1, keepdims=True)
                            + 1e-6) * scale).astype(dtype)


class GatedDeltaMixer(nn.Module):
    """The sequence mixer of a Gated DeltaNet layer (arXiv:2412.06464;
    HF ``Qwen3NextGatedDeltaNet``), sized by ``options``' ``gdn_*``
    fields: ``hk`` key heads of ``dk``, ``hv`` value heads of ``dv``,
    key head ``j`` serving value heads ``j hv / hk ...``.  On ``x (b,
    s, d)``:

        [q | k | v | z] = in_proj_qkvz(x)    widths hk dk | hk dk | hv dv | hv dv
        [b | a] = in_proj_ba(x)              widths hv | hv
        [q | k | v] = SiLU(conv1d([q | k | v]))   causal, depthwise, no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q = q / |q| / sqrt(dk);  k = k / |k|      a head
        o = gated_delta_scan(q, k, v, g, beta)
        out_proj(RMSNorm_head(o) * norm * SiLU(z))

    the last norm over each value head's ``dv`` with one plain gain
    ``norm (dv,)`` for all heads.  (HF lays the columns of the two
    in-projections out by key head; the same columns in another order.)
    Products in ``dtype``; ``beta``, ``g``, the normalisation of q and
    k, the scan's decays and states and the norm in float32.
    Single-device in the sequence and the heads, as the scan is
    (:mod:`chainermn_tpu.ops.gated_delta`): raises under ``seq_axis``,
    ``tp_axis`` or ``decode``."""

    options: BlockOptions
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    decode: bool = False

    @nn.compact
    @jax.named_scope(GDN_MIXER_SCOPE)
    def __call__(self, x):
        if self.tp_axis is not None or self.seq_axis is not None \
                or self.decode:
            raise ValueError(
                "the Gated DeltaNet mixer is single-device in sequence "
                "and heads: no seq_axis, tp_axis or decode")
        from chainermn_tpu.ops.gated_delta import gated_delta_scan
        from chainermn_tpu.ops.ssd_scan import causal_conv1d

        o = self.options
        b, s, d = x.shape
        hk, hv = o.gdn_key_heads, o.gdn_value_heads
        dk, dv = o.gdn_key_dim, o.gdn_value_dim
        keys, values = hk * dk, hv * dv
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        f32 = lambda name, init, shape: self.param(name, init, shape,
                                                   jnp.float32)
        taps = f32("conv_kernel", _ssm_taps, (o.gdn_conv, 2 * keys + values))
        rates = -jnp.exp(f32("A_log", _gdn_rates, (hv,)))
        step_bias = f32("dt_bias", _ssm_step_bias, (hv,))
        gain = f32("norm", nn.initializers.ones, (dv,))

        # named for the block's recomputation (an identity elsewhere)
        proj = checkpoint_name(
            dense(2 * keys + 2 * values, name="in_proj_qkvz")(x), "gdn_in")
        z = proj[..., 2 * keys + values:]
        write, step = jnp.split(
            dense(2 * hv, name="in_proj_ba")(x).astype(jnp.float32),
            2, axis=-1)
        q, k, v = jnp.split(
            # q | k | v where they lie in the projection's q | k | v | z
            causal_conv1d(proj, taps, scope=GDN_CONV_SCOPE, silu=True,
                          first_column=0),
            [keys, 2 * keys], axis=-1)
        # float32 inside, recomputed in the backward pass
        unit = jax.checkpoint(_unit_heads, static_argnums=(1, 2))
        out = gated_delta_scan(
            unit(q.reshape(b, s, hk, dk), dk ** -0.5, self.dtype),
            unit(k.reshape(b, s, hk, dk), 1.0, self.dtype),
            v.reshape(b, s, hv, dv),
            rates * jax.nn.softplus(step + step_bias),
            jax.nn.sigmoid(write), chunk=o.gdn_chunk, dtype=self.dtype)
        gated = jax.checkpoint(lambda out, z, gain: (
            rms_norm(out, gain, o.norm_eps, jnp.float32)
            * nn.silu(z.astype(jnp.float32))).astype(self.dtype))(
            out, z.reshape(b, s, hv, dv), gain)
        return dense(d, name="out_proj")(gated.reshape(b, s, values))


class KdaMixer(nn.Module):
    """The sequence mixer of a Kimi Delta Attention layer
    (arXiv:2510.26692; HF ``KimiDeltaAttention``): a delta rule whose
    state decays by a factor of its own in each key channel.  Sized by
    ``options``' ``gdn_*`` fields: ``h = gdn_value_heads`` heads, a key
    head each, of ``dk`` / ``dv``, ``gdn_conv`` taps, the scan's
    ``gdn_chunk``; the two low-rank projections go through ``dk``
    channels.  On ``x (b, s, d)``:

        [q | k | v] = SiLU(conv1d(in_proj_qkv(x)))   widths h dk | h dk | h dv
        g = -exp(A_log) softplus(f_b(f_a(x)) + dt_bias)   (s, h, dk): d -> dk -> h dk
        beta = sigmoid(b_proj(x))                    a head
        q = q / |q| / sqrt(dk);  k = k / |k|         a head
        o = gated_delta_scan(q, k, v, g, beta)       g a key channel's
        out_proj(RMSNorm_head(o) * norm * sigmoid(g_b(g_a(x))))   d -> dk -> h dv

    ``A_log`` a head, ``dt_bias`` a channel of every head, ``g_b`` with
    a bias, no other; the last norm over each head's ``dv`` with one
    plain gain ``norm (dv,)``.  Products in ``dtype``; ``beta``, ``g``,
    the normalisation of q and k, the scan's decays and states and the
    norm in float32.  Single-device in the sequence and the heads, as
    the scan is (:mod:`chainermn_tpu.ops.gated_delta`): raises under
    ``seq_axis``, ``tp_axis`` or ``decode``."""

    options: BlockOptions
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    decode: bool = False

    @nn.compact
    @jax.named_scope(KDA_MIXER_SCOPE)
    def __call__(self, x):
        if self.tp_axis is not None or self.seq_axis is not None \
                or self.decode:
            raise ValueError(
                "the KDA mixer is single-device in sequence and heads: "
                "no seq_axis, tp_axis or decode")
        from chainermn_tpu.ops.gated_delta import gated_delta_scan
        from chainermn_tpu.ops.ssd_scan import causal_conv1d

        o = self.options
        b, s, d = x.shape
        h, dk, dv = o.gdn_value_heads, o.gdn_key_dim, o.gdn_value_dim
        keys, values = h * dk, h * dv
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        f32 = lambda name, init, shape: self.param(name, init, shape,
                                                   jnp.float32)
        taps = f32("conv_kernel", _ssm_taps, (o.gdn_conv, 2 * keys + values))
        rates = -jnp.exp(f32("A_log", _ssm_rates, (h,)))
        step_bias = f32("dt_bias", _ssm_step_bias, (h, dk))
        gain = f32("norm", nn.initializers.ones, (dv,))

        # named for the block's recomputation (an identity elsewhere)
        qkv = checkpoint_name(
            dense(2 * keys + values, name="in_proj_qkv")(x), "kda_in")
        q, k, v = jnp.split(
            causal_conv1d(qkv, taps, scope=KDA_CONV_SCOPE, silu=True),
            [keys, 2 * keys], axis=-1)
        step = dense(keys, name="f_b_proj")(dense(dk, name="f_a_proj")(x))
        write = dense(h, name="b_proj")(x)
        gate = nn.Dense(values, dtype=self.dtype, name="g_b_proj")(
            dense(dk, name="g_a_proj")(x))
        # float32 inside, recomputed in the backward pass
        unit = jax.checkpoint(_unit_heads, static_argnums=(1, 2))
        decay = jax.checkpoint(lambda step, rates, step_bias: (
            rates[:, None] * jax.nn.softplus(
                step.reshape(b, s, h, dk).astype(jnp.float32) + step_bias)))
        with jax.named_scope(KDA_SCAN_SCOPE):
            out = gated_delta_scan(
                unit(q.reshape(b, s, h, dk), dk ** -0.5, self.dtype),
                unit(k.reshape(b, s, h, dk), 1.0, self.dtype),
                v.reshape(b, s, h, dv), decay(step, rates, step_bias),
                jax.nn.sigmoid(write.astype(jnp.float32)),
                chunk=o.gdn_chunk, dtype=self.dtype)
        gated = jax.checkpoint(lambda out, gate, gain: (
            rms_norm(out, gain, o.norm_eps, jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype))(
            out, gate.reshape(b, s, h, dv), gain)
        return dense(d, name="out_proj")(gated.reshape(b, s, values))


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434):
    keys and values are expanded, a head its own, from one compression
    of ``latent_kv_rank`` channels that all heads share.  With ``dn =
    latent_nope_dim``, ``ds = latent_shared_dim``, ``dv =
    latent_value_dim``, on ``x (b, s, d)``:

        [q_a | q_b] = q_proj(x)                 a head: dn | ds
        [c | k_b] = kv_a_proj(x)                latent_kv_rank | ds
        [k_a | v] = kv_b_proj(RMSNorm(c))       a head: dn | dv
        q_b, k_b = R_t q_b, R_t k_b             with rope_theta only
        q = [q_a | q_b];  k = [k_a | k_b]       k_b the same for all heads
        o_proj(causal_softmax_attention(q, k, v))   at (dn + ds) ** -0.5

    ``rope_theta``: the family's decoupled rotation (DeepSeek-V2 / V3,
    Moonlight).  ``R_t`` turns the neighbouring channels ``(2i, 2i +
    1)`` of the ``ds`` shared ones by ``t * rope_theta ** (-2i / ds)``,
    ``t`` the position inside the sequence from 0; the ``dn`` channels
    carry no position (:func:`rotate_pairs`, on the whole query
    without taking it apart; angles and products float32).  Without
    ``rope_theta`` (``no_positions``) no channel
    is rotated, as Kimi Linear's full-attention layers run it, and the
    program holds no operation of the rotation.  Keys
    are wider than values: with ``use_flash`` the block-causal kernels
    take the two widths apart, else a dense masked softmax runs.
    ``attention_scale`` replaces the scale.  Single-device in the
    sequence and the heads, no cache: raises under ``seq_axis``,
    ``tp_axis`` or ``decode``."""

    n_heads: int
    options: BlockOptions
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x):
        o = self.options
        if self.tp_axis is not None or self.seq_axis is not None \
                or self.decode:
            raise ValueError(
                "latent attention is single-device in sequence and heads "
                "and has no cache: no seq_axis, tp_axis or decode")
        b, s, d = x.shape
        h, rank = self.n_heads, o.latent_kv_rank
        dn, ds, dv = o.latent_nope_dim, o.latent_shared_dim, \
            o.latent_value_dim
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        with jax.named_scope(LATENT_PROJ_SCOPE):
            # named for the block's recomputation (an identity elsewhere)
            q = checkpoint_name(dense(h * (dn + ds), name="q_proj")(x),
                                "latent_in").reshape(b, s, h, dn + ds)
            latent, shared = jnp.split(
                dense(rank + ds, name="kv_a_proj")(x), [rank], axis=-1)
            gain = norm_gain(self, "kv_a_norm", rank, o.zero_centered_norm)
            own, v = jnp.split(
                dense(h * (dn + dv), name="kv_b_proj")(jax.checkpoint(
                    functools.partial(rms_norm, eps=o.norm_eps,
                                      dtype=self.dtype))(latent, gain)
                ).reshape(b, s, h, dn + dv), [dn], axis=-1)
        if o.rope_theta:
            # float32 inside, recomputed in the backward pass
            turn = jax.checkpoint(lambda t: rotate_pairs(
                t, jnp.arange(s), o.rope_theta, ds))
            with jax.named_scope(LATENT_ROPE_SCOPE):
                q, shared = turn(q), turn(shared[:, :, None])[:, :, 0]
        with jax.named_scope(LATENT_PROJ_SCOPE):
            k = jnp.concatenate([own, jnp.broadcast_to(
                shared[:, :, None], (b, s, h, ds))], axis=-1)
        scale = o.attention_scale or (dn + ds) ** -0.5
        if o.use_flash:
            from chainermn_tpu.ops import pallas_attention as pa

            # causal is block-causal at block length 1
            out, _ = pa.block_causal_attention_with_lse(q, k, v, 1,
                                                        scale=scale)
        else:
            from chainermn_tpu.ops import multi_head_attention

            out = multi_head_attention(q, k, v, causal=True, scale=scale)
        with jax.named_scope(LATENT_PROJ_SCOPE):
            return dense(d, name="o_proj")(out.reshape(b, s, h * dv))


def make_mixer(kind: str, n_heads: int, options: BlockOptions, dtype,
               **attention):
    """The sequence mixer of a layer of ``kind`` (one of
    :data:`LAYER_KINDS`): where both LMs' blocks get theirs.
    ``attention``: :class:`SelfAttention`'s other fields; the other
    mixers take of them what they refuse (``seq_axis``, ``tp_axis``,
    ``decode``)."""
    if kind == "attention":
        return SelfAttention(n_heads, dtype=dtype, options=options,
                             **attention)
    if kind == "window_attention":
        return SelfAttention(options.attention_heads(kind, n_heads),
                             dtype=dtype, options=options, windowed=True,
                             **attention)
    refused = {name: attention[name] for name in (
        "seq_axis", "tp_axis", "decode") if name in attention}
    if kind == "latent_attention":
        return LatentAttention(n_heads, options, dtype=dtype, **refused)
    recurrent = {"mamba": Mamba2Mixer, "linear_attention": GatedDeltaMixer,
                 "kda": KdaMixer}
    return recurrent[kind](options, dtype=dtype, **refused)


def block_under_plan(block_cls, keep: Tuple[str, ...], serial: int):
    """``block_cls`` as a layer under per-block recomputation, for both
    LMs: the backward pass computes the block's forward again from its
    input and from what the plan keeps of it (``keep``, names of
    :data:`REMAT_NAMES`: the policy's), under the name the ``serial``-th
    block of its class has without recomputation: one parameter tree
    either way."""
    return functools.partial(nn.remat(block_cls, policy=_keep(keep)),
                             name=f"{block_cls.__name__}_{serial}")


class TpMlpBlock(nn.Module):
    """Megatron MLP: column-parallel up-projection -> gelu ->
    row-parallel down-projection — exactly one psum per block."""

    d_ff: int
    tp_axis: str = "mn_model"
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from chainermn_tpu.parallel import (
            ColumnParallelDense,
            RowParallelDense,
        )

        d = x.shape[-1]
        h = ColumnParallelDense(
            self.d_ff, axis_name=self.tp_axis, dtype=self.dtype,
        )(x)
        h = nn.gelu(h)
        return RowParallelDense(
            d, axis_name=self.tp_axis, dtype=self.dtype,
        )(h)


class TransformerBlock(nn.Module):
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_impl: str = "ring"
    decode: bool = False
    cache_len: int = 0
    dropout_rate: float = 0.0
    deterministic: bool = False
    attention_fn: Optional[Callable] = None
    # fp32 LayerNorm is the numerics-safe default; bf16 is a perf knob
    # no cell runs (LayerNorm rides the matmul fusions: PERF.md section 5)
    ln_dtype: Any = jnp.float32
    options: BlockOptions = BlockOptions()
    # the sequence mixer, one of LAYER_KINDS (BlockOptions.layer_type)
    kind: str = "attention"

    @nn.compact
    def __call__(self, x):
        o = self.options
        ln = lambda: make_norm(o, self.ln_dtype)

        def drop(h):
            h = _stream_dropout(
                self, h, self.dropout_rate, self.deterministic,
                self.seq_axis, self.tp_axis,
            )
            if o.residual_multiplier != 1.0:
                h = (h.astype(jnp.float32)
                     * o.residual_multiplier).astype(h.dtype)
            return h

        mixer = make_mixer(
            self.kind, self.n_heads, o, self.dtype, seq_axis=self.seq_axis,
            tp_axis=self.tp_axis, sp_impl=self.sp_impl,
            decode=self.decode, cache_len=self.cache_len,
            attention_fn=self.attention_fn)
        x = x + drop(mixer(ln()(x).astype(self.dtype)))
        if o.gated_mlp:
            if self.tp_axis is not None:
                raise ValueError("the gated MLP has no tensor-parallel "
                                 "form: no tp_axis")
            mlp = GatedMlp(self.d_ff, dtype=self.dtype)
        elif self.tp_axis is not None:
            mlp = TpMlpBlock(self.d_ff, tp_axis=self.tp_axis,
                             dtype=self.dtype)
        else:
            mlp = MlpBlock(self.d_ff, dtype=self.dtype)
        x = x + drop(mlp(ln()(x).astype(self.dtype)))
        return x


def make_lm_embed(parent: nn.Module, vocab_size: int, d_model: int,
                  tp_axis, vocab_parallel: bool):
    """The embedding module both LM families construct: dense
    ``nn.Embed`` (named "embed") or, with ``vocab_parallel=True``, a
    :class:`~chainermn_tpu.parallel.VocabParallelEmbed` sharded over
    ``tp_axis`` (auto-named so the class marker stays in the flax path
    for spec derivation).  Must be called from inside ``parent``'s
    compact ``__call__`` (the submodule registers on ``parent``)."""
    del parent  # registration happens via the nn.compact caller's scope
    if vocab_parallel:
        if tp_axis is None:
            raise ValueError(
                "vocab_parallel=True requires tp_axis (the vocab "
                "shards over the model axis)"
            )
        from chainermn_tpu.parallel import VocabParallelEmbed

        return VocabParallelEmbed(
            vocab_size, d_model, axis_name=tp_axis, dtype=jnp.float32,
        )
    return nn.Embed(
        vocab_size, d_model,
        embedding_init=nn.initializers.normal(0.02),
        dtype=jnp.float32, name="embed",
    )


#: device scope (``jax.named_scope``) of the head projection and of the
#: cross-entropy losses: the head + CE share of a traced step
HEAD_CE_SCOPE = "head_ce"


class TransformerLM(nn.Module):
    """Causal LM: tokens (batch, seq) -> logits (batch, seq, vocab).

    Inside ``shard_map`` with tokens sequence-sharded over ``seq_axis``,
    the returned logits are the local sequence shard's logits (global
    positions preserved).
    """

    vocab_size: int
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    d_ff: Optional[int] = None
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_impl: str = "ring"
    # KV-cache decode: see SelfAttention.decode; generate(use_cache=True)
    # builds the decode-mode twin automatically, sizing cache_len to the
    # actual generation length (0 = default to max_len).
    decode: bool = False
    cache_len: int = 0
    # Residual-stream dropout (attention out, MLP out, token
    # embeddings) — applied identically on the TP and non-TP paths, with
    # per-shard independent masks under SP; 0.0 draws no rng.  Construct
    # an eval twin with deterministic=True to switch it off (generate()
    # does this automatically).
    dropout_rate: float = 0.0
    deterministic: bool = False
    # Shard the embedding table AND the tied output head over tp_axis
    # (Megatron VocabParallelEmbedding): logits come back as the LOCAL
    # vocab block — train with vp_lm_loss, which assembles the softmax
    # statistics with collectives instead of materializing (.., V) rows.
    vocab_parallel: bool = False
    # Return the post-LayerNorm hidden states (b, s, d) instead of
    # logits: the chunked fused linear+CE loss
    # (ops.chunked_lm_loss) applies the weight-tied head itself, one
    # vocab chunk at a time, so the (b, s, V) logits never materialize.
    return_hidden: bool = False
    attention_fn: Optional[Callable] = None
    # fp32 LayerNorm is the numerics-safe default; bf16 is a perf knob
    # no cell runs (LayerNorm rides the matmul fusions: PERF.md section 5)
    ln_dtype: Any = jnp.float32
    # the block's options (norm, attention, state-space mixer, gated MLP,
    # the stream's multipliers, per-block recomputation):
    # BlockOptions.  With ``rope_theta`` or ``no_positions`` the model
    # holds no position table.
    options: BlockOptions = BlockOptions()

    def _positions(self, s: int):
        """The learned position table's rows of this call's ``s``
        tokens, ``(s, d_model)``."""
        pos_table = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.d_model), jnp.float32,
        )
        # dynamic_slice clamps out-of-range starts, which would silently
        # reuse positional rows — guard statically instead (shapes and axis
        # sizes are static under jit).
        offset = 0
        if self.seq_axis is not None:
            n_shards = lax.axis_size(self.seq_axis)
            if n_shards * s > self.max_len:
                raise ValueError(
                    f"global sequence length {n_shards}*{s} exceeds "
                    f"max_len={self.max_len}; raise max_len"
                )
            # Global positions: shard r holds [r*s, (r+1)*s).
            offset = lax.axis_index(self.seq_axis) * s
        elif s > self.max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_len={self.max_len}; "
                "raise max_len"
            )
        if self.decode:
            # global position of this call's first token = tokens cached
            # so far (a dedicated counter so the embedding stays in sync
            # with the attention caches)
            pos_idx = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            offset = pos_idx.value
            pos_idx.value = offset + s
        return lax.dynamic_slice_in_dim(pos_table, offset, s, axis=0)

    def remat_plan(self, tokens: int):
        """:func:`remat_plan` of this model's layers for ``tokens``
        positions a device and step, under ``options``' budget."""
        return model_remat_plan(self, tokens)

    @nn.compact
    def __call__(self, tokens):
        b, s = tokens.shape
        o = self.options
        d_ff = self.d_ff or 4 * self.d_model
        embed = make_lm_embed(
            self, self.vocab_size, self.d_model, self.tp_axis,
            self.vocab_parallel,
        )
        pos = None if o.rope_theta or o.no_positions \
            else self._positions(s)
        x = embed(tokens)
        if pos is not None:
            x = x + pos[None]
        if o.embedding_multiplier != 1.0:
            x = x * o.embedding_multiplier
        x = x.astype(self.dtype)
        x = _stream_dropout(
            self, x, self.dropout_rate, self.deterministic, self.seq_axis,
            self.tp_axis,
        )
        if o.remat_blocks:
            plan = self.remat_plan(b * s)
        for i in range(self.n_layers):
            block = block_under_plan(TransformerBlock, plan[i], i) \
                if o.remat_blocks else TransformerBlock
            x = block(
                self.n_heads, d_ff, dtype=self.dtype,
                seq_axis=self.seq_axis, tp_axis=self.tp_axis,
                sp_impl=self.sp_impl, decode=self.decode,
                cache_len=self.cache_len or self.max_len,
                dropout_rate=self.dropout_rate,
                deterministic=self.deterministic,
                attention_fn=self.attention_fn,
                ln_dtype=self.ln_dtype, options=o,
                kind=o.layer_type(i),
            )(x)
        x = make_norm(o, self.ln_dtype)(x).astype(jnp.float32)
        if o.logits_scaling != 1.0:
            # the head is linear: dividing what it reads divides the
            # logits, for the ``return_hidden`` twin's callers too
            x = x / o.logits_scaling
        if self.return_hidden:
            return x
        # Weight-tied head, under the device scope the losses share
        with jax.named_scope(HEAD_CE_SCOPE):
            if self.vocab_parallel:
                # local vocab block
                return embed.attend(x)
            return x @ embed.embedding.T


@jax.named_scope(HEAD_CE_SCOPE)
def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross entropy over a (batch, seq) token block."""
    import optax

    targets = tokens[:, 1:]
    preds = logits[:, :-1]
    return optax.softmax_cross_entropy_with_integer_labels(
        preds, targets
    ).mean()


def noised_copy(tokens: jnp.ndarray, mask: jnp.ndarray, mask_id: int):
    """Block-diffusion input: the clean copy of every sequence followed
    by its noised copy (``mask_id`` where ``mask``), ``(b, 2s)``."""
    return jnp.concatenate(
        [tokens, jnp.where(mask, mask_id, tokens)], axis=1)


@jax.named_scope(HEAD_CE_SCOPE)
def block_diffusion_loss(hidden: jnp.ndarray, head: jnp.ndarray,
                         tokens: jnp.ndarray, weights: jnp.ndarray,
                         dtype=jnp.bfloat16) -> jnp.ndarray:
    """The block-diffusion objective (BD3-LM, arXiv:2503.09573) from the
    final hidden states ``(b, 2s, d)`` of a [clean; noised] pair:
    ``sum_i weights_i * -log p(tokens_i | noised copy, clean prefix)``
    over the number of sample tokens, the logits taken on the noised
    copy at the position itself (no shift) against the untied ``head
    (vocab, d)``; ``weights`` is ``1 / t_b`` on the masked positions of
    block ``b`` and 0 elsewhere.  Operands in ``dtype``, float32
    logits."""
    s = tokens.shape[1]
    logits = jnp.einsum(
        "bsd,vd->bsv", hidden[:, s:].astype(dtype), head.astype(dtype),
        preferred_element_type=jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) / tokens.size


def _sp_targets(tokens: jnp.ndarray, axis_name: str):
    """The shard-boundary protocol shared by the sequence-parallel
    losses: each shard's last position predicts the NEXT shard's first
    token (targets cross the boundary via ``ppermute`` — the
    differentiable p2p layer the reference's send/recv points at), and
    the final *global* position has no target.  Returns
    ``(targets (b, s), valid (1, s) float mask)``."""
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, s = tokens.shape
    nxt = _cc.ppermute(
        tokens[:, :1], axis_name,
        [((i + 1) % n, i) for i in range(n)],
    )
    targets = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
    # mask the last global position (wrapped target is shard 0's BOS)
    global_pos = me * s + jnp.arange(s)[None, :]
    valid = (global_pos < n * s - 1).astype(jnp.float32)
    return targets, valid


def _sp_masked_mean(ce: jnp.ndarray, valid: jnp.ndarray,
                    axis_name: str) -> jnp.ndarray:
    valid = jnp.broadcast_to(valid.astype(ce.dtype), ce.shape)
    total = _cc.psum(jnp.sum(ce * valid), axis_name)
    count = _cc.psum(jnp.sum(valid), axis_name)
    return total / count


@jax.named_scope(HEAD_CE_SCOPE)
def sp_lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
               axis_name: str) -> jnp.ndarray:
    """Next-token cross entropy for a sequence-sharded block
    (boundary-crossing targets per :func:`_sp_targets`).  Returns the
    global mean (psum-reduced), identical on every shard."""
    import optax

    targets, valid = _sp_targets(tokens, axis_name)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return _sp_masked_mean(ce, valid, axis_name)


@jax.named_scope(HEAD_CE_SCOPE)
def vp_lm_loss(logits_local: jnp.ndarray, tokens: jnp.ndarray,
               model_axis: str,
               seq_axis: Optional[str] = None) -> jnp.ndarray:
    """Next-token cross entropy from vocab-sharded logits
    (``TransformerLM(vocab_parallel=True)``): per-position CE is
    assembled by :func:`~chainermn_tpu.parallel.vocab_parallel_cross_entropy`
    (one pmax + two psums over ``model_axis`` — no full-vocab row), with
    the same boundary-crossing targets as :func:`sp_lm_loss` when the
    sequence is also sharded over ``seq_axis``."""
    from chainermn_tpu.parallel import vocab_parallel_cross_entropy

    if seq_axis is not None:
        targets, valid = _sp_targets(tokens, seq_axis)
        ce = vocab_parallel_cross_entropy(
            logits_local, targets, model_axis
        )
        return _sp_masked_mean(ce, valid, seq_axis)
    ce = vocab_parallel_cross_entropy(
        logits_local[:, :-1], tokens[:, 1:], model_axis
    )
    return ce.mean()


def generate(model: TransformerLM, params, prompt: jnp.ndarray,
             max_new_tokens: int, *, temperature: float = 0.0,
             rng=None, use_cache: Optional[bool] = None,
             comm=None, param_specs=None) -> jnp.ndarray:
    """Autoregressive sampling from a (dense, single-device) LM.

    Greedy when ``temperature == 0``, else softmax sampling at the given
    temperature.  Two tiers, numerically identical (pinned by test):

    * ``use_cache=True`` (default for cache-capable models): the model's
      decode-mode twin prefills the prompt once, then each new token
      attends against the KV cache — O(max_len) per token.
    * ``use_cache=False``: one jitted ``fori_loop`` re-running the
      causal forward on a statically padded buffer each step — positions
      past the frontier cannot influence earlier logits, so the
      recompute is exact.  Works for ANY logits-or-(logits, aux) model;
      for capacity-routed MoE models (e.g. dense-mode
      ``MoeTransformerLM``) the twin's expert capacity is raised to the
      no-drop bound so a pad token's route can never evict a real
      token's (see :func:`_recompute_twin`).  An explicitly pinned
      ``model.capacity`` is therefore *not honored* during generation —
      a ``UserWarning`` is emitted when one gets raised.

    Both compiled loops are cached per (model config, shapes,
    temperature).  Tensor-parallel models sample natively: pass ``comm``
    (whose mesh binds ``model.tp_axis``) and ``param_specs`` — the whole
    loop then runs in one ``shard_map`` with head-sharded KV caches and
    a row-parallel psum per decoded token.  Vocab-parallel models
    (``vocab_parallel=True``) sample natively too: the embedding/tied
    head stay vocab-sharded and only the frontier logits row is
    all-gathered per decoded token (b x V floats — never the
    (b, s, V) tensor), making tokens identical to the dense head's.
    Sequence-parallel is training-only; materialize a ``seq_axis=None``
    model (same param tree) to sample.

    Args:
      prompt: (batch, prompt_len) int32 token ids.
      max_new_tokens: tokens to append; ``prompt_len + max_new_tokens``
        must fit ``model.max_len``.
      rng: PRNGKey, required when ``temperature > 0``.
      use_cache: ``None`` auto-selects (cache when the model supports
        decode mode and runs single-device dense).
    Returns:
      (batch, prompt_len + max_new_tokens) tokens, prompt included.
    """
    b, s0 = prompt.shape
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got "
                         f"{max_new_tokens}")
    if max_new_tokens == 0:
        return prompt
    total = s0 + max_new_tokens
    if total > model.max_len:
        raise ValueError(
            f"prompt_len + max_new_tokens = {total} exceeds "
            f"max_len={model.max_len}"
        )
    if temperature > 0 and rng is None:
        raise ValueError("temperature > 0 needs an rng key")
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused in greedy mode
    tp_axis = getattr(model, "tp_axis", None)
    vocab_parallel = getattr(model, "vocab_parallel", False)
    if getattr(model, "seq_axis", None) is not None:
        raise ValueError(
            "generate() samples from dense (optionally tensor-/vocab-"
            "parallel) models; construct one with seq_axis=None (the "
            "param tree is compatible)"
        )
    if tp_axis is not None and (comm is None or param_specs is None):
        raise ValueError(
            "a tensor-parallel model generates under its mesh: pass "
            "comm= (whose mesh binds the tp axis) and param_specs= "
            "(the parameter PartitionSpec tree, e.g. "
            "megatron_param_specs/moe_param_specs)"
        )
    # vocab_parallel implies tp_axis (enforced at model construction),
    # so the TP-tier requirements above already hold; sampling gathers
    # only the frontier logits row per token (_full_vocab).
    vp_axis = tp_axis if vocab_parallel else None
    if use_cache is not False and getattr(
            model, "options", BlockOptions()).layer_attention:
        raise ValueError(
            "generate() cannot serve this model from a cache: window "
            "layers, a gate a head, a head count of a layer's own and a "
            "scaled rotation are training-only (SelfAttention's decode "
            "path has none of them, serving/ no page lifetime a layer "
            "kind); use_cache=False recomputes the whole forward a token")
    if use_cache is None:
        use_cache = _has_decode_field(model)
    if use_cache:
        loop = _cached_decode_loop(
            _decode_twin(model, total, batch=b), s0, max_new_tokens,
            float(temperature), vp_axis=vp_axis,
        )
        run, args = loop, (params, prompt, rng)
    else:
        buf0 = jnp.zeros((b, total), jnp.int32)
        buf0 = lax.dynamic_update_slice(buf0, prompt, (0, 0))
        loop = _generate_loop(
            _recompute_twin(model, b, total), s0, max_new_tokens,
            float(temperature), vp_axis=vp_axis,
        )
        run = lambda p, buf, key: loop(p, buf, key)[0]
        args = (params, buf0, rng)
    if tp_axis is None:
        return run(*args)
    # TP tier: the whole sampling loop runs inside one shard_map over
    # the communicator's mesh — head-sharded KV caches live as scan
    # carries within the body, the row-parallel projections carry one
    # psum per decoded token.  Tokens are replicated (P()) outputs.
    from jax.sharding import PartitionSpec as P

    sharded = jax.jit(
        jax.shard_map(
            run, mesh=comm.mesh,
            in_specs=(param_specs, P(), P()), out_specs=P(),
            check_vma=False,
        )
    )
    return sharded(*args)


def _has_decode_field(model) -> bool:
    import dataclasses

    try:
        return "decode" in {f.name for f in dataclasses.fields(model)}
    except TypeError:
        return False


def _eval_twin(model):
    """The same architecture with dropout off (``deterministic=True``
    where the field exists) — sampling must not apply training-time
    dropout, and the 'dropout' rng collection isn't threaded through
    the generation loops."""
    import dataclasses

    fields = {
        f.name: getattr(model, f.name)
        for f in dataclasses.fields(model)
        if f.name not in ("parent", "name")
    }
    if "deterministic" in fields:
        fields["deterministic"] = True
    return type(model)(**fields)


def _recompute_twin(model, batch: int, total: int):
    """Eval twin made exact for capacity-routed MoE models.

    The recompute tier runs the forward on a zero-padded (batch, total)
    buffer; positions past the frontier are routed by the MoE gate like
    real tokens, and with a finite per-expert capacity a pad token's
    route can claim a queue slot ahead of a real token's (route-major
    slot assignment), changing earlier logits.  Overriding capacity to
    the flattened token count makes drops impossible (an expert can be
    chosen by at most every token once, since top-k picks distinct
    experts), restoring the padding-invariance the tier's exactness
    claim rests on."""
    import dataclasses

    twin = _eval_twin(model)
    names = {f.name for f in dataclasses.fields(twin)}
    if "capacity" in names:
        fields = {
            f.name: getattr(twin, f.name)
            for f in dataclasses.fields(twin)
            if f.name not in ("parent", "name")
        }
        _warn_capacity_override(fields.get("capacity"), batch * total)
        # dense path: per-call no-drop capacity (cap = this call's token
        # count); EP path keeps the static prefill-sized bound
        fields["capacity"] = batch * total
        if "no_drop" in names:
            fields["no_drop"] = True
        twin = type(twin)(**fields)
    return twin


def _warn_capacity_override(pinned, no_drop: int) -> None:
    """Generation overrides a user-pinned MoE ``capacity`` with the
    no-drop bound (padding-exactness needs it), which means sampling
    routes tokens through a *less drop-constrained* model than the one
    trained.  Outputs stay deterministic and the two generate tiers
    agree with each other — but not necessarily with train-time routing,
    so say so rather than diverge silently."""
    if pinned is not None and pinned != no_drop:
        import warnings

        warnings.warn(
            f"generate(): model.capacity={pinned} is overridden to the "
            f"no-drop bound {no_drop} for padding-exact generation; "
            "sampled routing may differ from the capacity-constrained "
            "routing seen in training",
            stacklevel=3,
        )


def _decode_twin(model, cache_len: int, batch: Optional[int] = None):
    """The eval twin with ``decode=True`` and caches sized to the
    actual generation length (not max_len — a short sample from a
    long-context model shouldn't pay full-context attention per step);
    parameters are layout-identical.  Capacity-routed MoE models get the
    same no-drop capacity override as :func:`_recompute_twin` (prefill
    routes batch*prompt_len tokens at once; a drop there would desync
    the two generate tiers)."""
    import dataclasses

    if not _has_decode_field(model):
        raise ValueError(
            f"{type(model).__name__} has no decode mode; call "
            "generate(..., use_cache=False) for the recompute tier"
        )
    twin = _eval_twin(model)
    fields = {
        f.name: getattr(twin, f.name)
        for f in dataclasses.fields(twin)
        if f.name not in ("parent", "name")
    }
    fields["decode"] = True
    if "cache_len" in fields:
        fields["cache_len"] = cache_len
    if "capacity" in fields and batch is not None:
        _warn_capacity_override(fields.get("capacity"), batch * cache_len)
        # dense path: no_drop sizes each call's expert queues to its own
        # token count — the prefill routes batch*prompt tokens but each
        # decode step routes only batch, so queues shrink ~cache_len-fold
        fields["capacity"] = batch * cache_len
        if "no_drop" in fields:
            fields["no_drop"] = True
    return type(model)(**fields)


def _full_vocab(step_logits, vp_axis):
    """Vocab-parallel models emit the LOCAL vocab block; sampling needs
    the full row.  One tiled all_gather of the (b, V/n) frontier row —
    shard r holds global rows [r*V/n, (r+1)*V/n), so concatenation in
    axis order IS global vocab order and the downstream `_sample` is
    token-identical to the dense head's.  Only the sampled position is
    gathered (b x V floats per token), never the (b, s, V) tensor the
    vp training path exists to avoid."""
    if vp_axis is None:
        return step_logits
    return _cc.all_gather(step_logits, vp_axis, axis=-1, tiled=True)


def _sample(step_logits, key, temperature: float):
    """One sampling decision — shared by both generate tiers so their
    pinned numerical identity can't drift (same key-split order)."""
    if temperature > 0:
        key, sub = jax.random.split(key)
        return jax.random.categorical(
            sub, step_logits / temperature, axis=-1
        ).astype(jnp.int32), key
    return jnp.argmax(step_logits, axis=-1).astype(jnp.int32), key


@functools.lru_cache(maxsize=32)
def _cached_decode_loop(dmodel, s0: int, max_new_tokens: int,
                        temperature: float, vp_axis=None):
    """Compiled KV-cache sampling: prefill the prompt, then scan one
    token at a time against the caches."""

    def logits_of(out):
        # (logits, aux) models (MoeTransformerLM) vs plain logits
        return out[0] if isinstance(out, tuple) else out

    @jax.jit
    def run(params, prompt, key):
        out, mut = dmodel.apply(params, prompt, mutable=["cache"])
        cache = mut["cache"]
        nxt, key = _sample(
            _full_vocab(
                logits_of(out)[:, -1].astype(jnp.float32), vp_axis
            ), key, temperature
        )

        def body(carry, _):
            cache, tok, key = carry
            out, mut = dmodel.apply(
                {**params, "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            nxt, key = _sample(
                _full_vocab(
                    logits_of(out)[:, -1].astype(jnp.float32), vp_axis
                ), key, temperature
            )
            return (mut["cache"], nxt, key), nxt

        (_, _, key), rest = lax.scan(
            body, (cache, nxt, key), None, length=max_new_tokens - 1
        )
        new = jnp.concatenate(
            [nxt[:, None], jnp.moveaxis(rest, 0, 1)], axis=1
        ) if max_new_tokens > 1 else nxt[:, None]
        return jnp.concatenate([prompt, new], axis=1)

    return run


@functools.lru_cache(maxsize=32)
def _generate_loop(model, s0: int, max_new_tokens: int,
                   temperature: float, vp_axis=None):
    """Compiled sampling loop, cached per (model config, shapes,
    temperature) so repeated generate() calls reuse the executable
    (flax modules are frozen/hashable; a fresh jit per call would
    re-trace every time)."""

    @jax.jit
    def run(params, buf0, key):
        def body(i, carry):
            buf, key = carry
            out = model.apply(params, buf)
            logits = out[0] if isinstance(out, tuple) else out
            step_logits = lax.dynamic_index_in_dim(
                logits, s0 + i - 1, axis=1, keepdims=False
            )  # (b, V) at the frontier position ((b, V/n) under vp)
            nxt, key = _sample(
                _full_vocab(step_logits.astype(jnp.float32), vp_axis),
                key, temperature
            )
            buf = lax.dynamic_update_slice(
                buf, nxt[:, None], (0, s0 + i)
            )
            return buf, key

        return lax.fori_loop(0, max_new_tokens, body, (buf0, key))

    return run
