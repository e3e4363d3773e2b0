"""Mixture-of-Experts transformer LM — the composed-parallelism flagship.

The reference's parallelism ceiling was data parallel + hand-built model
parallel over its collective functions (SURVEY.md section 2 strategy
table).  This model is the modern composition those primitives point at,
all four families in ONE compiled train step on a
:class:`~chainermn_tpu.communicators.MeshCommunicator`'s
``(mn_data, mn_seq, mn_model)`` mesh:

* **DP**  — batch rows shard over ``mn_data``; gradient reduction is
  generated by vma-checked shard_map autodiff.
* **SP**  — sequence positions shard over ``mn_seq``; attention is
  :func:`~chainermn_tpu.parallel.ring_attention` (K/V blocks rotating on
  the ICI ring) and the loss crosses shard boundaries via ``ppermute``
  (:func:`~chainermn_tpu.models.transformer.sp_lm_loss`).
* **TP**  — attention heads and MLP width shard over ``mn_model``
  (Megatron column/row pairs, one psum per sub-block).
* **EP**  — MoE layers split tokens over ``mn_model``, route them to
  experts sharded over the same axis with ONE ``all_to_all`` each way
  (:func:`~chainermn_tpu.parallel.expert_parallel_moe`), and all_gather
  the combined outputs back to the TP-replicated residual stream.

Correctness story: the SAME module runs on any mesh factorization —
``(n,1,1)`` is plain DP, ``(a,b,c)`` is the full composition — so tests
assert that factorizations agree numerically (capacity held fixed), which
exercises every collective the composition inserts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

# Communication goes through the audited wrappers (analysis.lint gate).
from chainermn_tpu.functions import collectives as _cc
from chainermn_tpu.models.transformer import (
    HEAD_CE_SCOPE,
    BlockOptions,
    TransformerBlock,
    block_under_plan,
    lm_loss,
    make_lm_embed,
    make_mixer,
    make_norm,
    model_remat_plan,
    sp_lm_loss,
    vp_lm_loss,
)
from chainermn_tpu.parallel.expert_parallel import (
    combine_from_queues,
    compute_capacity,
    dispatch_to_queues,
    expert_parallel_moe,
    held_experts_moe,
    load_balancing_loss,
    mlp_experts,
    resolve_dispatch_impl,
    route_plan,
)

#: flax collection the dropless expert layers ``sow`` their counters
#: into (``moe_rows_routed``, ``moe_rows_computed``, ``moe_dropped``)
COUNTERS = "counters"
#: and the one for their routing decisions: ``chosen (tokens, k)``, the
#: experts each token's routes went to
ROUTES = "routes"
#: device scope of the shared expert and its gate
MOE_SHARED_SCOPE = "moe_shared"


@dataclasses.dataclass(frozen=True)
class RouterOptions:
    """What a dropless expert layer's router and shared expert can be
    besides the defaults (a softmax over the experts, weights
    renormalised over the chosen, a shared expert under a sigmoid gate);
    one hashable field, ``router_options``, of the layer, the block and
    the LM.

    ``score="sigmoid"``: a sigmoid an expert in the softmax's place.
    ``selection_bias``: a number an expert (``router_bias (n_experts,)``,
    initialised 0) added to the scores for the choice of the ``k``
    alone, outside the weights and with no gradient; the optimizer is to
    leave it alone too (a balancing rule outside the loss moves it,
    where one runs).  ``routed_scale``: a factor on the renormalised
    weights.  ``shared_gated=False``: the shared expert without its
    gate, ``W_d(silu(W_g x) * W_u x)`` added as it is (no
    ``shared_gate``); several shared experts are one of their summed
    width.  ``seq_aux``: the load-balancing loss counted a sequence
    (:func:`~chainermn_tpu.parallel.expert_parallel.sequence_balancing_loss`)
    instead of over all rows of the batch."""

    score: str = "softmax"
    selection_bias: bool = False
    routed_scale: float = 1.0
    shared_gated: bool = True
    seq_aux: bool = False


class MoeMlp(nn.Module):
    """Routed expert MLP replacing a transformer's dense MLP block.

    With ``expert_axis`` set (inside ``shard_map``): tokens are split over
    the axis, routed, exchanged via ``all_to_all``, processed by this
    chip's experts, exchanged back, and the combined outputs all_gathered
    so the residual stream stays replicated over the axis (the TP
    convention).  With ``expert_axis=None``: the identical routing math
    runs locally over all experts — the single-device tier and the
    numerics oracle for the parallel path.

    Returns ``(y, aux)``: combined outputs (dropped tokens yield zeros —
    add the residual outside) and the load-balancing auxiliary loss.

    ``routing="dropless"`` (SiLU-gated experts ``W_d(silu(W_g x) * W_u
    x)``, parameters ``expert_wg`` / ``expert_wu`` / ``expert_wd``): no
    capacity queue and no drop.  The layer routes over all
    ``n_experts`` (float32 softmax, top ``k``, weights renormalised over
    them) and computes the part of the result that the experts it holds
    give, every route to them at any imbalance, adding nothing for the
    absent ones (:func:`~chainermn_tpu.parallel.expert_parallel.held_experts_moe`).
    ``held=(first, count)`` says which it holds: with ``expert_axis``
    unset that is one chip's share of a layer several chips hold
    together (default: all of them); with ``expert_axis`` set every chip
    of the axis runs this same layer on its own ``n_experts /
    axis_size`` experts and the parts are summed over the axis.  Its
    counters are sown into the ``"counters"`` collection, its routing
    decisions into ``"routes"``.  ``shared_d_ff``: beside the routed
    experts a shared one of that width that every token goes through,
    under a sigmoid gate of its own, ``sigmoid(w_s . x) W_d(silu(W_g x)
    * W_u x)`` (parameters ``shared_wg`` / ``shared_wu`` / ``shared_wd``
    / ``shared_gate``, held whole by every chip): computed by every
    chip alike, outside the sum over ``expert_axis``, so it is counted
    once.  ``router_options`` (:class:`RouterOptions`, dropless only):
    sigmoid scores, a selection bias outside the weights, a scaling
    factor on the routed weights, the shared expert without its gate.
    """

    n_experts: int
    d_ff: int
    k: int = 2
    capacity_factor: float = 1.25
    capacity: Optional[int] = None  # explicit per-source-chip queue length
    # no_drop: capacity = the per-call token count, the exact no-drop
    # bound (top-k picks distinct experts, so an expert sees each token
    # at most once).  Generation twins use this instead of a static
    # prefill-sized capacity: a KV-cache decode step routes only
    # ``batch`` tokens, so its expert queues shrink from
    # (E, batch*cache_len, d) to (E, batch, d).  Dense-path only; the
    # expert-parallel branch keeps ``capacity``.
    no_drop: bool = False
    expert_axis: Optional[str] = None
    # axes to average load-balancing statistics over (see
    # load_balancing_loss); every token-splitting axis makes the aux
    # loss the exact global-batch value, invariant to factorization
    aux_stat_axes: Optional[tuple] = None
    dispatch_impl: str = "auto"  # einsum | scatter | auto
    dtype: Any = jnp.bfloat16
    routing: str = "capacity"  # capacity | dropless
    held: Optional[Tuple[int, int]] = None  # dropless: (first, count)
    shared_d_ff: int = 0  # dropless: width of the shared expert, 0 none
    router_options: RouterOptions = RouterOptions()  # dropless

    @jax.named_scope(MOE_SHARED_SCOPE)
    def _shared(self, x):
        """The shared expert's result on ``x (tokens, d)``, float32:
        the gate's logit (where it is gated) in float32, the three
        products in ``x``'s dtype."""
        d = x.shape[-1]
        init = nn.initializers.normal(0.02)
        wg = self.param("shared_wg", init, (d, self.shared_d_ff),
                        jnp.float32)
        wu = self.param("shared_wu", init, (d, self.shared_d_ff),
                        jnp.float32)
        wd = self.param("shared_wd", init, (self.shared_d_ff, d),
                        jnp.float32)
        mm = lambda a, w: jnp.dot(a, w.astype(a.dtype),
                                  preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(mm(x, wg)) * mm(x, wu)).astype(x.dtype)
        if not self.router_options.shared_gated:
            return mm(hidden, wd)
        gate = self.param("shared_gate", init, (d, 1), jnp.float32)
        return jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), gate,
            precision=lax.Precision.HIGHEST)) * mm(hidden, wd)

    def _dropless(self, x, router, local_e: int):
        """The held experts' part of the layer, without a drop."""
        from chainermn_tpu.parallel.tensor_parallel import _sharded_init

        b, s, d = x.shape
        if self.expert_axis is not None:
            if self.held is not None:
                raise ValueError("held is the axis's to say under "
                                 "expert_axis")
            first = lax.axis_index(self.expert_axis) * local_e
            count = local_e
        else:
            first, count = self.held or (0, self.n_experts)
        init = nn.initializers.normal(0.02)
        if self.expert_axis is not None:
            init = _sharded_init(init, self.expert_axis)
        wg = self.param("expert_wg", init, (count, d, self.d_ff),
                        jnp.float32)
        wu = self.param("expert_wu", init, (count, d, self.d_ff),
                        jnp.float32)
        wd = self.param("expert_wd", init, (count, self.d_ff, d),
                        jnp.float32)
        ro = self.router_options
        bias = self.param("router_bias", nn.initializers.zeros,
                          (self.n_experts,), jnp.float32) \
            if ro.selection_bias else None
        y, aux, counters, chosen = held_experts_moe(
            x.reshape(-1, d), router, wg, wu, wd,
            num_experts=self.n_experts, k=self.k, first=first,
            aux_stat_axes=self.aux_stat_axes, score=ro.score,
            selection_bias=bias, routed_scale=ro.routed_scale,
            aux_sequences=b if ro.seq_aux else None,
        )
        if self.expert_axis is not None:
            y = _cc.psum(y, self.expert_axis)
        if self.shared_d_ff:
            # every chip's own, the same on each: added after the sum
            y = y.astype(jnp.float32) + self._shared(x.reshape(-1, d))
        for name, value in counters.items():
            self.sow(COUNTERS, name, value)
        self.sow(ROUTES, "chosen", chosen)
        return y.reshape(b, s, d).astype(x.dtype), aux

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        b, s, d = x.shape
        n = 1 if self.expert_axis is None else lax.axis_size(
            self.expert_axis
        )
        if self.n_experts % n:
            raise ValueError(
                f"n_experts ({self.n_experts}) not divisible by the "
                f"'{self.expert_axis}' axis size ({n})"
            )
        local_e = self.n_experts // n
        router = self.param(
            "router", nn.initializers.normal(0.02),
            (d, self.n_experts), jnp.float32,
        )
        if self.routing == "dropless":
            return self._dropless(x, router, local_e)
        if self.routing != "capacity":
            raise ValueError(f"routing must be capacity or dropless, got "
                             f"{self.routing!r}")
        if self.shared_d_ff or self.router_options != RouterOptions():
            raise ValueError(
                "the shared expert (gated or ungated), sigmoid scores, "
                "the selection bias, the scaling factor on the routed "
                "weights and the balance loss a sequence (router_options) "
                "come with dropless routing")
        # Per-chip expert blocks are independent draws (fold the axis
        # index into the RNG — tensor_parallel's sharded-init convention).
        from chainermn_tpu.parallel.tensor_parallel import _sharded_init

        init = nn.initializers.lecun_normal()
        w_init = (
            _sharded_init(init, self.expert_axis)
            if self.expert_axis is not None
            else init
        )
        # Self-identifying names: moe_param_specs shards any
        # "expert_w*" leaf's leading (expert) dim, wherever it lives.
        w1 = self.param("expert_w1", w_init, (local_e, d, self.d_ff),
                        jnp.float32)
        w2 = self.param("expert_w2", w_init, (local_e, self.d_ff, d),
                        jnp.float32)
        expert_fn = mlp_experts(w1, w2)

        x_flat = x.reshape(-1, d)
        t = x_flat.shape[0]
        if self.expert_axis is None:
            if self.no_drop:
                cap = t  # exact no-drop bound for this call's tokens
            elif self.capacity is not None:
                cap = self.capacity
            else:
                cap = compute_capacity(t, self.n_experts, self.k,
                                       self.capacity_factor)
            probs = jax.nn.softmax(
                jnp.asarray(x_flat, jnp.float32)
                @ jnp.asarray(router, jnp.float32),
                axis=-1,
            )
            plan = route_plan(probs, self.k, cap)
            impl = resolve_dispatch_impl(
                self.dispatch_impl, t, self.n_experts, cap
            )
            queues = dispatch_to_queues(
                x_flat, plan, self.n_experts, cap, impl
            )
            out = expert_fn(queues)
            y = combine_from_queues(out, plan, self.n_experts, cap, impl)
            aux = load_balancing_loss(probs, plan.raw_routes,
                                      axes=self.aux_stat_axes)
        else:
            if t % n:
                raise ValueError(
                    f"local token count {t} (= batch*seq per chip) not "
                    f"divisible by the '{self.expert_axis}' axis size {n}"
                )
            tloc = t // n
            me = lax.axis_index(self.expert_axis)
            # The residual stream is replicated over the expert/TP axis;
            # each chip routes a distinct token slice so routing work and
            # all_to_all volume scale 1/n.
            x_loc = lax.dynamic_slice_in_dim(x_flat, me * tloc, tloc)
            y_loc, aux = expert_parallel_moe(
                x_loc, router, expert_fn, self.expert_axis,
                self.n_experts, k=self.k,
                capacity_factor=self.capacity_factor,
                capacity=self.capacity,
                aux_stat_axes=self.aux_stat_axes,
                dispatch_impl=self.dispatch_impl,
            )
            y = _cc.all_gather(
                y_loc, self.expert_axis, axis=0, tiled=True
            )
        return y.reshape(b, s, d).astype(x.dtype), aux


class MoeTransformerBlock(nn.Module):
    """Pre-LN block: TP/SP-aware attention, or with ``options``'
    ``layer_types`` the mixer of the layer's ``kind``
    (:func:`~chainermn_tpu.models.transformer.make_mixer`), + MoE MLP."""

    n_heads: int
    d_ff: int
    n_experts: int
    k: int = 2
    capacity_factor: float = 1.25
    capacity: Optional[int] = None
    no_drop: bool = False
    aux_stat_axes: Optional[tuple] = None
    dispatch_impl: str = "auto"
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_impl: str = "ring"
    expert_axis: Optional[str] = None
    decode: bool = False
    cache_len: int = 0
    attention_fn: Optional[Callable] = None
    options: BlockOptions = BlockOptions()
    routing: str = "capacity"
    held: Optional[Tuple[int, int]] = None
    shared_d_ff: int = 0
    router_options: RouterOptions = RouterOptions()
    # the sequence mixer, one of transformer.LAYER_KINDS
    kind: str = "attention"

    @nn.compact
    def __call__(self, x):
        ln = lambda: make_norm(self.options)
        x = x + make_mixer(
            self.kind, self.n_heads, self.options, self.dtype,
            seq_axis=self.seq_axis, tp_axis=self.tp_axis,
            sp_impl=self.sp_impl, decode=self.decode,
            cache_len=self.cache_len, attention_fn=self.attention_fn,
        )(ln()(x).astype(self.dtype))
        y, aux = MoeMlp(
            self.n_experts, self.d_ff, k=self.k,
            capacity_factor=self.capacity_factor, capacity=self.capacity,
            no_drop=self.no_drop,
            expert_axis=self.expert_axis,
            aux_stat_axes=self.aux_stat_axes,
            dispatch_impl=self.dispatch_impl, dtype=self.dtype,
            routing=self.routing, held=self.held,
            shared_d_ff=self.shared_d_ff,
            router_options=self.router_options,
        )(ln()(x).astype(self.dtype))
        return x + y, aux


class MoeTransformerLM(nn.Module):
    """Causal MoE LM: tokens (batch, seq) -> (logits, aux_loss).

    Every ``moe_every``-th block's MLP is a routed :class:`MoeMlp`
    (GShard-style interleaving; ``moe_every=1`` makes every block MoE);
    the rest are dense (TP-sharded when ``tp_axis`` is set).  On the
    general path the first ``first_dense`` layers' MLPs are dense
    whatever ``moe_every`` says, ``dense_d_ff`` wide where that is not
    the experts' ``d_ff``.  ``aux_loss`` is the summed load-balancing
    loss — feed the pair to :func:`moe_lm_loss`.
    """

    vocab_size: int
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    n_experts: int = 8
    d_ff: Optional[int] = None
    moe_every: int = 2
    k: int = 2
    capacity_factor: float = 1.25
    capacity: Optional[int] = None
    # per-call no-drop capacity (generation twins; see MoeMlp.no_drop)
    no_drop: bool = False
    aux_stat_axes: Optional[tuple] = None
    dispatch_impl: str = "auto"
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_impl: str = "ring"
    expert_axis: Optional[str] = None
    # shard the embedding table + tied head over tp_axis (train with
    # moe_lm_loss(vocab_parallel=True)); see TransformerLM.vocab_parallel
    vocab_parallel: bool = False
    # KV-cache decode mode (generate's cache tier): attention caches of
    # length cache_len accumulate across calls; MoE routing needs no
    # cross-step state (each call routes its own tokens).  Composes with
    # tp_axis/expert_axis, not seq_axis.
    decode: bool = False
    cache_len: int = 0
    attention_fn: Optional[Callable] = None
    # the block's options (norm, grouped-query heads, rotary positions,
    # q/k norm, block diffusion, the kind of each layer's mixer,
    # per-block recomputation): transformer.BlockOptions.  With
    # ``rope_theta`` or ``no_positions`` the model holds no position
    # table.
    options: BlockOptions = BlockOptions()
    # expert layers without drops over the held experts, their shared
    # expert and their router's other forms: MoeMlp
    routing: str = "capacity"
    held: Optional[Tuple[int, int]] = None
    shared_d_ff: int = 0
    router_options: RouterOptions = RouterOptions()
    # leading layers whose MLP is dense, and the dense MLP's width
    first_dense: int = 0
    dense_d_ff: Optional[int] = None
    # an output head of its own (``lm_head (vocab, d)``) instead of the
    # embedding's transpose
    tie_head: bool = True
    # return the final-norm hidden states instead of logits: the
    # block-diffusion loss applies the head to the noised copy alone
    return_hidden: bool = False

    def remat_plan(self, tokens: int):
        """What each block keeps across its recomputation, for
        ``tokens`` positions a device and step
        (:func:`~chainermn_tpu.models.transformer.remat_plan`)."""
        return model_remat_plan(self, tokens)

    def sparse_layer(self, i: int) -> bool:
        """Whether layer ``i``'s MLP is an expert layer."""
        return (i + 1) % self.moe_every == 0 and i >= self.first_dense

    def _general(self, tokens):
        """The model on the general attention path: no position table,
        every block ``options``' block, its mixer of the layer's kind
        and, with ``remat_blocks``, computed again in the backward pass
        (both from the code ``TransformerLM`` takes them from)."""
        o = self.options
        d_ff = self.d_ff or 4 * self.d_model
        embed = make_lm_embed(
            self, self.vocab_size, self.d_model, self.tp_axis,
            self.vocab_parallel,
        )
        head = embed.embedding if self.tie_head else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (self.vocab_size, self.d_model), jnp.float32)
        x = embed(tokens).astype(self.dtype)
        aux = jnp.zeros((), jnp.float32)
        if o.remat_blocks:
            plan = self.remat_plan(tokens.size)
        serial = {MoeTransformerBlock: 0, TransformerBlock: 0}
        for i in range(self.n_layers):
            sparse = self.sparse_layer(i)
            cls = MoeTransformerBlock if sparse else TransformerBlock
            block = block_under_plan(cls, plan[i], serial[cls]) \
                if o.remat_blocks else cls
            serial[cls] += 1
            if sparse:
                x, a = block(
                    self.n_heads, d_ff, self.n_experts, k=self.k,
                    capacity_factor=self.capacity_factor,
                    capacity=self.capacity, no_drop=self.no_drop,
                    aux_stat_axes=self.aux_stat_axes,
                    dispatch_impl=self.dispatch_impl, dtype=self.dtype,
                    expert_axis=self.expert_axis, options=o,
                    routing=self.routing, held=self.held,
                    shared_d_ff=self.shared_d_ff,
                    router_options=self.router_options,
                    kind=o.layer_type(i),
                )(x)
                aux = aux + a
            else:
                x = block(
                    self.n_heads, self.dense_d_ff or d_ff, dtype=self.dtype,
                    options=o, kind=o.layer_type(i),
                )(x)
        x = make_norm(o)(x)
        if self.return_hidden:
            return x, aux
        with jax.named_scope(HEAD_CE_SCOPE):
            return x.astype(jnp.float32) @ head.T, aux

    @nn.compact
    def __call__(self, tokens):
        if self.options.rope_theta or self.options.no_positions:
            return self._general(tokens)
        if self.options != BlockOptions() or not self.tie_head \
                or self.return_hidden or self.routing != "capacity" \
                or self.shared_d_ff or self.first_dense or self.dense_d_ff \
                or self.router_options != RouterOptions():
            raise ValueError(
                "the block's options, dropless routing, a shared expert, "
                "the router's options, leading dense layers, an untied "
                "head and return_hidden come with rotary positions "
                "(options.rope_theta) or none (options.no_positions)")
        b, s = tokens.shape
        d_ff = self.d_ff or 4 * self.d_model
        embed = make_lm_embed(
            self, self.vocab_size, self.d_model, self.tp_axis,
            self.vocab_parallel,
        )
        pos_table = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.d_model), jnp.float32,
        )
        offset = 0
        if self.seq_axis is not None:
            n_shards = lax.axis_size(self.seq_axis)
            if n_shards * s > self.max_len:
                raise ValueError(
                    f"global sequence length {n_shards}*{s} exceeds "
                    f"max_len={self.max_len}; raise max_len"
                )
            offset = lax.axis_index(self.seq_axis) * s
        elif s > self.max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_len={self.max_len}; "
                "raise max_len"
            )
        if self.decode:
            # position of this call's first token = tokens cached so far
            # (same counter convention as TransformerLM decode mode)
            pos_idx = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            offset = pos_idx.value
            pos_idx.value = offset + s
        pos = lax.dynamic_slice_in_dim(pos_table, offset, s, axis=0)

        x = (embed(tokens) + pos[None]).astype(self.dtype)
        aux = jnp.zeros((), jnp.float32)
        for i in range(self.n_layers):
            if (i + 1) % self.moe_every == 0:
                x, a = MoeTransformerBlock(
                    self.n_heads, d_ff, self.n_experts, k=self.k,
                    capacity_factor=self.capacity_factor,
                    capacity=self.capacity, no_drop=self.no_drop,
                    aux_stat_axes=self.aux_stat_axes,
                    dispatch_impl=self.dispatch_impl, dtype=self.dtype,
                    seq_axis=self.seq_axis, tp_axis=self.tp_axis,
                    sp_impl=self.sp_impl,
                    expert_axis=self.expert_axis,
                    decode=self.decode,
                    cache_len=self.cache_len or self.max_len,
                    attention_fn=self.attention_fn,
                )(x)
                aux = aux + a
            else:
                x = TransformerBlock(
                    self.n_heads, d_ff, dtype=self.dtype,
                    seq_axis=self.seq_axis, tp_axis=self.tp_axis,
                    sp_impl=self.sp_impl, decode=self.decode,
                    cache_len=self.cache_len or self.max_len,
                    attention_fn=self.attention_fn,
                )(x)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        if self.vocab_parallel:
            return embed.attend(x.astype(jnp.float32)), aux
        logits = x.astype(jnp.float32) @ embed.embedding.T
        return logits, aux


def moe_lm_loss(
    model_out: Tuple[jnp.ndarray, jnp.ndarray],
    tokens: jnp.ndarray,
    *,
    seq_axis: Optional[str] = None,
    model_axis: Optional[str] = None,
    aux_coef: float = 1e-2,
    vocab_parallel: bool = False,
) -> jnp.ndarray:
    """Next-token cross entropy + load-balancing auxiliary for a
    (logits, aux) pair from :class:`MoeTransformerLM`.

    ``seq_axis``: sequence-sharded targets cross shard boundaries via
    ``sp_lm_loss``; the aux term is pmean'd over the axis.  ``model_axis``:
    the total is pmean'd over it — the MoE token-split makes the residual
    stream formally axis-varying even though values agree, and the pmean
    both certifies the invariance to vma-checked shard_map and is free on
    the wire (all shards hold the same value).

    ``vocab_parallel``: set when the model was built with
    ``vocab_parallel=True`` — ``logits`` are then each chip's LOCAL
    vocab block and ``model_axis`` (required) doubles as the vocab axis;
    the CE routes through ``vp_lm_loss`` instead of
    ``sp_lm_loss``/``lm_loss``.  Forgetting to mirror the flag would
    feed block logits to a full-vocab loss.
    """
    logits, aux = model_out
    if vocab_parallel:
        if model_axis is None:
            raise ValueError(
                "vocab_parallel=True needs model_axis (the vocab axis)"
            )
        main = vp_lm_loss(logits, tokens, model_axis, seq_axis=seq_axis)
        if seq_axis is not None:
            aux = _cc.pmean(aux, seq_axis)
    elif seq_axis is not None:
        main = sp_lm_loss(logits, tokens, seq_axis)
        aux = _cc.pmean(aux, seq_axis)
    else:
        main = lm_loss(logits, tokens)
    total = main + aux_coef * aux
    if model_axis is not None:
        total = _cc.pmean(total, model_axis)
    return total


def moe_param_specs(params, model_axis: str = "mn_model"):
    """PartitionSpec pytree for a :class:`MoeTransformerLM` parameter
    tree: Megatron column/row kernels shard over ``model_axis`` (as in
    :func:`~chainermn_tpu.parallel.megatron_param_specs`), MoE expert
    blocks (``expert_w*``) shard their leading expert
    dimension over the same axis, VocabParallelEmbed tables shard their
    vocab rows, and everything else (dense embeddings, LayerNorms,
    routers) replicates."""
    from jax.sharding import PartitionSpec as P
    import jax.tree_util as jtu

    from chainermn_tpu.parallel.tensor_parallel import (
        _path_keys,
        _tp_leaf_spec,
    )

    def leaf_spec(path, leaf):
        keys = _path_keys(path)
        last = keys[-1] if keys else ""
        if last.startswith("expert_w"):
            return P(model_axis)  # leading expert dim
        spec = _tp_leaf_spec(keys, model_axis)
        return P() if spec is None else spec

    return jtu.tree_map_with_path(leaf_spec, params)
