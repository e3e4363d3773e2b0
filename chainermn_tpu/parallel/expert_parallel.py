"""Expert parallelism: Mixture-of-Experts over an ``expert`` mesh axis.

The reference has no MoE, but its differentiable ``alltoall``
(``chainermn/functions/collective_communication.py``, SURVEY.md section 2
#19 and the parallelism table: "EP: `alltoall` is the primitive it would
need") is exactly the dispatch/combine exchange expert parallelism is
built from.  This module is that capability, TPU-native:

* Experts are sharded across the chips of one mesh axis; each chip holds
  ``num_experts / axis_size`` expert parameter sets.
* A token's route is decided by a learned router (top-1 "Switch" or
  top-2 "GShard" style) with a static capacity — shapes stay fixed so the
  whole layer jits once; overflow tokens are dropped (standard MoE
  semantics) and flow through the residual connection.
* Dispatch and return are each ONE ``lax.all_to_all`` riding ICI; the
  expert compute between them is a batched matmul over
  ``(local_experts, axis_size * capacity, d)`` blocks — MXU-shaped.

Everything is differentiable end to end (all_to_all's transpose is
all_to_all in the reverse direction; XLA generates it), so the router
learns through the combine weights exactly as in GShard/Switch.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.grouped_matmul import grouped_matmul, sum_to_vma


def compute_capacity(tokens: int, num_experts: int, k: int,
                     capacity_factor: float) -> int:
    """Static per-expert queue length for ``tokens`` routed k ways."""
    return max(int(math.ceil(tokens * k * capacity_factor / num_experts)), 1)


class RoutePlan(NamedTuple):
    """Static-shape routing decision shared by both dispatch backends.

    chosen/gates/slot/keep are (tokens, k): expert index, re-normalized
    gate, queue position within that expert, and survives-capacity flag
    for each of a token's k routes; raw_routes is the (tokens,
    num_experts) pre-capacity indicator for the balance loss.
    """

    chosen: jnp.ndarray
    gates: jnp.ndarray
    slot: jnp.ndarray
    keep: jnp.ndarray
    raw_routes: jnp.ndarray


def route_plan(probs: jnp.ndarray, k: int, capacity: int) -> RoutePlan:
    """Top-k routing with static per-expert capacity.

    Queue positions count earlier claims on the same expert in
    route-major then token order (all first choices before all second
    choices — GShard's ordering, so a token's secondary route is dropped
    before any primary route).
    """
    t, e = probs.shape
    if k > e:
        raise ValueError(f"k ({k}) cannot exceed num_experts ({e})")
    # lax.top_k guarantees k distinct indices with values read from the
    # original row — no hand-rolled argmax-and-mask loop needed.
    gate_arr, chosen_arr = lax.top_k(probs, k)
    onehots = [
        jax.nn.one_hot(chosen_arr[:, j], e, dtype=jnp.int32)
        for j in range(k)
    ]
    gate_sum = jnp.sum(gate_arr, axis=1, keepdims=True) if k > 1 else None
    gates = (
        gate_arr / (gate_sum + 1e-9) if gate_sum is not None else gate_arr
    )
    slots, keeps = [], []
    prior = jnp.zeros((e,), jnp.int32)
    for oh in onehots:
        pos = jnp.cumsum(oh, axis=0) - oh  # earlier tokens, this route
        pos = pos + prior[None, :]  # plus all earlier routes
        prior = prior + jnp.sum(oh, axis=0)
        slot = jnp.sum(pos * oh, axis=-1)  # (tokens,)
        slots.append(slot)
        keeps.append(slot < capacity)
    raw_routes = sum(oh.astype(probs.dtype) for oh in onehots)
    return RoutePlan(
        chosen=chosen_arr,
        gates=gates.astype(probs.dtype),
        slot=jnp.stack(slots, axis=1),
        keep=jnp.stack(keeps, axis=1),
        raw_routes=raw_routes,
    )


def top_k_routing(
    probs: jnp.ndarray, k: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Build DENSE dispatch mask and combine weights from router
    probabilities — the einsum backend's (tokens, num_experts, capacity)
    tensors.  O(t*e*cap) memory; prefer :func:`route_plan` +
    :func:`scatter_dispatch` at scale.

    Returns:
      dispatch: (tokens, num_experts, capacity) one-hot {0,1} — token t
        occupies slot c of expert e's queue.
      combine: same shape, dispatch scaled by the (re-normalized) router
        probability of the chosen expert.
      raw_routes: (tokens, num_experts) pre-capacity route indicator (sum
        of the k choice one-hots) — feed this, not dispatch, to
        :func:`load_balancing_loss` so dropped claims still count.
    """
    plan = route_plan(probs, k, capacity)
    dispatch, combine = _dense_masks(plan, probs.shape[1], capacity,
                                     probs.dtype)
    return dispatch, combine, plan.raw_routes


def _dense_masks(plan: RoutePlan, e: int, capacity: int, dtype):
    t, k = plan.chosen.shape
    dispatch = jnp.zeros((t, e, capacity), dtype)
    combine = jnp.zeros((t, e, capacity), dtype)
    for j in range(k):
        oh = jax.nn.one_hot(plan.chosen[:, j], e, dtype=dtype)
        oh_slot = jax.nn.one_hot(plan.slot[:, j], capacity, dtype=dtype)
        d = oh[:, :, None] * oh_slot[:, None, :]
        keep = plan.keep[:, j].astype(dtype)
        dispatch = dispatch + d * keep[:, None, None]
        # gates are fp32; cast the per-route weight so the (t, e, cap)
        # combine tensor stays in the requested dtype (and CSEs with the
        # dispatch mask instead of silently promoting to fp32)
        w = (plan.gates[:, j].astype(dtype) * keep)
        combine = combine + d * w[:, None, None]
    return dispatch, combine


def scatter_dispatch(x: jnp.ndarray, plan: RoutePlan, num_experts: int,
                     capacity: int) -> jnp.ndarray:
    """Token rows into per-expert queues via scatter — O(t*k*d) work.

    The einsum backend builds the same (num_experts, capacity, d) queues
    as ``einsum('td,tec->ecd', x, dispatch)``, which costs
    t*e*cap*d FLOPs (a full matmul against a one-hot operand); at LM
    scale that rivals the model's own FLOPs.  Queue slots are unique by
    construction (the cumulative-position assignment), so this is a
    collision-free scatter.
    """
    t, d = x.shape
    k = plan.chosen.shape[1]
    dump = num_experts * capacity  # dropped routes land here
    dest = jnp.where(
        plan.keep, plan.chosen * capacity + plan.slot, dump
    )  # (t, k)
    queues = jnp.zeros((num_experts * capacity + 1, d), x.dtype)
    # route-major flattening pairs dest[:, j] with the token rows
    queues = queues.at[dest.T.reshape(-1)].set(
        jnp.tile(x, (k, 1)), mode="drop"
    )
    return queues[:-1].reshape(num_experts, capacity, d)


def gather_dispatch(x: jnp.ndarray, plan: RoutePlan, num_experts: int,
                    capacity: int) -> jnp.ndarray:
    """Token rows into queues via an id-scatter + row GATHER.

    :func:`scatter_dispatch` scatters t*k d-wide rows; here only t*k
    int32 token ids are scattered (into a (e*cap,) slot->token map) and
    the queue rows are then one contiguous gather — trading the
    random-access pattern from the wide write to the narrow one, which
    is the cheaper side on TPU.  Empty/dropped slots map to a zero pad
    row.  Numerics identical to both other backends.
    """
    t, d = x.shape
    k = plan.chosen.shape[1]
    dump = num_experts * capacity  # dropped routes land here
    dest = jnp.where(
        plan.keep, plan.chosen * capacity + plan.slot, dump
    )  # (t, k)
    ids = jnp.full((num_experts * capacity + 1,), t, jnp.int32)
    token_ids = jnp.tile(jnp.arange(t, dtype=jnp.int32), (k,))
    ids = ids.at[dest.T.reshape(-1)].set(token_ids, mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    return x_pad[ids[:-1]].reshape(num_experts, capacity, d)


def scatter_combine(out: jnp.ndarray, plan: RoutePlan,
                    capacity: int) -> jnp.ndarray:
    """Gather each token's surviving expert outputs and gate-sum them —
    the transpose of :func:`scatter_dispatch` (O(t*k*d))."""
    e, cap, d = out.shape
    flat = jnp.concatenate(
        [out.reshape(e * cap, d), jnp.zeros((1, d), out.dtype)]
    )
    dump = e * cap
    dest = jnp.where(plan.keep, plan.chosen * capacity + plan.slot, dump)
    y = jnp.zeros((plan.chosen.shape[0], d), out.dtype)
    for j in range(plan.chosen.shape[1]):
        rows = flat[dest[:, j]]
        w = (plan.gates[:, j] * plan.keep[:, j]).astype(out.dtype)
        y = y + rows * w[:, None]
    return y


def load_balancing_loss(probs: jnp.ndarray,
                        raw_routes: jnp.ndarray,
                        axes=None) -> jnp.ndarray:
    """Switch-style auxiliary loss: num_experts * <fraction routed to e> ·
    <mean router prob of e>, minimized at uniform routing.

    ``raw_routes`` must be the *pre-capacity* route indicator from
    :func:`top_k_routing`: counting only surviving dispatches would make a
    collapsed router score *better* once its queue overflows (dropped
    claims would vanish from the fraction).

    ``axes``: mesh axes to average the *statistics* (per-expert routed
    fraction and mean router probability) over before forming the
    product.  Averaging statistics — not per-slice losses — makes the
    result exactly the whole-population loss, i.e. invariant to how
    tokens are split across those axes (a mean of per-slice products
    would not be).  Token counts per shard must be equal (they are, on a
    mesh).  ``None`` computes the local-slice loss.
    """
    e = probs.shape[-1]
    routes_per_tok = jnp.sum(raw_routes) / raw_routes.shape[0]
    frac_raw = jnp.mean(raw_routes, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    if axes:
        routes_per_tok = lax.pmean(routes_per_tok, axes)
        frac_raw = lax.pmean(frac_raw, axes)
        mean_prob = lax.pmean(mean_prob, axes)
    frac = frac_raw / jnp.maximum(routes_per_tok, 1.0)
    return e * jnp.sum(frac * mean_prob)


def sequence_balancing_loss(probs: jnp.ndarray, raw_routes: jnp.ndarray,
                            sequences: int, axes=None) -> jnp.ndarray:
    """:func:`load_balancing_loss` counted a sequence (DeepSeek-V3's
    ``seq_aux``, arXiv:2412.19437 eq. 17-20): ``probs`` and
    ``raw_routes (sequences * T, e)`` hold ``sequences`` whole sequences
    of ``T`` rows one after another; the fractions and mean
    probabilities are taken over each sequence's rows, and the products
    are averaged over the sequences.  With one sequence it is
    :func:`load_balancing_loss`; sequences that route differently read
    higher than their batch does.  ``axes``: mesh axes that split the
    batch into whole sequences, equally many a shard; the mean is over
    all of them."""
    e = probs.shape[-1]
    loss = jnp.mean(jax.vmap(load_balancing_loss)(
        probs.reshape(sequences, -1, e),
        raw_routes.reshape(sequences, -1, e)))
    return lax.pmean(loss, axes) if axes else loss


def ep_flow_specs(axis_name: str) -> dict:
    """The MoE layer's sharding declaration for the analysis pass
    (``analysis.shardflow``): tokens arrive sharded over the expert
    axis (each chip routes its own rows), the router is replicated, and
    the stacked expert weights are sharded one block of
    ``num_experts / axis_size`` experts per chip.  Matches the operand
    layout ``expert_parallel_moe`` expects under shard_map — the
    dispatch/return ``all_to_all`` pair is the ONLY communication this
    layout requires, which is exactly what the ``ep_moe_layer`` budget
    pin and the implicit-collective attribution verify."""
    from jax.sharding import PartitionSpec as P

    return {
        "x": P(axis_name),
        "router_w": P(),
        "expert_w1": P(axis_name),
        "expert_w2": P(axis_name),
        "out": P(axis_name),
    }


def expert_parallel_moe(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    expert_fn: Callable[[jnp.ndarray], jnp.ndarray],
    axis_name: str,
    num_experts: int,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    capacity: Optional[int] = None,
    aux_stat_axes=None,
    dispatch_impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One expert-parallel MoE layer.  Call inside ``shard_map``.

    Args:
      x: (tokens, d) this chip's tokens.
      router_w: (d, num_experts) router weights (replicated).
      expert_fn: (local_experts, n*capacity, d) -> same shape — applies
        this chip's experts to their gathered queues (vmapped MLP etc.).
      axis_name: mesh axis the experts are sharded over.
      num_experts: total experts; divisible by the axis size.
      aux_stat_axes: mesh axes over which the load-balancing *statistics*
        are averaged before forming the loss (see
        :func:`load_balancing_loss`).  Defaults to ``(axis_name,)``;
        pass every token-splitting axis (data/seq/expert) to make the
        aux loss exactly the global-batch value, invariant to mesh
        factorization.
      dispatch_impl: 'einsum' (dense one-hot masks, exact GShard
        formulation), 'scatter' (collision-free scatter/gather,
        O(t*k*d) instead of O(t*e*cap*d) — identical numerics), or
        'auto' (scatter once the dense masks would be large).
    Returns:
      (y, aux_loss): y (tokens, d) combined expert outputs (dropped tokens
      get zeros — add the residual outside); aux_loss the load-balancing
      scalar (identical on every chip of the stat axes).
    """
    n = lax.axis_size(axis_name)
    if num_experts % n:
        raise ValueError(
            f"num_experts ({num_experts}) must be divisible by the "
            f"'{axis_name}' axis size ({n})"
        )
    local_e = num_experts // n
    t, d = x.shape
    cap = capacity if capacity is not None else compute_capacity(
        t, num_experts, k, capacity_factor
    )

    probs = jax.nn.softmax(
        jnp.asarray(x, jnp.float32) @ jnp.asarray(router_w, jnp.float32),
        axis=-1,
    )
    plan = route_plan(probs, k, cap)
    stat_axes = (axis_name,) if aux_stat_axes is None else tuple(
        aux_stat_axes
    )
    aux = load_balancing_loss(probs, plan.raw_routes, axes=stat_axes)

    impl = resolve_dispatch_impl(dispatch_impl, t, num_experts, cap)
    # Local queues: (num_experts, cap, d)
    dispatched = dispatch_to_queues(x, plan, num_experts, cap, impl)
    # To expert owners: split expert dim over chips, gather token sources.
    # (n, local_e, cap, d) -all_to_all-> every chip: its experts' queues
    # from all chips, concatenated along a new source axis.
    dispatched = dispatched.reshape(n, local_e, cap, d)
    gathered = lax.all_to_all(dispatched, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    # gathered: (n_src, local_e, cap, d) -> (local_e, n_src*cap, d)
    gathered = gathered.transpose(1, 0, 2, 3).reshape(local_e, n * cap, d)

    out = expert_fn(gathered)

    # Return trip: transpose the exchange.
    out = out.reshape(local_e, n, cap, d).transpose(1, 0, 2, 3)
    returned = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    returned = returned.reshape(num_experts, cap, d)
    y = combine_from_queues(returned, plan, num_experts, cap, impl)
    return y.astype(x.dtype), aux


def resolve_dispatch_impl(impl: str, t: int, num_experts: int,
                          cap: int) -> str:
    """'auto' picks scatter once the dense one-hot dispatch would cost
    more than ~1M mask elements per feature (t*e*cap) — past that the
    einsum against a one-hot operand dominates the layer's FLOPs."""
    if impl == "auto":
        return "scatter" if t * num_experts * cap >= (1 << 20) else "einsum"
    if impl not in ("einsum", "scatter", "gather"):
        raise ValueError(
            f"dispatch_impl must be 'auto', 'einsum', 'scatter' or "
            f"'gather'; got {impl!r}"
        )
    return impl


def dispatch_to_queues(x: jnp.ndarray, plan: RoutePlan, num_experts: int,
                       capacity: int, impl: str) -> jnp.ndarray:
    """(tokens, d) -> (num_experts, capacity, d) queues via the resolved
    backend ('einsum' | 'scatter' — see :func:`resolve_dispatch_impl`)."""
    if impl == "einsum":
        dispatch, _ = _dense_masks(plan, num_experts, capacity, x.dtype)
        return jnp.einsum("td,tec->ecd", x, dispatch)
    if impl == "gather":
        return gather_dispatch(x, plan, num_experts, capacity)
    return scatter_dispatch(x, plan, num_experts, capacity)


def combine_from_queues(out: jnp.ndarray, plan: RoutePlan,
                        num_experts: int, capacity: int,
                        impl: str) -> jnp.ndarray:
    """(num_experts, capacity, d) expert outputs -> (tokens, d)
    gate-weighted combination, transpose of :func:`dispatch_to_queues`
    (the einsum branch's masks CSE with the dispatch side's)."""
    if impl == "einsum":
        _, combine = _dense_masks(plan, num_experts, capacity, out.dtype)
        return jnp.einsum("ecd,tec->td", out, combine)
    return scatter_combine(out, plan, capacity)


def mlp_experts(w1: jnp.ndarray, w2: jnp.ndarray,
                activation: Callable = jax.nn.gelu) -> Callable:
    """Build an ``expert_fn`` from per-chip expert MLP weights.

    w1: (local_experts, d, hidden); w2: (local_experts, hidden, d).
    The returned fn is one batched einsum pair — (experts, tokens, d) x
    (experts, d, h): MXU-tiled per expert.
    """

    def fn(x):
        h = activation(jnp.einsum("etd,edh->eth", x, w1.astype(x.dtype)))
        return jnp.einsum("eth,ehd->etd", h, w2.astype(x.dtype))

    return fn


# ----------------------------------------------------------------------
# Routing without drops over the experts a chip holds
# ----------------------------------------------------------------------
# The capacity queues above lose a route when an expert's queue is full.
# The layer below loses none: it is told which experts it holds
# (``first``, ``count`` of ``num_experts``), routes over all of them,
# and computes every route that lands on a held expert, at any
# imbalance.  Routes to experts held elsewhere add nothing here: under
# expert parallelism another chip computes them, and the sum over the
# chips (a ``psum`` by the caller) is the whole layer.
#
# The held routes are sorted by expert into one buffer of row blocks,
# each expert's run padded to whole blocks (at least one), and the
# gated experts run over it as three grouped products
# (``ops.grouped_matmul``: a static grid, the same work every step
# whatever the routing was).  The buffer is sized for
# ``HELD_BUFFER_FACTOR`` times the balanced load; a step whose held
# routes need more blocks takes the exact dense path instead (every
# held expert over every token, weighted by its gate), slower and still
# without a drop.
# Dispatch and combine are gathers in both directions (their custom
# gradients gather too): no scatter meets a collision.  A layer moves
# rows four times (``held_rows_moved`` counts them).  Token rows into
# the buffer, dispatch's forward and combine's backward: one gather of
# the buffer's rows each.  Buffer rows back to token rows, combine's
# forward and dispatch's backward (``sum_rows_by_token``, gate-weighted
# and with weight 1): a gather of ``tokens`` rows a route, most of them
# the zero of a route held elsewhere, out of the buffer, the larger
# operand; it is cut into column pieces that XLA keeps in on-chip
# memory, where a gathered row costs a tenth of what it does from HBM.


class HeldRoutes(NamedTuple):
    """Where each route went.  ``row (tokens, k)``: the buffer row of a
    route, ``rows`` (one past the buffer) for a route not computed on
    the fast path; ``route_of_row (rows,)``: the flat route ``token * k
    + j`` a buffer row holds, ``tokens * k`` for padding;
    ``block_group``: the held expert of each row block; ``overflow``:
    the held routes need more blocks than the buffer has."""

    row: jnp.ndarray
    route_of_row: jnp.ndarray
    block_group: jnp.ndarray
    overflow: jnp.ndarray
    routed: jnp.ndarray


#: rows of a block of the sorted buffer (the grouped products' row tile)
HELD_BLOCK_ROWS = 256
#: the buffer holds this multiple of the held experts' balanced load
HELD_BUFFER_FACTOR = 2.0


def held_buffer_blocks(tokens: int, num_experts: int, k: int,
                       count: int) -> int:
    """Row blocks of the sorted buffer: ``HELD_BUFFER_FACTOR`` times the
    balanced load of the held experts, plus one block an expert for the
    padding of its run."""
    balanced = tokens * k * count / num_experts
    return int(math.ceil(
        HELD_BUFFER_FACTOR * balanced / HELD_BLOCK_ROWS)) + count


#: XLA:TPU (libtpu 0.0.34) gathers rows at 3-4 ns a row from an operand
#: it keeps in the chip's 128 MiB of on-chip memory, at 13-15 ns from one
#: under 128 MiB left in HBM and at 34-38 ns from a larger one, whatever
#: the indices are (PERF.md, PR 36).  In a whole train step it kept
#: pieces of 36 MiB there and not pieces of 72: half of that memory is
#: the most an operand may take, so that the next can be staged beside it
GATHER_OPERAND_BYTES = 64 << 20


def gather_column_pieces(rows: int, width: int, itemsize: int) -> int:
    """Into how many column pieces (a power of two, each a multiple of
    128 columns) a ``(rows, width)`` gather operand is cut so that a
    piece is at most ``GATHER_OPERAND_BYTES``; 1 where the whole is."""
    pieces = 1
    while (rows * (width // pieces) * itemsize > GATHER_OPERAND_BYTES
           and width % (256 * pieces) == 0):
        pieces *= 2
    return pieces


def held_rows_moved(tokens: int, num_experts: int, k: int, count: int,
                    width: int, itemsize: int = 2) -> dict:
    """What a layer's four movements of rows gather, forward and
    backward, from the Python ints the layer itself works from (static,
    not traced): ``rows`` gathered and ``gathers`` made by movement,
    the buffer's ``buffer_rows``, and the column ``pieces`` of
    ``piece_bytes`` the buffer is gathered from
    (:func:`sum_rows_by_token`)."""
    rows = held_buffer_blocks(tokens, num_experts, k, count) \
        * HELD_BLOCK_ROWS
    pieces = gather_column_pieces(rows, width, itemsize)
    return {
        "buffer_rows": rows, "pieces": pieces,
        "piece_bytes": rows * (width // pieces) * itemsize,
        "rows": {"dispatch": rows, "combine": tokens * k,
                 "combine_backward": rows,
                 "dispatch_backward": tokens * k},
        "gathers": {"dispatch": 1, "combine": k * pieces,
                    "combine_backward": 1,
                    "dispatch_backward": k * pieces},
    }


def plan_held_routes(chosen: jnp.ndarray, first, count: int,
                     block_rows: int, n_blocks: int) -> HeldRoutes:
    """Lay the routes ``chosen (tokens, k)`` that land on experts
    ``first .. first + count - 1`` out in a buffer of ``n_blocks`` row
    blocks, sorted by expert, token order within an expert."""
    t, k = chosen.shape
    rows = n_blocks * block_rows
    local = (chosen - first).reshape(-1)
    held = (local >= 0) & (local < count)
    onehot = ((local[:, None] == jnp.arange(count)[None, :])
              & held[:, None]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    per_expert = jnp.sum(onehot, axis=0)
    blocks = jnp.maximum(-(-per_expert // block_rows), 1)
    first_block = jnp.cumsum(blocks) - blocks
    overflow = jnp.sum(blocks) > n_blocks
    safe = jnp.clip(local, 0, count - 1)
    row = jnp.where(held, first_block[safe] * block_rows + rank, rows)
    row = jnp.minimum(row, rows)  # an overflowing plan is not run
    block_group = jnp.clip(
        jnp.sum(jnp.arange(n_blocks)[:, None] >= first_block[None, :],
                axis=-1) - 1, 0, count - 1).astype(jnp.int32)
    # the route a buffer row holds: the held routes in (expert, flat
    # route) order are a stable sort's; row r of expert e's run is the
    # r-th of them
    order = jnp.argsort(jnp.where(held, local, count), stable=True)
    run_start = jnp.cumsum(per_expert) - per_expert
    r = jnp.arange(rows)
    e = block_group[r // block_rows]
    within = r - first_block[e] * block_rows
    valid = within < per_expert[e]
    route_of_row = jnp.where(
        valid, order[jnp.clip(run_start[e] + within, 0, t * k - 1)], t * k)
    return HeldRoutes(row.reshape(t, k), route_of_row.astype(jnp.int32),
                      block_group, overflow, jnp.sum(per_expert))


def _pad_row(x):
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])


def _take_rows(a, index):
    """``a[index]`` for an in-bounds ``index (n,)``: the gather itself,
    without what NumPy-style indexing traces around it (a step traces
    this 256 times)."""
    return lax.gather(
        a, index[:, None],
        lax.GatherDimensionNumbers(offset_dims=(1,),
                                   collapsed_slice_dims=(0,),
                                   start_index_map=(0,)),
        (1, a.shape[1]), mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def sum_rows_by_token(rows_of, weights, plan: HeldRoutes):
    """The movement from buffer rows to token rows: ``y[p] = sum_j
    weights[p, j] * rows_of[plan.row[p, j]]`` (``weights`` None: 1), a
    route without a row adding nothing; float32 sum in ``j`` order,
    rounded once to ``rows_of``'s dtype.  One gather of ``tokens`` rows
    a route and column piece (:func:`gather_column_pieces`: what is
    static here picks the number, no argument does; one gather of all
    routes' rows a piece is not kept on chip and costs twice today's)."""
    t, k = plan.row.shape
    rows, d = rows_of.shape
    # by route: index clipped into the buffer, rows of routes without a
    # buffer row masked out (no zero row is appended: no copy for it)
    index = jnp.minimum(plan.row, rows - 1).T
    computed = (plan.row < rows).T[:, :, None]
    gate = None if weights is None else weights.T[:, :, None]
    pieces = gather_column_pieces(rows, d, rows_of.dtype.itemsize)
    width = d // pieces
    parts = []
    for c in range(pieces):
        piece = rows_of[:, c * width:(c + 1) * width]
        y = jnp.zeros((t, width), jnp.float32)
        for j in range(k):
            v = jnp.where(computed[j],
                          _take_rows(piece, index[j]).astype(jnp.float32),
                          0.0)
            y = y + (v if gate is None else gate[j] * v)
        parts.append(y.astype(rows_of.dtype))
    return parts[0] if pieces == 1 else jnp.concatenate(parts, axis=1)


@jax.custom_vjp
def dispatch_rows(x, plan: HeldRoutes):
    """Token rows ``x (tokens, d)`` into the sorted buffer ``(rows,
    d)``; padding rows are zero.  Its gradient is the movement back,
    :func:`sum_rows_by_token` with weight 1."""
    k = plan.row.shape[1]
    return _pad_row(x)[plan.route_of_row // k]


def _dispatch_fwd(x, plan):
    return dispatch_rows(x, plan), (plan, x[:0])


def _dispatch_bwd(residuals, g):
    plan, like = residuals
    return sum_to_vma(sum_rows_by_token(g, None, plan), like), None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(out, weights, plan: HeldRoutes):
    """``y[p] = sum_j weights[p, j] * out[row of route (p, j)]``: each
    token's computed routes, gate-weighted (float32 sum, ``out``'s
    dtype); a route without a row adds nothing.  Its gradient gathers
    ``dy`` into the buffer's rows (``d_out``, and ``d_weights`` from
    their products with ``out``)."""
    return sum_rows_by_token(out, weights, plan)


def _combine_fwd(out, weights, plan):
    return combine_rows(out, weights, plan), (out, weights, plan)


def _combine_bwd(residuals, dy):
    out, weights, plan = residuals
    k = weights.shape[1]
    w_of_row = _pad_row(weights.reshape(-1))[plan.route_of_row]
    dy_rows = _pad_row(dy)[plan.route_of_row // k].astype(jnp.float32)
    d_out = (w_of_row[:, None] * dy_rows).astype(out.dtype)
    dots = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
    d_weights = _pad_row(dots)[plan.row].astype(weights.dtype)
    return sum_to_vma(d_out, out), sum_to_vma(d_weights, weights), None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


#: device scopes (``jax.named_scope``) of the two halves of the layer
MOE_ROUTE_SCOPE = "moe_route"
MOE_EXPERTS_SCOPE = "moe_experts"


def held_experts_moe(x, router_w, w_gate, w_up, w_down, *,
                     num_experts: int, k: int, first=0,
                     aux_stat_axes=None, score: str = "softmax",
                     selection_bias=None, routed_scale: float = 1.0,
                     aux_sequences: Optional[int] = None):
    """One chip's part of a dropless mixture-of-experts layer.

    ``x (tokens, d)``; ``router_w (d, num_experts)``; ``w_gate`` /
    ``w_up (count, d, f)`` and ``w_down (count, f, d)``: the SiLU-gated
    experts ``first .. first + count - 1`` held here, ``W_d(silu(W_g x)
    * W_u x)``.  The router's scores are float32 over all
    ``num_experts``, a softmax or (``score="sigmoid"``) a sigmoid an
    expert; the top ``k`` are kept with weights renormalised over them
    (a sigmoid's with 1e-20 under the sum) and multiplied by
    ``routed_scale``.  ``selection_bias (num_experts,)``: added to the
    scores for the choice of the ``k`` alone, outside the weights and
    outside the gradient.  Returns ``(y, aux, counters, chosen)``: the
    held experts' part of the result; the load-balancing loss over all
    ``num_experts`` (:func:`load_balancing_loss`, on the scores over
    their sum where they are sigmoids; with ``aux_sequences``, the
    whole sequences ``x`` holds one after another,
    :func:`sequence_balancing_loss`); and ``moe_rows_routed`` (routes
    to held experts), ``moe_rows_computed`` (rows the expert products
    ran over), ``moe_dropped`` (held routes no path computed: always 0)
    and, under a bias, ``moe_routes_biased`` (routes, of all ``tokens x
    k``, to an expert that is not among the ``k`` best scores: what the
    bias changed), each an int32 scalar."""
    t, d = x.shape
    count = w_gate.shape[0]
    block_rows = HELD_BLOCK_ROWS
    n_blocks = held_buffer_blocks(t, num_experts, k, count)
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score must be softmax or sigmoid, got {score!r}")
    biased = None
    with jax.named_scope(MOE_ROUTE_SCOPE):
        logits = jnp.einsum(
            "td,de->te", x.astype(jnp.float32),
            router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST)
        if score == "softmax":
            probs = scores = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        if selection_bias is None:
            top, chosen = lax.top_k(scores, k)
        else:
            _, chosen = lax.top_k(
                scores + lax.stop_gradient(selection_bias), k)
            top = jnp.take_along_axis(scores, chosen, axis=-1)
            # a route whose score k others or more beat
            better = jnp.sum(scores[:, None, :] > top[:, :, None], axis=-1)
            biased = jnp.sum(better >= k).astype(jnp.int32)
        total = jnp.sum(top, axis=-1, keepdims=True)
        if score == "sigmoid":
            total = total + 1e-20
        weights = top / total
        if routed_scale != 1.0:
            weights = weights * routed_scale
        raw_routes = jnp.sum(
            jax.nn.one_hot(chosen, num_experts, dtype=probs.dtype), axis=1)
        aux = load_balancing_loss(probs, raw_routes, axes=aux_stat_axes) \
            if aux_sequences is None else sequence_balancing_loss(
                probs, raw_routes, aux_sequences, axes=aux_stat_axes)
        plan = plan_held_routes(chosen, first, count, block_rows, n_blocks)
    def fast(x, weights, w_gate, w_up, w_down):
        with jax.named_scope(MOE_ROUTE_SCOPE):
            rows = dispatch_rows(x, plan)
        with jax.named_scope(MOE_EXPERTS_SCOPE):
            gm = lambda a, w: grouped_matmul(a, w, plan.block_group,
                                             block_rows)
            # float32 inside, recomputed in the backward pass
            gated = jax.checkpoint(lambda g, u: (
                jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
            ).astype(x.dtype))
            out = gm(gated(gm(rows, w_gate), gm(rows, w_up)), w_down)
        with jax.named_scope(MOE_ROUTE_SCOPE):
            return combine_rows(out, weights, plan)

    def exact(x, weights, w_gate, w_up, w_down):
        local = chosen - first

        # recomputed in the backward pass from the layer's inputs: the
        # untaken branch of the ``cond`` then keeps no buffer of its
        # own (a scan's saved intermediates are allocated either way)
        @jax.checkpoint
        def term(x, weights, wg, wu, wd, e):
            gate = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)
            mm = lambda a, w: jnp.dot(
                a, w.astype(a.dtype), preferred_element_type=jnp.float32)
            hidden = (jax.nn.silu(mm(x, wg)) * mm(x, wu)).astype(x.dtype)
            return gate[:, None] * mm(hidden, wd)

        def one(y, ws):
            return y + term(x, weights, *ws), None

        zero = jnp.zeros((t, d), jnp.float32)
        vma = frozenset().union(*(jax.typeof(a).vma for a in (
            x, weights, w_gate, w_up, w_down)))
        if vma:  # under shard_map the carry varies as its terms do
            zero = lax.pcast(zero, tuple(vma), to="varying")
        with jax.named_scope(MOE_EXPERTS_SCOPE):
            y, _ = lax.scan(one, zero,
                            (w_gate, w_up, w_down, jnp.arange(count)))
        return y.astype(x.dtype)

    y = lax.cond(plan.overflow, exact, fast, x, weights, w_gate, w_up,
                 w_down)
    on_fast = jnp.sum(plan.row < n_blocks * block_rows).astype(jnp.int32)
    counters = {
        "moe_rows_routed": plan.routed.astype(jnp.int32),
        "moe_rows_computed": jnp.where(
            plan.overflow, count * t, n_blocks * block_rows
        ).astype(jnp.int32),
        "moe_dropped": jnp.where(
            plan.overflow, 0, plan.routed.astype(jnp.int32) - on_fast),
    }
    if biased is not None:
        counters["moe_routes_biased"] = biased
    return y, aux, counters, chosen
