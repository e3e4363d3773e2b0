"""Tensor (operator) parallel layers.

Reference parity: the reference ships no ready-made sharded layers — its
docs show hand-building "parallel convolution"-style layers from the
collective functions (SURVEY.md section 2, row MP/TP).  These are those
patterns productized: Megatron-style column/row-parallel Dense pairs whose
collectives ride the ``axis_name`` mesh axis inside ``shard_map``.

ColumnParallelDense: Y = X @ [W1 | W2 | ...] — each chip holds a column
block; outputs are feature-sharded (no comm in forward;
``gather_output=True`` all-gathers).

RowParallelDense: Y = sum_i X_i @ W_i — inputs feature-sharded, one psum
in forward.  The canonical MLP block is Column(gather=False) -> activation
-> Row(): exactly one all-reduce per MLP, the Megatron recipe.

Under plain ``jit`` + GSPMD, prefer annotating an ordinary Dense's kernel
with ``PartitionSpec(None, 'tp')`` and letting the partitioner insert the
same collectives; these explicit modules are for shard_map-style code and
for teaching the cost model.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ..observability.timeline import phased as _phased


class ColumnParallelDense(nn.Module):
    """Dense whose output features are sharded across ``axis_name``.

    ``features`` is the *global* output width; each chip materializes
    ``features / axis_size`` columns.
    """

    features: int
    axis_name: str = "tp"
    use_bias: bool = True
    gather_output: bool = False
    dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        n = lax.axis_size(self.axis_name)
        if self.features % n:
            raise ValueError(
                f"features ({self.features}) not divisible by tp size {n}"
            )
        local = self.features // n
        # Per-chip init: fold the chip index into the RNG so column blocks
        # are independent draws (matches a sharded global init).
        kernel = self.param(
            "kernel", _sharded_init(self.kernel_init, self.axis_name),
            (x.shape[-1], local), jnp.float32,
        )
        y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (local,), jnp.float32
            )
            y = y + bias.astype(self.dtype)
        if self.gather_output:
            y = lax.all_gather(y, self.axis_name, axis=y.ndim - 1,
                               tiled=True)
        return y


class RowParallelDense(nn.Module):
    """Dense whose input features are sharded across ``axis_name``; the
    partial products are psum-reduced (one allreduce)."""

    features: int
    axis_name: str = "tp"
    use_bias: bool = True
    dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", _sharded_init(self.kernel_init, self.axis_name),
            (x.shape[-1], self.features), jnp.float32,
        )
        partial = x.astype(self.dtype) @ kernel.astype(self.dtype)
        y = lax.psum(partial, self.axis_name)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.features,), jnp.float32
            )
            y = y + bias.astype(self.dtype)
        return y


# Extra markers the spec-derivation helpers recognize as column-/row-
# parallel owners, matched against a flax path segment EXACTLY.
# "ColumnParallel"/"RowParallel" always match as substrings (covering
# every auto-generated name like "ColumnParallelDense_0" — which is why
# the in-repo transformer modules deliberately do NOT rename their TP
# projections).  Users who do pass ``name=`` can register those names
# here; exact matching keeps an unrelated module named e.g. "audio_proj"
# from being silently mis-sharded.  Or build the spec tree by hand — it
# is plain data.
COLUMN_PARALLEL_NAMES: tuple = ()
ROW_PARALLEL_NAMES: tuple = ()
VOCAB_PARALLEL_NAMES: tuple = ()


def _path_keys(path):
    keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    return [k for k in keys if isinstance(k, str)]


def _tp_owner_kind(keys) -> Optional[str]:
    """'col' / 'row' / 'vocab' / None for a flax param path, innermost
    match wins."""
    for k in reversed(keys):
        if "ColumnParallel" in k or k in COLUMN_PARALLEL_NAMES:
            return "col"
        if "RowParallel" in k or k in ROW_PARALLEL_NAMES:
            return "row"
        if "VocabParallel" in k or k in VOCAB_PARALLEL_NAMES:
            return "vocab"
    return None


class VocabParallelEmbed(nn.Module):
    """Embedding table sharded over the vocab dimension (Megatron's
    VocabParallelEmbedding): chip ``i`` holds rows
    ``[i*V/n, (i+1)*V/n)``.  Lookup masks out-of-range tokens locally and
    psums the partial embeddings — one allreduce; ``attend(x)`` is the
    weight-tied output head, returning the LOCAL vocab block's logits
    (feed them to :func:`vocab_parallel_cross_entropy` / the model-level
    ``vp_lm_loss``, which never materialize the full-vocab row)."""

    vocab_size: int
    features: int
    axis_name: str = "tp"
    dtype: Any = jnp.float32
    embedding_init: Callable = nn.initializers.normal(0.02)

    def setup(self):
        n = lax.axis_size(self.axis_name)
        if self.vocab_size % n:
            raise ValueError(
                f"vocab_size ({self.vocab_size}) not divisible by the "
                f"'{self.axis_name}' axis size ({n})"
            )
        self.embedding = self.param(
            "embedding",
            _sharded_init(self.embedding_init, self.axis_name),
            (self.vocab_size // n, self.features), jnp.float32,
        )

    def _range(self):
        local_v = self.embedding.shape[0]
        start = lax.axis_index(self.axis_name) * local_v
        return start, local_v

    def __call__(self, tokens):
        start, local_v = self._range()
        local = tokens - start
        in_range = (local >= 0) & (local < local_v)
        safe = jnp.clip(local, 0, local_v - 1)
        out = jnp.take(self.embedding, safe, axis=0)
        out = jnp.where(in_range[..., None], out, 0)
        return lax.psum(out.astype(self.dtype), self.axis_name)

    def attend(self, x):
        """(..., features) -> (..., local_vocab) logits against this
        chip's vocab block (the tied head; no collective here)."""
        return x @ self.embedding.T.astype(x.dtype)


def vocab_parallel_cross_entropy(logits_local: jnp.ndarray,
                                 targets: jnp.ndarray,
                                 axis_name: str) -> jnp.ndarray:
    """Per-position cross entropy from vocab-sharded logits.

    ``logits_local``: (..., V/n) — this chip's vocab block;
    ``targets``: (...) global token ids.  The softmax statistics are
    assembled with one pmax and two psums; the (..., V) full-vocab row
    never exists on any chip (Megatron's parallel cross entropy).
    """
    local_v = logits_local.shape[-1]
    start = lax.axis_index(axis_name) * local_v
    logits_f = logits_local.astype(jnp.float32)
    # the max is a pure numerical-stability shift (lse is exactly
    # invariant to it), so stopping its gradient is exact — and pmax has
    # no differentiation rule anyway
    m = lax.pmax(
        lax.stop_gradient(jnp.max(logits_f, axis=-1)), axis_name
    )
    z = lax.psum(
        jnp.sum(jnp.exp(logits_f - m[..., None]), axis=-1), axis_name
    )
    lse = m + jnp.log(z)
    local_t = targets - start
    in_range = (local_t >= 0) & (local_t < local_v)
    safe = jnp.clip(local_t, 0, local_v - 1)
    picked = jnp.take_along_axis(
        logits_f, safe[..., None], axis=-1
    )[..., 0]
    target_logit = lax.psum(jnp.where(in_range, picked, 0.0), axis_name)
    return lse - target_logit


def _tp_leaf_spec(keys, model_axis):
    """The Megatron sharding convention for one flax param path, or None
    when the leaf belongs to no column/row-parallel owner: column kernels
    shard output features (``P(None, axis)``, bias ``P(axis)``), row
    kernels shard input features (``P(axis, None)``, bias replicated)."""
    from jax.sharding import PartitionSpec as P

    last = keys[-1] if keys else ""
    kind = _tp_owner_kind(keys)
    if kind == "col":
        return P(None, model_axis) if last == "kernel" else P(model_axis)
    if kind == "row":
        return P(model_axis, None) if last == "kernel" else P()
    if kind == "vocab":
        return P(model_axis, None) if last == "embedding" else P()
    return None


def megatron_param_specs(params, model_axis: str = "tp"):
    """Derive the ``param_specs`` pytree for ``build_train_step``'s hybrid
    DP x TP mode from a parameter tree containing Column/RowParallelDense
    modules (auto-generated names matched by substring, plus the exact
    path segments in ``COLUMN_PARALLEL_NAMES`` / ``ROW_PARALLEL_NAMES``).

    Column kernels shard their output features (``P(None, axis)``, bias
    ``P(axis)``); Row kernels shard their input features
    (``P(axis, None)``, bias replicated); VocabParallelEmbed tables shard
    their vocab rows (``P(axis, None)``); everything else replicates.
    For custom-named modules, register the name in the ``*_NAMES``
    tuples above or build the spec tree by hand — it is plain data.
    """
    from jax.sharding import PartitionSpec as P
    import jax.tree_util as jtu

    def leaf_spec(path, leaf):
        spec = _tp_leaf_spec(_path_keys(path), model_axis)
        return P() if spec is None else spec

    return jtu.tree_map_with_path(leaf_spec, params)


def tp_flow_specs(params, model_axis: str = "tp",
                  batch_spec=None) -> dict:
    """The tensor-parallel step's sharding declaration for the analysis
    pass (``analysis.shardflow``): the Megatron param layout
    (:func:`megatron_param_specs`) bundled with the activation/batch
    layout so the sharding-flow pass can seed a hybrid DP x TP step's
    invars in one call.  Activations between TP blocks are replicated
    along features by construction (Column(gather=False) -> Row ends in
    its psum), which is why a correctly-composed Megatron block adds no
    partitioner-inserted collectives — the attribution check's
    invariant for this layout."""
    from jax.sharding import PartitionSpec as P

    return {
        "params": megatron_param_specs(params, model_axis),
        "batch": P() if batch_spec is None else batch_spec,
        "out": P(),
    }


@_phased("setup.init_params")
def sharded_init(init_fn: Callable, mesh, in_specs, param_specs_fn,
                 *args):
    """Initialize a model whose parameters live sharded on ``mesh``.

    Runs ``init_fn(*args) -> params`` per-shard under ``shard_map`` twice:
    once abstractly (``eval_shape``) to discover the parameter tree, once
    for real with ``out_specs = param_specs_fn(abstract_params)`` so
    sharded leaves (TP kernels, expert blocks) assemble into global arrays
    while replicated leaves stay replicated.  Returns ``(params, specs)``
    — feed both to ``build_train_step(param_specs=specs)``.

    ``in_specs``: PartitionSpec(s) for ``*args`` (e.g. the sample batch's
    layout).  ``init_fn`` typically closes over the module and RNG key:
    ``lambda x: model.init(jax.random.PRNGKey(0), x)``.
    """
    from jax.sharding import PartitionSpec as P

    abstract = jax.eval_shape(
        jax.shard_map(
            init_fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,
        ),
        *args,
    )
    specs = param_specs_fn(abstract)
    params = jax.jit(
        jax.shard_map(
            init_fn, mesh=mesh, in_specs=in_specs, out_specs=specs,
            check_vma=False,
        )
    )(*args)
    return params, specs


def _sharded_init(init: Callable, axis_name: str) -> Callable:
    """Make an initializer draw a different block per chip (fold the axis
    index into the key) while staying deterministic per chip."""

    def wrapped(key, shape, dtype=jnp.float32):
        try:
            idx = lax.axis_index(axis_name)
            key = jax.random.fold_in(key, idx)
        except NameError:
            pass  # single-device init outside shard_map
        return init(key, shape, dtype)

    return wrapped
