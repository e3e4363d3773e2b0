"""Sharding-flow pass: propagate PartitionSpecs through a closed jaxpr.

The jaxpr walker (:mod:`.trace`) sees every collective the *author*
wrote; the XLA SPMD partitioner can still insert all-gathers and
reshards behind our backs whenever the shardings flowing into an
equation don't line up (a sharded operand feeding a replicated-output
dot, two operands sharded along different axes, a declared output
sharding the natural result layout doesn't match).  Those inserted
collectives never appear in the jaxpr, so the trace census under-counts
the wire — silently, which is how an accidental resharding all-gather
eats bandwidth for months.

This module closes the gap statically: :func:`shardflow` seeds the
jaxpr's invars with the program's input PartitionSpecs and propagates
them equation by equation, descending — like the trace walker — into
``pjit`` calls and into ``scan``/``cond``/``while`` bodies (consts and
carries pass through, stacked scan inputs lose their leading dim,
loop-carried layouts must be iteration-stable to stay known;
``shard_map`` regions are manual — their collectives are authored and
already traced, so the flow takes their declared ``out_names`` and
moves on).  Wherever propagation
finds a layout the partitioner cannot reconcile without communication,
it records a :class:`ReshardSite` — the equation index, primitive, and
``file:line`` of the responsible call, plus the collective class the
partitioner will insert.  ``checks.check_implicit_collectives`` then
joins three artifacts:

* the authored census (trace records),
* the lowered/compiled HLO census (:mod:`.hlo` — the compiled text is
  the authoritative one: GSPMD partitions at compile time),
* this pass's reshard sites,

so every surplus collective in the HLO is either attributed to a cited
equation or flagged as unattributed.

Propagation is deliberately conservative: unknown primitives produce
*unknown* specs, and unknown specs accuse nobody — the pass
under-reports rather than mis-reports, the same contract as the
narrowing-cast audit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax

from .trace import _source_of

# A dim spec is a tuple of mesh axis names sharding that dimension (()
# = unsharded); an array spec is a tuple of dim specs; None = unknown.
DimSpec = Tuple[str, ...]
ArraySpec = Optional[Tuple[DimSpec, ...]]


def canon_spec(spec, ndim: int) -> ArraySpec:
    """A ``PartitionSpec`` (or already-canonical tuple) as a canonical
    per-dimension tuple of axis-name tuples, padded to ``ndim``."""
    if spec is None:
        return None
    parts = tuple(spec)
    out = []
    for i in range(ndim):
        p = parts[i] if i < len(parts) else None
        if p is None:
            out.append(())
        elif isinstance(p, (tuple, list)):
            out.append(tuple(str(a) for a in p))
        else:
            out.append((str(p),))
    return tuple(out)


def _replicated(ndim: int) -> ArraySpec:
    return ((),) * ndim


def _is_sharded(spec: ArraySpec) -> bool:
    return spec is not None and any(spec)


def spec_str(spec: ArraySpec) -> str:
    if spec is None:
        return "?"
    return "P(" + ", ".join(
        "+".join(d) if d else "None" for d in spec
    ) + ")"


@dataclass(frozen=True)
class ReshardSite:
    """One equation where the partitioner must insert communication."""

    # 1-based equation counter in WALK order (top-level and descended
    # sub-jaxpr equations interleaved) — a stable label for findings,
    # not an index into any one eqn list; ``source`` is the
    # authoritative pointer to the responsible call.
    eqn_index: int
    primitive: str
    cls: str                # collective class the partitioner inserts
    note: str               # why (human-readable layout mismatch)
    source: Optional[str]   # file:line of the responsible call

    def citation(self) -> str:
        where = f" [{self.source}]" if self.source else ""
        return (
            f"walk-eqn#{self.eqn_index} {self.primitive}: {self.note} "
            f"(partitioner inserts {self.cls}){where}"
        )


@dataclass(frozen=True)
class ShardFlowReport:
    """Propagated output specs + every reshard site the flow found."""

    label: str
    out_specs: Tuple[ArraySpec, ...]
    reshard_sites: Tuple[ReshardSite, ...]
    n_eqns: int

    def sites_of_class(self, cls: str) -> Tuple[ReshardSite, ...]:
        return tuple(s for s in self.reshard_sites if s.cls == cls)


# primitives whose output follows the (single known) operand layout —
# a closed allowlist of genuinely elementwise ops.  Deliberately NOT a
# shapes-all-equal fallback: a same-shape scan/sort/cumsum is not
# layout-preserving, and fabricating a spec for it would let downstream
# equations be accused of (or excused from) reshards they don't cause —
# unknown primitives must produce unknown specs.
_ELEMENTWISE_HINTS = (
    "add", "add_any", "sub", "mul", "div", "max", "min", "pow", "rem",
    "and", "or", "xor", "not", "neg", "sign", "floor", "ceil", "round",
    "exp", "expm1", "log", "log1p", "tanh", "tan", "sinh", "cosh",
    "asin", "acos", "atan", "asinh", "acosh", "atanh", "logistic",
    "sqrt", "rsqrt", "cbrt", "abs", "cos", "sin", "erf", "erfc",
    "erf_inv", "convert_element_type", "integer_pow", "select_n", "ne",
    "eq", "ge", "gt", "le", "lt", "stop_gradient", "copy", "clamp",
    "is_finite", "nextafter", "real", "imag", "square",
)


class _Flow:
    def __init__(self, label: str):
        self.label = label
        self.sites: list = []
        self._eqn_index = 0  # running index across the whole walk

    # -- env helpers ---------------------------------------------------
    @staticmethod
    def _get(env, v) -> ArraySpec:
        if hasattr(v, "val"):  # Literal: replicated by construction
            return _replicated(getattr(v.val, "ndim", 0))
        return env.get(id(v))

    @staticmethod
    def _set(env, v, spec: ArraySpec) -> None:
        if spec is not None:
            env[id(v)] = spec

    def _site(self, eqn, cls: str, note: str) -> None:
        self.sites.append(ReshardSite(
            eqn_index=self._eqn_index,
            primitive=eqn.primitive.name,
            cls=cls,
            note=note,
            source=_source_of(eqn),
        ))

    # -- the walk ------------------------------------------------------
    def walk(self, jaxpr_like, env: dict) -> dict:
        """Propagate through one (closed) jaxpr; ``env`` maps var ids to
        specs and is updated in place.  Returns the env."""
        inner = getattr(jaxpr_like, "jaxpr", jaxpr_like)
        for cv in inner.constvars:
            env.setdefault(
                id(cv), _replicated(len(getattr(cv.aval, "shape", ())))
            )
        for eqn in inner.eqns:
            self._eqn_index += 1
            self._propagate(eqn, env)
        return env

    def _propagate(self, eqn, env) -> None:
        name = eqn.primitive.name
        in_specs = [self._get(env, v) for v in eqn.invars]

        if name in ("jit", "remat", "remat2", "checkpoint",
                    "custom_jvp_call", "custom_vjp_call",
                    "custom_vjp_call_jaxpr", "closed_call", "core_call"):
            self._descend(eqn, env, in_specs)
            return
        if name == "shard_map":
            self._shard_map_out(eqn, env)
            return
        if name == "scan":
            self._scan(eqn, env, in_specs)
            return
        if name == "cond" and "branches" in eqn.params:
            self._cond(eqn, env, in_specs)
            return
        if name == "while":
            self._while(eqn, env, in_specs)
            return

        out_spec: ArraySpec = None
        known = [s for s in in_specs if s is not None]

        if name == "transpose":
            perm = eqn.params.get("permutation")
            if in_specs and in_specs[0] is not None and perm is not None:
                out_spec = tuple(in_specs[0][p] for p in perm)
        elif name == "broadcast_in_dim":
            dims = eqn.params.get("broadcast_dimensions", ())
            src = in_specs[0] if in_specs else None
            nd = len(getattr(eqn.outvars[0].aval, "shape", ()))
            if src is not None:
                out = [()] * nd
                for i, d in enumerate(dims):
                    if i < len(src):
                        out[d] = src[i]
                out_spec = tuple(out)
        elif name == "reshape":
            src = in_specs[0] if in_specs else None
            in_shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
            if src is not None and not _is_sharded(src):
                out_spec = _replicated(len(out_shape))
            elif src is not None and in_shape == out_shape:
                out_spec = src
        elif name in ("reduce_sum", "reduce_max", "reduce_min",
                      "reduce_prod", "reduce_and", "reduce_or",
                      "argmax", "argmin"):
            src = in_specs[0] if in_specs else None
            axes = tuple(eqn.params.get("axes", ()))
            if src is not None:
                if any(i < len(src) and src[i] for i in axes):
                    self._site(
                        eqn, "all_reduce",
                        "reduction over a sharded dimension — partial "
                        "results must be combined across shards",
                    )
                out_spec = tuple(
                    d for i, d in enumerate(src) if i not in axes
                )
        elif name == "dot_general":
            out_spec = self._dot_general(eqn, env, in_specs)
        elif name in _ELEMENTWISE_HINTS:
            shaped = [
                (s, v) for s, v in zip(in_specs, eqn.invars)
                if s is not None
                and len(getattr(getattr(v, "aval", None), "shape", ()))
                == len(getattr(eqn.outvars[0].aval, "shape", ()))
            ]
            sharded = [(s, v) for s, v in shaped if _is_sharded(s)]
            distinct = {s for s, _ in sharded}
            if len(distinct) > 1:
                a, b = sorted(distinct)[:2]
                self._site(
                    eqn, "all_gather",
                    f"operands carry incompatible shardings "
                    f"{spec_str(a)} vs {spec_str(b)} — one side must be "
                    "resharded",
                )
            if sharded:
                out_spec = sharded[0][0]
            elif shaped:
                out_spec = shaped[0][0]

        for ov in eqn.outvars:
            if type(ov).__name__ == "DropVar":
                continue
            nd = len(getattr(getattr(ov, "aval", None), "shape", ()))
            if out_spec is not None and len(out_spec) == nd:
                self._set(env, ov, out_spec)

    def _descend(self, eqn, env, in_specs) -> None:
        """pjit-style call: positional invar alignment in, outvar
        alignment out (the same exact mapping the trace walker uses)."""
        for val in eqn.params.values():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for sub in subs:
                if not (hasattr(sub, "eqns") or hasattr(sub, "jaxpr")):
                    continue
                inner = getattr(sub, "jaxpr", sub)
                sub_env: dict = {}
                if len(inner.invars) == len(eqn.invars):
                    for iv, s in zip(inner.invars, in_specs):
                        self._set(sub_env, iv, s)
                self.walk(sub, sub_env)
                if len(inner.outvars) == len(eqn.outvars):
                    for sv, ov in zip(inner.outvars, eqn.outvars):
                        self._set(env, ov, self._get(sub_env, sv))
                return  # one callable sub-jaxpr per call eqn

    @staticmethod
    def _join(a: ArraySpec, b: ArraySpec) -> ArraySpec:
        """Specs agree -> the spec; any disagreement or unknown ->
        unknown (conservative: accuse nobody)."""
        return a if a == b else None

    def _walk_sub(self, sub, invar_specs) -> list:
        """Walk one sub-jaxpr with the given invar specs; returns the
        propagated outvar specs."""
        inner = getattr(sub, "jaxpr", sub)
        sub_env: dict = {}
        for iv, s in zip(inner.invars, invar_specs):
            self._set(sub_env, iv, s)
        self.walk(sub, sub_env)
        return [self._get(sub_env, ov) for ov in inner.outvars]

    def _scan(self, eqn, env, in_specs) -> None:
        """scan invars = consts + carry + xs (stacked, leading time
        dim); body sees consts/carry as-is and xs with the leading dim
        sliced off.  Outputs: carry (joined with the incoming carry
        spec — a layout that changes per iteration is unknown, not
        trusted) and ys re-stacked behind an unsharded leading dim."""
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        body = eqn.params.get("jaxpr")
        if body is None:
            return
        body_in = list(in_specs[:n_consts + n_carry]) + [
            (s[1:] if s else s) if s is not None else None
            for s in in_specs[n_consts + n_carry:]
        ]
        outs = self._walk_sub(body, body_in)
        carry_in = in_specs[n_consts:n_consts + n_carry]
        for i, ov in enumerate(eqn.outvars):
            if i < n_carry:
                spec = self._join(
                    carry_in[i] if i < len(carry_in) else None,
                    outs[i] if i < len(outs) else None,
                )
            else:
                y = outs[i] if i < len(outs) else None
                spec = ((),) + y if y is not None else None
            if spec is not None:
                self._set(env, ov, spec)

    def _cond(self, eqn, env, in_specs) -> None:
        """Both branches walked with the operand specs (predicate
        skipped); outputs must agree across branches to be known."""
        branch_outs = [
            self._walk_sub(b, in_specs[1:])
            for b in eqn.params["branches"]
        ]
        for i, ov in enumerate(eqn.outvars):
            specs = [
                outs[i] if i < len(outs) else None
                for outs in branch_outs
            ]
            spec = specs[0]
            for s in specs[1:]:
                spec = self._join(spec, s)
            if spec is not None:
                self._set(env, ov, spec)

    def _while(self, eqn, env, in_specs) -> None:
        """invars = cond_consts + body_consts + carry; each sub-jaxpr
        walked once with its consts + the carry; outputs (the carry)
        must be loop-stable (join of carry-in and body-out) to be
        known."""
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        carry = in_specs[cn + bn:]
        if "cond_jaxpr" in eqn.params:
            self._walk_sub(
                eqn.params["cond_jaxpr"], list(in_specs[:cn]) + carry
            )
        outs: list = []
        if "body_jaxpr" in eqn.params:
            outs = self._walk_sub(
                eqn.params["body_jaxpr"],
                list(in_specs[cn:cn + bn]) + carry,
            )
        for i, ov in enumerate(eqn.outvars):
            spec = self._join(
                carry[i] if i < len(carry) else None,
                outs[i] if i < len(outs) else None,
            )
            if spec is not None:
                self._set(env, ov, spec)

    def _shard_map_out(self, eqn, env) -> None:
        """A manual region: outputs carry the declared out_names (its
        internal collectives are authored — the trace walker owns them).
        """
        out_names = eqn.params.get("out_names", ())
        for ov, names in zip(eqn.outvars, out_names):
            nd = len(getattr(getattr(ov, "aval", None), "shape", ()))
            spec = [()] * nd
            try:
                for dim, axes in dict(names).items():
                    if dim < nd:
                        spec[dim] = tuple(str(a) for a in axes)
            except Exception:
                continue
            self._set(env, ov, tuple(spec))

    def _dot_general(self, eqn, env, in_specs) -> ArraySpec:
        """Megatron arithmetic: sharded contracting dims force a
        cross-shard combine; free dims carry their operand's sharding —
        and one mesh axis appearing on two output dims is impossible, so
        the partitioner gathers one side."""
        dnums = eqn.params.get("dimension_numbers")
        if dnums is None:
            return None
        (lc, rc), (lb, rb) = dnums
        lhs, rhs = (in_specs + [None, None])[:2]

        contracted_shard = []
        for side, spec, dims in (("lhs", lhs, lc), ("rhs", rhs, rc)):
            if spec is None:
                continue
            for d in dims:
                if d < len(spec) and spec[d]:
                    contracted_shard.append((side, d, spec[d]))
        if contracted_shard:
            both = {s for s, _, _ in contracted_shard} == {"lhs", "rhs"}
            self._site(
                eqn,
                "all_reduce" if both else "all_gather",
                "contracting dimension is sharded "
                + (
                    "on both operands — partial products must be "
                    "all-reduced"
                    if both
                    else f"on {contracted_shard[0][0]} only — the "
                    "partitioner gathers it"
                ),
            )

        def free_dims(spec, contract, batch):
            if spec is None:
                return None
            return [
                spec[d] for d in range(len(spec))
                if d not in contract and d not in batch
            ]

        lfree = free_dims(lhs, lc, lb)
        rfree = free_dims(rhs, rc, rb)
        if lfree is None or rfree is None:
            return None
        batch = [
            (lhs[d] if lhs is not None and d < len(lhs) else ())
            for d in lb
        ]
        out = tuple(batch + lfree + rfree)
        used: set = set()
        for d in out:
            for a in d:
                if a in used:
                    self._site(
                        eqn, "all_gather",
                        f"mesh axis {a!r} would shard two output "
                        "dimensions — the partitioner gathers one "
                        "operand",
                    )
                    return None
                used.add(a)
        return out


def shardflow_jaxpr(jaxpr_like, in_specs: Sequence[Any],
                    label: str = "flow",
                    declared_out_specs: Optional[Sequence[Any]] = None,
                    ) -> ShardFlowReport:
    """Run the flow over an already-made (closed) jaxpr.

    ``in_specs``: one ``PartitionSpec`` (or None = unknown) per jaxpr
    invar.  ``declared_out_specs``: the program's declared output
    shardings — a propagated output MORE sharded than its declaration
    is a reshard the partitioner resolves with an all-gather, and is
    recorded as a site against the whole program.
    """
    inner = getattr(jaxpr_like, "jaxpr", jaxpr_like)
    flow = _Flow(label)
    env: dict = {}
    invars = list(inner.invars)
    specs = list(in_specs) + [None] * (len(invars) - len(in_specs))
    for v, s in zip(invars, specs):
        nd = len(getattr(getattr(v, "aval", None), "shape", ()))
        flow._set(env, v, canon_spec(s, nd))
    flow.walk(jaxpr_like, env)

    outs = tuple(flow._get(env, v) for v in inner.outvars)
    if declared_out_specs is not None:
        for i, (got, want) in enumerate(zip(outs, declared_out_specs)):
            if got is None:
                continue
            nd = len(got)
            want_c = canon_spec(want, nd)
            if want_c is None:
                continue
            for d in range(nd):
                extra = [a for a in got[d] if a not in want_c[d]]
                if extra:
                    flow.sites.append(ReshardSite(
                        eqn_index=-1,
                        primitive="<output>",
                        cls="all_gather",
                        note=(
                            f"output {i} propagates as "
                            f"{spec_str(got)} but is declared "
                            f"{spec_str(want_c)} — the partitioner "
                            "gathers it to match"
                        ),
                        source=None,
                    ))
                    break
    return ShardFlowReport(
        label=label,
        out_specs=outs,
        reshard_sites=tuple(flow.sites),
        n_eqns=flow._eqn_index,
    )


def shardflow(fn, *args, in_specs: Sequence[Any],
              out_specs: Optional[Sequence[Any]] = None,
              label: Optional[str] = None, **kwargs) -> ShardFlowReport:
    """Trace ``fn(*args, **kwargs)`` and run the sharding-flow pass.

    ``in_specs``: PartitionSpecs aligned with the *flattened* positional
    args (one spec per array leaf, tree-flatten order — matching how
    the jaxpr receives them).  Nothing is compiled or executed.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    # one spec per flattened arg leaf; None (= unknown) is itself a leaf
    flat_specs = jax.tree_util.tree_leaves(
        tuple(in_specs), is_leaf=lambda x: x is None
    )
    return shardflow_jaxpr(
        jaxpr, flat_specs,
        label=label or getattr(fn, "__name__", "flow"),
        declared_out_specs=out_specs,
    )
