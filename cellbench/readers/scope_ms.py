"""Device time per step, on chip 0, of the operations whose ``op_name``
matches ``pattern``, in ms.  ``op_name`` is the path of
``jax.named_scope``s and flax modules an operation was traced under,
forward (``jvp(...)``) and backward (``transpose(jvp(...))``) alike; a
fusion carries that of its root.  Collectives are left out: an
all-reduce autodiff inserts carries the scope of the layer whose
gradient it sums (the tied embedding's lies under ``head_ce``), and is
the exchange's time, a row of its own, not the layer's.  Prints the
whole partition of the step by scope, ``unscoped`` as a row, once a
trace."""

import re

from .. import trace_reduce
from . import _xplane

#: what JAX wraps a scope in: ``jit(f)``, ``jvp(M)``, ``transpose(jvp(M))``
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")
_SERIAL = re.compile(r"_\d+$")
#: the operation of an HLO line's right-hand side: the first lower-case
#: word that opens a parenthesis after white space (a layout's
#: ``T(8,128)`` follows a colon)
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
#: path components that are transformations, not scopes
_NOT_SCOPES = {"shard_map", "pjit", "jit", "checkpoint", "remat",
               "custom_vjp_call", "custom_jvp_call", "while", "body",
               "cond", "branch", "closed_call", "core_call"}
#: the program's scopes outside its modules, the gradient exchange
#: first (``grad_sync`` lies inside ``optimizer``); one on an
#: operation's path names its row
PROGRAM_SCOPES = ("grad_sync", "head_ce", "optimizer")
#: below the model: block, then layer
_DEPTH = 3


def scopes_of(op_name: str) -> list:
    """``jit(_step)/shard_map/transpose(jvp(LM))/Block_3/LayerNorm_0/mul``
    -> ``["LM", "Block", "LayerNorm"]``: wrappers, jitted functions'
    names, serial numbers and the final primitive taken off."""
    out = []
    for part in op_name.split("/")[:-1]:
        m = _WRAPPED.match(part)
        if m:
            if part.startswith(("jit(", "pjit(")):
                continue  # a jitted function's name, not a scope
            part = m.group(1)
        if part and part not in _NOT_SCOPES:
            out.append(_SERIAL.sub("", part))
    return out


def is_collective(name: str) -> bool:
    """By the operation, not by the instruction's name alone: autodiff's
    ``%psum_invariant.3 = f32[50257,1536]{...} all-reduce(...)`` is one."""
    opcode = _OPCODE.search(name.partition(" = ")[2])
    return trace_reduce.is_collective(name) or bool(
        opcode and trace_reduce.COLLECTIVE.search(opcode.group(1)))


def row_of(name: str, op_name) -> str:
    """The partition's row of one operation."""
    if op_name and op_name.endswith("/pallas_call"):
        # a kernel, under the name its pallas_call was given
        return "kernel " + op_name.split("/")[-2]
    scopes = scopes_of(op_name) if op_name else []
    if PROGRAM_SCOPES[0] in scopes:
        return PROGRAM_SCOPES[0]
    if is_collective(name):
        # what autodiff inserted, wherever in the model it did
        return "collective outside grad_sync"
    for scope in PROGRAM_SCOPES[1:]:
        if scope in scopes:
            return scope
    if not scopes:
        return "unscoped"
    return scopes[:_DEPTH][-1]


def self_times(ops):
    """``(name, op_name, self_ps)`` of every operation: its duration
    less that of the operations nested inside it (a ``while`` holds its
    body's), so that the times sum to the time some operation ran."""
    out, stack = [], []  # stack of [end, index]
    for name, start, dur, op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= dur
        stack.append([start + dur, len(out)])
        out.append([name, op, dur])
    return out


def rows_of(timed) -> dict:
    rows = {}
    for name, op, ps in timed:
        row = row_of(name, op)
        rows[row] = rows.get(row, 0) + ps
    return rows


def partition(ops) -> dict:
    return rows_of(self_times(ops))


def _print_partition(tr, timed):
    rows, steps = rows_of(timed), len(tr.steps)
    busy = trace_reduce.total(tr.busy())
    total = sum(rows.values())
    print(f"scope partition of chip 0 over {steps} steps: rows sum to "
          f"{total / steps / 1e9:.4f} ms a step, an operation runs "
          f"{busy / steps / 1e9:.4f} ms a step")
    for row, ps in sorted(rows.items(), key=lambda kv: -kv[1]):
        print(f"  {row:32s} {ps / steps / 1e9:10.4f} ms "
              f"{100.0 * ps / total:6.2f} %")


def read(ctx, pattern):
    tr = _xplane.load(ctx)
    if tr is None or not tr.steps or not tr.ops:
        return None
    if not any(op for _, _, _, op in tr.ops):
        return None  # the trace keeps no op_name
    timed = self_times(tr.ops)
    if tr.first_time("scope partition"):
        _print_partition(tr, timed)
    rx = re.compile(pattern)
    hit = sum(ps for name, op, ps in timed
              if op and rx.search(op) and not is_collective(name))
    if not hit:
        return None
    return hit / len(tr.steps) / 1e9
