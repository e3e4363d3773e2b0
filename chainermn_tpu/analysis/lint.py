"""mnlint — repo-level AST lint for collective discipline.

Run from the repo root (CI / conftest wire it into tier-1)::

    python -m chainermn_tpu.analysis.lint          # lint the repo
    python -m chainermn_tpu.analysis.lint PATH...  # lint specific paths

Exit status 0 = clean, 1 = violations (one ``path:line: [rule] message``
per line).

Rules
-----
``raw-collective``
    ``lax.psum``-family calls (psum / pmean / pmax / pmin / all_gather /
    all_gather_invariant / all_to_all / psum_scatter / ppermute /
    pshuffle / pgather) are forbidden outside the sanctioned
    communication modules — in every spelling: ``lax.psum``,
    ``jax.lax.psum``, module aliases (``import jax.lax as jl`` /
    ``from jax import lax as L`` / ``mylax = jax.lax``), and
    ``from jax.lax import psum`` smuggling.  Everything else must route
    through the audited wrappers (``functions.collectives`` /
    ``functions.point_to_point``) or the communicator API — that is what
    keeps the static analyzer's trace the single source of truth for
    what ships on the wire.  Sanctioned: ``comm_wire/`` (wire codecs),
    ``functions/`` (the audited wrappers themselves), ``parallel/``
    (SP/TP/EP/pipeline layers), ``communicators/`` (the eager tier),
    ``optimizers.py`` (the compiled-tier sync),
    and ``analysis/`` (this package names primitives to find them).

``untimed-row``
    A benchmark row (dict literal in a file under ``benchmarks/`` or
    named ``bench*.py``)
    carrying a timing-shaped key (``*_ms``, ``sec_per_*``, ``*_per_sec``,
    ``tflops*``, ...) must also carry the min-of-N protocol disclosure
    ``n_measurements`` (``spread_max_over_min`` rides along where >= 2
    positive samples exist).  Rows assembled dynamically (``**`` /
    ``.update``) are skipped — the rule targets literal rows that
    silently present one-shot timings as measurements.

``raw-timing``
    ``time.perf_counter()`` / ``time.time()`` calls are forbidden inside
    ``chainermn_tpu/`` outside the two sanctioned timing modules —
    ``observability/`` (the span timeline IS the timing layer) and
    ``utils/benchmarking.py`` (the min-of-N measurement protocol) — in
    every spelling: ``time.time``, module aliases (``import time as
    t``), and ``from time import perf_counter`` smuggling.  Ad-hoc
    timing in the package is how measurements drift from the protocol
    and escape the telemetry stream; route through
    ``observability.span``/``Timeline`` (or ``time.monotonic`` for
    plain interval arithmetic, which the rule deliberately permits —
    it is the clock both sanctioned layers run on).

Host-protocol rules (``--host-protocol`` / ``host_protocol=True``)
------------------------------------------------------------------
Ride-alongs from :mod:`.protolint` (exchange-site catalog rules:
``proto-duplicate-site`` / ``proto-raw-allgather`` / ``proto-magic-tag``
/ ``proto-adhoc-manifest``) plus three SPMD-determinism rules scoped to
``DECISION_MODULES`` — the modules whose values feed cross-rank
decisions (serving placement, fleet rendezvous, elastic resharding,
checkpoint step election, wire planning), where any per-process
nondeterminism becomes a protocol divergence:

``spmd-hash``
    Builtin ``hash()`` is salted per process (``PYTHONHASHSEED``): two
    ranks hashing the same string disagree.  Use ``hashlib`` digests
    for anything that crosses a rank boundary.

``spmd-unsorted-scan``
    Iterating a raw ``os.listdir``/``os.scandir``/``glob.glob``/
    ``glob.iglob`` result (directly, or via a name assigned from one),
    or iterating a ``set``, yields filesystem/hash order — which
    differs across hosts.  Wrap in ``sorted(...)``; generator
    expressions fed straight into an order-insensitive reducer
    (``sorted``/``min``/``max``/``sum``/``len``/``any``/``all``/
    ``set``/``frozenset``) are exempt.

``spmd-random``
    ``random``-module draws (and ``np.random`` global-state draws) are
    seeded per process; a cross-rank decision sampled from them
    diverges silently.  Use ``jax.random`` with an explicitly agreed
    key, or a seeded ``np.random.RandomState``/``default_rng``
    instance (constructors are not draws, so those are untouched).

The SPMD allowlist is **closed and empty** (``SPMD_ALLOWLIST = ()``):
no decision module is exempt; escapes are per-line pragmas only.

Per-line escape hatch (same line or the line above)::

    # mnlint: allow(raw-collective)
    # mnlint: allow(untimed-row)
    # mnlint: allow(raw-timing)
    # mnlint: allow(spmd-hash)
    # mnlint: allow(spmd-unsorted-scan)
    # mnlint: allow(spmd-random)
"""

from __future__ import annotations

import ast
import os
import re
import sys
from dataclasses import dataclass
from typing import List, Optional, Sequence

COLLECTIVE_CALLS = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "ppermute", "pshuffle", "pgather",
    "all_gather_invariant",
})

# repo-relative path prefixes (POSIX separators) sanctioned for raw
# lax collective calls — the communication layer itself
SANCTIONED = (
    "chainermn_tpu/comm_wire/",
    "chainermn_tpu/functions/",
    "chainermn_tpu/parallel/",
    "chainermn_tpu/communicators/",
    "chainermn_tpu/analysis/",
    "chainermn_tpu/optimizers.py",
)

SKIP_DIRS = {"__pycache__", ".git", "csrc", "_build", ".claude"}

# raw-timing: the forbidden wall/benchmark clocks, and where raw use of
# them IS the job (the timing layer itself)
TIMING_CALLS = frozenset({"time", "perf_counter"})
TIMING_SANCTIONED = (
    "chainermn_tpu/observability/",
    "chainermn_tpu/utils/benchmarking.py",
)

TIMING_KEY_RE = re.compile(
    r"(^|_)ms($|_)|_ms$"            # iter_ms, step_time_ms, rtt_ms, ms_*
    r"|(^|_)sec(ond)?s?($|_)"       # sec_per_generate, seconds, *_sec
    r"|_per_sec$|_per_s$"           # new_tokens_per_sec
    r"|^tflops|^gflops"             # tflops_per_sec
    r"|_per_step$"
)

PRAGMA_RE = re.compile(r"#\s*mnlint:\s*allow\(([a-z-]+)\)")

# ----------------------------------------------------------------------
# host-protocol (--host-protocol) rule scoping
# ----------------------------------------------------------------------
# Modules whose values feed cross-rank decisions: serving placement and
# scan-driven admission, fleet rendezvous/control, elastic resharding,
# peer-checkpoint healing, checkpoint step election, wire planning.
# Per-process nondeterminism here IS a protocol divergence.
DECISION_MODULES = (
    "chainermn_tpu/serving/",
    "chainermn_tpu/fleet/",
    "chainermn_tpu/resilience/adaptive.py",
    "chainermn_tpu/resilience/elastic.py",
    "chainermn_tpu/resilience/peer_ckpt.py",
    "chainermn_tpu/extensions/checkpoint.py",
    "chainermn_tpu/comm_wire/planner.py",
    "chainermn_tpu/comm_wire/autotune.py",
    "chainermn_tpu/comm_wire/schedules.py",
)

# CLOSED allowlist: no decision module may opt out wholesale.  Escapes
# are per-line pragmas only, so every exemption is visible in the diff
# that introduces it.  (The tuple stays defined so tests can pin that
# serving/ and fleet/ never creep onto it.)
SPMD_ALLOWLIST: tuple = ()

# spmd-unsorted-scan: raw directory/glob scans whose order is
# filesystem-dependent, and the order-insensitive reducers a generator
# over one may feed directly
SCAN_CALLS = frozenset({"listdir", "scandir", "glob", "iglob"})
ORDER_INSENSITIVE = frozenset({
    "sorted", "min", "max", "sum", "len", "any", "all", "set",
    "frozenset",
})

# spmd-random: global-state draw names on random / np.random.
# Constructors (RandomState, default_rng, PRNGKey, Generator) are NOT
# here — a seeded instance is the sanctioned fix.
RANDOM_DRAWS = frozenset({
    "random", "rand", "randn", "randint", "randrange", "shuffle",
    "permutation", "choice", "sample", "uniform", "gauss", "seed",
    "getrandbits", "standard_normal", "bytes",
})


@dataclass(frozen=True)
class Violation:
    path: str       # repo-relative
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(lines: Sequence[str], lineno: int, rule: str) -> bool:
    """Pragma on the flagged line or the line directly above."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = PRAGMA_RE.search(lines[ln - 1])
            if m and m.group(1) == rule:
                return True
    return False


def _is_lax_base(node: ast.expr, aliases=frozenset()) -> bool:
    """True for ``lax`` / ``jax.lax`` / ``...lax`` attribute bases and
    for any name the file has aliased to the lax module."""
    if isinstance(node, ast.Name):
        return node.id in ("lax", "plax") or node.id in aliases
    if isinstance(node, ast.Attribute):
        return node.attr == "lax"
    return False


def _module_aliases(tree: ast.AST, leaf: str,
                    seeds: tuple = ()) -> frozenset:
    """Names the file binds to a module whose dotted path ends in
    ``leaf`` — ``import jax.lax as jl`` / ``from jax import lax as L``
    / ``mylax = jax.lax`` respellings.  ONE walker shared by the
    raw-collective (``lax``) and raw-timing (``time``) rules, so an
    alias-tracking fix cannot land in one and silently miss the
    other.  ``seeds`` are extra bare names already known to denote
    the module (re-assigning them aliases it too)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname and (
                    a.name == leaf or a.name.endswith("." + leaf)
                ):
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == leaf and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.Assign):
            v = node.value
            if (isinstance(v, ast.Attribute) and v.attr == leaf) or (
                isinstance(v, ast.Name) and (
                    v.id == leaf or v.id in seeds
                )
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return frozenset(out)


def _lax_aliases(tree: ast.AST) -> frozenset:
    """Names the file binds to the lax module — the satellite gap:
    ``import jax.lax as jl`` / ``from jax import lax as L`` /
    ``mylax = jax.lax`` all put raw collectives one attribute access
    away without the ``lax`` spelling the base check keys on."""
    return _module_aliases(tree, "lax", seeds=("plax",))


def _lint_raw_collectives(tree: ast.AST, lines, rel: str) -> List[Violation]:
    out = []
    aliases = _lax_aliases(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if (node.func.attr in COLLECTIVE_CALLS
                    and _is_lax_base(node.func.value, aliases)):
                if not _allowed(lines, node.lineno, "raw-collective"):
                    out.append(Violation(
                        rel, node.lineno, "raw-collective",
                        f"raw lax.{node.func.attr} outside the sanctioned "
                        "communication modules; use functions.collectives"
                        " / functions.point_to_point or the communicator "
                        "API",
                    ))
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.endswith("lax"):
                bad = [a.name for a in node.names
                       if a.name in COLLECTIVE_CALLS]
                if bad and not _allowed(lines, node.lineno,
                                        "raw-collective"):
                    out.append(Violation(
                        rel, node.lineno, "raw-collective",
                        f"importing {', '.join(bad)} from jax.lax "
                        "smuggles raw collectives past the lint; call "
                        "through functions.collectives",
                    ))
    return out


def _lint_raw_timing(tree: ast.AST, lines, rel: str) -> List[Violation]:
    out = []
    aliases = _module_aliases(tree, "time")
    # names from-imported out of the time module (perf_counter smuggling)
    smuggled = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in TIMING_CALLS:
                    smuggled.add(a.asname or a.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        hit = None
        if isinstance(node.func, ast.Attribute):
            base = node.func.value
            if node.func.attr in TIMING_CALLS and isinstance(
                base, ast.Name
            ) and (base.id == "time" or base.id in aliases):
                hit = f"time.{node.func.attr}"
        elif isinstance(node.func, ast.Name) and node.func.id in smuggled:
            hit = node.func.id
        if hit and not _allowed(lines, node.lineno, "raw-timing"):
            out.append(Violation(
                rel, node.lineno, "raw-timing",
                f"raw {hit}() timing outside observability//"
                "utils/benchmarking.py; record through "
                "observability.span / the timeline (time.monotonic is "
                "fine for plain interval arithmetic)",
            ))
    return out


_EMIT_FUNCS = {"dumps", "print", "write"}


_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)


def _scope_body_walk(scope: ast.AST):
    """Walk a scope's body WITHOUT descending into nested function
    definitions — each nested function is its own scope, and pooling
    their names would let function A's enriched ``rec`` exempt function
    B's unrelated literal of the same name."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, _SCOPE_NODES[:2]):
            stack.extend(ast.iter_child_nodes(n))


def _dynamic_row_dicts(tree: ast.AST) -> set:
    """Dict literals whose protocol fields may arrive dynamically: args
    of ``.update()`` calls, and ``x = {...}`` literals whose name is
    later handed to a non-emission helper (``_copy_spread(rec, ...)``
    and friends enrich rows in place; ``json.dumps``/``print`` only
    emit, so they don't exempt).  Name tracking is per actual scope."""
    skip: set = set()
    scopes = [n for n in ast.walk(tree) if isinstance(n, _SCOPE_NODES)]
    for scope in scopes:
        assigned: dict = {}   # name -> [dict nodes]
        enriched: set = set()  # names passed to a non-emission call
        for n in _scope_body_walk(scope):
            if isinstance(n, ast.Call):
                fname = None
                if isinstance(n.func, ast.Attribute):
                    fname = n.func.attr
                    if fname == "update":
                        skip.update(
                            a for a in n.args if isinstance(a, ast.Dict)
                        )
                elif isinstance(n.func, ast.Name):
                    fname = n.func.id
                if fname and fname not in _EMIT_FUNCS:
                    for a in list(n.args) + [kw.value for kw in n.keywords]:
                        if isinstance(a, ast.Name):
                            enriched.add(a.id)
            elif isinstance(n, ast.Assign) and isinstance(
                n.value, ast.Dict
            ):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        assigned.setdefault(t.id, []).append(n.value)
        for name in enriched:
            skip.update(assigned.get(name, []))
    return skip


def _lint_untimed_rows(tree: ast.AST, lines, rel: str) -> List[Violation]:
    out = []
    dynamic = _dynamic_row_dicts(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict) or node in dynamic:
            continue
        if any(k is None for k in node.keys):
            continue  # ** expansion: protocol fields may arrive there
        keys = [k.value for k in node.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)]
        timed = [k for k in keys if TIMING_KEY_RE.search(k)]
        if not timed or "n_measurements" in keys:
            continue
        if _allowed(lines, node.lineno, "untimed-row"):
            continue
        out.append(Violation(
            rel, node.lineno, "untimed-row",
            f"timed bench row (key {timed[0]!r}) lacks the "
            "'n_measurements' min-of-N disclosure "
            "(add it, with 'spread_max_over_min' where >= 2 positive "
            "samples exist)",
        ))
    return out


def _lint_spmd_hash(tree: ast.AST, lines, rel: str) -> List[Violation]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Name
        ) and node.func.id == "hash":
            if not _allowed(lines, node.lineno, "spmd-hash"):
                out.append(Violation(
                    rel, node.lineno, "spmd-hash",
                    "builtin hash() is salted per process "
                    "(PYTHONHASHSEED); in a decision module use a "
                    "hashlib digest for anything that crosses a rank "
                    "boundary",
                ))
    return out


def _parent_map(tree: ast.AST) -> dict:
    parents: dict = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _scan_hit(node: ast.expr, scan_mods: frozenset,
              smuggled: frozenset):
    """``"os.listdir"`` when ``node`` is a raw scan call, else None."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in SCAN_CALLS:
        base = f.value
        if isinstance(base, ast.Name) and (
            base.id in ("os", "glob") or base.id in scan_mods
        ):
            return f"{base.id}.{f.attr}"
        # pathlib: p.glob / p.iterdir have no stable base name; keep
        # the rule to os/glob where the repo's scans live
    if isinstance(f, ast.Name) and f.id in smuggled:
        return f.id
    return None


def _set_hit(node: ast.expr):
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "a set"
    if isinstance(node, ast.Call) and isinstance(
        node.func, ast.Name
    ) and node.func.id in ("set", "frozenset"):
        return f"{node.func.id}(...)"
    return None


def _lint_spmd_unsorted_scan(tree: ast.AST, lines,
                             rel: str) -> List[Violation]:
    out = []
    parents = _parent_map(tree)
    scan_mods = _module_aliases(tree, "glob") | _module_aliases(
        tree, "os")
    smuggled = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in (
            "os", "glob"
        ):
            for a in node.names:
                if a.name in SCAN_CALLS:
                    smuggled.add(a.asname or a.name)
    smuggled = frozenset(smuggled)

    def flag(lineno, what):
        if not _allowed(lines, lineno, "spmd-unsorted-scan"):
            out.append(Violation(
                rel, lineno, "spmd-unsorted-scan",
                f"iterating {what} yields filesystem/hash order, "
                "which differs across hosts; wrap in sorted(...) "
                "before any cross-rank decision depends on it",
            ))

    for scope in (n for n in ast.walk(tree)
                  if isinstance(n, _SCOPE_NODES)):
        # names assigned a raw scan result inside this scope
        tainted = set()
        for n in _scope_body_walk(scope):
            if isinstance(n, ast.Assign) and _scan_hit(
                n.value, scan_mods, smuggled
            ):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
        for n in _scope_body_walk(scope):
            if isinstance(n, ast.For):
                iters = [(n.iter, n.iter.lineno, None)]
            elif isinstance(n, (ast.ListComp, ast.SetComp,
                                ast.DictComp, ast.GeneratorExp)):
                iters = [(g.iter, g.iter.lineno, n)
                         for g in n.generators]
            else:
                continue
            for it, lineno, comp in iters:
                hit = _scan_hit(it, scan_mods, smuggled)
                if hit is None and isinstance(it, ast.Name) \
                        and it.id in tainted:
                    hit = f"{it.id} (a raw scan result)"
                if hit is None:
                    hit = _set_hit(it)
                if hit is None:
                    continue
                # a comprehension handed straight to an
                # order-insensitive reducer is fine
                if comp is not None:
                    p = parents.get(comp)
                    if isinstance(p, ast.Call) and isinstance(
                        p.func, ast.Name
                    ) and p.func.id in ORDER_INSENSITIVE:
                        continue
                flag(lineno, hit)
    return out


def _lint_spmd_random(tree: ast.AST, lines, rel: str) -> List[Violation]:
    out = []
    aliases = set(_module_aliases(tree, "random"))
    # names bound to jax.random are fine — jax PRNG draws take an
    # explicit key, which is exactly the sanctioned discipline
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname and a.name == "jax.random":
                    aliases.discard(a.asname)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "random":
                        aliases.discard(a.asname or a.name)
        elif isinstance(node, ast.Assign):
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "random" \
                    and isinstance(v.value, ast.Name) \
                    and v.value.id == "jax":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases.discard(t.id)
    smuggled = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.split(".")[-1] == "random" and \
                not node.module.startswith("jax"):
            for a in node.names:
                if a.name in RANDOM_DRAWS:
                    smuggled.add(a.asname or a.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        hit = None
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in RANDOM_DRAWS:
            base = f.value
            if isinstance(base, ast.Name) and (
                base.id == "random" or base.id in aliases
            ):
                hit = f"{base.id}.{f.attr}"
            elif isinstance(base, ast.Attribute) and \
                    base.attr == "random" and isinstance(
                        base.value, ast.Name
                    ) and base.value.id != "jax":
                hit = f"{base.value.id}.random.{f.attr}"
        elif isinstance(f, ast.Name) and f.id in smuggled:
            hit = f.id
        if hit and not _allowed(lines, node.lineno, "spmd-random"):
            out.append(Violation(
                rel, node.lineno, "spmd-random",
                f"{hit}() draws from per-process global RNG state; "
                "in a decision module use jax.random with an agreed "
                "key or a seeded RandomState/default_rng instance",
            ))
    return out


def _is_bench_file(rel: str) -> bool:
    parts = rel.split("/")
    return "benchmarks" in parts or parts[-1].startswith("bench")


def lint_file(path: str, repo_root: str,
              host_protocol: bool = False) -> List[Violation]:
    rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    except (OSError, UnicodeDecodeError):
        return []
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [Violation(rel, e.lineno or 0, "syntax",
                          f"file does not parse: {e.msg}")]
    lines = src.splitlines()
    out: List[Violation] = []
    if not any(rel.startswith(p) for p in SANCTIONED):
        out += _lint_raw_collectives(tree, lines, rel)
    if _is_bench_file(rel):
        out += _lint_untimed_rows(tree, lines, rel)
    if rel.startswith("chainermn_tpu/") and not any(
        rel.startswith(p) for p in TIMING_SANCTIONED
    ):
        out += _lint_raw_timing(tree, lines, rel)
    if host_protocol and any(
        rel.startswith(p) for p in DECISION_MODULES
    ) and not any(rel.startswith(p) for p in SPMD_ALLOWLIST):
        out += _lint_spmd_hash(tree, lines, rel)
        out += _lint_spmd_unsorted_scan(tree, lines, rel)
        out += _lint_spmd_random(tree, lines, rel)
    return sorted(out, key=lambda v: (v.path, v.line))


def _iter_py_files(root: str):
    if os.path.isfile(root):
        if root.endswith(".py"):
            yield root
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def repo_root() -> str:
    """The checkout containing this package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def default_targets(root: Optional[str] = None) -> List[str]:
    """What the repo gate lints: the package, the benchmarks and the
    examples.  Tests are deliberately excluded — they construct raw
    collectives on purpose to exercise the analyzer."""
    root = root or repo_root()
    out = []
    for name in ("chainermn_tpu", "benchmarks", "examples"):
        p = os.path.join(root, name)
        if os.path.exists(p):
            out.append(p)
    return out


def run_lint(paths: Optional[Sequence[str]] = None,
             root: Optional[str] = None,
             host_protocol: bool = False) -> List[Violation]:
    root = root or repo_root()
    targets = list(paths) if paths else default_targets(root)
    out: List[Violation] = []
    for t in targets:
        for f in _iter_py_files(t):
            out += lint_file(f, root, host_protocol=host_protocol)
    if host_protocol:
        # lazy: protolint imports this module's helpers
        from . import protolint
        out += protolint.catalog_violations(paths or None, root)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    host_protocol = "--host-protocol" in argv
    argv = [a for a in argv if a != "--host-protocol"]
    violations = run_lint(argv or None, host_protocol=host_protocol)
    for v in violations:
        print(v)
    if violations:
        print(f"mnlint: {len(violations)} violation(s)", file=sys.stderr)
        return 1
    print("mnlint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
