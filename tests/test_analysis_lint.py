"""mnlint repo gate (ISSUE 5 satellite): the repo self-lints in tier-1,
and the rules behave as documented on synthetic files.

Fast by construction: pure AST work, no jax import in the linted path.
"""

import os
import subprocess
import sys
import textwrap

from chainermn_tpu.analysis.lint import (
    SANCTIONED,
    Violation,
    default_targets,
    lint_file,
    repo_root,
    run_lint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_src(tmp_path, src, name="offender.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    # tmp files live outside the repo: lint relative to tmp_path so
    # sanctioned-prefix matching sees a clean relative name
    return lint_file(str(p), str(tmp_path))


# ----------------------------------------------------------------------
# the gate itself
# ----------------------------------------------------------------------
class TestRepoGate:
    def test_repo_self_lints_clean(self):
        """Acceptance: the repo AST lint runs clean in tier-1.  Every
        raw-collective site is either routed through the audited
        wrappers or inside the sanctioned comm modules; every timed
        bench row carries the min-of-N disclosure (or an explicit
        pragma naming why not)."""
        violations = run_lint()
        assert violations == [], "\n".join(str(v) for v in violations)

    def test_console_entry_exits_zero_on_clean_repo(self):
        """``python -m chainermn_tpu.analysis.lint`` is the CI gate."""
        proc = subprocess.run(
            [sys.executable, "-m", "chainermn_tpu.analysis.lint"],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "clean" in proc.stdout

    def test_console_entry_exits_nonzero_on_violation(self, tmp_path):
        bad = tmp_path / "offender.py"
        bad.write_text("from jax import lax\nlax.psum(1, 'mn')\n")
        proc = subprocess.run(
            [sys.executable, "-m", "chainermn_tpu.analysis.lint",
             str(bad)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "raw-collective" in proc.stdout

    def test_default_targets_cover_the_surface(self):
        names = {os.path.basename(t) for t in default_targets()}
        assert {"chainermn_tpu", "benchmarks", "examples"} == names
        # tests are deliberately NOT linted: they construct raw
        # collectives on purpose to exercise the analyzer
        assert "tests" not in names
        assert repo_root() == REPO


# ----------------------------------------------------------------------
# rule: raw-collective
# ----------------------------------------------------------------------
class TestRawCollectiveRule:
    def test_lax_attribute_calls_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                return lax.psum(x, 'mn') + lax.pmean(x, 'mn')
        """)
        assert [v.rule for v in vs] == ["raw-collective"] * 2

    def test_jax_lax_dotted_calls_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import jax
            def f(x):
                return jax.lax.all_gather(x, 'mn', axis=0, tiled=True)
        """)
        assert len(vs) == 1 and vs[0].line == 4

    def test_from_import_smuggling_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax.lax import psum, ppermute
        """)
        assert len(vs) == 1
        assert "smuggles" in vs[0].message

    def test_import_alias_flagged(self, tmp_path):
        """ISSUE 6 satellite: module aliases put raw collectives one
        attribute access away without the ``lax`` spelling the base
        check keys on."""
        vs = _lint_src(tmp_path, """
            import jax.lax as jl
            def f(x):
                return jl.all_gather(x, 'mn', axis=0, tiled=True)
        """)
        assert [v.rule for v in vs] == ["raw-collective"]
        assert vs[0].line == 4

    def test_from_import_alias_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax as L
            def f(x):
                return L.psum_scatter(x, 'mn', scatter_dimension=0)
        """)
        assert [v.rule for v in vs] == ["raw-collective"]

    def test_assignment_alias_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import jax
            mylax = jax.lax
            def f(x):
                return mylax.psum(x, 'mn')
        """)
        assert [v.rule for v in vs] == ["raw-collective"]

    def test_alias_of_non_lax_module_not_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import numpy.linalg as jl
            def f(x):
                return jl.psum(x, 'mn')  # not lax: someone else's psum
        """)
        assert vs == []

    def test_extended_collective_names_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                a = lax.pshuffle(x, 'mn', [0])
                b = lax.all_gather_invariant(x, 'mn')
                return a + b
        """)
        assert [v.rule for v in vs] == ["raw-collective"] * 2

    def test_non_collective_lax_ok(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                return lax.axis_index('mn') + lax.rsqrt(x) + lax.scan
        """)
        assert vs == []

    def test_wrapper_calls_ok(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from chainermn_tpu.functions import collectives as cc
            def f(x):
                return cc.psum(x, 'mn') + cc.pmean(x, 'mn')
        """)
        assert vs == []

    def test_pragma_allows(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                return lax.psum(x, 'mn')  # mnlint: allow(raw-collective)
        """)
        assert vs == []

    def test_pragma_on_preceding_line_allows(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                # mnlint: allow(raw-collective)
                return lax.psum(x, 'mn')
        """)
        assert vs == []

    def test_wrong_pragma_rule_does_not_allow(self, tmp_path):
        vs = _lint_src(tmp_path, """
            from jax import lax
            def f(x):
                return lax.psum(x, 'mn')  # mnlint: allow(untimed-row)
        """)
        assert len(vs) == 1

    def test_sanctioned_prefixes_are_the_comm_layer(self):
        assert "chainermn_tpu/comm_wire/" in SANCTIONED
        assert "chainermn_tpu/functions/" in SANCTIONED
        assert "chainermn_tpu/parallel/" in SANCTIONED
        assert "chainermn_tpu/optimizers.py" in SANCTIONED
        # models/links/extensions are NOT sanctioned — they must route
        # through the wrappers (fixed in this PR)
        assert not any(p.startswith("chainermn_tpu/models") for p in SANCTIONED)

    def test_sanctioned_file_not_flagged(self):
        # optimizers.py is the compiled-tier sync layer: full of psums,
        # sanctioned by name
        path = os.path.join(REPO, "chainermn_tpu", "optimizers.py")
        assert [v for v in lint_file(path, REPO)
                if v.rule == "raw-collective"] == []

    def test_adaptive_stays_off_the_sanctioned_list(self):
        """ISSUE 15 satellite: the straggler-adaptive policy engine is
        a DECISION layer — its exchanges ride the obj store's audited
        lockstep retry, never raw device collectives — so neither
        ``resilience/adaptive.py`` nor the resilience package may ever
        join the raw-psum sanctioned list, and the module self-lints
        clean (raw-collective AND raw-timing)."""
        assert not any(
            p.startswith("chainermn_tpu/resilience") for p in SANCTIONED
        ), "resilience/ (adaptive.py included) must stay unsanctioned"
        path = os.path.join(
            REPO, "chainermn_tpu", "resilience", "adaptive.py"
        )
        assert lint_file(path, REPO) == []


# ----------------------------------------------------------------------
# rule: untimed-row
# ----------------------------------------------------------------------
class TestUntimedRowRule:
    def test_timed_row_without_protocol_flagged(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import json
            print(json.dumps({"variant": "x", "step_time_ms": 1.2}))
        """, name="bench_x.py")
        assert [v.rule for v in vs] == ["untimed-row"]

    def test_row_with_n_measurements_ok(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import json
            print(json.dumps({
                "step_time_ms": 1.2, "n_measurements": 3,
                "spread_max_over_min": 1.1,
            }))
        """, name="bench_x.py")
        assert vs == []

    def test_double_star_expansion_skipped(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import json
            fields = {"n_measurements": 2}
            print(json.dumps({"step_time_ms": 1.2, **fields}))
        """, name="bench_x.py")
        assert vs == []

    def test_update_arg_skipped(self, tmp_path):
        vs = _lint_src(tmp_path, """
            rec = {"n_measurements": 2}
            rec.update({"extra_ms": 3.4})
        """, name="bench_x.py")
        assert vs == []

    def test_dict_enriched_by_helper_skipped(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import json
            def emit(merge_protocol):
                rec = {"step_time_ms": 1.2}
                merge_protocol(rec)
                print(json.dumps(rec))
        """, name="bench_x.py")
        assert vs == []

    def test_enrichment_in_one_function_does_not_exempt_another(
        self, tmp_path
    ):
        """Regression: name tracking is per actual scope.  Function B's
        enriched ``out`` must not exempt function A's unrelated literal
        of the same name."""
        vs = _lint_src(tmp_path, """
            import json
            def a():
                out = {"step_time_ms": 1.2}
                print(json.dumps(out))
            def b():
                out = {"other": 1}
                enrich(out)
        """, name="bench_x.py")
        assert [v.rule for v in vs] == ["untimed-row"]
        assert vs[0].line == 4

    def test_emission_calls_do_not_exempt(self, tmp_path):
        vs = _lint_src(tmp_path, """
            import json
            def emit():
                rec = {"step_time_ms": 1.2}
                print(json.dumps(rec))
        """, name="bench_x.py")
        assert len(vs) == 1

    def test_rule_only_applies_to_bench_files(self, tmp_path):
        src = """
            row = {"step_time_ms": 1.2}
        """
        assert _lint_src(tmp_path, src, name="bench_y.py") != []
        assert _lint_src(tmp_path, src, name="module.py") == []

    def test_untimed_keys_ok(self, tmp_path):
        vs = _lint_src(tmp_path, """
            cfg = {"batch": 8, "layers": 2, "milestones": [1, 2]}
        """, name="bench_x.py")
        assert vs == []

    def test_violation_formatting(self):
        v = Violation("b.py", 3, "untimed-row", "msg")
        assert str(v) == "b.py:3: [untimed-row] msg"


# ----------------------------------------------------------------------
# rule: raw-timing (ISSUE 10 satellite)
# ----------------------------------------------------------------------
class TestRawTimingRule:
    def _lint_pkg(self, tmp_path, src, rel="chainermn_tpu/mod.py"):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        return lint_file(str(p), str(tmp_path))

    def test_time_time_and_perf_counter_flagged(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            import time
            def f():
                return time.time() + time.perf_counter()
        """)
        assert [v.rule for v in vs] == ["raw-timing"] * 2

    def test_monotonic_is_permitted(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            import time
            def f():
                return time.monotonic(), time.sleep(0)
        """)
        assert vs == []

    def test_module_alias_tracked(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            import time as t
            def f():
                return t.perf_counter()
        """)
        assert [v.rule for v in vs] == ["raw-timing"]

    def test_from_import_smuggling_flagged(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            from time import perf_counter as pc
            def f():
                return pc()
        """)
        assert [v.rule for v in vs] == ["raw-timing"]

    def test_sanctioned_timing_modules_exempt(self, tmp_path):
        src = """
            import time
            def f():
                return time.perf_counter()
        """
        assert self._lint_pkg(
            tmp_path, src, rel="chainermn_tpu/observability/timeline.py"
        ) == []
        assert self._lint_pkg(
            tmp_path, src, rel="chainermn_tpu/utils/benchmarking.py"
        ) == []
        # the rule is scoped to the package: bench scripts measure
        # with raw clocks by design
        assert self._lint_pkg(
            tmp_path, src, rel="benchmarks/some_bench.py"
        ) == []

    def test_pragma_escape(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            import time
            WALL = time.time()  # mnlint: allow(raw-timing)
        """)
        assert vs == []

    def test_unrelated_attributes_not_flagged(self, tmp_path):
        vs = self._lint_pkg(tmp_path, """
            class Clock:
                def time(self):
                    return 0
            def f(c):
                return c.time()
        """)
        assert vs == []


# ----------------------------------------------------------------------
# host-protocol rules (ISSUE 20): spmd-hash / spmd-unsorted-scan /
# spmd-random, scoped to DECISION_MODULES, behind --host-protocol
# ----------------------------------------------------------------------
class TestSpmdRules:
    def _lint_decision(self, tmp_path, src,
                       name="chainermn_tpu/serving/mod.py"):
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        return lint_file(str(p), str(tmp_path), host_protocol=True)

    def test_builtin_hash_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            def pick(key, n):
                return hash(key) % n
        """)
        assert [v.rule for v in vs] == ["spmd-hash"]

    def test_hashlib_not_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import hashlib
            def pick(key, n):
                return int(hashlib.sha256(key).hexdigest(), 16) % n
        """)
        assert vs == []

    def test_unsorted_listdir_iteration_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import os
            def scan(root):
                for name in os.listdir(root):
                    yield name
        """)
        assert [v.rule for v in vs] == ["spmd-unsorted-scan"]

    def test_tainted_name_iteration_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import os
            def scan(root):
                names = os.listdir(root)
                return [n for n in names]
        """)
        assert [v.rule for v in vs] == ["spmd-unsorted-scan"]

    def test_glob_alias_and_smuggled_listdir_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import glob as _glob
            from os import listdir
            def scan(root):
                for p in _glob.glob(root + "/*"):
                    pass
                for n in listdir(root):
                    pass
        """)
        assert [v.rule for v in vs] == ["spmd-unsorted-scan"] * 2

    def test_sorted_scan_is_clean(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import glob, os
            def scan(root):
                for name in sorted(os.listdir(root)):
                    pass
                for p in sorted(glob.glob(root + "/*")):
                    pass
        """)
        assert vs == []

    def test_order_insensitive_reducer_exempts_genexp(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import os
            def scan(root):
                n = len([x for x in os.listdir(root)])
                newest = max(int(x) for x in os.listdir(root))
                every = all(x for x in os.listdir(root))
                return n, newest, every
        """)
        assert vs == []

    def test_set_iteration_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            def f(items):
                for x in set(items):
                    pass
                for y in {1, 2, 3}:
                    pass
        """)
        assert [v.rule for v in vs] == ["spmd-unsorted-scan"] * 2

    def test_sorted_set_is_clean(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            def f(items):
                for x in sorted(set(items)):
                    pass
        """)
        assert vs == []

    def test_random_module_draws_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import random
            import numpy as np
            def f(items):
                random.shuffle(items)
                return np.random.randint(10)
        """)
        assert [v.rule for v in vs] == ["spmd-random"] * 2

    def test_smuggled_draw_flagged(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            from random import choice
            def f(items):
                return choice(items)
        """)
        assert [v.rule for v in vs] == ["spmd-random"]

    def test_jax_random_and_seeded_instances_clean(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import jax
            import numpy as np
            def f(seed):
                key = jax.random.PRNGKey(seed)
                key = jax.random.split(key)[0]
                rng = np.random.RandomState(seed)
                gen = np.random.default_rng(seed)
                return key, rng.randn(3), gen.standard_normal(3)
        """)
        assert vs == []

    def test_pragma_escapes_each_rule(self, tmp_path):
        vs = self._lint_decision(tmp_path, """
            import os, random
            def f(root, items, key):
                h = hash(key)  # mnlint: allow(spmd-hash)
                # mnlint: allow(spmd-unsorted-scan)
                for n in os.listdir(root):
                    pass
                random.shuffle(items)  # mnlint: allow(spmd-random)
                return h
        """)
        assert vs == []

    def test_rules_scoped_to_decision_modules(self, tmp_path):
        """The same hazards OUTSIDE a decision module (and anywhere
        with host_protocol off) are not flagged — the rules target
        cross-rank decision surfaces, not all Python."""
        src = """
            import os, random
            def f(root, items, key):
                random.shuffle(items)
                for n in os.listdir(root):
                    pass
                return hash(key)
        """
        vs = self._lint_decision(
            tmp_path, src, name="chainermn_tpu/utils/mod.py"
        )
        assert vs == []
        p = tmp_path / "chainermn_tpu/serving/off.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        assert lint_file(str(p), str(tmp_path)) == []  # flag off

    def test_spmd_allowlist_is_closed_and_empty(self):
        """ISSUE 20 acceptance: serving/ and fleet/ are decision
        modules and sit on NO sanctioned allowlist — not the raw-psum
        one, not the timing one, and the SPMD allowlist itself is
        empty by contract."""
        from chainermn_tpu.analysis.lint import (
            DECISION_MODULES,
            SPMD_ALLOWLIST,
            TIMING_SANCTIONED,
        )

        assert SPMD_ALLOWLIST == ()
        for pkg in ("chainermn_tpu/serving/", "chainermn_tpu/fleet/"):
            assert pkg in DECISION_MODULES
            assert not any(pkg.startswith(p) for p in SANCTIONED)
            assert not any(pkg.startswith(p) for p in TIMING_SANCTIONED)
            assert not any(pkg.startswith(p) for p in SPMD_ALLOWLIST)


class TestHostProtocolGate:
    def test_repo_self_lints_clean_under_host_protocol(self):
        """ISSUE 20 acceptance: the repo passes the FULL rule set —
        the classic rules, the SPMD-determinism rules over every
        decision module, and the protolint catalog rules — in tier-1."""
        violations = run_lint(host_protocol=True)
        assert violations == [], "\n".join(str(v) for v in violations)

    def test_cli_flag_folds_protolint_in(self, tmp_path):
        import subprocess
        import sys

        bad = tmp_path / "offender.py"
        bad.write_text("SHARD_TAG = 4242\n")
        proc = subprocess.run(
            [sys.executable, "-m", "chainermn_tpu.analysis.lint",
             "--host-protocol", str(bad)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1
        assert "proto-magic-tag" in proc.stdout

    def test_unsorted_listdir_fixture_trips_gate(self, tmp_path):
        """The end-to-end satellite contract: a decision-module file
        iterating a raw listdir fails the gate."""
        p = tmp_path / "chainermn_tpu/fleet/bad.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(
            "import os\n"
            "def pick(root):\n"
            "    return [d for d in os.listdir(root)]\n"
        )
        vs = run_lint([str(tmp_path)], str(tmp_path),
                      host_protocol=True)
        assert [v.rule for v in vs] == ["spmd-unsorted-scan"]

    def test_flag_off_keeps_legacy_behaviour(self, tmp_path):
        p = tmp_path / "chainermn_tpu/fleet/bad.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("import os\nX = [d for d in os.listdir('.')]\n")
        assert run_lint([str(tmp_path)], str(tmp_path)) == []
