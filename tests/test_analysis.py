"""Tests for the static analysis subsystem (``repro.analysis``).

Three layers:

  * known-bad fixtures -- tiny deliberately broken entry points, one per
    contract rule family (host callback in jit, dropped donation,
    float64/weak-type carry, misaligned + narrow Pallas BlockSpec,
    unstable carry), each asserting its rule FIRES. This is the seeded-
    violation demonstration: any of these landing in the real registry
    turns the CI ``analysis`` job red.
  * the real repo -- the full ``run_analysis()`` pass must be clean
    (exit 0): every registered hot entry point traced, no unsuppressed
    violation, every suppression carrying a reason.
  * runtime sanitizers -- the compile counter enforces the pinned
    recompile budgets (``analysis/budgets.json``): a warm engine's
    steady-state step compiles EXACTLY once, then never again.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    Suppression,
    Violation,
    load_budgets,
    load_suppressions,
    run_analysis,
    split_suppressed,
)
from repro.analysis import contracts, lint
from repro.analysis.registry import EntrySpec, build_registry
from repro.analysis.sanitizers import CompileCounter, guard_methods
from repro.serving import api


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _rules_fired(entry):
    return {v.rule for v in contracts.check_entry(entry)}


# ---------------------------------------------------------------------------
# Known-bad fixtures: each contract rule must fire on its seeded bug.
# ---------------------------------------------------------------------------

class TestSeededViolations:
    def test_host_callback_fires(self):
        def bad(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x
            )

        entry = EntrySpec(name="bad.callback", fn=bad, args=(_sds((4,)),))
        assert "host-callback" in _rules_fired(entry)

    def test_dropped_donation_fires(self):
        # Donates a (4,) input but returns a (2,) output: no shape-
        # compatible output exists, so XLA drops the donation with only
        # a UserWarning -- exactly the silent regression the rule pins.
        def bad(x):
            return x[:2] * 2.0

        entry = EntrySpec(
            name="bad.dropped_donation", fn=bad, args=(_sds((4,)),),
            donate_argnums=(0,),
        )
        assert "donation-surviving" in _rules_fired(entry)

    def test_undeclared_donation_fires(self):
        # Promises aliasing (must_alias) but ships no donation at all.
        def bad(x):
            return x * 2.0

        entry = EntrySpec(
            name="bad.no_donation", fn=bad, args=(_sds((4,)),),
            must_alias=(0,),
        )
        assert "donation-declared" in _rules_fired(entry)

    def test_surviving_donation_is_clean(self):
        jitted = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
        entry = EntrySpec(
            name="good.donation", fn=jitted, args=(_sds((4,)),),
            donate_argnums=(0,), must_alias=(0,),
        )
        fired = _rules_fired(entry)
        assert "donation-surviving" not in fired
        assert "donation-declared" not in fired

    def test_float64_output_fires(self):
        def bad(x):
            return x.astype(jnp.float64)

        entry = EntrySpec(name="bad.f64", fn=bad, args=(_sds((4,)),))
        with jax.enable_x64(True):
            assert "float64-leak" in _rules_fired(entry)

    def test_weak_type_carry_fires(self):
        # The carry comes back as a weakly-typed scalar (a Python-scalar
        # constant), so its aval differs from the strong input aval:
        # both the weak-type leak and the carry-stability rule object.
        def bad(state, x):
            return jnp.sin(1.0), x * 2.0

        entry = EntrySpec(
            name="bad.weak_carry", fn=bad, args=(_sds(()), _sds((4,))),
            carry=(0, 0),
        )
        fired = _rules_fired(entry)
        assert "float64-leak" in fired
        assert "carry-stable" in fired

    def test_carry_dtype_drift_fires(self):
        def bad(state, x):
            return state.astype(jnp.int32), x * 2.0

        entry = EntrySpec(
            name="bad.carry_drift", fn=bad, args=(_sds((3,)), _sds((4,))),
            carry=(0, 0),
        )
        assert "carry-stable" in _rules_fired(entry)

    @staticmethod
    def _pallas_entry(n_rows, block_rows, name):
        """A trivial Pallas copy kernel with a (block_rows, 2) block over
        an (n_rows, 2) array: ragged when block_rows does not divide
        n_rows, and always lane-narrow (2 < 128)."""
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def run(x):
            return pl.pallas_call(
                kernel,
                grid=(-(-n_rows // block_rows),),
                in_specs=[pl.BlockSpec((block_rows, 2), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((block_rows, 2), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((n_rows, 2), jnp.float32),
                interpret=True,
            )(x)

        return EntrySpec(name=name, fn=run, args=(_sds((n_rows, 2)),))

    def test_misaligned_blockspec_fires(self):
        entry = self._pallas_entry(6, 4, "bad.ragged_tile")  # 4 !| 6
        assert "pallas-tile-divides" in _rules_fired(entry)

    def test_narrow_output_tile_fires(self):
        entry = self._pallas_entry(8, 4, "bad.narrow_tile")
        fired = _rules_fired(entry)
        assert "pallas-narrow-output-tile" in fired
        assert "pallas-tile-divides" not in fired  # 4 | 8: aligned


# ---------------------------------------------------------------------------
# Lint rules on synthetic sources.
# ---------------------------------------------------------------------------

class TestLintRules:
    @staticmethod
    def _check(tmp_path, rel, source, rule):
        """Write ``source`` at ``rel`` under a fake repo root and run one
        lint rule over it."""
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
        modules = [lint._Module(str(tmp_path), str(path))]
        reachable = lint.jit_reachable(modules)
        return lint.RULES[rule](modules, reachable)

    def test_numpy_in_jit_fires(self, tmp_path):
        src = (
            "import jax\nimport numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return helper(x)\n"
            "def helper(x):\n"
            "    return np.asarray(x) + 1\n"
        )
        found = self._check(
            tmp_path, "src/repro/serving/bad.py", src, "numpy-in-jit"
        )
        assert len(found) == 1
        assert "np.asarray" in found[0].message

    def test_numpy_dtype_attrs_are_benign(self, tmp_path):
        src = (
            "import jax\nimport numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x.astype(np.float32)\n"
        )
        assert not self._check(
            tmp_path, "src/repro/serving/ok.py", src, "numpy-in-jit"
        )

    def test_host_coercion_fires(self, tmp_path):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x.sum().item()\n"
        )
        found = self._check(
            tmp_path, "src/repro/core/bad.py", src, "host-coercion-in-jit"
        )
        assert len(found) == 1

    def test_jnp_in_host_loop_fires_only_in_hot_modules(self, tmp_path):
        src = (
            "import jax.numpy as jnp\n"
            "def f(items):\n"
            "    out = []\n"
            "    for it in items:\n"
            "        out.append(jnp.asarray(it))\n"
            "    return out\n"
        )
        assert self._check(
            tmp_path, "src/repro/serving/bad.py", src,
            "jnp-construction-in-host-loop",
        )
        assert not self._check(
            tmp_path, "src/repro/models/cool.py", src,
            "jnp-construction-in-host-loop",
        )

    def test_kernel_missing_interpret_fires(self, tmp_path):
        src = (
            "from repro.kernels.foo import kernel as _k\n"
            "def foo_op(x, use_pallas=True):\n"
            "    return _k.run(x)\n"
        )
        found = self._check(
            tmp_path, "src/repro/kernels/foo/ops.py", src,
            "kernel-interpret-fallback",
        )
        assert len(found) == 1

    def test_unreferenced_export_fires(self, tmp_path):
        src = (
            "def used(): pass\n"
            "def never_called_anywhere_xyz(): pass\n"
            "__all__ = ['used', 'never_called_anywhere_xyz']\n"
        )
        other = tmp_path / "src/repro/other.py"
        other.parent.mkdir(parents=True, exist_ok=True)
        other.write_text("from repro.mod import used\n")
        path = tmp_path / "src/repro/mod.py"
        path.write_text(src)
        modules = [lint._Module(str(tmp_path), str(path))]
        found = lint.rule_unreferenced_export(
            modules, set(), root=str(tmp_path)
        )
        assert [v for v in found if "never_called" in v.message]
        assert not [v for v in found if "'used'" in v.message]


# ---------------------------------------------------------------------------
# Suppressions machinery.
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_committed_file_loads_and_every_entry_has_reason(self):
        sups = load_suppressions()
        assert sups, "committed suppressions file should not be empty"
        for s in sups:
            assert s.reason.strip()

    def test_empty_reason_rejected(self, tmp_path):
        p = tmp_path / "sup.json"
        p.write_text(json.dumps([{"rule": "r", "subject": "s", "reason": ""}]))
        with pytest.raises(ValueError, match="reason"):
            load_suppressions(str(p))

    def test_prefix_matching(self):
        s = Suppression(rule="r", subject="src/repro/x.py", reason="why")
        assert s.matches(Violation("r", "src/repro/x.py:12", "m"))
        assert not s.matches(Violation("r", "src/repro/y.py:12", "m"))
        assert not s.matches(Violation("other", "src/repro/x.py:12", "m"))
        live, quiet = split_suppressed(
            [Violation("r", "src/repro/x.py:1", "m"), Violation("r", "z", "m")],
            [s],
        )
        assert len(live) == 1 and len(quiet) == 1


# ---------------------------------------------------------------------------
# The real repo must be clean.
# ---------------------------------------------------------------------------

class TestRealRegistry:
    def test_registry_covers_every_hot_entry_point(self):
        names = {e.name for e in build_registry()}
        # The serving step + stateless scorer, the streaming frontend
        # (both overlap settings) + its scan, both training entry
        # points, and every kernels/* op: the PR 7 acceptance list.
        required = {
            "serving.engine_step", "serving.score_chunks",
            "serving.splice_state", "serving.init_state",
            "serving.engine_restore", "serving.engine_swap_program",
            "signal.frontend_step", "signal.frontend_step_overlap2",
            "signal.process_windows_scan",
            "core.fit_forest_binned", "core.fit_mapreduce_map",
            "kernels.forest.forest_predict_proba",
            "kernels.histogram.class_histogram",
            "kernels.gram.gram", "kernels.wpd.wpd_level",
            "kernels.ssd.ssd_scan",
            "kernels.flash_attention.flash_attention",
        }
        assert required <= names

    def test_at_least_eight_distinct_rules(self):
        assert len(contracts.RULES) + len(lint.RULES) >= 8
        assert len(contracts.RULES) >= 6

    def test_full_analysis_is_clean(self):
        report = run_analysis()
        assert report["violations"] == [], report["violations"]
        assert report["summary"]["entries_traced"] == len(build_registry())
        # Suppressed findings are inventoried, not hidden.
        for v in report["suppressed"]:
            assert v["reason"].strip()

    def test_cli_exit_codes(self, tmp_path, capsys):
        from repro.analysis.__main__ import main

        out = tmp_path / "report.json"
        assert main(["--lint-only", "--json", str(out)]) == 0
        report = json.loads(out.read_text())
        assert report["summary"]["violations"] == 0
        capsys.readouterr()


# ---------------------------------------------------------------------------
# Runtime sanitizers: compile counting + the pinned recompile budgets.
# ---------------------------------------------------------------------------

class TestSanitizers:
    def test_compile_counter_counts(self):
        @jax.jit
        def fresh_fn_for_counter(x):
            return x * 3.0

        with CompileCounter() as cc:
            fresh_fn_for_counter(jnp.ones((3,)))
            fresh_fn_for_counter(jnp.ones((3,)))  # cache hit
        assert cc.count("fresh_fn_for_counter") == 1
        with CompileCounter() as cc2:
            fresh_fn_for_counter(jnp.ones((3,)))
        assert cc2.count("fresh_fn_for_counter") == 0

    def test_guard_methods_blocks_implicit_transfer(self):
        inc = jax.jit(lambda a: a + 1)

        class Host:
            def leaky(self, x):
                return jnp.asarray(x) + 1  # implicit host->device

            def clean(self, x):
                # The real hot-path shape: explicit device_put at the
                # boundary, arithmetic inside jit (eager `+ 1` would
                # itself transfer a scalar constant -- also guarded).
                return inc(jax.device_put(x))

        h = Host()
        with guard_methods(Host, "leaky", "clean"):
            with pytest.raises(Exception, match="[Tt]ransfer"):
                h.leaky(np.ones((3,), np.float32))
            h.clean(np.ones((3,), np.float32))  # explicit: legal
        h.leaky(np.ones((3,), np.float32))  # guard restored away

    def test_engine_recompile_budget(self, program, chunk_pool):
        budgets = load_budgets()
        quiet, _ = chunk_pool
        engine = api.SeizureEngine(program, max_batch=2, replay_depth=1)
        session = engine.open_session(0)
        with CompileCounter() as warm:
            for _ in range(3):
                session.push(quiet)
                engine.poll()
        # The step compiles AT MOST once across the warmup polls (zero
        # if an earlier test already populated the shared jit cache for
        # this signature) -- the pinned budget.
        assert warm.count("_engine_step") <= budgets["engine_steady_state"]
        # Steady state: the warm engine never compiles ANYTHING again.
        with CompileCounter() as steady:
            for _ in range(4):
                session.push(quiet)
                engine.poll()
        assert steady.total == 0, steady.by_name

    def test_engine_replay_mixed_depth_recompile_budget(
        self, program, chunk_pool
    ):
        # Ragged backlogs (1, 3, 2, 4 chunks per poll) against a
        # replay_depth=4 engine: the megabatch step pads every dispatch
        # to the fixed D, so the whole mixed-depth schedule must compile
        # ONE program -- the historical depth bucketing compiled up to
        # replay_depth distinct ones.
        budgets = load_budgets()
        quiet, pre = chunk_pool
        engine = api.SeizureEngine(program, max_batch=2, replay_depth=4)
        session = engine.open_session(0)
        with CompileCounter() as warm:
            for n_chunks in (1, 3, 2, 4):
                session.push(
                    np.concatenate([quiet, pre] * 2)[: n_chunks * 60]
                )
                engine.poll()
        assert warm.count("_engine_step_megabatch") <= (
            budgets["engine_replay_mixed_depth"]
        )
        with CompileCounter() as steady:
            for n_chunks in (2, 1, 4):
                session.push(
                    np.concatenate([quiet, pre] * 2)[: n_chunks * 60]
                )
                engine.poll()
        assert steady.total == 0, steady.by_name

    def test_score_chunks_recompile_budget(self, program, chunk_pool):
        budgets = load_budgets()
        quiet, _ = chunk_pool
        engine = api.SeizureEngine(program, max_batch=1)
        batch = quiet[None]
        engine.score_chunks(batch)  # warmup (may compile once)
        with CompileCounter() as steady:
            engine.score_chunks(batch)
            engine.score_chunks(batch)
        assert steady.count("_score_chunks") <= (
            budgets["score_chunks_steady_state"] - 1
        )


# ---------------------------------------------------------------------------
# The jit-reachability closure resolves the repo's real call graph.
# ---------------------------------------------------------------------------

def test_jit_reachability_covers_cross_module_calls():
    modules = lint.load_modules()
    reachable = lint.jit_reachable(modules)
    rels = {(rel.replace("\\", "/"), fn) for rel, fn in reachable}
    # scan_stream is a jit root in signal/frontend.py; frontend_step and
    # chunk_features must be reachable from it (same-module closure).
    assert ("src/repro/signal/frontend.py", "frontend_step") in rels
    assert ("src/repro/signal/frontend.py", "chunk_features") in rels
    # and the cross-module hop into the feature extractor.
    assert any(
        rel == "src/repro/signal/features.py" for rel, _ in rels
    ), sorted(r for r in rels if "features" in r[0])


def test_lint_check_tree_runs_clean_modulo_suppressions():
    violations = lint.check_tree()
    live, _ = split_suppressed(violations, load_suppressions())
    assert live == [], live
