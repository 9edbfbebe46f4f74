"""Tests for the static-analysis plane (repro.analysis).

Every rule gets a positive fixture (violates exactly that rule) and a
clean twin (negative), plus an end-to-end run over the real ``src/repro``
tree asserting zero unbaselined violations — the same gate CI runs.
"""
import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    ENTRY_POINTS,
    EntryPoint,
    TracedEntry,
    lint_file,
    lint_tree,
    reduces_full_counters,
    run_jaxpr_pass,
)
from repro.analysis.contracts import (
    Violation,
    apply_baseline,
    check_retrace_query_families,
)
from repro.analysis.jaxpr_lint import check_entry_point
from repro.analysis.runner import main, run_analysis

SRC_REPRO = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
TESTS_DIR = pathlib.Path(__file__).resolve().parent


def _rules(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# jaxpr pass — one positive + one negative per contract
# ---------------------------------------------------------------------------


def _ep(name, contracts, entry):
    return EntryPoint(name=name, contracts=contracts, build=lambda: entry)


def test_no_host_callback_positive_and_negative():
    x = jnp.ones(4)

    def dirty(a):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(a.shape, a.dtype), a
        )

    bad = check_entry_point(
        _ep("fix.cb", ("no-host-callback",), TracedEntry(dirty, (x,)))
    )
    assert _rules(bad) == ["no-host-callback"]
    good = check_entry_point(
        _ep("fix.clean", ("no-host-callback",), TracedEntry(lambda a: a + 1, (x,)))
    )
    assert good == []


def test_no_wide_dtype_positive_and_negative():
    x = jnp.ones(4)

    def dirty(a):
        with jax.enable_x64():
            return a.astype(jnp.float64) * 2.0

    bad = check_entry_point(
        _ep("fix.wide", ("no-wide-dtype",), TracedEntry(dirty, (x,)))
    )
    assert _rules(bad) == ["no-wide-dtype"]
    good = check_entry_point(
        _ep("fix.narrow", ("no-wide-dtype",), TracedEntry(lambda a: a * 2.0, (x,)))
    )
    assert good == []


def test_no_counter_reduction_positive_and_negative():
    counters = jnp.ones((2, 8, 8))
    shape = (2, 8, 8)
    bad = check_entry_point(
        _ep(
            "fix.reduce",
            ("no-counter-reduction",),
            TracedEntry(lambda c: jnp.sum(c), (counters,), counters_shape=shape),
        )
    )
    assert _rules(bad) == ["no-counter-reduction"]
    good = check_entry_point(
        _ep(
            "fix.gather",
            ("no-counter-reduction",),
            TracedEntry(lambda c: c[:, 0, 0], (counters,), counters_shape=shape),
        )
    )
    assert good == []
    # the test-facing helper agrees (used by test_query_engine.py)
    assert reduces_full_counters(lambda c: jnp.sum(c), shape, counters)
    assert not reduces_full_counters(lambda c: c[:, 0, 0], shape, counters)


def test_collectives_only_under_shard_map():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    # a vmapped psum sits OUTSIDE any shard_map region -> violation
    naked = jax.vmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    bad = check_entry_point(
        _ep(
            "fix.naked_psum",
            ("collectives-under-shard-map",),
            TracedEntry(naked, (jnp.ones((1, 4)),)),
        )
    )
    assert _rules(bad) == ["collectives-under-shard-map"]

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    sharded = shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P(),
    )
    good = check_entry_point(
        _ep(
            "fix.sharded_psum",
            ("collectives-under-shard-map",),
            TracedEntry(sharded, (jnp.ones(4),)),
        )
    )
    assert good == []


def test_donation_applied_positive_and_negative():
    x = jnp.ones((8, 8))
    undonated = jax.jit(lambda a: a + 1.0)
    bad = check_entry_point(
        _ep(
            "fix.undonated",
            ("donation-applied",),
            TracedEntry(undonated, (x,), jit_fn=undonated),
        )
    )
    assert _rules(bad) == ["donation-applied"]
    donated = jax.jit(lambda a: a + 1.0, donate_argnums=0)
    good = check_entry_point(
        _ep(
            "fix.donated",
            ("donation-applied",),
            TracedEntry(donated, (x,), jit_fn=donated),
        )
    )
    assert good == []


def test_retrace_detector_flags_salted_cache():
    from repro.core import queries
    from repro.core.query_engine import QueryEngine

    class RetracingEngine:
        """Minimal engine whose jit cache is salted per call — every
        dispatch re-traces, the exact failure mode the detector exists
        to catch."""

        def __init__(self, backend, pad_q=8):
            self._jits = {}
            self._calls = 0

        def _fn(self, family, fn):
            return self._jits.setdefault(
                family, jax.jit(fn, static_argnames=("salt",))
            )

        def _call(self, family, fn, *args):
            self._calls += 1
            return self._fn(family, fn)(*args, salt=self._calls)

        def edge(self, sk, src, dst):
            return self._call(
                "edge", lambda s, a, b, salt: queries.edge_query(s, a, b), sk, src, dst
            )

        def in_flow(self, sk, keys):
            return self._call(
                "in_flow", lambda s, k, salt: queries.node_in_flow(s, k), sk, keys
            )

        def out_flow(self, sk, keys):
            return self._call(
                "out_flow", lambda s, k, salt: queries.node_out_flow(s, k), sk, keys
            )

        def flow(self, sk, keys):
            return self._call(
                "flow", lambda s, k, salt: queries.node_flow(s, k), sk, keys
            )

        def heavy_rel_vec(self, sk, keys, thetas):
            return self._call(
                "heavy_rel_vec",
                lambda s, k, t, salt: queries.check_heavy_keys_rel_vec(s, k, t),
                sk, keys, thetas,
            )

    bad = check_retrace_query_families(RetracingEngine)
    assert bad and all(v.rule == "retrace" for v in bad)
    assert check_retrace_query_families(QueryEngine) == []


# ---------------------------------------------------------------------------
# source pass — fixture trees, one rule each
# ---------------------------------------------------------------------------


def _write(tmp_path, rel, body):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return p


def test_direct_jit_rule(tmp_path):
    bad = _write(
        tmp_path, "core/adhoc.py",
        """
        import jax

        def f(fn):
            return jax.jit(fn)
        """,
    )
    assert _rules(lint_file(bad, "core/adhoc.py")) == ["direct-jit"]
    # the engine cache module is allowed; so is code outside the scoped dirs
    assert lint_file(bad, "core/query_engine.py") == []
    assert lint_file(bad, "launch/adhoc.py") == []


def test_host_sync_rule(tmp_path):
    bad = _write(
        tmp_path, "kernels/foo/ops.py",
        """
        def f(x):
            return x.item()
        """,
    )
    assert _rules(lint_file(bad, "kernels/foo/ops.py")) == ["host-sync"]
    bad_np = _write(
        tmp_path, "core/reach_bad.py",
        """
        import numpy as np

        def f(x):
            return np.asarray(x)
        """,
    )
    assert _rules(lint_file(bad_np, "core/reach.py")) == ["host-sync"]
    # api/ modules stage host<->device transfers by design: out of scope
    assert lint_file(bad, "api/stream.py") == []
    clean = _write(
        tmp_path, "kernels/foo/clean_ops.py",
        """
        import jax.numpy as jnp

        def f(x):
            return jnp.sum(x)
        """,
    )
    assert lint_file(clean, "kernels/foo/clean_ops.py") == []


def test_jnp_in_loop_rule(tmp_path):
    bad = _write(
        tmp_path, "core/hot.py",
        """
        import jax.numpy as jnp

        def f(xs):
            out = []
            for x in xs:
                out.append(jnp.sum(x))
            return out
        """,
    )
    assert _rules(lint_file(bad, "core/hot.py")) == ["jnp-in-loop"]
    clean = _write(
        tmp_path, "core/cold.py",
        """
        import jax.numpy as jnp

        def f(xs):
            return jnp.sum(jnp.stack(list(xs)))
        """,
    )
    assert lint_file(clean, "core/cold.py") == []
    # api/ is not a hot module for this rule
    assert lint_file(bad, "api/hot.py") == []


def test_env_read_rule(tmp_path):
    bad = _write(
        tmp_path, "api/cfg.py",
        """
        import os

        def f():
            return os.environ.get("REPRO_QUERY_BACKEND", "")
        """,
    )
    assert _rules(lint_file(bad, "api/cfg.py")) == ["env-read"]
    bad_sub = _write(
        tmp_path, "api/cfg2.py",
        """
        import os

        def f():
            return os.environ["REPRO_INGEST_BACKEND"]
        """,
    )
    assert _rules(lint_file(bad_sub, "api/cfg2.py")) == ["env-read"]
    # the dispatch boundaries are allowed; non-REPRO vars anywhere are fine
    assert lint_file(bad, "core/ingest.py") == []
    clean = _write(
        tmp_path, "api/cfg3.py",
        """
        import os

        def f():
            return os.environ.get("HOME", "")
        """,
    )
    assert lint_file(clean, "api/cfg3.py") == []


def test_kernel_ref_rule(tmp_path):
    root = tmp_path / "pkg"
    _write(root, "kernels/newk/kernel.py", "def k():\n    return 0\n")
    _write(root, "kernels/newk/ops.py", "def op():\n    return 0\n")
    tests = tmp_path / "tests"
    _write(tmp_path, "tests/test_kernels.py", "# no imports of newk\n")
    found = lint_tree(root, tests)
    assert _rules(found) == ["kernel-ref"]
    # missing ref.py + neither ops nor ref imported by the harness
    assert len(found) == 3

    _write(root, "kernels/newk/ref.py", "def k_ref():\n    return 0\n")
    _write(
        tmp_path, "tests/test_kernels.py",
        """
        from pkg.kernels.newk.ops import op
        from pkg.kernels.newk.ref import k_ref
        """,
    )
    assert lint_tree(root, tests) == []


# ---------------------------------------------------------------------------
# baseline mechanism + CLI + end-to-end gate
# ---------------------------------------------------------------------------


def test_baseline_marks_but_keeps_violations():
    v = Violation(rule="direct-jit", subject="core/x.py::f:3", message="m",
                  pass_name="source")
    out = apply_baseline([v], {("direct-jit", "core/x.py::f:3"): "why"})
    assert out[0].baselined and out[0].justification == "why"
    out2 = apply_baseline([v], {("direct-jit", "core/other.py::f:3"): "why"})
    assert not out2[0].baselined


def test_cli_exit_codes_and_json_report(tmp_path):
    bad_root = tmp_path / "pkg"
    _write(
        bad_root, "core/adhoc.py",
        """
        import jax

        def f(fn):
            return jax.jit(fn)
        """,
    )
    report_path = tmp_path / "report.json"
    rc = main([
        "--passes", "source", "--root", str(bad_root),
        "--format", "json", "--output", str(report_path),
    ])
    assert rc == 1
    report = json.loads(report_path.read_text())
    assert not report["ok"]
    assert report["counts"]["violations"] == 1
    assert report["violations"][0]["rule"] == "direct-jit"

    clean_root = tmp_path / "pkg2"
    _write(clean_root, "core/clean.py", "def f():\n    return 0\n")
    assert main(["--passes", "source", "--root", str(clean_root)]) == 0


def test_jaxpr_pass_respects_entry_point_override():
    counters = jnp.ones((2, 8, 8))
    eps = (
        _ep(
            "fix.reduce",
            ("no-counter-reduction",),
            TracedEntry(lambda c: jnp.sum(c), (counters,), counters_shape=(2, 8, 8)),
        ),
    )
    found = run_jaxpr_pass(eps)
    assert _rules(found) == ["no-counter-reduction"]


def test_end_to_end_repo_is_clean():
    """The CI gate: jaxpr + source passes over the real tree, zero
    unbaselined (costlint has its own gate test below — compiling the
    cost ladders here would double the suite's compile bill)."""
    report = run_analysis(("jaxpr", "source"), root=SRC_REPRO, tests_dir=TESTS_DIR)
    new = [v for v in report["violations"] if not v["baselined"]]
    assert report["ok"], "unbaselined violations:\n" + "\n".join(
        f"{v['rule']} {v['subject']}: {v['message']}" for v in new
    )
    # the registry really covers the engine surface, including the
    # turnstile-delete and window-advance session boundaries
    assert report["counts"]["entry_points"] == len(ENTRY_POINTS) >= 26
    names = set(report["checked_entry_points"])
    assert {"ingest.delete_boundary", "window.advance_boundary"} <= names


# ---------------------------------------------------------------------------
# costlint — exponent fits, planted twins, donation proof, budgets
# ---------------------------------------------------------------------------


def test_fit_exponent_basics():
    from repro.analysis.costlint import _fit_exponent

    assert _fit_exponent((2, 4, 8), (7.0, 7.0, 7.0)) == pytest.approx(0.0)
    assert _fit_exponent((2, 4), (10.0, 40.0)) == pytest.approx(2.0)
    # all-zero metric clips at 1 -> exponent 0, not -inf
    assert _fit_exponent((2, 4), (0.0, 0.0)) == pytest.approx(0.0)


def test_planted_quadratic_ingest_fails_B_contract():
    """An ingest twin with a hidden O(B²) pairwise coupling must blow the
    declared O(B) flops exponent — the exact regression costlint exists
    to catch.  The coupling rides in at 1e-9 so XLA cannot DCE it."""
    from repro.analysis.contracts import (
        AxisContract,
        CostEntryPoint,
        CostProbe,
    )
    from repro.analysis.costlint import run_cost_pass

    def build(B=64):
        from repro.core.ingest import ingest
        from repro.core.sketch import GLavaSketch, SketchConfig

        cfg = SketchConfig(depth=2, width_rows=64, width_cols=64)
        sk = GLavaSketch.empty(cfg, jax.random.key(0))
        src = jnp.arange(B, dtype=jnp.uint32)
        rows, cols = sk.hash_edges(src, src + jnp.uint32(B))
        wts = jnp.ones(B, jnp.float32)

        def bad(c, r, cc, ww):
            sim = jnp.sum(ww[:, None] * ww[None, :], axis=1)  # O(B²)
            return ingest(c, r, cc, ww + 1e-9 * sim, backend="scatter")

        return CostProbe(
            fn=bad, args=(sk.counters, rows, cols, wts),
            state_bytes=4 * 2 * 64 * 64,
        )

    ep = CostEntryPoint(
        name="fix.cost.quadratic_ingest",
        axes=(AxisContract("B", 1.0, (64, 128, 256)),),
        build=build,
    )
    violations, meas = run_cost_pass([ep], check_budgets=False)
    assert _rules(violations) == ["cost-exponent"]
    assert violations[0].subject == "fix.cost.quadratic_ingest[B]"
    assert meas[0]["axes"][0]["measured"] > 1.35


def test_planted_tenant_wide_reduction_fails_T_contract():
    """A fleet query twin that also scans the whole tenant stack must blow
    the declared O(1)-in-T flops exponent — tenant isolation is the
    fleet's headline claim."""
    from repro.analysis.contracts import (
        AxisContract,
        CostEntryPoint,
        CostProbe,
    )
    from repro.analysis.costlint import run_cost_pass

    def build(T=2):
        from repro.fleet.query import FleetQueryEngine

        fn, args, shape = FleetQueryEngine.family_probe(
            "in_flow", tenants=T, width=64, depth=2, n_queries=32
        )

        def bad(state, *rest):
            return fn(state, *rest) + 1e-9 * jnp.sum(state.counters)

        n = 1
        for s in shape:
            n *= s
        return CostProbe(fn=bad, args=args, state_bytes=4 * n)

    ep = CostEntryPoint(
        name="fix.cost.tenant_scan",
        axes=(AxisContract("T", 0.0, (2, 8)),),
        build=build,
    )
    violations, meas = run_cost_pass([ep], check_budgets=False)
    assert _rules(violations) == ["cost-exponent"]
    assert violations[0].subject == "fix.cost.tenant_scan[T]"
    assert meas[0]["axes"][0]["measured"] > 0.35


def test_donation_memory_proof_positive_and_negative():
    """An undonated jit presented as a donated boundary aliases 0 bytes ->
    cost-donation-memory; the real session boundary aliases the sketch."""
    from repro.analysis.contracts import (
        COST_ENTRY_POINTS,
        AxisContract,
        CostEntryPoint,
        CostProbe,
    )
    from repro.analysis.costlint import run_cost_pass

    def build(w=64):
        counters = jnp.ones((2, w, w))
        jf = jax.jit(lambda c: c * 2.0 + 1.0)
        return CostProbe(
            fn=jf, args=(counters,), jit_fn=jf, state_bytes=4 * 2 * w * w
        )

    undonated = CostEntryPoint(
        name="fix.cost.undonated",
        axes=(AxisContract("w", 3.0, (32, 64), tol=1.0),),
        build=build,
        donated=True,
    )
    violations, _ = run_cost_pass([undonated], check_budgets=False)
    assert _rules(violations) == ["cost-donation-memory"]
    assert "donation dropped" in violations[0].message

    real = next(
        ep for ep in COST_ENTRY_POINTS if ep.name == "cost.ingest.jit_boundary"
    )
    clean, _ = run_cost_pass([real], check_budgets=False)
    assert clean == []


def test_broken_probe_is_a_finding_not_a_crash():
    from repro.analysis.contracts import (
        AxisContract,
        CostEntryPoint,
    )
    from repro.analysis.costlint import run_cost_pass

    def build(Q=8):
        raise RuntimeError("probe exploded")

    ep = CostEntryPoint(
        name="fix.cost.broken",
        axes=(AxisContract("Q", 1.0, (8, 16)),),
        build=build,
    )
    violations, meas = run_cost_pass([ep], check_budgets=False)
    assert _rules(violations) == ["cost-entry-broken"]
    assert meas == []


def test_cost_registry_passes_committed_budgets():
    """The costlint CI gate: every registry entry measured at >=2 sizes
    per axis, every exponent within contract, every committed ceiling
    honored."""
    from repro.analysis.contracts import COST_ENTRY_POINTS
    from repro.analysis.costlint import load_budgets, run_cost_pass

    budgets = load_budgets()
    assert budgets is not None, "ANALYSIS_BUDGETS.json must be committed"
    violations, measurements = run_cost_pass(budgets=budgets)
    assert violations == [], "\n".join(v.render() for v in violations)
    assert len(measurements) == len(COST_ENTRY_POINTS) >= 10
    for m in measurements:
        for fit in m["axes"]:
            assert len(fit["sizes"]) >= 2 and len(fit["values"]) >= 2


def test_budget_ratchet_roundtrip(tmp_path):
    """update -> clean run passes -> hand-shrunk ceiling -> exit 1 with a
    human-readable regression diff."""
    budgets = tmp_path / "budgets.json"
    entry = "cost.query.in_flow"
    assert main([
        "--update-budgets", "--cost-entries", entry,
        "--budgets", str(budgets),
    ]) == 0
    data = json.loads(budgets.read_text())
    assert entry in data["entries"]
    # a filtered update must not ratchet the full-registry compile count
    assert "compile_count" not in data

    assert main([
        "--passes", "costlint", "--cost-entries", entry,
        "--budgets", str(budgets),
    ]) == 0

    data["entries"][entry]["peak_bytes"] = 1
    budgets.write_text(json.dumps(data))
    report_path = tmp_path / "report.json"
    rc = main([
        "--passes", "costlint", "--cost-entries", entry,
        "--budgets", str(budgets),
        "--format", "json", "--output", str(report_path),
    ])
    assert rc == 1
    report = json.loads(report_path.read_text())
    bad = [v for v in report["violations"] if v["rule"] == "cost-budget"]
    assert bad and "exceeds committed ceiling" in bad[0]["message"]


def test_missing_budgets_file_is_a_violation(tmp_path):
    from repro.analysis.costlint import run_cost_pass

    violations, _ = run_cost_pass([], budgets=None, full_registry=False)
    assert _rules(violations) == ["cost-budget"]
    assert violations[0].subject == "ANALYSIS_BUDGETS.json"


# ---------------------------------------------------------------------------
# baseline staleness + prune
# ---------------------------------------------------------------------------


def test_stale_baseline_warns_and_prunes(tmp_path):
    from repro.analysis.baseline import load_baseline

    clean_root = tmp_path / "pkg"
    _write(clean_root, "core/clean.py", "def f():\n    return 0\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": "direct-jit", "subject": "core/gone.py::f:1",
         "justification": "code was deleted"},
    ]))

    # the rule's pass ran and nothing matched -> stale, but only a WARN
    report = run_analysis(
        ("source",), root=clean_root, baseline=load_baseline(bl)
    )
    assert report["ok"]
    assert report["stale_baseline"] == [["direct-jit", "core/gone.py::f:1"]]
    assert report["counts"]["stale_baseline"] == 1

    # the rule's pass did NOT run -> staleness is undecidable, no warn
    report2 = run_analysis(
        ("jaxpr",), root=clean_root, entry_points=(),
        baseline=load_baseline(bl),
    )
    assert report2["stale_baseline"] == []

    # --prune-baseline deletes it from the file
    assert main([
        "--passes", "source", "--root", str(clean_root),
        "--baseline", str(bl), "--prune-baseline",
    ]) == 0
    assert json.loads(bl.read_text()) == []


def test_live_baseline_entry_is_not_stale(tmp_path):
    from repro.analysis.baseline import load_baseline

    root = tmp_path / "pkg"
    _write(
        root, "core/adhoc.py",
        """
        import jax

        def f(fn):
            return jax.jit(fn)
        """,
    )
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": "direct-jit", "subject": "core/adhoc.py::f:5",
         "justification": "still here"},
    ]))
    report = run_analysis(("source",), root=root, baseline=load_baseline(bl))
    assert report["ok"] and report["stale_baseline"] == []
    assert report["counts"]["baselined"] == 1


def test_committed_baseline_loads_and_maps_rules():
    from repro.analysis.baseline import BASELINE, RULE_PASS

    assert BASELINE, "committed baseline.json must load"
    for rule, _subject in BASELINE:
        assert rule in RULE_PASS, f"rule {rule} missing from RULE_PASS"


# ---------------------------------------------------------------------------
# benchmark trajectory history
# ---------------------------------------------------------------------------


def test_bench_history_append(tmp_path):
    import sys

    repo_root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo_root))
    try:
        from benchmarks.run import append_history
    finally:
        sys.path.remove(str(repo_root))

    p = tmp_path / "BENCH_x.json"
    # legacy flat list becomes the pr-0 seed record
    p.write_text(json.dumps([{"name": "a", "us_per_call": 1.0}]))
    h = append_history(p, [{"name": "b"}], pr=9, commit="abc1234")
    assert [r["pr"] for r in h] == [0, 9]
    assert h[0]["commit"] == "legacy"
    assert h[0]["rows"] == [{"name": "a", "us_per_call": 1.0}]

    # re-running the same PR replaces its record, no duplicates
    h = append_history(p, [{"name": "c"}], pr=9, commit="def5678")
    assert [r["pr"] for r in h] == [0, 9]
    assert h[-1]["rows"] == [{"name": "c"}]

    # no explicit pr -> one past the last record
    h = append_history(p, [{"name": "d"}], commit="eee9999")
    assert h[-1]["pr"] == 10
    # and the file round-trips as history, not a flat list
    on_disk = json.loads(p.read_text())
    assert [r["pr"] for r in on_disk] == [0, 9, 10]
