"""Query-plane tests: maintained flow registers (bit-match recomputed sums
under arbitrary update/merge/window/scale sequences), register-served point
queries (no full-counter reduction in the jaxpr), the monitor oracle,
heavy-hitter one-sidedness, the QueryEngine dispatch (padding/chunking,
backend equality, epoch-tagged closure cache), and checkpoint schema
evolution for register-less sketches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    GLavaSketch,
    QueryEngine,
    SketchConfig,
    SlidingWindowSketch,
    queries,
)


def _stream(rng, n, n_nodes=200):
    return (
        jnp.asarray(rng.integers(0, n_nodes, n), jnp.uint32),
        jnp.asarray(rng.integers(0, n_nodes, n), jnp.uint32),
        jnp.asarray(rng.integers(1, 6, n), jnp.float32),
    )


def _assert_registers_fresh(sk, err=""):
    """Maintained registers must BIT-match freshly recomputed marginals."""
    np.testing.assert_array_equal(
        np.asarray(sk.row_flows), np.asarray(jnp.sum(sk.counters, axis=2)),
        err_msg=f"row register drift {err}",
    )
    np.testing.assert_array_equal(
        np.asarray(sk.col_flows), np.asarray(jnp.sum(sk.counters, axis=1)),
        err_msg=f"col register drift {err}",
    )


# ---------------------------------------------------------------------------
# register maintenance
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    ops=st.lists(
        st.sampled_from(["update", "merge", "scale", "delete", "sequential"]),
        min_size=1,
        max_size=6,
    ),
)
def test_registers_bitmatch_recomputed_sums(seed, ops):
    rng = np.random.default_rng(seed)
    cfg = SketchConfig(depth=3, width_rows=32, width_cols=32)
    sk = GLavaSketch.empty(cfg, jax.random.key(seed % 7))
    for op in ops:
        src, dst, w = _stream(rng, int(rng.integers(1, 80)))
        if op == "update":
            sk = sk.update(src, dst, w, backend=str(rng.choice(["scatter", "onehot"])))
        elif op == "sequential":
            sk = sk.update_sequential(src, dst, w)
        elif op == "delete":
            sk = sk.delete(src, dst, w)
        elif op == "merge":
            other = GLavaSketch.empty(cfg, jax.random.key(seed % 7))
            sk = sk.merge(other.update(src, dst, w))
        elif op == "scale":
            sk = sk.scale(0.5)
        _assert_registers_fresh(sk, err=f"after {op}")


def test_registers_nonsquare_and_undirected():
    rng = np.random.default_rng(3)
    for cfg in (
        SketchConfig(depth=2, width_rows=96, width_cols=40),
        SketchConfig(depth=3, width_rows=64, width_cols=64, directed=False),
    ):
        sk = GLavaSketch.empty(cfg, jax.random.key(1))
        src, dst, w = _stream(rng, 150)
        sk = sk.update(src, dst, w)
        _assert_registers_fresh(sk, err=str(cfg))


def test_registers_conservative_update():
    rng = np.random.default_rng(4)
    cfg = SketchConfig(depth=3, width_rows=32, width_cols=32)
    sk = GLavaSketch.empty(cfg, jax.random.key(0))
    src, dst, w = _stream(rng, 200, n_nodes=60)
    sk = sk.update_conservative(src, dst, w)
    _assert_registers_fresh(sk, err="after conservative update")


def test_positional_construction_backfills_registers():
    """Old call sites construct GLavaSketch without registers — __post_init__
    derives them from the counters."""
    cfg = SketchConfig(depth=2, width_rows=16, width_cols=16)
    tmpl = GLavaSketch.empty(cfg, jax.random.key(0))
    counters = jnp.asarray(
        np.random.default_rng(0).integers(0, 9, (2, 16, 16)), jnp.float32
    )
    sk = GLavaSketch(counters, tmpl.row_hash, tmpl.col_hash, cfg)
    _assert_registers_fresh(sk)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 1000),
    ops=st.lists(
        st.sampled_from(["update", "advance"]), min_size=1, max_size=8
    ),
)
def test_window_registers_bitmatch(seed, ops):
    rng = np.random.default_rng(seed)
    cfg = SketchConfig(depth=2, width_rows=32, width_cols=32)
    win = SlidingWindowSketch.empty(cfg, n_slices=3, key=jax.random.key(0))
    for op in ops:
        if op == "update":
            src, dst, w = _stream(rng, int(rng.integers(1, 40)))
            win = win.update(src, dst, w)
        else:
            win = win.advance()
    # per-slice registers match per-slice counter marginals...
    np.testing.assert_array_equal(
        np.asarray(win.row_flows), np.asarray(jnp.sum(win.slices, axis=3))
    )
    np.testing.assert_array_equal(
        np.asarray(win.col_flows), np.asarray(jnp.sum(win.slices, axis=2))
    )
    # ...and the materialized window sketch inherits exact registers.
    _assert_registers_fresh(win.window_sketch(), err="window_sketch")


# ---------------------------------------------------------------------------
# register-served queries: no full-counter reduction in the jaxpr
# ---------------------------------------------------------------------------


# The jaxpr walking + reduction detection lives in the shared analysis
# plane now (repro.analysis.jaxpr_lint drives it over the whole entry-point
# registry); this test keeps the focused per-family assertions.
from repro.analysis import reduces_full_counters as _reduces_full_counters


def test_point_queries_have_no_counter_reduction():
    cfg = SketchConfig(depth=3, width_rows=64, width_cols=64)
    sk = GLavaSketch.empty(cfg, jax.random.key(0))
    keys = jnp.zeros(8, jnp.uint32)
    shape = tuple(sk.counters.shape)
    assert not _reduces_full_counters(queries.node_in_flow, shape, sk, keys)
    assert not _reduces_full_counters(queries.node_out_flow, shape, sk, keys)
    assert not _reduces_full_counters(
        lambda s, k: queries.check_heavy_keys(s, k, 10.0), shape, sk, keys
    )

    def monitor(s, src, dst, w, watch):
        return queries.monitor_step(s, src, dst, w, watch, theta=100.0)

    src = jnp.zeros(16, jnp.uint32)
    w = jnp.ones(16, jnp.float32)
    assert not _reduces_full_counters(
        monitor, shape, sk, src, src, w, keys[0]
    )
    # sanity: the recompute path DOES reduce the counters (the checker works)
    assert _reduces_full_counters(
        lambda s, k: jnp.min(
            jnp.take_along_axis(jnp.sum(s.counters, axis=1), s.col_hash(k), axis=1),
            axis=0,
        ),
        shape,
        sk,
        keys,
    )


# ---------------------------------------------------------------------------
# monitor oracle + heavy hitters
# ---------------------------------------------------------------------------


def test_monitor_step_matches_recompute_oracle():
    rng = np.random.default_rng(5)
    cfg = SketchConfig(depth=3, width_rows=64, width_cols=64)
    sk = GLavaSketch.empty(cfg, jax.random.key(2))
    watch = jnp.asarray(7, jnp.uint32)
    for step in range(6):
        src, dst, w = _stream(rng, 50, n_nodes=30)
        # Oracle: in-flow from freshly recomputed column sums (the pre-PR
        # semantics), alarm decision recomputed by hand.
        oracle_sk = sk.with_counters(sk.counters)
        col_sums = jnp.sum(oracle_sk.counters, axis=1)
        h = oracle_sk.col_hash(watch[None])
        inflow = jnp.min(jnp.take_along_axis(col_sums, h, axis=1), axis=0)[0]
        hits = jnp.sum((dst == watch) * w)
        for theta in (float(inflow + hits) - 0.5, float(inflow + hits) + 10.0):
            want = bool(inflow + hits > theta)
            alarm, _ = queries.monitor_step(sk, src, dst, w, watch, theta)
            assert bool(alarm) == want, f"step {step} theta {theta}"
        _, sk = queries.monitor_step(sk, src, dst, w, watch, 1e9)
        _assert_registers_fresh(sk, err=f"after monitor step {step}")


def test_heavy_hitters_no_false_negatives():
    rng = np.random.default_rng(6)
    cfg = SketchConfig(depth=3, width_rows=16, width_cols=16)  # collision-heavy
    sk = GLavaSketch.empty(cfg, jax.random.key(3))
    n_nodes = 50
    src, dst, w = _stream(rng, 1000, n_nodes=n_nodes)
    sk = sk.update(src, dst, w)
    exact_in = np.zeros(n_nodes)
    exact_out = np.zeros(n_nodes)
    for s, d, wt in zip(np.asarray(src), np.asarray(dst), np.asarray(w)):
        exact_out[int(s)] += float(wt)
        exact_in[int(d)] += float(wt)
    keys = jnp.arange(n_nodes, dtype=jnp.uint32)
    for theta in (np.percentile(exact_in, 50), np.percentile(exact_in, 90)):
        in_flag, out_flag = queries.check_heavy_keys(sk, keys, float(theta))
        # CountMin over-estimates: every true heavy hitter MUST be flagged.
        assert np.all(np.asarray(in_flag)[exact_in > theta])
        assert np.all(np.asarray(out_flag)[exact_out > theta])


# ---------------------------------------------------------------------------
# QueryEngine dispatch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def loaded_sketch():
    rng = np.random.default_rng(1)
    cfg = SketchConfig(depth=3, width_rows=128, width_cols=128)
    sk = GLavaSketch.empty(cfg, jax.random.key(1))
    src, dst, w = _stream(rng, 2000, n_nodes=500)
    return sk.update(src, dst, w), src, dst


@pytest.mark.parametrize("q", [1, 17, 256, 300])
def test_engine_matches_direct_queries_ragged_batches(loaded_sketch, q):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp")
    qs, qd = src[:q], dst[:q]
    np.testing.assert_array_equal(
        np.asarray(eng.edge(sk, qs, qd)),
        np.asarray(queries.edge_query(sk, qs, qd)),
    )
    np.testing.assert_array_equal(
        np.asarray(eng.in_flow(sk, qs)), np.asarray(queries.node_in_flow(sk, qs))
    )
    np.testing.assert_array_equal(
        np.asarray(eng.out_flow(sk, qs)),
        np.asarray(queries.node_out_flow(sk, qs)),
    )


def test_engine_chunking_matches_direct(loaded_sketch):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp", pad_q=8, chunk_q=16)
    q = 37  # 2 full chunks + ragged tail, tail padded 5->8
    np.testing.assert_array_equal(
        np.asarray(eng.edge(sk, src[:q], dst[:q])),
        np.asarray(queries.edge_query(sk, src[:q], dst[:q])),
    )


@pytest.mark.parametrize("q", [0, 1, 8, 16, 17, 37])
def test_padded_len_counts_the_key_slots_dispatched(loaded_sketch, q):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp", pad_q=8, chunk_q=16)
    fn, slots = eng._fn("in_flow"), []
    eng._jits["in_flow"] = lambda s, keys: slots.append(keys.shape[0]) or fn(s, keys)
    got = eng.in_flow(sk, src[:q])
    assert sum(slots) == eng.padded_len(q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(queries.node_in_flow(sk, src[:q])))


def test_engine_pallas_backend_matches_jnp(loaded_sketch):
    sk, src, dst = loaded_sketch
    a = QueryEngine("jnp")
    b = QueryEngine("pallas")
    qs, qd = src[:100], dst[:100]
    np.testing.assert_array_equal(
        np.asarray(a.edge(sk, qs, qd)), np.asarray(b.edge(sk, qs, qd))
    )
    rq = jnp.asarray([1, 5, 9], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(a.reach(sk, rq, rq, epoch=0)),
        np.asarray(b.reach(sk, rq, rq, epoch=0)),
    )


def test_engine_backends_dtype_agree_int_undirected():
    """Both backends must return the COUNTER dtype, including through the
    undirected self-loop correction (int stays int)."""
    import dataclasses

    cfg = SketchConfig(depth=2, width_rows=64, width_cols=64, directed=False)
    sk = GLavaSketch.empty(cfg, jax.random.key(0))
    src = jnp.asarray([5, 5, 9], jnp.uint32)
    dst = jnp.asarray([5, 7, 9], jnp.uint32)
    sk = sk.update(src, dst, jnp.asarray([3, 2, 1], jnp.float32))
    cast = dataclasses.replace(
        sk,
        counters=sk.counters.astype(jnp.int32),
        row_flows=sk.row_flows.astype(jnp.int32),
        col_flows=sk.col_flows.astype(jnp.int32),
    )
    got_j = QueryEngine("jnp").edge(cast, src, dst)
    got_p = QueryEngine("pallas").edge(cast, src, dst)
    assert got_j.dtype == jnp.int32
    assert got_p.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_j), np.asarray(got_p))
    np.testing.assert_array_equal(np.asarray(got_j), [3, 2, 1])


def test_engine_heavy_and_subgraph(loaded_sketch):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp")
    keys = src[:33]
    in_h, out_h = eng.heavy(sk, keys, 10.0)
    ref_in, ref_out = queries.check_heavy_keys(sk, keys, 10.0)
    np.testing.assert_array_equal(np.asarray(in_h), np.asarray(ref_in))
    np.testing.assert_array_equal(np.asarray(out_h), np.asarray(ref_out))
    assert in_h.shape == keys.shape
    np.testing.assert_array_equal(
        np.asarray(eng.subgraph(sk, src[:3], dst[:3])),
        np.asarray(queries.subgraph_query(sk, src[:3], dst[:3])),
    )


def test_engine_closure_epoch_cache(loaded_sketch):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp")
    qs = jnp.asarray([1, 2], jnp.uint32)
    eng.reach(sk, qs, qs, epoch=0)
    assert eng.closure_refreshes == 1
    eng.reach(sk, qs, qs, epoch=0)  # cached
    assert eng.closure_refreshes == 1
    eng.reach(sk, qs, qs, epoch=1)  # sketch changed -> rebuild
    assert eng.closure_refreshes == 2
    eng.invalidate()
    eng.reach(sk, qs, qs, epoch=1)
    assert eng.closure_refreshes == 3
    # results against the cached closure equal the from-scratch query
    from repro.core import reach as reach_mod

    got = eng.reach(sk, src[:20], dst[:20], epoch=1)
    ref = reach_mod.reach_query(sk, src[:20], dst[:20])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_engine_jit_cache_is_persistent(loaded_sketch):
    sk, src, dst = loaded_sketch
    eng = QueryEngine("jnp")
    eng.edge(sk, src[:64], dst[:64])
    fn = eng._jits["edge"]
    eng.edge(sk, src[:64], dst[:64])
    assert eng._jits["edge"] is fn  # same jitted callable, no re-wrap


def test_resolve_query_backend_env(monkeypatch):
    from repro.core.query_engine import resolve_query_backend

    monkeypatch.setenv("REPRO_QUERY_BACKEND", "pallas")
    assert resolve_query_backend("auto") == "pallas"
    monkeypatch.delenv("REPRO_QUERY_BACKEND")
    assert resolve_query_backend(None) in ("jnp", "pallas")
    with pytest.raises(ValueError):
        resolve_query_backend("nope")


# ---------------------------------------------------------------------------
# checkpoint schema evolution (register-less sketches)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrips_registers(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    rng = np.random.default_rng(2)
    cfg = SketchConfig(depth=2, width_rows=32, width_cols=32)
    sk = GLavaSketch.empty(cfg, jax.random.key(4))
    src, dst, w = _stream(rng, 100)
    sk = sk.update(src, dst, w)
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, sk)
    restored, meta = mgr.restore(like=sk)
    assert meta["step"] == 1
    np.testing.assert_array_equal(
        np.asarray(restored.row_flows), np.asarray(sk.row_flows)
    )
    _assert_registers_fresh(restored, err="restored")


def test_checkpoint_fill_missing_for_old_sketches(tmp_path):
    """A checkpoint saved WITHOUT registers restores into the new schema:
    missing float leaves fill with NaN (stale reads fail loudly instead of
    silently answering 0), are reported, and with_counters rebuilds them
    exactly."""
    from repro.checkpoint.manager import CheckpointManager

    rng = np.random.default_rng(3)
    cfg = SketchConfig(depth=2, width_rows=32, width_cols=32)
    sk = GLavaSketch.empty(cfg, jax.random.key(5))
    src, dst, w = _stream(rng, 100)
    sk = sk.update(src, dst, w)
    mgr = CheckpointManager(tmp_path)
    # old-schema state: counters + hashes only (what a pre-register
    # checkpoint held)
    mgr.save(7, {"counters": sk.counters})
    like = {
        "counters": sk.counters,
        "row_flows": sk.row_flows,
        "col_flows": sk.col_flows,
    }
    with pytest.raises(KeyError):
        mgr.restore(like=like)
    restored, meta = mgr.restore(like=like, fill_missing=True)
    assert sorted(meta["filled_leaves"]) == ["['col_flows']", "['row_flows']"]
    assert np.all(np.isnan(np.asarray(restored["row_flows"])))
    rebuilt = sk.with_counters(restored["counters"])
    _assert_registers_fresh(rebuilt, err="rebuilt from old checkpoint")
    np.testing.assert_array_equal(
        np.asarray(rebuilt.counters), np.asarray(sk.counters)
    )
