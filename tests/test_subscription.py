"""Standing-query plane tests: subscription lifecycle (register → compiled
plan → mutation-driven re-eval → event emission), incremental closure
refresh (element-identity to from-scratch closures under random
ingest/delete/advance_window sequences, the 1-full-build + N-incremental
acceptance count, staleness-budget fallback), subscription results
bit-matching the one-shot ``gs.query`` oracle at every tick, the
empty-QueryBatch fast path, and θ validation."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import (
    GraphStream,
    IngestReceipt,
    Query,
    QueryBatch,
    SketchConfig,
    Subscription,
    validate_theta,
)
from repro.core import GLavaSketch, QueryEngine, reach
from repro.core.query_engine import CLOSURE_REFRESH_PAD_T


CFG = SketchConfig(depth=3, width_rows=128, width_cols=128)


def _open(**kw):
    return GraphStream.open(
        CFG, ingest_backend="scatter", query_backend="jnp", **kw
    )


def _batches(rng, n, size=12, nodes=400):
    return [
        (
            rng.integers(0, nodes, size).astype(np.uint32),
            rng.integers(0, nodes, size).astype(np.uint32),
        )
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# satellite: empty QueryBatch returns [] without touching the engine
# ---------------------------------------------------------------------------


def test_empty_batch_returns_empty_without_engine():
    gs = _open()
    gs.ingest([1, 2], [2, 3])
    gs.query(Query.edge(1, 2))  # warm: some dispatches exist
    before = dict(gs.engine.dispatches)
    served = gs.stats.queries_served
    assert gs.query(QueryBatch([])) == []
    assert gs.query() == []
    assert dict(gs.engine.dispatches) == before  # engine untouched
    assert gs.stats.queries_served == served


def test_empty_batch_does_not_flush():
    gs = _open()
    gs.ingest(np.arange(64, dtype=np.uint32), np.arange(64, dtype=np.uint32))
    inflight = len(gs._inflight)
    assert gs.query(QueryBatch([])) == []
    assert len(gs._inflight) == inflight  # no flush either


# ---------------------------------------------------------------------------
# satellite: θ validation (0 < θ <= 1) at every construction site
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bad", [0.0, -0.5, 1.5, 600.0, float("nan"), float("inf"), "half", None]
)
def test_theta_validation_rejects(bad):
    with pytest.raises(ValueError):
        validate_theta(bad)
    with pytest.raises(ValueError):
        Query.heavy(7, theta=bad)
    gs = _open()
    with pytest.raises(ValueError):
        gs.monitor([1], [2], np.ones(1, np.float32), watch=2, theta=bad)


def test_theta_validation_accepts_boundaries():
    assert validate_theta(1.0) == 1.0
    assert validate_theta(1e-9) == 1e-9
    assert Query.heavy(7, theta=0.5).theta == 0.5


def test_subscription_validates_every_and_batch():
    gs = _open()
    with pytest.raises(ValueError):
        gs.subscribe(every=1)  # no queries
    with pytest.raises(ValueError):
        gs.subscribe(Query.in_flow(1), every=0)


# ---------------------------------------------------------------------------
# subscription lifecycle: registration -> re-eval cadence -> events
# ---------------------------------------------------------------------------


def test_subscription_event_cadence_and_payload():
    gs = _open()
    rng = np.random.default_rng(0)
    seen = []
    sub = gs.subscribe(
        Query.in_flow(np.arange(6, dtype=np.uint32)),
        Query.edge(1, 2),
        every=3,
        on_result=seen.append,
        name="cadence",
    )
    assert isinstance(sub, Subscription)
    for s, d in _batches(rng, 7):
        gs.ingest(s, d)
    # 7 mutations, every=3 -> ticks after mutations 3 and 6
    assert sub.ticks == 2
    events = sub.poll()
    assert [e.tick for e in events] == [1, 2]
    assert [e.epoch for e in events] == [3, 6]
    assert seen == events  # callback saw the same events, in order
    ev = events[-1]
    assert ev.subscription_id == sub.id and ev.name == "cadence"
    assert ev.timestamp > 0 and ev.alarm is None
    assert len(ev.results) == 2
    assert ev.results[0].query is sub.batch[0]  # request-ordered
    # the session-wide feed carries both events
    assert [e.tick for e in gs.events()] == [1, 2]
    assert list(gs.events()) == []  # drained
    assert sub.poll() == []


def test_event_timestamp_is_taken_after_the_plan_answers(monkeypatch):
    gs = _open()
    sub = gs.subscribe(Query.in_flow(np.arange(4, dtype=np.uint32)), every=1)
    run, answered = sub.plan.run, []

    def slow_run(*args, **kw):
        time.sleep(0.05)
        out = run(*args, **kw)
        answered.append(time.time())
        return out

    monkeypatch.setattr(sub.plan, "run", slow_run)
    t0 = time.time()
    gs.ingest([1, 2], [3, 4])
    (event,) = sub.poll()
    assert event.timestamp >= answered[0] >= t0 + 0.05


def test_subscription_cancel_and_multiple_subscribers():
    gs = _open()
    rng = np.random.default_rng(1)
    a = gs.subscribe(Query.in_flow(1), every=1)
    b = gs.subscribe(Query.out_flow(2), every=2)
    for s, d in _batches(rng, 2):
        gs.ingest(s, d)
    assert (a.ticks, b.ticks) == (2, 1)
    a.cancel()
    a.cancel()  # idempotent
    assert not a.active
    assert gs.subscriptions == (b,)
    pending = a.pending
    for s, d in _batches(rng, 2):
        gs.ingest(s, d)
    assert (a.ticks, b.ticks) == (2, 2)  # a stopped, b kept ticking
    assert a.pending == pending  # cancelled: no new events delivered


def test_subscription_alarm_predicate():
    gs = _open()
    sub = gs.subscribe(
        Query.in_flow(7),
        every=1,
        alarm=lambda results: float(np.asarray(results[0].value)) > 100.0,
    )
    gs.ingest(np.zeros(5, np.uint32), np.full(5, 7, np.uint32))
    assert sub.poll()[-1].alarm is False
    gs.ingest(
        np.zeros(20, np.uint32),
        np.full(20, 7, np.uint32),
        np.full(20, 10.0, np.float32),
    )
    assert sub.poll()[-1].alarm is True


def test_subscription_fires_on_window_and_delete_mutations():
    gs = GraphStream.open(
        CFG, window_slices=2, ingest_backend="scatter", query_backend="jnp"
    )
    sub = gs.subscribe(Query.edge(10, 20), every=1)
    gs.ingest([10], [20])
    assert float(np.asarray(sub.poll()[-1].results[0].value)) == 1.0
    gs.advance_window()
    gs.advance_window()  # expiry wraps: the slice holding (10,20) zeroes
    assert sub.ticks == 3
    assert float(np.asarray(sub.poll()[-1].results[0].value)) == 0.0

    gs2 = _open()
    sub2 = gs2.subscribe(Query.edge(1, 2), every=1)
    gs2.ingest([1, 1], [2, 2])
    gs2.delete([1], [2])
    ticks = sub2.poll()
    assert [float(np.asarray(e.results[0].value)) for e in ticks] == [2.0, 1.0]


def test_ingest_returns_receipt_with_touched_keys():
    gs = _open()
    r = gs.ingest(np.asarray([5, 5, 9], np.uint32), np.asarray([7, 8, 9], np.uint32))
    assert isinstance(r, IngestReceipt)
    assert r.epoch == 1 and r.n_edges == 3
    np.testing.assert_array_equal(r.touched_keys, [5, 9])  # unique src keys
    # deletes are not additions-only: no touched set
    r2 = gs.delete(np.asarray([5], np.uint32), np.asarray([7], np.uint32))
    assert r2.touched_keys is None
    # tracking stays poisoned (hot path skips the scans) until the next
    # closure sync forces a full rebuild
    r3 = gs.ingest(np.asarray([1], np.uint32), np.asarray([2], np.uint32))
    assert r3.touched_keys is None


# ---------------------------------------------------------------------------
# incremental closure refresh: exactness, acceptance count, budget fallback
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_refresh_closure_matches_from_scratch(seed):
    """Property: after any additions-only history, refresh_closure(touched)
    is element-identical to a from-scratch transitive closure."""
    rng = np.random.default_rng(seed)
    sk = GLavaSketch.empty(
        SketchConfig(depth=2, width_rows=64, width_cols=64), jax.random.key(0)
    )
    eng = QueryEngine("jnp")
    src = jnp.asarray(rng.integers(0, 300, 150), jnp.uint32)
    dst = jnp.asarray(rng.integers(0, 300, 150), jnp.uint32)
    sk = sk.update(src, dst)
    eng.closure_for(sk, epoch=0)  # seed the cache: 1 full build
    epoch = 0
    for step in range(rng.integers(1, 4)):
        n = int(rng.integers(1, 10))
        s2 = rng.integers(0, 300, n).astype(np.uint32)
        d2 = rng.integers(0, 300, n).astype(np.uint32)
        sk = sk.update(jnp.asarray(s2), jnp.asarray(d2))
        epoch += 1
        got = eng.refresh_closure(sk, np.unique(s2), epoch=epoch)
        want = reach.transitive_closure(sk.counters)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=f"seed {seed} step {step}"
        )
    assert eng.closure_refreshes == 1  # never rebuilt from scratch again
    assert eng.closure_incremental_refreshes >= 1


def test_refresh_closure_pad_boundary_exact():
    """Touched counts straddling the pad width (T = 64) stay exact."""
    rng = np.random.default_rng(3)
    sk = GLavaSketch.empty(
        SketchConfig(depth=2, width_rows=512, width_cols=512), jax.random.key(1)
    )
    eng = QueryEngine("jnp")
    sk = sk.update(
        jnp.asarray(rng.integers(0, 2000, 400), jnp.uint32),
        jnp.asarray(rng.integers(0, 2000, 400), jnp.uint32),
    )
    eng.closure_for(sk, epoch=0)
    for i, n in enumerate(
        [CLOSURE_REFRESH_PAD_T - 1, CLOSURE_REFRESH_PAD_T, CLOSURE_REFRESH_PAD_T + 1]
    ):
        s2 = np.arange(5000 + 100 * i, 5000 + 100 * i + n, dtype=np.uint32)
        d2 = rng.integers(0, 2000, n).astype(np.uint32)
        sk = sk.update(jnp.asarray(s2), jnp.asarray(d2))
        got = eng.refresh_closure(sk, s2, epoch=i + 1)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(reach.transitive_closure(sk.counters))
        )
    assert eng.closure_refreshes == 1
    assert eng.closure_incremental_refreshes == 3


def test_refresh_closure_fallback_paths():
    rng = np.random.default_rng(4)
    sk = GLavaSketch.empty(
        SketchConfig(depth=2, width_rows=64, width_cols=64), jax.random.key(2)
    )
    sk = sk.update(
        jnp.asarray(rng.integers(0, 100, 80), jnp.uint32),
        jnp.asarray(rng.integers(0, 100, 80), jnp.uint32),
    )
    # no cached closure -> full build
    eng = QueryEngine("jnp")
    eng.refresh_closure(sk, np.asarray([1], np.uint32), epoch=0)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (1, 0)
    # touched=None (delete / unknown history) -> full build
    eng.refresh_closure(sk, None, epoch=1)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (2, 0)
    # touched fraction above the budget -> full build
    eng.refresh_closure(sk, np.arange(60, dtype=np.uint32), epoch=2)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (3, 0)
    # small touched set -> incremental
    eng.refresh_closure(sk, np.arange(4, dtype=np.uint32), epoch=3)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (3, 1)
    # fresh epoch -> no-op
    eng.refresh_closure(sk, np.arange(4, dtype=np.uint32), epoch=3)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (3, 1)
    # empty touched set retags without counting
    eng.refresh_closure(sk, np.zeros(0, np.uint32), epoch=4)
    assert (eng.closure_refreshes, eng.closure_incremental_refreshes) == (3, 1)
    assert eng._closure_epoch == 4


def test_refresh_closure_staleness_budget():
    rng = np.random.default_rng(5)
    sk = GLavaSketch.empty(
        SketchConfig(depth=2, width_rows=64, width_cols=64), jax.random.key(3)
    )
    sk = sk.update(
        jnp.asarray(rng.integers(0, 100, 80), jnp.uint32),
        jnp.asarray(rng.integers(0, 100, 80), jnp.uint32),
    )
    eng = QueryEngine("jnp", closure_staleness_budget=2)
    eng.closure_for(sk, epoch=0)
    for epoch in range(1, 4):
        sk = sk.update(jnp.asarray([epoch], jnp.uint32), jnp.asarray([0], jnp.uint32))
        eng.refresh_closure(sk, np.asarray([epoch], np.uint32), epoch=epoch)
    # budget 2: refreshes at epochs 1, 2 incremental; epoch 3 rebuilt full
    assert eng.closure_incremental_refreshes == 2
    assert eng.closure_refreshes == 2


# ---------------------------------------------------------------------------
# THE acceptance property: reach subscription over N batches = 1 full build
# + N incremental refreshes, bit-identical to the one-shot oracle per tick
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000))
def test_reach_subscription_incremental_and_oracle_identical(seed):
    rng = np.random.default_rng(seed)
    gs = _open()
    oracle = _open()  # replayed mutations, fresh-engine one-shot pulls

    qs = rng.integers(0, 400, 16).astype(np.uint32)
    qd = rng.integers(0, 400, 16).astype(np.uint32)
    workload = QueryBatch(
        [
            Query.reach(qs, qd),
            Query.in_flow(qs[:8]),
            Query.heavy(qs[:4], theta=0.01),
            Query.edge(qs[:8], qd[:8]),
        ]
    )
    sub = gs.subscribe(workload, every=1, name="acceptance")

    n_batches = 6
    seed_batch = _batches(rng, 1, size=60)[0]
    batches = [seed_batch] + _batches(rng, n_batches - 1)
    for s, d in batches:
        gs.ingest(s, d)
        oracle.ingest(s, d)
        # one-shot oracle: a FRESH engine answers from scratch
        oracle.engine.invalidate()
        want = oracle.query(QueryBatch(list(workload)))
        got = sub.poll()[-1].results
        for i, (g, w) in enumerate(zip(got, want)):
            if isinstance(g.value, tuple):
                for gg, ww in zip(g.value, w.value):
                    np.testing.assert_array_equal(
                        np.asarray(gg), np.asarray(ww),
                        err_msg=f"seed {seed} slot {i}",
                    )
            else:
                np.testing.assert_array_equal(
                    np.asarray(g.value), np.asarray(w.value),
                    err_msg=f"seed {seed} slot {i}",
                )

    # at most 1 full closure build; every other tick refreshed incrementally
    assert gs.engine.closure_refreshes == 1
    assert gs.engine.closure_incremental_refreshes == n_batches - 1
    assert gs.stats.subscription_ticks == n_batches


def test_subscription_delete_forces_one_full_rebuild_then_incremental():
    rng = np.random.default_rng(9)
    gs = _open()
    sub = gs.subscribe(Query.reach(1, 2), every=1)
    for s, d in _batches(rng, 3):
        gs.ingest(s, d)
    assert gs.engine.closure_refreshes == 1
    assert gs.engine.closure_incremental_refreshes == 2
    gs.delete([1], [2])  # not additions-only -> full rebuild on next tick
    assert gs.engine.closure_refreshes == 2
    for s, d in _batches(rng, 2):
        gs.ingest(s, d)
    assert gs.engine.closure_refreshes == 2  # back to incremental
    assert gs.engine.closure_incremental_refreshes == 4
    assert sub.ticks == 6


# ---------------------------------------------------------------------------
# delete-driven rebuild property: any interleaving of ingest / delete /
# advance serves reach (and register families) bit-identical to an oracle
# that replays the same mutations and rebuilds from scratch every tick
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_closure_under_interleaved_deletes_matches_oracle(seed):
    """Property: the subscription plane's closure maintenance (incremental
    refreshes, delete-poisoned full rebuilds, window expiry) never drifts
    from a from-scratch oracle, no matter how ingest / delete / advance
    interleave.  Deletes replay earlier edges with negated weights, so the
    turnstile path must cancel exactly."""
    rng = np.random.default_rng(seed)
    gs = _open(window_slices=4)
    oracle = _open(window_slices=4)

    qs = rng.integers(0, 400, 12).astype(np.uint32)
    qd = rng.integers(0, 400, 12).astype(np.uint32)
    workload = QueryBatch(
        [Query.reach(qs, qd), Query.in_flow(qs[:6]), Query.edge(qs[:6], qd[:6])]
    )
    sub = gs.subscribe(workload, every=1, name="oracle-check")

    history = []  # ingested (src, dst) batches, the delete pool
    n_deletes = 0
    for step in range(10):
        op = rng.choice(["ingest", "ingest", "delete", "advance"])
        if op == "delete" and history:
            s, d = history[rng.integers(0, len(history))]
            k = max(1, s.size // 2)
            gs.delete(s[:k], d[:k])
            oracle.delete(s[:k], d[:k])
            n_deletes += 1
        elif op == "advance":
            gs.advance_window()
            oracle.advance_window()
        else:
            s, d = _batches(rng, 1)[0]
            history.append((s, d))
            gs.ingest(s, d)
            oracle.ingest(s, d)
        oracle.engine.invalidate()  # from-scratch answers, every tick
        want = oracle.query(QueryBatch(list(workload)))
        got = sub.poll()[-1].results
        for i, (g, w) in enumerate(zip(got, want)):
            gv = g.value if isinstance(g.value, tuple) else (g.value,)
            wv = w.value if isinstance(w.value, tuple) else (w.value,)
            for gg, ww in zip(gv, wv):
                np.testing.assert_array_equal(
                    np.asarray(gg), np.asarray(ww),
                    err_msg=f"seed {seed} step {step} op {op} slot {i}",
                )
    assert sub.ticks == 10
    # every delete poisons touched-key tracking: the NEXT closure sync is
    # a full rebuild (cheaper histories may coalesce several into one)
    if n_deletes:
        assert gs.engine.closure_refreshes >= 1
