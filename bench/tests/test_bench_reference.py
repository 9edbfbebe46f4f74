"""The plain reference against brute force at a tiny size, and its hash
family against the program's."""
import numpy as np
import pytest

from bench import reference
from bench.traffic import Pool, QuerySet

CONFIG = {"sketch": {"depth": 2, "width_rows": 16, "width_cols": 16, "directed": True}}
HOSTS = 64


def _pool(seed=0, n=600):
    rng = np.random.default_rng(seed)
    return Pool(
        src=rng.integers(0, HOSTS, n).astype(np.uint32),
        dst=rng.integers(0, HOSTS, n).astype(np.uint32),
        weight=rng.integers(1, 301, n).astype(np.float32),
    )


def _queries(rng):
    es = rng.integers(0, HOSTS, 12).astype(np.uint32)
    ed = rng.integers(0, HOSTS, 12).astype(np.uint32)
    return QuerySet(es, ed, es[:6], es[:4], 0.05, es[:5], ed[5:10])


def _dense(h, pool, batches):
    """Counters after each batch, by np.add.at over every edge."""
    c = np.zeros((h.depth, h.wr, h.wc))
    out = []
    for s, m in batches:
        idx = pool.index(s, m)
        for i in range(h.depth):
            np.add.at(c[i], (h.row(pool.src[idx])[i], h.col(pool.dst[idx])[i]),
                      pool.weight[idx].astype(np.float64))
        out.append(c.copy())
    return out


def _closure(adj):
    a = (adj > 0) | np.eye(adj.shape[0], dtype=bool)
    for _ in range(8):
        a = a | ((a.astype(np.int64) @ a.astype(np.int64)) > 0)
    return a


def test_replay_and_reach_match_brute_force():
    rng = np.random.default_rng(3)
    pool = _pool()
    h = reference.Hashes(CONFIG, 12345)
    batches = [(0, 50), (50, 7), (590, 30), (100, 200)]  # the third wraps the pool
    qs = _queries(rng)
    dense = _dense(h, pool, batches)
    at = np.arange(len(batches))
    got = reference.Replay(h, pool, batches, qs).answers(at)
    reach = reference.reach_answers(h, pool, batches, qs, at)
    totals = np.cumsum([pool.weight[pool.index(s, m)].astype(np.float64).sum() for s, m in batches])
    d = range(h.depth)
    for k, c in enumerate(dense):
        er, ec = h.row(qs.edge_src), h.col(qs.edge_dst)
        edge = np.min([c[i][er[i], ec[i]] for i in d], axis=0)
        inflow = np.min([c[i].sum(axis=0)[h.col(qs.in_flow)[i]] for i in d], axis=0)
        hin = np.min([c[i].sum(axis=0)[h.col(qs.heavy)[i]] for i in d], axis=0)
        hout = np.min([c[i].sum(axis=1)[h.row(qs.heavy)[i]] for i in d], axis=0)
        np.testing.assert_array_equal(got["edge"][k], edge)
        np.testing.assert_array_equal(got["in_flow"][k], inflow)
        np.testing.assert_array_equal(got["heavy_in"][k], hin > qs.theta * totals[k])
        np.testing.assert_array_equal(got["heavy_out"][k], hout > qs.theta * totals[k])
        rs, rd = h.row(qs.reach_src), h.row(qs.reach_dst)
        want = np.all([_closure(c[i])[rs[i], rd[i]] for i in d], axis=0)
        np.testing.assert_array_equal(reach[k], want)


def test_bf16_control_rounds_every_batch():
    pool = _pool(n=4000)
    h = reference.Hashes(CONFIG, 7)
    batches = [(i * 400, 400) for i in range(10)]
    qs = _queries(np.random.default_rng(1))
    rep = reference.Replay(h, pool, batches, qs)
    at = np.arange(len(batches))
    exact, control = rep.answers(at), rep.answers(at, control=True)
    assert reference.rel_err(control["edge"], exact["edge"]) > 1e-3


def test_hash_family_is_the_programs():
    """The reference derives the family by the stated recipe; the program's
    session must have drawn the same one."""
    import jax
    from repro.core.sketch import GLavaSketch, SketchConfig

    for seed in (0, 2**31 - 2):
        h = reference.Hashes(CONFIG, seed)
        sk = GLavaSketch.empty(SketchConfig(2, 16, 16), jax.random.key(seed))
        keys = np.arange(HOSTS, dtype=np.uint32)
        np.testing.assert_array_equal(np.asarray(sk.row_hash(keys)), h.row(keys))
        np.testing.assert_array_equal(np.asarray(sk.col_hash(keys)), h.col(keys))


@pytest.mark.parametrize("flow,cut,want", [(101.0, 100.0, 1), (100.05, 100.0, 0)])
def test_heavy_band(flow, cut, want):
    got = reference.heavy_wrong(np.array([False]), np.array([True]), np.array([flow]),
                                np.array([cut]), band=1e-3)
    assert got == want
