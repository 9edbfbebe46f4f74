"""The ingest work count on hand-checked batches, and the generator."""
import numpy as np
import pytest

from bench import work
from bench.traffic import MIN_BATCH, Arrivals, Pool, Seeds, kronecker, make_pool, scramble

GRAPH = {"scale": 10, "initiator": [0.57, 0.19, 0.19, 0.05]}


def test_batch_work_counts_by_hand():
    pool = Pool(
        src=np.array([1, 1, 2, 3], np.uint32),
        dst=np.array([5, 5, 5, 6], np.uint32),
        weight=np.ones(4, np.float32),
    )
    w = work.batch_work(pool, 0, 4)
    assert (w.edges, w.pairs, w.sources, w.destinations) == (4, 3, 3, 2)
    assert work.ingest_bytes(w, depth=5) == 12 * 4 + 8 * 5 * (3 + 3 + 2)


def test_pool_is_a_function_of_the_seed():
    big = 2**31 + 2**30 + 12345
    a = make_pool(GRAPH, Seeds(big), pool_edges=1000)
    b = make_pool(GRAPH, Seeds(big), pool_edges=1000)
    c = make_pool(GRAPH, Seeds(big + 1), pool_edges=1000)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.weight, b.weight)
    assert not np.array_equal(a.src, c.src)
    assert a.src.max() < 1024 and a.dst.max() < 1024
    assert 0.0 <= a.weight.min() and a.weight.max() < 1.0


def test_scramble_is_a_bijection():
    v = np.arange(1 << 12, dtype=np.uint32)
    out = scramble(v, 12, np.random.default_rng(5))
    assert np.array_equal(np.sort(out), v) and not np.array_equal(out, v)


def test_kronecker_bits_follow_the_initiator():
    """At one level (where the scramble is the identity) an edge falls in
    each quadrant with the initiator's odds A, B, C, D."""
    graph = {"scale": 1, "initiator": [0.57, 0.19, 0.19, 0.05]}
    n = 400_000
    s, d = kronecker(graph, np.random.default_rng(0), n)
    quad = np.bincount(2 * s.astype(np.int64) + d, minlength=4) / n
    assert quad == pytest.approx([0.57, 0.19, 0.19, 0.05], abs=4e-3)


def test_poisson_pickup_waits_for_min_batch_and_caps():
    cap = 3 * MIN_BATCH
    mix = {"arrivals": {"mode": "poisson", "rate": 1e5, "cap": cap}}
    arr = Arrivals(mix, Seeds(1), seconds=2.0)
    n, wait = arr.pickup(0, 0.0)
    assert n == 0 and wait == arr.due[MIN_BATCH - 1]
    n, wait = arr.pickup(0, float(arr.due[MIN_BATCH - 1]))
    assert n == MIN_BATCH and wait == 0.0
    n, _ = arr.pickup(MIN_BATCH, 1.0)
    assert n == cap  # a backlog is handed over a cap at a time
