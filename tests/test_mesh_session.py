"""A session whose counters do not fit one device: the placement rule,
the row-sharded session it opens (4 placeholder host devices, Pallas in
interpret mode) against one device and a numpy replay, and the deployment's
split of the hash functions over hosts, each host answering from its own."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api.stream import row_shards
from repro.configs import glava
from repro.core import queries
from repro.core.hashing import HashFamily
from repro.core.sketch import GLavaSketch, SketchConfig

GB = 10**9
WEB_SHARE = SketchConfig(depth=2, width_rows=65536, width_cols=65536)


class _Device:
    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


@pytest.mark.parametrize(
    "config,limits,want",
    [
        (glava.BASE, [16 * GB] * 4, 1),       # 1.34 GB fits one chip: stays local
        (WEB_SHARE, [16 * GB] * 4, 4),        # 34.4 GB: rows over all four
        (WEB_SHARE, [16 * GB] * 8, 8),
        (WEB_SHARE, [None] * 4, 1),           # no memory reported (the CPU)
        (WEB_SHARE, [16 * GB], 1),            # nothing to shard over
        (SketchConfig(2, 8192, 8192), [16 * GB, 1 * GB], 2),  # the smallest device decides
    ],
    ids=["base-fits", "web-share-4", "web-share-8", "no-memory-stats", "one-device", "smallest"],
)
def test_placement_rule(config, limits, want):
    n = row_shards(config, [_Device(x) for x in limits])
    assert n == want
    assert config.width_rows % n == 0


def test_placement_refuses_rows_that_do_not_divide():
    with pytest.raises(ValueError, match="do not divide"):
        row_shards(WEB_SHARE, [_Device(16 * GB)] * 3)


def test_host_shares_answer_as_the_whole_family():
    """The deployment splits d=4 hash functions over two hosts, two each;
    the minimum of the hosts' partial answers is the d=4 answer."""
    cfg4 = SketchConfig(depth=4, width_rows=256, width_cols=256)
    whole = GLavaSketch.empty(cfg4, jax.random.key(5))

    def share(lo):
        h = whole.row_hash
        part = HashFamily(a=h.a[lo:lo + 2], b=h.b[lo:lo + 2], w=h.w)
        cfg2 = SketchConfig(depth=2, width_rows=256, width_cols=256)
        empty = GLavaSketch.empty(cfg2, jax.random.key(0))
        return GLavaSketch(empty.counters, part, part, cfg2, empty.row_flows, empty.col_flows)

    shares = [share(0), share(2)]
    rng = np.random.default_rng(3)
    for _ in range(3):
        src, dst = rng.integers(0, 2000, (2, 3000)).astype(np.uint32)
        w = rng.integers(0, 5, 3000).astype(np.float32)
        whole = whole.update(src, dst, w, backend="scatter")
        shares = [s.update(src, dst, w, backend="scatter") for s in shares]
    qs, qd = rng.integers(0, 2000, (2, 512)).astype(np.uint32)
    for fn, args in (
        (queries.edge_query, (qs, qd)),
        (queries.node_in_flow, (qd,)),
        (queries.node_out_flow, (qs,)),
    ):
        partial = [np.asarray(fn(s, *args)) for s in shares]
        np.testing.assert_array_equal(np.minimum(*partial), np.asarray(fn(whole, *args)))


_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_INGEST_BACKEND"] = "pallas"  # `auto` as on a TPU
    import jax
    import numpy as np
    import repro.api.stream as stream
    from repro.api import GraphStream, Query, QueryBatch, SketchConfig
    from repro.core.hashing import affine_hash_np

    cfg = SketchConfig(depth=2, width_rows=2048, width_cols=1024)
    # Devices of 24 MB: the 16 MB of counters take more than half of one.
    stream._bytes_limit = lambda device: 24 << 20
    dist = GraphStream(cfg, seed=11)
    stream._bytes_limit = lambda device: None
    local = GraphStream(cfg, seed=11)
    assert local._mesh is None
    assert dict(dist._mesh.shape) == {"data": 1, "model": 4}, dist._mesh
    assert dist.ingest_backend == "pallas", dist.ingest_backend
    shards = dist._sketch.counters.addressable_shards
    assert sorted(s.data.shape[1] for s in shards) == [512] * 4
    assert len({s.device for s in shards}) == 4

    a = np.asarray(dist._sketch.row_hash.a, np.uint64)
    b = np.asarray(dist._sketch.row_hash.b, np.uint64)
    ac = np.asarray(dist._sketch.col_hash.a, np.uint64)
    bc = np.asarray(dist._sketch.col_hash.b, np.uint64)
    rows = lambda k: np.stack([affine_hash_np(k, a[i], b[i], 2048) for i in range(2)])
    cols = lambda k: np.stack([affine_hash_np(k, ac[i], bc[i], 1024) for i in range(2)])
    counters = np.zeros((2, 2048, 1024), np.float64)

    rng = np.random.default_rng(0)
    # Host-collapsed batches (>= 1024 edges) and a raw one; few nodes, so
    # pairs repeat; zero weights; rows of every shard.
    for n in (4096, 4096, 700):
        src, dst = rng.integers(0, 1500, (2, n)).astype(np.uint32)
        w = rng.integers(0, 4, n).astype(np.float32)
        dist.ingest(src, dst, w)
        local.ingest(src, dst, w)
        r, c = rows(src), cols(dst)
        for i in range(2):
            np.add.at(counters[i], (r[i], c[i]), w)
    assert {int(x) // 512 for x in r.ravel()} == {0, 1, 2, 3}

    for name in ("counters", "row_flows", "col_flows"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dist.sketch, name)), np.asarray(getattr(local.sketch, name)),
            err_msg=name)
    np.testing.assert_array_equal(np.asarray(dist.sketch.counters), counters)
    np.testing.assert_array_equal(np.asarray(dist.sketch.row_flows), counters.sum(axis=2))
    np.testing.assert_array_equal(np.asarray(dist.sketch.col_flows), counters.sum(axis=1))

    qs = np.concatenate([src[:300], rng.integers(0, 1500, 212)]).astype(np.uint32)
    qd = np.concatenate([dst[:300], rng.integers(0, 1500, 212)]).astype(np.uint32)
    batch = QueryBatch([
        Query.edge(qs, qd), Query.in_flow(qd[:256]), Query.heavy(qd[:64], theta=1e-3),
    ])
    got, want = dist.query(batch), local.query(batch)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.value), np.asarray(x.value), err_msg=g.query.family)
    d = np.arange(2)[:, None]
    np.testing.assert_array_equal(got[0].value, counters[d, rows(qs), cols(qd)].min(axis=0))
    in_flow = counters.sum(axis=1)[d, cols(qd[:256])].min(axis=0)
    np.testing.assert_array_equal(got[1].value, in_flow)
    total = counters.sum(axis=(1, 2)).min()
    np.testing.assert_array_equal(got[2].value[0], in_flow[:64] > np.float32(1e-3) * np.float32(total))
    print("MESH_OK")
    """
)


@pytest.mark.slow
def test_mesh_session_opened_with_defaults_matches_one_device_and_numpy():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_OK" in proc.stdout
