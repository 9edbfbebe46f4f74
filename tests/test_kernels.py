"""Per-kernel allclose sweeps: every Pallas kernel vs its ref.py pure-jnp
oracle across shapes and value regimes (interpret mode executes the kernel
body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GLavaSketch, SketchConfig, queries
from repro.core.hashing import make_hash_family
from repro.kernels.closure.ops import transitive_closure as closure_pallas
from repro.kernels.closure.ref import closure_step_ref
from repro.kernels.closure.kernel import closure_step_pallas
from repro.kernels.countsketch.ops import countsketch
from repro.kernels.countsketch.ref import countsketch_ref
from repro.kernels.flow.ops import flows
from repro.kernels.flow.ref import flows_ref
from repro.kernels.ingest.ops import sketch_ingest
from repro.kernels.ingest.ref import sketch_ingest_ref
from repro.kernels.ingest_fused.ops import fused_ingest
from repro.kernels.ingest_fused.ref import fused_ingest_ref
from repro.kernels.query.ops import edge_query_cells, edge_query_min
from repro.kernels.query.ref import edge_query_min_ref, edge_query_ref
from repro.core import reach as reach_mod
from repro.train.compression import CompressorConfig, init_compressor, _sketch

RNG = np.random.default_rng(7)


INGEST_SHAPES = [
    (1, 64, 64, 33),
    (2, 256, 256, 512),
    (3, 300, 200, 1000),
    (4, 512, 128, 2048),
]


@pytest.mark.parametrize("d,wr,wc,b", INGEST_SHAPES)
def test_ingest_kernel_matches_ref(d, wr, wc, b):
    # integer-valued counters/weights: the paper's counting regime, where the
    # kernel is bit-exact vs the scatter oracle (fp32 ints < 2**24)
    counters = jnp.asarray(RNG.integers(0, 1000, (d, wr, wc)), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, wr, (d, b)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, wc, (d, b)), jnp.int32)
    w = jnp.asarray(RNG.integers(1, 9, b), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sketch_ingest(counters, rows, cols, w)),
        np.asarray(sketch_ingest_ref(counters, rows, cols, w)),
    )


def test_ingest_kernel_fp_weights_close():
    counters = jnp.zeros((2, 128, 128), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, 128, (2, 700)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, 128, (2, 700)), jnp.int32)
    w = jnp.asarray(RNG.normal(0, 1, 700), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(sketch_ingest(counters, rows, cols, w)),
        np.asarray(sketch_ingest_ref(counters, rows, cols, w)),
        rtol=1e-6, atol=1e-5,
    )


@pytest.mark.parametrize("d,wr,wc,q", [(1, 64, 64, 17), (3, 256, 512, 300), (4, 300, 300, 1024)])
def test_query_kernel_matches_ref(d, wr, wc, q):
    counters = jnp.asarray(RNG.integers(0, 100, (d, wr, wc)), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, wr, (d, q)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, wc, (d, q)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(edge_query_cells(counters, rows, cols)),
        np.asarray(edge_query_ref(counters, rows, cols)),
    )


@pytest.mark.parametrize(
    "d,wr,wc,q", [(1, 64, 64, 17), (3, 256, 512, 300), (4, 300, 300, 1024)]
)
def test_fused_multi_query_kernel_matches_ref(d, wr, wc, q):
    """The fused kernel's in-pass Γ (min over d) bit-matches the jnp oracle."""
    counters = jnp.asarray(RNG.integers(0, 100, (d, wr, wc)), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, wr, (d, q)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, wc, (d, q)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(edge_query_min(counters, rows, cols)),
        np.asarray(edge_query_min_ref(counters, rows, cols)),
    )


def test_query_kernel_end_to_end_matches_core():
    cfg = SketchConfig(depth=3, width_rows=128, width_cols=128)
    sk = GLavaSketch.empty(cfg, jax.random.key(0))
    src = jnp.asarray(RNG.integers(0, 500, 400), jnp.uint32)
    dst = jnp.asarray(RNG.integers(0, 500, 400), jnp.uint32)
    sk = sk.update(src, dst)
    from repro.kernels.query.ops import edge_query as kernel_eq

    np.testing.assert_array_equal(
        np.asarray(kernel_eq(sk, src[:100], dst[:100])),
        np.asarray(queries.edge_query(sk, src[:100], dst[:100])),
    )


@pytest.mark.parametrize("w", [64, 256, 300])
def test_closure_step_matches_ref(w):
    a = (RNG.random((w, w)) < 0.02).astype(np.float32)
    if w % 256 == 0:
        out = np.asarray(closure_step_pallas(jnp.asarray(a)))
        np.testing.assert_array_equal(out, np.asarray(closure_step_ref(jnp.asarray(a))))
    # full closure (auto-padding path) vs jnp reference closure
    got = np.asarray(closure_pallas(jnp.asarray(a)))
    ref = np.asarray(reach_mod.transitive_closure(jnp.asarray(a)))
    np.testing.assert_array_equal(got, ref)


def test_closure_batched_over_sketches():
    a = (RNG.random((3, 64, 64)) < 0.03).astype(np.float32)
    got = np.asarray(closure_pallas(jnp.asarray(a)))
    ref = np.asarray(reach_mod.transitive_closure(jnp.asarray(a)))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("d,wr,wc", [(1, 64, 64), (3, 256, 512), (4, 300, 200)])
def test_flow_kernel_matches_ref(d, wr, wc):
    counters = jnp.asarray(RNG.integers(0, 50, (d, wr, wc)), jnp.float32)
    rs, cs = flows(counters)
    rs_ref, cs_ref = flows_ref(counters)
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(rs_ref))
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(cs_ref))


def test_flow_point_query_matches_core():
    cfg = SketchConfig(depth=3, width_rows=200, width_cols=200)
    sk = GLavaSketch.empty(cfg, jax.random.key(1))
    src = jnp.asarray(RNG.integers(0, 100, 300), jnp.uint32)
    dst = jnp.asarray(RNG.integers(0, 100, 300), jnp.uint32)
    sk = sk.update(src, dst)
    from repro.kernels.flow.ops import node_in_flow, node_out_flow

    keys = src[:20]
    np.testing.assert_array_equal(
        np.asarray(node_in_flow(sk, keys)), np.asarray(queries.node_in_flow(sk, keys))
    )
    np.testing.assert_array_equal(
        np.asarray(node_out_flow(sk, keys)), np.asarray(queries.node_out_flow(sk, keys))
    )


@pytest.mark.parametrize("n,w,d", [(100, 64, 3), (5000, 256, 5), (3000, 300, 4)])
def test_countsketch_kernel_matches_ref(n, w, d):
    fam = make_hash_family(jax.random.key(2), d, w)
    vec = jnp.asarray(RNG.normal(0, 1, n), jnp.float32)
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = fam(idx).astype(jnp.int32)
    s = fam.signs(idx)
    got = np.asarray(countsketch(vec, fam))
    ref = np.asarray(countsketch_ref(vec, h, s, w))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-4)


def test_countsketch_kernel_matches_compression_module():
    ccfg = CompressorConfig(depth=4, width=256)
    st = init_compressor(ccfg, 1000, jax.random.key(3))
    vec = jnp.asarray(RNG.normal(0, 1, 1000), jnp.float32)
    got = np.asarray(countsketch(vec, st.hash))
    ref = np.asarray(_sketch(st, vec))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-4)


FUSED_SHAPES = [
    (1, 64, 64, 33),
    (2, 256, 128, 512),
    (3, 300, 200, 1000),
]


@pytest.mark.parametrize("d,wr,wc,b", FUSED_SHAPES)
def test_fused_ingest_kernel_matches_ref(d, wr, wc, b):
    """One-pass fused kernel (interpret mode) vs the three-pass jnp twin:
    counters, row_flows, col_flows bit-equal, touched bitmap identical —
    including -1 sentinel rows (padding slots must be inert everywhere)."""
    counters = jnp.asarray(RNG.integers(0, 1000, (d, wr, wc)), jnp.float32)
    rf = jnp.asarray(RNG.integers(0, 1000, (d, wr)), jnp.float32)
    cf = jnp.asarray(RNG.integers(0, 1000, (d, wc)), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, wr, (d, b)), jnp.int32)
    # sprinkle padding sentinels into every depth
    sentinel = RNG.random((d, b)) < 0.1
    rows = jnp.where(jnp.asarray(sentinel), -1, rows)
    cols = jnp.asarray(RNG.integers(0, wc, (d, b)), jnp.int32)
    w = jnp.asarray(RNG.integers(1, 9, b), jnp.float32)
    got = fused_ingest(counters, rf, cf, rows, cols, w, interpret=True)
    ref = fused_ingest_ref(counters, rf, cf, rows, cols, w)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_fused_ingest_fp_weights_close():
    counters = jnp.zeros((2, 128, 128), jnp.float32)
    rf = jnp.zeros((2, 128), jnp.float32)
    cf = jnp.zeros((2, 128), jnp.float32)
    rows = jnp.asarray(RNG.integers(0, 128, (2, 700)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, 128, (2, 700)), jnp.int32)
    w = jnp.asarray(RNG.normal(0, 1, 700), jnp.float32)
    got = fused_ingest(counters, rf, cf, rows, cols, w, interpret=True)
    ref = fused_ingest_ref(counters, rf, cf, rows, cols, w)
    for g, r in zip(got[:3], ref[:3]):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=1e-6, atol=1e-5
        )
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(ref[3]))


def test_fused_ingest_sentinel_rows_are_inert():
    """An all-sentinel batch changes nothing: not counters, not either
    register plane, and the touched bitmap stays empty."""
    counters = jnp.asarray(RNG.integers(0, 50, (2, 64, 64)), jnp.float32)
    rf = jnp.asarray(RNG.integers(0, 50, (2, 64)), jnp.float32)
    cf = jnp.asarray(RNG.integers(0, 50, (2, 64)), jnp.float32)
    rows = jnp.full((2, 40), -1, jnp.int32)
    cols = jnp.asarray(RNG.integers(0, 64, (2, 40)), jnp.int32)
    w = jnp.ones(40, jnp.float32)
    got = fused_ingest(counters, rf, cf, rows, cols, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(counters))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(rf))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(cf))
    assert not bool(np.asarray(got[3]).any())


def test_sketch_update_fused_matches_scatter_composition():
    """GLavaSketch.update_fused == update(backend='scatter') bit-exactly,
    and its touched bitmap marks exactly the hashed rows of the batch."""
    cfg = SketchConfig(depth=3, width_rows=128, width_cols=128)
    sk = GLavaSketch.empty(cfg, jax.random.key(5))
    src = jnp.asarray(RNG.integers(0, 900, 600), jnp.uint32)
    dst = jnp.asarray(RNG.integers(0, 900, 600), jnp.uint32)
    fused, touched = sk.update_fused(src, dst)
    oracle = sk.update(src, dst, backend="scatter", preagg="off")
    np.testing.assert_array_equal(
        np.asarray(fused.counters), np.asarray(oracle.counters)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.row_flows), np.asarray(oracle.row_flows)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.col_flows), np.asarray(oracle.col_flows)
    )
    rows = np.asarray(sk.row_hash(src))  # (d, B)
    want = np.zeros((3, 128), bool)
    for di in range(3):
        want[di, np.unique(rows[di])] = True
    np.testing.assert_array_equal(np.asarray(touched), want)


def test_sketch_pallas_backend_via_core_api():
    """GLavaSketch.update(backend='pallas') equals the scatter semantics."""
    cfg = SketchConfig(depth=2, width_rows=256, width_cols=256)
    sk = GLavaSketch.empty(cfg, jax.random.key(4))
    src = jnp.asarray(RNG.integers(0, 900, 600), jnp.uint32)
    dst = jnp.asarray(RNG.integers(0, 900, 600), jnp.uint32)
    a = sk.update(src, dst, backend="scatter")
    b = sk.update(src, dst, backend="pallas")
    np.testing.assert_array_equal(np.asarray(a.counters), np.asarray(b.counters))


def test_fused_ingest_refuses_widths_past_its_kernel():
    """Where the kernel runs, a column width it cannot serve is an error,
    not a silent hand-off to the jnp twin."""
    from repro.kernels.ingest_fused.ops import MAX_FUSED_WC

    wc = MAX_FUSED_WC + 1
    counters = jnp.zeros((1, 8, wc), jnp.float32)
    rows = jnp.zeros((1, 4), jnp.int32)
    args = (counters, jnp.zeros((1, 8)), jnp.zeros((1, wc)), rows, rows, jnp.ones(4))
    with pytest.raises(ValueError, match="column widths up to"):
        fused_ingest(*args, interpret=True)
    ref = fused_ingest_ref(*args)  # the twin still serves CPU sessions
    np.testing.assert_array_equal(np.asarray(fused_ingest(*args)[0]), np.asarray(ref[0]))
