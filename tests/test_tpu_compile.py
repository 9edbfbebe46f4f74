"""Compile rehearsals for one TPU v5e at the `base` preset (d=5, 8192²).

Each test compiles a main-path program for a v5e that is described, not
attached: the TPU compiler refuses here what the chip would refuse (block
shapes off the tiling rule, scoped VMEM, device memory).  Nothing runs, so
these say nothing about results or speed.  The topology is described inside
a module fixture, never at import, so every test worker collects the same
tests and only the one given this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, W, B, Q = 5, 8192, 16384, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """Shape-and-placement factory for one described chip.  A compile for a
    described chip is written to the persistent cache but cannot be read
    back without one, so the cache stays off while these compile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ingest_kernel_compiles_at_base(on_chip):
    from repro.kernels.ingest.kernel import ingest_pallas

    _compile(
        functools.partial(ingest_pallas, interpret=False),
        on_chip((D, W, W), jnp.float32),
        on_chip((D, B), jnp.int32),
        on_chip((D, B), jnp.int32),
        on_chip((B,), jnp.float32),
    )


def test_ingest_kernel_compiles_in_a_vma_checked_shard_map(topo, on_chip):
    """Per row shard inside a `shard_map` that checks varying axes: the
    kernel's output varies over the axes of the counters it aliases."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels.ingest.kernel import ingest_pallas

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    rows = P(None, "model", None)
    body = jax.shard_map(
        functools.partial(ingest_pallas, interpret=False),
        mesh=mesh, in_specs=(rows, P(), P(), P()), out_specs=rows,
    )
    on = lambda shape, dtype, spec: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )
    _compile(
        body,
        on((D, 4 * 256, 512), jnp.float32, rows),
        on((D, 256), jnp.int32, P()),
        on((D, 256), jnp.int32, P()),
        on((256,), jnp.float32, P()),
    )


def test_multi_query_kernel_compiles_at_base(on_chip):
    from repro.kernels.query.kernel import multi_query_pallas

    _compile(
        functools.partial(multi_query_pallas, interpret=False),
        on_chip((D, W, W), jnp.float32),
        on_chip((D, Q), jnp.int32),
        on_chip((D, Q), jnp.int32),
    )


def test_vmapped_closure_compiles_at_base(on_chip):
    from repro.kernels.closure.ops import transitive_closure

    compiled = _compile(
        functools.partial(transitive_closure, interpret=False),
        on_chip((D, W, W), jnp.float32),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


def test_fused_ingest_kernel_compiles_at_widest_served_width(on_chip):
    from repro.kernels.ingest_fused.kernel import fused_ingest_pallas
    from repro.kernels.ingest_fused.ops import MAX_FUSED_WC

    _compile(
        functools.partial(fused_ingest_pallas, interpret=False),
        on_chip((D, W, MAX_FUSED_WC), jnp.float32),
        on_chip((D, W), jnp.float32),
        on_chip((D, MAX_FUSED_WC), jnp.float32),
        on_chip((D, B), jnp.int32),
        on_chip((D, B), jnp.int32),
        on_chip((B,), jnp.float32),
    )


def test_session_donated_update_compiles_at_base(on_chip, monkeypatch):
    """The donated ingest boundary of `GraphStream.open("base")` as a TPU
    resolves it (`auto` -> the Pallas kernel), counters updated in place."""
    from repro.api import GraphStream

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        gs = GraphStream.open("base")
        assert gs.ingest_backend == "pallas" and gs.engine.backend == "pallas"
        leaves = jax.tree_util.tree_leaves(gs._sketch)
        uniq = tuple(
            on_chip(leaves[i].shape, leaves[i].dtype) for i in gs._uniq_leaf_idx
        )
        del gs._sketch, leaves
        keys, vals = on_chip((B,), jnp.uint32), on_chip((B,), jnp.float32)
        compiled = (
            gs._jit_update_pre.lower(uniq, keys, keys, vals, keys, vals, keys, vals)
            .compile()
        )
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().alias_size_in_bytes >= D * W * W * 4
    finally:
        # The kernel traced here resolved `interpret` for a TPU; drop it.
        jax.clear_caches()


COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter", "all-to-all")


def _collectives(hlo: str):
    """The collective instructions of a compiled module's text."""
    return [
        line.strip()
        for line in hlo.splitlines()
        if any(f" {k}(" in line or f" {k}-start(" in line for k in COLLECTIVES)
    ]


def test_web_share_session_compiles_row_sharded_on_four_chips(topo, monkeypatch):
    """`GraphStream(SketchConfig(2, 65536, 65536))` opened with the defaults on
    a described 2x2 v5e host: the placement rule shards the 34.4 GB of
    counters over the four chips, the donated mesh update runs the Pallas
    kernel per shard in place with no collective, and the edge query's only
    collective is its pmin over d x Q values."""
    import repro.api.stream as stream
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.sketch import GLavaSketch, SketchConfig

    def abstract_sharded(config, key, mesh):
        shapes = jax.eval_shape(functools.partial(GLavaSketch.empty, config), key)
        rows, rep = NamedSharding(mesh, P(None, "model", None)), NamedSharding(mesh, P())
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=rows if x is shapes.counters else rep
            ),
            shapes,
        )

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: list(topo.devices))
    monkeypatch.setattr(stream, "_bytes_limit", lambda device: 16 * 10**9)
    monkeypatch.setattr(stream, "_empty_sharded", abstract_sharded)
    try:
        cfg = SketchConfig(depth=2, width_rows=65536, width_cols=65536)
        gs = stream.GraphStream(cfg)
        assert dict(gs._mesh.shape) == {"data": 1, "model": 4}
        assert gs.ingest_backend == "pallas"
        per_chip = cfg.space_bytes() // 4
        leaves = jax.tree_util.tree_leaves(gs._sketch)
        uniq = tuple(leaves[i] for i in gs._uniq_leaf_idx)
        rep = gs._replicated
        keys = jax.ShapeDtypeStruct((65536,), jnp.uint32, sharding=rep)
        vals = jax.ShapeDtypeStruct((65536,), jnp.float32, sharding=rep)
        update = gs._jit_update_mesh.lower(uniq, keys, keys, vals, keys, vals, keys, vals).compile()
        text = update.as_text()
        assert "tpu_custom_call" in text and _collectives(text) == []
        mem = update.memory_analysis()
        assert mem.alias_size_in_bytes >= per_chip
        assert mem.temp_size_in_bytes < 2**26
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 10**9
        q = jax.ShapeDtypeStruct((1024,), jnp.uint32, sharding=rep)
        edge = gs.engine._fn("edge").lower(gs._sketch, q, q).compile()
        (pmin,) = _collectives(edge.as_text())
        assert " all-reduce(" in pmin and "f32[2,1024]" in pmin, pmin
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
