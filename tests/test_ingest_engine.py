"""IngestEngine contract tests: one dispatch point, every backend and every
sharding decomposition bit-identical for integer weights (see
repro/core/ingest.py module docstring)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GLavaSketch, SketchConfig
from repro.core.ingest import BACKENDS, IngestEngine, ingest, resolve_backend

CONFIGS = (
    SketchConfig(depth=3, width_rows=64, width_cols=64),    # square (paper)
    SketchConfig(depth=2, width_rows=96, width_cols=40),    # non-square §6.1.2
)


def _stream(n=700, seed=0, max_w=4):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, 500, n), jnp.uint32),
        jnp.asarray(rng.integers(0, 500, n), jnp.uint32),
        jnp.asarray(rng.integers(1, max_w, n), jnp.float32),
    )


@pytest.mark.parametrize("cfg", CONFIGS, ids=["square", "nonsquare"])
def test_all_backends_bit_equal(cfg):
    sk = GLavaSketch.empty(cfg, jax.random.key(0))
    src, dst, w = _stream()
    ref = np.asarray(sk.update(src, dst, w, backend="scatter").counters)
    for backend in BACKENDS:
        got = np.asarray(sk.update(src, dst, w, backend=backend).counters)
        np.testing.assert_array_equal(ref, got, err_msg=backend)


@pytest.mark.parametrize("cfg", CONFIGS, ids=["square", "nonsquare"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_row_shard_decomposition_exact(cfg, backend):
    """Concatenating per-shard row-offset ingests == unsharded ingest, for
    every backend — the invariant the distributed psum merge rests on."""
    sk = GLavaSketch.empty(cfg, jax.random.key(1))
    src, dst, w = _stream(seed=1)
    r, c = sk.hash_edges(src, dst)
    ref = np.asarray(ingest(sk.counters, r, c, w, backend="scatter"))
    n_shards = 4
    assert cfg.width_rows % n_shards == 0
    wr_shard = cfg.width_rows // n_shards
    shards = [
        np.asarray(
            ingest(
                jnp.zeros((cfg.depth, wr_shard, cfg.width_cols), jnp.float32),
                r, c, w, backend=backend, row_offset=i * wr_shard,
            )
        )
        for i in range(n_shards)
    ]
    np.testing.assert_array_equal(ref, np.concatenate(shards, axis=1))


def test_engine_resolves_auto(monkeypatch):
    monkeypatch.delenv("REPRO_INGEST_BACKEND", raising=False)
    resolved = resolve_backend("auto")
    assert resolved in BACKENDS
    if jax.default_backend() != "tpu":
        assert resolved == "scatter"
    monkeypatch.setenv("REPRO_INGEST_BACKEND", "onehot")
    assert resolve_backend("auto") == "onehot"
    assert IngestEngine("auto").backend == "onehot"
    with pytest.raises(ValueError):
        resolve_backend("systolic")


_DIST_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import GLavaSketch, SketchConfig
    from repro.core.distributed import distributed_ingest
    from repro.distributed.sharding import sketch_plane_shardings

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    counter_sh, stream_sh = sketch_plane_shardings(mesh)

    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, 500, 256), jnp.uint32)
    dst = jnp.asarray(rng.integers(0, 500, 256), jnp.uint32)
    w = jnp.asarray(rng.integers(1, 4, 256), jnp.float32)

    for wr, wc in ((64, 64), (64, 48)):          # square and non-square
        cfg = SketchConfig(depth=3, width_rows=wr, width_cols=wc)
        sk = GLavaSketch.empty(cfg, jax.random.key(0))
        sk_sharded = dataclasses.replace(
            sk, counters=jax.device_put(sk.counters, counter_sh)
        )
        args = [jax.device_put(a, stream_sh) for a in (src, dst, w)]
        for backend in ("onehot", "scatter", "pallas"):
            out = distributed_ingest(mesh, sk_sharded, *args, backend=backend)
            ref = sk.update(src, dst, w, backend="scatter")  # local oracle
            np.testing.assert_array_equal(
                np.asarray(out.counters), np.asarray(ref.counters),
                err_msg=f"{wr}x{wc} {backend}",
            )
        print(f"{wr}x{wc} OK")
    print("ALL_OK")
    """
)


@pytest.mark.slow
def test_distributed_matches_local_oracle_square_and_nonsquare():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL_OK" in proc.stdout, proc.stdout
