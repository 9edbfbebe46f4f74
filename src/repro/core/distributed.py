"""Distributed gLava: the paper's Section 6.3 as an explicit JAX program.

The sketch is a *linear* projection of the stream, so the distributed recipe
is exactly the paper's: every worker ingests its local shard of the stream
into a local copy of the (same-hash-family) sketch, and the global sketch is
the elementwise SUM of the locals.  Expressed with ``shard_map``:

- the edge batch is sharded over the ``(pod, data)`` mesh axes,
- the counter tensor's ROW axis is sharded over ``model`` (so a 16-way model
  axis holds w_r/16 rows per chip — sketches wider than one chip's HBM are
  supported),
- each device accumulates only rows it owns (the one-hot formulation masks
  out-of-shard rows for free), and
- ``psum`` over (pod, data) merges the partial sketches.

Query-side collectives: point/edge queries gather from the row-owner and
psum-combine masked partials.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ingest import ingest
from repro.core.sketch import GLavaSketch, scatter_flows, scatter_register


def distributed_ingest(
    mesh: jax.sharding.Mesh,
    sketch: GLavaSketch,
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    stream_axes: Sequence[str] = ("data",),
    model_axis: str = "model",
    backend: str = "onehot",
    preagg_marginals=None,
) -> GLavaSketch:
    """Ingest a GLOBAL edge batch, sharded over `stream_axes`, into a sketch
    whose rows are sharded over `model_axis`.  Returns the updated sketch
    with the same shardings.

    Per-device accumulation goes through the same :mod:`repro.core.ingest`
    dispatch as local ingest (``row_offset`` masks out-of-shard rows), so
    the distributed result is bit-identical to the local oracle for
    integer weights — the engine's exact-equivalence contract.

    Pre-aggregation composes from the outside: a host-collapsed batch
    (:func:`repro.core.ingest.preaggregate_host`) is just a smaller edge
    batch, so callers (the GraphStream mesh branch) pass the collapsed
    pairs here directly.  When they do, ``preagg_marginals`` =
    ``(src_unique, src_totals, dst_unique, dst_totals)`` lets the
    replicated flow registers update from the per-endpoint totals — one
    register add per distinct endpoint instead of per pair."""
    if weights is None:
        weights = jnp.ones(src.shape, jnp.float32)
    weights = weights.astype(jnp.float32)
    r, c = sketch.hash_edges(src, dst)  # (d, B) — computed under pjit; cheap
    d, wr, wc = sketch.counters.shape
    tp = mesh.shape[model_axis]
    assert wr % tp == 0, f"sketch rows {wr} must divide model axis {tp}"
    wr_shard = wr // tp
    stream_spec = P(None, tuple(stream_axes))  # (d, B) sharded on batch

    def body(counters_shard, r, c, w):
        row_lo = jax.lax.axis_index(model_axis) * wr_shard
        # The counters arrive replicated over the stream axes; each stream
        # shard's partial update varies over them, so the ingest starts
        # from a varying copy.
        local = jax.lax.pcast(counters_shard, tuple(stream_axes), to="varying")
        upd = ingest(local, r, c, w, backend=backend, row_offset=row_lo)
        # Merge stream shards: the paper's distributed merge-by-add.
        delta = jax.lax.psum(upd - local, tuple(stream_axes))
        return counters_shard + delta

    counters = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, model_axis, None),  # counters: rows sharded
            stream_spec,                # r
            stream_spec,                # c
            P(tuple(stream_axes)),      # weights
        ),
        out_specs=P(None, model_axis, None),
    )(sketch.counters, r, c, weights)
    # Flow registers are O(d·w) and replicated — maintain them with the
    # plain global scatter (same add order as local ingest, so the
    # registers stay bit-identical to the local oracle's), or from the
    # per-endpoint marginal totals when the batch was host-collapsed.
    if preagg_marginals is not None:
        src_unique, src_totals, dst_unique, dst_totals = preagg_marginals
        row_flows = scatter_register(
            sketch.row_flows, sketch.row_hash(src_unique), src_totals
        )
        col_flows = scatter_register(
            sketch.col_flows, sketch.col_hash(dst_unique), dst_totals
        )
    else:
        # The registers are replicated, so the batch is too: a flat scatter
        # index cannot be reshaped out of an axis sharded over the stream.
        replicated = NamedSharding(mesh, P())
        row_flows, col_flows = scatter_flows(
            sketch.row_flows,
            sketch.col_flows,
            *(jax.sharding.reshard(x, replicated) for x in (r, c, weights)),
        )
    return dataclasses.replace(
        sketch, counters=counters, row_flows=row_flows, col_flows=col_flows
    )


def distributed_edge_query(
    mesh: jax.sharding.Mesh,
    sketch: GLavaSketch,
    src: jax.Array,
    dst: jax.Array,
    *,
    model_axis: str = "model",
) -> jax.Array:
    """Batched f̃_e over a row-sharded sketch: each shard contributes the
    cells it owns (others contribute +inf), min-reduced over model axis."""
    r, c = sketch.hash_edges(src, dst)  # (d, Q)
    d, wr, wc = sketch.counters.shape
    tp = mesh.shape[model_axis]
    wr_shard = wr // tp

    def body(counters_shard, r, c):
        my_idx = jax.lax.axis_index(model_axis)
        local_r = r - my_idx * wr_shard
        in_shard = (local_r >= 0) & (local_r < wr_shard)
        safe_r = jnp.clip(local_r, 0, wr_shard - 1)
        d_idx = jnp.broadcast_to(jnp.arange(d)[:, None], r.shape)
        vals = counters_shard[d_idx, safe_r, c]
        vals = jnp.where(in_shard, vals, jnp.inf)
        vals = jax.lax.pmin(vals, model_axis)  # (d, Q) now replicated
        return jnp.min(vals, axis=0)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, model_axis, None), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(sketch.counters, r, c)


def distributed_point_query(
    mesh: jax.sharding.Mesh,
    sketch: GLavaSketch,
    keys: jax.Array,
    direction: str = "in",
    *,
    model_axis: str = "model",
    use_registers: bool = True,
) -> jax.Array:
    """f̃_v over a row-sharded sketch.

    Fast path (default): the flow registers are replicated and maintained by
    :func:`distributed_ingest`, so a point query is an O(d·Q) gather with no
    collective at all.  ``use_registers=False`` keeps the counter-reduction
    collective path (owner-shard row sums for out-flow; psum of partial
    column sums for in-flow) for counters that were mutated outside the
    sketch API and may carry stale registers."""
    if use_registers:
        from repro.core import queries

        if direction == "in":
            return queries.node_in_flow(sketch, keys)
        return queries.node_out_flow(sketch, keys)
    d, wr, wc = sketch.counters.shape
    tp = mesh.shape[model_axis]
    wr_shard = wr // tp
    if direction == "in":
        h = sketch.col_hash(keys)  # (d, Q) — column index, not sharded

        def body(counters_shard, h):
            col_sums = jax.lax.psum(
                jnp.sum(counters_shard, axis=1), model_axis
            )  # (d, wc)
            vals = jnp.take_along_axis(col_sums, h, axis=1)
            return jnp.min(vals, axis=0)

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, model_axis, None), P()),
            out_specs=P(),
            check_vma=False,
        )(sketch.counters, h)
    else:
        h = sketch.row_hash(keys)

        def body(counters_shard, h):
            my_idx = jax.lax.axis_index(model_axis)
            local = h - my_idx * wr_shard
            in_shard = (local >= 0) & (local < wr_shard)
            safe = jnp.clip(local, 0, wr_shard - 1)
            row_sums = jnp.sum(counters_shard, axis=2)  # (d, wr_shard)
            vals = jnp.take_along_axis(row_sums, safe, axis=1)
            vals = jnp.where(in_shard, vals, jnp.inf)
            vals = jax.lax.pmin(vals, model_axis)
            return jnp.min(vals, axis=0)

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, model_axis, None), P()),
            out_specs=P(),
            check_vma=False,
        )(sketch.counters, h)
