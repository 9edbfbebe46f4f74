"""Distributed gLava: the paper's Section 6.3 as an explicit JAX program.

The sketch is a *linear* projection of the stream, so the distributed recipe
is exactly the paper's: every worker ingests its local shard of the stream
into a local copy of the (same-hash-family) sketch, and the global sketch is
the elementwise SUM of the locals.  Expressed with ``shard_map``:

- the counter tensor's ROW axis is sharded over ``model`` (a 4-way model
  axis holds w_r/4 rows per chip — sketches wider than one chip's HBM are
  supported), the edge batch over the stream axes (``data``);
- each device folds the batch into the rows it owns through the same
  :func:`repro.core.ingest.ingest` dispatch as one device: ``row_offset``
  shifts the batch's rows into shard coordinates, and rows outside the
  shard become -1, which the Pallas kernel's grouping sorts past the last
  tile (inert, no work item) and the scatter and one-hot backends mask by
  index;
- on a 1 × n mesh (stream axes of one device) that is all: the shard is
  updated in place, and a caller that donates the counters through its jit
  boundary (``GraphStream``) keeps one copy of them per device.  With more
  than one stream device, ``psum`` over the stream axes merges the partial
  sketches.

Flow registers are O(d·w) and replicated: every device scatters the whole
batch into its copy, with no collective.

Query-side collectives: an edge query gathers each (depth, query) cell from
the shard that owns its row and ``pmin``-combines (``distributed_edge_query``,
d × Q values); point queries read the replicated registers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import queries
from repro.core.ingest import ingest
from repro.core.sketch import GLavaSketch, scatter_flows, scatter_register


def _sharded_counters(
    mesh, counters, r, c, w, *, stream_axes, model_axis, backend
):
    """One hashed batch folded into row-sharded counters (see module doc)."""
    wr = counters.shape[1]
    tp = mesh.shape[model_axis]
    assert wr % tp == 0, f"sketch rows {wr} must divide model axis {tp}"
    wr_shard = wr // tp
    stream_axes = tuple(stream_axes)
    merge = math.prod(mesh.shape[a] for a in stream_axes) > 1

    def body(counters_shard, r, c, w):
        row_lo = jax.lax.axis_index(model_axis) * wr_shard
        upd = ingest(counters_shard, r, c, w, backend=backend, row_offset=row_lo)
        if not merge:
            return upd
        # Merge stream shards: the paper's distributed merge-by-add.
        return counters_shard + jax.lax.psum(upd - counters_shard, stream_axes)

    stream_spec = P(None, stream_axes)  # (d, B) sharded on batch
    # check_vma stays off as in the query bodies: the Pallas interpreter
    # (CPU) indexes varying operands with unvarying grid indices.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, model_axis, None),  # counters: rows sharded
            stream_spec,                # r
            stream_spec,                # c
            P(stream_axes),             # weights
        ),
        out_specs=P(None, model_axis, None),
        check_vma=False,
    )(counters, r, c, w)


def distributed_ingest(
    mesh: jax.sharding.Mesh,
    sketch: GLavaSketch,
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    stream_axes: Sequence[str] = ("data",),
    model_axis: str = "model",
    backend: str = "auto",
    preagg_marginals=None,
) -> GLavaSketch:
    """Ingest a GLOBAL edge batch, sharded over `stream_axes`, into a sketch
    whose rows are sharded over `model_axis`.  Returns the updated sketch
    with the same shardings.

    ``backend`` resolves as for one device (``auto``: the Pallas kernel on
    TPU).  The distributed result is bit-identical to the local oracle for
    integer weights — the engine's exact-equivalence contract — and
    undirected sketches mirror each edge as :meth:`GLavaSketch.update` does.

    Pre-aggregation composes from the outside: a host-collapsed batch
    (:func:`repro.core.ingest.preaggregate_host`) is just a smaller edge
    batch, so callers (the GraphStream mesh boundary) pass the collapsed
    pairs here directly.  When they do, ``preagg_marginals`` =
    ``(src_unique, src_totals, dst_unique, dst_totals)`` lets the
    replicated flow registers update from the per-endpoint totals — one
    register add per distinct endpoint instead of per pair."""
    if weights is None:
        weights = jnp.ones(src.shape, jnp.float32)
    weights = weights.astype(jnp.float32)
    directions = [(src, dst)]
    if not sketch.config.directed:
        directions.append((dst, src))
    counters = sketch.counters
    row_flows, col_flows = sketch.row_flows, sketch.col_flows
    # The registers are replicated, so their batch is too: a flat scatter
    # index cannot be reshaped out of an axis sharded over the stream.
    replicated = NamedSharding(mesh, P())
    for s, d in directions:
        r, c = sketch.hash_edges(s, d)  # (d, B) — computed under pjit; cheap
        counters = _sharded_counters(
            mesh, counters, r, c, weights,
            stream_axes=stream_axes, model_axis=model_axis, backend=backend,
        )
        if preagg_marginals is None:
            row_flows, col_flows = scatter_flows(
                row_flows,
                col_flows,
                *(jax.sharding.reshard(x, replicated) for x in (r, c, weights)),
            )
    if preagg_marginals is not None:
        src_unique, src_totals, dst_unique, dst_totals = preagg_marginals
        ends = [(src_unique, src_totals, dst_unique, dst_totals)]
        if not sketch.config.directed:
            ends.append((dst_unique, dst_totals, src_unique, src_totals))
        for su, st, du, dt in ends:
            row_flows = scatter_register(row_flows, sketch.row_hash(su), st)
            col_flows = scatter_register(col_flows, sketch.col_hash(du), dt)
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
    cells it owns (others contribute +inf), min-reduced over model axis.
    The only collective is that ``pmin`` over d × Q values; no counter
    leaves its shard.  Answers equal :func:`repro.core.queries.edge_query`
    on the same counters."""
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

    est = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, model_axis, None), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(sketch.counters, r, c)
    if not sketch.config.directed:
        est = queries.undirected_selfloop_correction(est, src, dst)
    return est


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
