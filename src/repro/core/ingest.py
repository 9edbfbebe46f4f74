"""IngestEngine — the ONE dispatch point for sketch ingest.

Every path that folds an edge batch into gLava counters (local update,
sliding-window slices, the serving engine, the row-sharded distributed
plane, and the Pallas kernel wrapper) routes through :func:`ingest` /
:class:`IngestEngine`.  The engine owns the hash-bucket scatter semantics,
the padding/chunking bookkeeping, and the row-shard masking, so backends
cannot drift apart.

Exact-equivalence contract
--------------------------
For integer-valued fp32 weights with total per-cell mass below ``2**24``,
all backends — and any row-sharded decomposition of them — produce
BIT-IDENTICAL counters:

    ingest(C, r, c, w, backend=B1)
      == ingest(C, r, c, w, backend=B2)                       (any B1, B2)
      == sum over shards of ingest(C_shard, r, c, w, row_offset=k*wr_shard)

because fp32 addition of exactly-representable integers is associative in
the reachable range, and out-of-shard edges contribute exactly zero (index
masking, never weight rounding).  ``repro.core.distributed`` relies on this
for its psum merge; tests assert it for square and non-square configs.

Ingest-backend selection
------------------------
``scatter``  The paper-faithful semantics: ``M[h(x), h(y)] += w`` as one
             vectorized scatter-add.  Best on CPU/GPU and the reference
             oracle everywhere.
``onehot``   The MXU formulation: per edge chunk of size ``chunk``,
             ``M += OneHot(r)^T @ (OneHot(c) * w)`` — a systolic matmul.
             Best for XLA:TPU without Pallas.
``pallas``   The Pallas TPU kernel (``repro.kernels.ingest``): the batch is
             sorted by counter tile inside the jit, and each touched
             (TR x TC) tile contracts only its own entries as one-hot MXU
             matmuls, so the work follows the batch, not the sketch.
             Untouched tiles are never read.  Compiled on TPU hardware; on
             CPU hosts it runs in interpret mode (a correctness artifact,
             not a perf claim).
``auto``     Resolves via the ``REPRO_INGEST_BACKEND`` environment
             variable if set, else ``pallas`` on TPU backends and
             ``scatter`` elsewhere.

Row-sharded ingest (``row_offset``/``num_rows_total``) shifts global row
ids into shard-local coordinates and masks out-of-shard edges; every
backend supports it, so the distributed plane can use the same fast path
as a single device.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_CHUNK = 2048
BACKENDS = ("scatter", "onehot", "pallas")


def resolve_backend(backend: Optional[str]) -> str:
    """Resolve "auto"/None to a concrete backend name."""
    if backend in (None, "auto"):
        env = os.environ.get("REPRO_INGEST_BACKEND", "").strip().lower()
        if env:
            backend = env
        else:
            backend = (
                "pallas" if jax.default_backend() == "tpu" else "scatter"
            )
    if backend not in BACKENDS:
        raise ValueError(f"unknown ingest backend: {backend!r} (want {BACKENDS})")
    return backend


def touched_row_keys(src, dst=None, cap: Optional[int] = None):
    """The unique uint32 node keys whose ROW buckets one ingest batch can
    touch — ``src`` always; ``dst`` too when the sketch mirrors edges
    (undirected ingest writes row h(dst) as well).  Feeds the query plane's
    incremental closure refresh (``QueryEngine.refresh_closure``), which
    only needs a SUPERSET of the changed rows.

    Returns ``None`` when the unique count exceeds ``cap`` (typically the
    sketch row width): past that the refresh would touch most rows anyway,
    so callers fall back to a full rebuild rather than carry the set."""
    keys = np.atleast_1d(np.asarray(src))
    if dst is not None:
        keys = np.concatenate([keys, np.atleast_1d(np.asarray(dst))])
    uniq = np.unique(keys.astype(np.uint32, copy=False))
    if cap is not None and uniq.size > cap:
        return None
    return uniq


def pad_to(x: jax.Array, multiple: int, axis: int, value=0) -> jax.Array:
    """Right-pad ``axis`` to the next multiple (shared by kernel wrappers)."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# backends — all take shard-LOCAL row ids plus the in-shard mask
# ---------------------------------------------------------------------------


def _scatter(counters, local_r, cols, weights, in_shard, chunk):
    d, wr, wc = counters.shape
    d_idx = jnp.broadcast_to(jnp.arange(d)[:, None], local_r.shape)
    w = jnp.where(in_shard, jnp.broadcast_to(weights[None, :], local_r.shape), 0.0)
    safe_r = jnp.where(in_shard, local_r, 0)
    # Flat 1-D scatter with the bounds check promised away: safe_r/cols are
    # in-range by construction (masking above; hash codomain), and the flat
    # formulation measures ~40% faster than the 3-D scatter on XLA:CPU.
    flat = ((d_idx * wr + safe_r) * wc + cols).reshape(-1)
    return (
        counters.reshape(-1)
        .at[flat]
        .add(w.reshape(-1), mode="promise_in_bounds")
        .reshape(d, wr, wc)
    )


def _onehot(counters, local_r, cols, weights, in_shard, chunk):
    d, wr, wc = counters.shape
    batch = local_r.shape[1]
    chunk = min(chunk, batch)
    # Out-of-shard rows hit the sentinel one-hot class, sliced away below —
    # masking by INDEX, so weights stay untouched (exactness contract).
    # Padded slots (batch rounded up to a whole number of chunks) use the
    # same sentinel with weight zero, so ONE scan body covers every chunk
    # and the remainder no longer costs a second trace.
    r_sent = jnp.where(in_shard, local_r, wr)
    r_sent = pad_to(r_sent, chunk, 1, value=wr)
    cols = pad_to(cols, chunk, 1)
    weights = pad_to(weights, chunk, 0)

    def one_chunk(counters, args):
        rc, cc, wchunk = args  # (d, C), (d, C), (C,)
        oh_r = jax.nn.one_hot(rc, wr + 1, dtype=jnp.float32)[..., :wr]  # (d, C, wr)
        oh_c = jax.nn.one_hot(cc, wc, dtype=jnp.float32)                # (d, C, wc)
        oh_c = oh_c * wchunk[None, :, None]
        # HIGHEST: a TPU's default f32 matmul rounds operands to bf16, which
        # would round weighted one-hots and break the exactness contract.
        upd = jnp.einsum(
            "dbr,dbc->drc", oh_r, oh_c, precision=jax.lax.Precision.HIGHEST
        )
        return counters + upd, None

    n = r_sent.shape[1] // chunk
    rs = r_sent.reshape(d, n, chunk).transpose(1, 0, 2)
    cs = cols.reshape(d, n, chunk).transpose(1, 0, 2)
    ws = weights.reshape(n, chunk)
    counters, _ = jax.lax.scan(one_chunk, counters, (rs, cs, ws))
    return counters


def _pallas(counters, local_r, cols, weights, in_shard, chunk):
    from repro.kernels.ingest.kernel import CHUNK_B, TILE_C, TILE_R, ingest_pallas

    d, wr, wc = counters.shape
    # Out-of-shard rows become -1: the kernel's iota compare matches nothing.
    r = jnp.where(in_shard, local_r, -1).astype(jnp.int32)
    cp = pad_to(pad_to(counters.astype(jnp.float32), TILE_R, 1), TILE_C, 2)
    rp = pad_to(r, CHUNK_B, 1, value=-1)
    cl = pad_to(cols.astype(jnp.int32), CHUNK_B, 1)
    wp = pad_to(weights, CHUNK_B, 0)  # padded edges carry weight 0
    out = ingest_pallas(cp, rp, cl, wp)
    return out[:, :wr, :wc]


_BACKEND_FNS = {"scatter": _scatter, "onehot": _onehot, "pallas": _pallas}


# ---------------------------------------------------------------------------
# the dispatch point
# ---------------------------------------------------------------------------


def ingest(
    counters: jax.Array,   # (d, wr_local, wc) fp32
    rows: jax.Array,       # (d, B) int — GLOBAL row buckets
    cols: jax.Array,       # (d, B) int — column buckets
    weights: jax.Array,    # (B,) fp32
    *,
    backend: str = "scatter",
    chunk: int = DEFAULT_CHUNK,
    row_offset: jax.Array | int = 0,
) -> jax.Array:
    """Fold one hashed edge batch into ``counters`` (see module docstring).

    ``row_offset`` is the global row id of this counter shard's row 0; rows
    outside ``[row_offset, row_offset + wr_local)`` contribute exactly
    nothing.  ``row_offset=0`` with full-width counters is plain local
    ingest (the mask is all-true and free after fusion).
    """
    backend = resolve_backend(backend)
    wr_local = counters.shape[1]
    local_r = rows.astype(jnp.int32) - jnp.asarray(row_offset, jnp.int32)
    in_shard = (local_r >= 0) & (local_r < wr_local)
    cols = cols.astype(jnp.int32)
    weights = weights.astype(jnp.float32)
    return _BACKEND_FNS[backend](counters, local_r, cols, weights, in_shard, chunk)


@dataclasses.dataclass(frozen=True)
class IngestEngine:
    """A resolved (backend, chunk) pair with the `ingest` dispatch bound."""

    backend: str = "scatter"
    chunk: int = DEFAULT_CHUNK

    def __post_init__(self):
        object.__setattr__(self, "backend", resolve_backend(self.backend))

    def __call__(self, counters, rows, cols, weights, row_offset=0):
        return ingest(
            counters,
            rows,
            cols,
            weights,
            backend=self.backend,
            chunk=self.chunk,
            row_offset=row_offset,
        )


# ---------------------------------------------------------------------------
# in-batch pre-aggregation — the heavy-tail fast path (DESIGN.md Section 10)
# ---------------------------------------------------------------------------
#
# Real graph streams are heavy-tailed: a zipf(1.5) batch of 32768 edges has
# only ~20% unique (src, dst) pairs, so a plain scatter pays for every
# duplicate.  Pre-aggregation collapses the batch to one slot per distinct
# pair BEFORE any backend sees it.  Because the collapse is a plain sum of
# signed weights it is EXACT for turnstile deletes and sliding-window slices
# too, and in the integer-fp32 regime (per-pair |Σw| and every running
# prefix < 2**24) it is bit-identical to ingesting the raw batch.
#
# Two implementations with one semantics:
#   * ``preaggregate_edges`` — traced, static-shape (sort + segment sums via
#     cumsum prefix differences; no ``jnp.unique``).  Rides INSIDE any jit,
#     so device-resident pipelines (TPU) collapse without a host round-trip.
#   * ``preaggregate_host`` — numpy (argsort + ``np.add.reduceat``).  The
#     session boundary (``api/stream.py``) is already host-side for label
#     encoding, and one host argsort is ~3x cheaper than the XLA:CPU sort,
#     so GraphStream uses this variant and additionally gets the per-src /
#     per-dst marginal totals that let the flow registers collapse further.

PREAGG_MIN_BATCH = 1024  # below this the sort costs more than it saves
PREAGG_SHRINK = 4        # in-jit collapsed slots = batch // PREAGG_SHRINK
PREAGG_MIN_OUT = 256     # floor on the collapsed slot count


def resolve_preagg(mode: Optional[str] = None, batch: Optional[int] = None) -> bool:
    """Resolve a pre-aggregation mode ("auto"/"on"/"off"/None) to a bool.

    "auto" (and None) honours the ``REPRO_INGEST_PREAGG`` environment
    variable if set, else enables pre-aggregation for batches of at least
    ``PREAGG_MIN_BATCH`` edges.  "on" forces it regardless of batch size
    (tests exercise small batches this way); "off" disables it."""
    if mode in (None, "auto"):
        env = os.environ.get("REPRO_INGEST_PREAGG", "").strip().lower()
        mode = env or "auto"
    if mode == "auto":
        return batch is None or batch >= PREAGG_MIN_BATCH
    if mode in ("on", "1", "true"):
        return True
    if mode in ("off", "0", "false"):
        return False
    raise ValueError(f"unknown preagg mode: {mode!r} (want auto/on/off)")


def preaggregate_edges(src, dst, weights, out_size: int):
    """Collapse duplicate (src, dst) pairs inside a jit — static shapes only.

    Sorts the batch by a 32-bit mixed pair key, finds run boundaries by
    neighbour compare on the sorted (src, dst) themselves (so key collisions
    merely split a run — never merge distinct pairs), and segment-sums the
    weights by cumulative-sum prefix differences (O(B) gathers; NOT
    ``jax.ops.segment_sum``, whose scatter would cost as much as the ingest
    it is meant to save).

    Returns ``(s_rep, d_rep, w_agg, n_seg)`` with static shape
    ``(out_size,)`` each: representative keys and summed weights for the
    first ``min(n_seg, out_size)`` segments.  Slots past ``n_seg`` carry
    weight exactly 0.0 with a (duplicated) real key, so scattering them is a
    no-op in the counting regime.  When ``n_seg > out_size`` the collapse
    does not fit — callers branch to the raw batch (``lax.cond``)."""
    from repro.core.hashing import mix_keys

    b = src.shape[0]
    key = mix_keys(src, dst)
    _, order = jax.lax.sort_key_val(key, jnp.arange(b, dtype=jnp.int32))
    s2, d2, w2 = src[order], dst[order], weights[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (s2[1:] != s2[:-1]) | (d2[1:] != d2[:-1])]
    )
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1  # (B,) non-decreasing
    n_seg = seg[-1] + 1
    csum = jnp.concatenate([jnp.zeros((1,), w2.dtype), jnp.cumsum(w2)])
    starts = jnp.searchsorted(
        seg, jnp.arange(out_size, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    ends = jnp.concatenate([starts[1:], jnp.full((1,), b, jnp.int32)])
    w_agg = csum[ends] - csum[starts]
    reps = jnp.clip(starts, 0, b - 1)
    return s2[reps], d2[reps], w_agg, n_seg


@dataclasses.dataclass(frozen=True)
class PreaggBatch:
    """A host-collapsed edge batch: distinct pairs plus marginal totals.

    ``src/dst/weights`` hold one slot per distinct (src, dst) pair of the
    raw batch with exactly-summed signed weights.  ``src_unique/src_totals``
    and ``dst_unique/dst_totals`` are the per-endpoint marginals — the flow
    registers only need those, which is a second collapse on top of the
    pair collapse (one row-register add per distinct src, not per pair)."""

    src: np.ndarray          # (P,) uint32 — distinct pair sources
    dst: np.ndarray          # (P,) uint32 — distinct pair destinations
    weights: np.ndarray      # (P,) float32 — per-pair summed weight
    src_unique: np.ndarray   # (S,) uint32
    src_totals: np.ndarray   # (S,) float32
    dst_unique: np.ndarray   # (D,) uint32
    dst_totals: np.ndarray   # (D,) float32

    @property
    def n_pairs(self) -> int:
        return int(self.src.size)


def preaggregate_host(src, dst, weights) -> PreaggBatch:
    """Numpy twin of :func:`preaggregate_edges` for the session boundary.

    One stable argsort of the 64-bit pair key gives the pair collapse via
    ``np.add.reduceat``; the per-src marginals fall out of the same order
    (sources are contiguous in pair order), and a second small argsort of
    the collapsed pairs gives the per-dst marginals.  Exact for signed
    weights; bit-identical to the raw batch in the integer regime."""
    sn = np.atleast_1d(np.asarray(src, np.uint32))
    dn = np.atleast_1d(np.asarray(dst, np.uint32))
    wn = np.atleast_1d(np.asarray(weights, np.float32))
    if sn.size == 0:
        empty_u, empty_f = sn[:0], wn[:0]
        return PreaggBatch(sn, dn, wn, empty_u, empty_f, empty_u, empty_f)
    pair = (sn.astype(np.uint64) << np.uint64(32)) | dn.astype(np.uint64)
    order = np.argsort(pair, kind="stable")
    ps, ss, ds, ws = pair[order], sn[order], dn[order], wn[order]
    first = np.empty(ps.size, bool)
    first[0] = True
    first[1:] = ps[1:] != ps[:-1]
    starts = np.flatnonzero(first)
    s_rep, d_rep = ss[starts], ds[starts]
    w_agg = np.add.reduceat(ws, starts).astype(np.float32)
    sfirst = np.empty(starts.size, bool)
    sfirst[0] = True
    sfirst[1:] = s_rep[1:] != s_rep[:-1]
    sstarts = np.flatnonzero(sfirst)
    src_unique = s_rep[sstarts]
    src_totals = np.add.reduceat(w_agg, sstarts).astype(np.float32)
    dorder = np.argsort(d_rep, kind="stable")
    dr, dw = d_rep[dorder], w_agg[dorder]
    dfirst = np.empty(dr.size, bool)
    dfirst[0] = True
    dfirst[1:] = dr[1:] != dr[:-1]
    dstarts = np.flatnonzero(dfirst)
    dst_unique = dr[dstarts]
    dst_totals = np.add.reduceat(dw, dstarts).astype(np.float32)
    return PreaggBatch(
        s_rep, d_rep, w_agg, src_unique, src_totals, dst_unique, dst_totals
    )


def bucket_size(n: int, minimum: int = 256) -> int:
    """Next power-of-two at or above ``n`` (floored at ``minimum``) — the
    padded-shape ladder that bounds how many traces variable-size collapsed
    batches can cost at a jit boundary."""
    size = minimum
    while size < n:
        size *= 2
    return size


def pad_bucket(x: np.ndarray, minimum: int = 256, value=0) -> np.ndarray:
    """Right-pad a 1-D host array to its :func:`bucket_size` with ``value``."""
    pad = bucket_size(x.size, minimum) - x.size
    if pad == 0:
        return x
    return np.concatenate([x, np.full(pad, value, x.dtype)])
