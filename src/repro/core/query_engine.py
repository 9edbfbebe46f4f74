"""QueryEngine — the ONE dispatch point for sketch queries.

Mirrors :class:`repro.core.ingest.IngestEngine` on the query side: every
query family (edge, point/flow, heavy-hitter, subgraph, reachability) is
served through one engine that owns

- the **jit cache**: one persistent ``jax.jit`` callable per (family,
  backend); jit itself then caches per (shape, dtype), so repeated queries
  never re-trace — callers like ``SketchServer`` stop paying a trace per
  freshly-created lambda;
- **query-batch padding/chunking**: key batches are right-padded to a
  multiple of ``pad_q`` (and processed in ``chunk``-sized pieces beyond
  that), so the per-(family, shape) cache stays small no matter how ragged
  the arriving batch sizes are;
- the **epoch-tagged closure cache**: reachability needs the transitive
  closure of the counters — O(w³ log w) to build, O(d·Q) to query.  The
  engine caches one closure tagged with the caller's *epoch* (any int that
  changes when the sketch changes, e.g. a count of ingested batches);
  repeated reach queries within an epoch amortize a single closure build;
- the **backend convention**: ``jnp`` (pure XLA) or ``pallas`` (the fused
  multi-query kernel from ``repro.kernels.query`` and the blocked closure
  kernel from ``repro.kernels.closure``); ``auto`` resolves via the
  ``REPRO_QUERY_BACKEND`` environment variable, else pallas on TPU and jnp
  elsewhere — the same convention as ingest.

See DESIGN.md Sections 3–4 for how the engine and the flow registers fit
together.
"""
from __future__ import annotations

import collections
import functools
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import queries, reach
from repro.core.sketch import GLavaSketch

QUERY_BACKENDS = ("jnp", "pallas")
DEFAULT_PAD_Q = 256
DEFAULT_CHUNK_Q = 16384
# Incremental-closure hygiene: touched-row batches pad to multiples of this
# (few jit shapes), refreshes fall back to a full rebuild when a batch
# touches more than CLOSURE_REFRESH_FRAC of the rows (the O(T·w²) refresh
# stops winning) or after CLOSURE_STALENESS_BUDGET incremental refreshes
# since the last full build (perf hygiene — the refresh itself is exact).
CLOSURE_REFRESH_PAD_T = 64
CLOSURE_REFRESH_FRAC = 0.25
CLOSURE_STALENESS_BUDGET = 256


def resolve_query_backend(backend: Optional[str]) -> str:
    """Resolve "auto"/None to a concrete query backend name."""
    if backend in (None, "auto"):
        env = os.environ.get("REPRO_QUERY_BACKEND", "").strip().lower()
        if env:
            backend = env
        else:
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in QUERY_BACKENDS:
        raise ValueError(
            f"unknown query backend: {backend!r} (want {QUERY_BACKENDS})"
        )
    return backend


def _pallas_edge_query(sketch: GLavaSketch, src: jax.Array, dst: jax.Array):
    from repro.kernels.query import ops as query_ops

    est = query_ops.edge_query(sketch, src, dst)
    # The kernel computes in fp32; counter values are exact integers there
    # (counting regime), so the cast back to the counter dtype is lossless
    # and keeps both backends dtype-identical.
    est = est.astype(sketch.counters.dtype)
    if not sketch.config.directed:
        est = queries.undirected_selfloop_correction(est, src, dst)
    return est


def _pallas_closure(counters: jax.Array):
    from repro.kernels.closure.ops import transitive_closure

    return transitive_closure(counters)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: jitted, its XLA module reads ``jit_<name>``,
    which is how a profile finds the query programs."""

    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return call


# Chunking and padding a key batch, and slicing the answers back to the
# asked length, are programs of the query path too, so they carry its name
# rather than run as eager ops.
_pad_keys = jax.jit(
    _named(
        lambda keys, lo, hi, pad: tuple(jnp.pad(k[lo:hi], (0, pad)) for k in keys),
        "glava_query_pad",
    ),
    static_argnums=(1, 2, 3),
)
_take = jax.jit(
    _named(lambda out, n: jax.tree_util.tree_map(lambda o: o[:n], out), "glava_query_take"),
    static_argnums=1,
)
_concat = jax.jit(
    _named(
        lambda outs: jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *outs),
        "glava_query_concat",
    )
)
# Touched node keys to the (d, T) row indices of a closure refresh, T padded
# with row 0.
_closure_rows = jax.jit(
    _named(
        lambda row_hash, keys, pad: jnp.pad(row_hash(keys), ((0, 0), (0, pad))),
        "glava_query_closure_rows",
    ),
    static_argnums=2,
)


def padded_len(q: int, pad_q: int, chunk_q: int) -> int:
    """Key slots a padded family dispatch of ``q`` keys runs: each chunk of
    up to ``chunk_q`` keys right-padded to a multiple of ``pad_q``."""
    full, rest = divmod(q, chunk_q)
    return full * (chunk_q + (-chunk_q) % pad_q) + rest + (-rest) % pad_q


# family -> (jnp fn, pallas fn); point/flow families are O(d·Q) register
# gathers either way, so both backends share the jnp path.
_FAMILIES: Dict[str, Tuple[Callable, Callable]] = {
    "edge": (queries.edge_query, _pallas_edge_query),
    "in_flow": (queries.node_in_flow, queries.node_in_flow),
    "out_flow": (queries.node_out_flow, queries.node_out_flow),
    "flow": (queries.node_flow, queries.node_flow),
    "heavy": (queries.check_heavy_keys, queries.check_heavy_keys),
    "heavy_vec": (queries.check_heavy_keys_vec, queries.check_heavy_keys_vec),
    "heavy_rel_vec": (
        queries.check_heavy_keys_rel_vec,
        queries.check_heavy_keys_rel_vec,
    ),
    "subgraph": (queries.subgraph_query, queries.subgraph_query),
    "subgraph_opt": (queries.subgraph_query_opt, queries.subgraph_query_opt),
    "subgraph_batch": (queries.subgraph_query_batch, queries.subgraph_query_batch),
    "reach_pre": (
        reach.reach_query_precomputed,
        reach.reach_query_precomputed,
    ),
    "closure": (reach.transitive_closure, _pallas_closure),
    # The touched-row refresh is small-matmul work XLA handles well on any
    # backend; the pallas closure kernel only pays off for full rebuilds.
    "closure_refresh": (reach.closure_refresh, reach.closure_refresh),
}

class QueryEngine:
    """A resolved query backend with per-family jit caching, query padding,
    and an epoch-tagged transitive-closure cache."""

    def __init__(
        self,
        backend: str = "auto",
        pad_q: int = DEFAULT_PAD_Q,
        chunk_q: int = DEFAULT_CHUNK_Q,
        closure_staleness_budget: int = CLOSURE_STALENESS_BUDGET,
        closure_refresh_frac: float = CLOSURE_REFRESH_FRAC,
        mesh: Optional[jax.sharding.Mesh] = None,
    ):
        self.backend = resolve_query_backend(backend)
        # Sketches whose counter rows are sharded over ``mesh``'s model
        # axis answer the edge family shard-locally (a gather in each shard,
        # one pmin); the other families read replicated registers or let
        # the compiler partition them.
        self.mesh = mesh
        self.shards = 1 if mesh is None else mesh.shape["model"]
        self.pad_q = pad_q
        self.chunk_q = max(chunk_q, pad_q)
        self.closure_staleness_budget = closure_staleness_budget
        self.closure_refresh_frac = closure_refresh_frac
        self._jits: Dict[str, Callable] = {}
        self._closure: Optional[jax.Array] = None
        self._closure_epoch: Optional[int] = None
        self._closure_family: Optional[bytes] = None
        self.closure_refreshes = 0           # full O(w³ log w) builds
        self.closure_incremental_refreshes = 0  # touched-row O(T·w²) refreshes
        self._incremental_since_full = 0
        # Engine dispatches per family (one per padded/chunked batch call) —
        # the API planner's one-dispatch-per-family contract is asserted
        # against these counts.
        self.dispatches: collections.Counter = collections.Counter()

    # -- jit cache -----------------------------------------------------------

    def _fn(self, family: str) -> Callable:
        fn = self._jits.get(family)
        if fn is None:
            jnp_fn, pallas_fn = _FAMILIES[family]
            impl = pallas_fn if self.backend == "pallas" else jnp_fn
            if family == "edge" and self.mesh is not None:
                from repro.core.distributed import distributed_edge_query

                impl = functools.partial(distributed_edge_query, self.mesh)
            fn = jax.jit(_named(impl, f"glava_query_{family}"))
            self._jits[family] = fn
        return fn

    @staticmethod
    def family_probe(
        family: str,
        *,
        width: int = 64,
        depth: int = 2,
        n_queries: int = 32,
    ):
        """Costlint sizing hook: the family's jnp estimator + args built at
        a parameterized (w, d, Q), so the cost pass can compile the same
        callable the engine jit-caches across a geometric size ladder.
        Returns ``(fn, args, counters_shape)``."""
        from repro.core import reach
        from repro.core.sketch import GLavaSketch, SketchConfig

        cfg = SketchConfig(depth=depth, width_rows=width, width_cols=width)
        sk = GLavaSketch.empty(cfg, jax.random.key(0))
        keys = jnp.arange(n_queries, dtype=jnp.uint32)
        shape = tuple(sk.counters.shape)
        jnp_fn = _FAMILIES[family][0]
        if family == "edge":
            return jnp_fn, (sk, keys, keys + jnp.uint32(1)), shape
        if family in ("in_flow", "out_flow", "flow"):
            return jnp_fn, (sk, keys), shape
        if family in ("heavy_vec", "heavy_rel_vec"):
            thetas = jnp.full((n_queries,), 0.5, jnp.float32)
            return jnp_fn, (sk, keys, thetas), shape
        if family == "closure":
            return jnp_fn, (sk.counters,), shape
        if family == "closure_refresh":
            closure = reach.transitive_closure(sk.counters)
            rows = sk.row_hash(keys[: min(8, n_queries)])
            return jnp_fn, (closure, sk.counters, rows), shape
        raise ValueError(f"no cost probe for query family {family!r}")

    # -- padding/chunking ----------------------------------------------------

    def padded_len(self, q: int) -> int:
        """Key slots :meth:`_run_padded` dispatches for ``q`` keys."""
        return padded_len(q, self.pad_q, self.chunk_q)

    def _run_padded(
        self,
        family: str,
        sketch_args,
        keys: Tuple[jax.Array, ...],
        tail_args: Tuple = (),
    ):
        """Run a per-query family over key arrays (each (Q,)): pad Q up to a
        multiple of pad_q so the jit cache sees few distinct shapes, chunk
        batches beyond chunk_q, slice the answers back to Q.  ``tail_args``
        ride along un-padded after the key arrays (e.g. a traced θ)."""
        self.dispatches[family] += 1
        fn = self._fn(family)
        q = keys[0].shape[0]
        outs = []
        for lo in range(0, max(q, 1), self.chunk_q):
            hi = min(q, lo + self.chunk_q)
            n = hi - lo
            pad = (-n) % self.pad_q
            part = keys if n == q and not pad else _pad_keys(keys, lo, hi, pad)
            out = fn(*sketch_args, *part, *tail_args)
            outs.append(_take(out, n) if pad else out)
        if len(outs) == 1:
            return outs[0]
        return _concat(tuple(outs))

    # -- query families ------------------------------------------------------

    def edge(self, sketch: GLavaSketch, src, dst):
        return self._run_padded("edge", (sketch,), (src, dst))

    def in_flow(self, sketch: GLavaSketch, keys):
        return self._run_padded("in_flow", (sketch,), (keys,))

    def out_flow(self, sketch: GLavaSketch, keys):
        return self._run_padded("out_flow", (sketch,), (keys,))

    def flow(self, sketch: GLavaSketch, keys):
        return self._run_padded("flow", (sketch,), (keys,))

    def heavy(self, sketch: GLavaSketch, keys, theta: float):
        # theta rides along as a traced array so one trace serves all θ.
        return self._run_padded(
            "heavy", (sketch,), (keys,), (jnp.asarray(theta, jnp.float32),)
        )

    def heavy_vec(self, sketch: GLavaSketch, keys, thetas):
        """Heavy-hitter check with a PER-QUERY θ array — lets the planner
        serve a mixed-θ heavy family in one dispatch.  ``thetas`` pads with
        zeros alongside the keys (padded lanes are sliced away)."""
        return self._run_padded(
            "heavy_vec",
            (sketch,),
            (keys, jnp.asarray(thetas, jnp.float32)),
        )

    def heavy_rel_vec(self, sketch: GLavaSketch, keys, thetas):
        """Per-query RELATIVE-θ heavy-hitter check: flows compare against
        θ·F̃ with F̃ the total-stream-weight register estimate — the API
        plane's heavy semantics (θ a fraction in (0, 1], validated at Query
        construction)."""
        return self._run_padded(
            "heavy_rel_vec",
            (sketch,),
            (keys, jnp.asarray(thetas, jnp.float32)),
        )

    def subgraph(self, sketch: GLavaSketch, src, dst, optimized: bool = False):
        # Subgraph queries reduce over the WHOLE edge set — zero-padding
        # would change the answer (absent-edge semantics) — so they jit at
        # their exact (small-k) shape instead of going through _run_padded.
        family = "subgraph_opt" if optimized else "subgraph"
        self.dispatches[family] += 1
        return self._fn(family)(sketch, src, dst)

    def subgraph_batch(self, sketch: GLavaSketch, src, dst, mask):
        """n subgraph queries padded to a common k with a validity mask —
        masked padding keeps the revised absent-edge semantics exact, so a
        whole subgraph family is one dispatch (jitted at the (n, k) shape)."""
        self.dispatches["subgraph_batch"] += 1
        return self._fn("subgraph_batch")(sketch, src, dst, mask)

    # -- reachability + closure cache ----------------------------------------

    @staticmethod
    def _family_key(sketch: GLavaSketch) -> bytes:
        """Hash-family identity BY VALUE: jit-updated sketches carry fresh
        array objects every batch, so object identity would spuriously miss;
        the (d, 1) coefficient array is cheap to snapshot."""
        return np.asarray(sketch.row_hash.a).tobytes()

    def _closure_fresh(self, sketch: GLavaSketch, epoch: Optional[int]) -> bool:
        return (
            self._closure is not None
            and epoch is not None
            and epoch == self._closure_epoch
            and self._closure_family == self._family_key(sketch)
        )

    def closure_for(
        self, sketch: GLavaSketch, epoch: Optional[int] = None
    ) -> jax.Array:
        """The transitive closure of ``sketch.counters``, rebuilt only when
        ``epoch`` differs from the cached tag (``None`` always rebuilds).

        The cache is additionally tagged with the sketch's hash-family
        VALUE, so one engine serving sketches from differently-seeded
        streams cannot cross-serve a closure even if their caller-managed
        epochs collide.  Two SAME-seeded streams share a family value, so
        the epoch is the only discriminator between them — the engine's
        contract is one stream per engine (the `GraphStream` facade owns
        an engine per session); core callers multiplexing one engine
        across same-family sketches must keep their epochs disjoint."""
        if not self._closure_fresh(sketch, epoch):
            self._closure = self._fn("closure")(sketch.counters)
            self._closure_epoch = epoch
            self._closure_family = self._family_key(sketch)
            self.closure_refreshes += 1
            self._incremental_since_full = 0
        return self._closure

    def refresh_closure(
        self,
        sketch: GLavaSketch,
        touched_keys,
        epoch: Optional[int] = None,
    ) -> jax.Array:
        """Bring the cached closure up to ``epoch`` INCREMENTALLY from the
        node keys whose rows the mutations since the cached epoch touched
        (``reach.closure_refresh`` — exact for additions-only histories).

        ``touched_keys`` is a unique (U,) uint32 key array, OR a (d, w_r)
        bool BITMAP of touched row buckets (the fused ingest kernel's
        device-emitted form — ``GLavaSketch.update_fused``), or ``None``
        meaning "unknown / not additions-only" (deletes, window expiry,
        merges) which — like a missing or foreign cached closure — falls
        back to a full :meth:`closure_for` build.  So does a refresh past
        the staleness budget (``closure_staleness_budget`` incremental
        refreshes since the last full build) or a batch touching more than
        ``closure_refresh_frac`` of the rows, where re-squaring is cheaper.
        The subscription plane drives this per re-evaluation tick; counts
        land in ``closure_incremental_refreshes``."""
        if self._closure_fresh(sketch, epoch):
            return self._closure
        can_incremental = (
            self._closure is not None
            and touched_keys is not None
            and epoch is not None
            and self._closure_family == self._family_key(sketch)
            and self._incremental_since_full < self.closure_staleness_budget
        )
        rows = None
        w_r = sketch.counters.shape[1]
        if can_incremental:
            touched_keys = np.atleast_1d(np.asarray(touched_keys))
            if touched_keys.ndim == 2:
                # Touched-row bitmap: per-depth row indices, right-padded
                # with row 0 to a shared T (idempotent under the union).
                bitmap = touched_keys.astype(bool)
                counts = bitmap.sum(axis=1)
                t_max = int(counts.max()) if counts.size else 0
                if t_max > self.closure_refresh_frac * w_r:
                    can_incremental = False
                elif t_max > 0:
                    t_pad = t_max + (-t_max) % CLOSURE_REFRESH_PAD_T
                    rows_np = np.zeros((bitmap.shape[0], t_pad), np.int32)
                    for i in range(bitmap.shape[0]):
                        idx = np.flatnonzero(bitmap[i])
                        rows_np[i, : idx.size] = idx
                    rows = jnp.asarray(rows_np)
                touched_size = t_max
            else:
                if touched_keys.size > self.closure_refresh_frac * w_r:
                    can_incremental = False
                touched_size = touched_keys.size
        if not can_incremental:
            return self.closure_for(sketch, epoch)
        if touched_size == 0:
            # Nothing touched: the counters are unchanged, only retag.
            self._closure_epoch = epoch
            return self._closure
        if rows is None:
            # Padding with row 0 is exact: an untouched row only restates
            # paths the cached closure already contains.
            rows = _closure_rows(
                sketch.row_hash,
                touched_keys.astype(np.uint32, copy=False),
                (-touched_keys.size) % CLOSURE_REFRESH_PAD_T,
            )
        self._closure = self._fn("closure_refresh")(
            self._closure, sketch.counters, rows
        )
        self._closure_epoch = epoch
        self.closure_incremental_refreshes += 1
        self._incremental_since_full += 1
        return self._closure

    def reach(
        self,
        sketch: GLavaSketch,
        src,
        dst,
        epoch: Optional[int] = None,
    ):
        """Batched r̃(a, b) against the epoch-cached closure: repeated reach
        queries amortize one O(w³ log w) closure instead of recomputing it
        per call."""
        closure = self.closure_for(sketch, epoch)
        return self._run_padded("reach_pre", (sketch, closure), (src, dst))

    def invalidate(self):
        """Drop the cached closure (e.g. the sketch object was swapped)."""
        self._closure = None
        self._closure_epoch = None
        self._closure_family = None
        self._incremental_since_full = 0
