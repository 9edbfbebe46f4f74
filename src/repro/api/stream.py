"""`GraphStream` — the one session facade over the paper's summary S.

The paper maintains a SINGLE summary supporting a wide range of graph
queries over one stream.  `GraphStream` is that object for callers: it
wraps the ingest plane (:class:`~repro.core.ingest.IngestEngine`, double-
buffered batched dispatch), the query plane (:class:`~repro.core.
query_engine.QueryEngine`, planned + fused by :mod:`repro.api.planner`),
the standing-query plane (:mod:`repro.api.subscription`), and the optional
sliding window (:class:`~repro.core.window.SlidingWindowSketch`),
distributed plane (`mesh=`), and :class:`~repro.checkpoint.manager.
CheckpointManager` behind one handle::

    from repro.api import GraphStream, Query

    gs = GraphStream.open("smoke")           # or a SketchConfig / (ε, δ)
    gs.ingest(["alice", "bob"], ["bob", "carol"])      # labels, not keys

    # one-shot pull
    res = gs.query(Query.edge("alice", "bob"),
                   Query.in_flow("bob"),
                   Query.reach("alice", "carol"))
    print(res[0].value, res[0].error)        # (ε, δ)-annotated estimate

    # standing subscription: compiled once, re-evaluated incrementally
    # after every 4th mutation, results as timestamped events
    sub = gs.subscribe(Query.reach("alice", "carol"),
                       Query.in_flow("carol"), every=4)
    gs.ingest(more_src, more_dst)
    for event in sub.poll():
        print(event.tick, event.results)

Node labels (str/int) are encoded exactly once at this boundary by the
vectorized key codec (:mod:`repro.api.codec`); everything below speaks
uint32.  Every entry point of the repo (serving engine, launch driver,
examples, benchmarks) routes through this facade — ``repro.core`` stays
importable for internals, but `repro.api` is the canonical public API.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.codec import encode_label, encode_labels
from repro.api.planner import execute
from repro.api.query import (
    ErrorBound,
    Query,
    QueryBatch,
    QueryResult,
    error_bound_for,
    validate_theta,
)
from repro.api.subscription import (
    DEFAULT_MAX_PENDING,
    Subscription,
    SubscriptionEvent,
    sub_progress_key,
)
from repro.core import queries as queries_mod
from repro.core.ingest import (
    pad_bucket,
    preaggregate_host,
    resolve_backend,
    resolve_preagg,
    touched_row_keys,
)
from repro.core.query_engine import QueryEngine
from repro.core.sketch import GLavaSketch, SketchConfig
from repro.core.window import SlidingWindowSketch
from repro.stream.events import EventFeed
from repro.stream.wal import (
    AdvanceMutation,
    EdgeMutation,
    WriteAheadLog,
)
from repro.stream.watermark import (
    DEFAULT_SOURCE,
    WatermarkTracker,
    slice_of,
    slices_of,
)

# Session-wide event feed bound (per-subscription queues have their own);
# past it the session's ``events_policy`` applies and ``events_dropped``
# counts the loss (no more silent truncation).
EVENT_LOG_MAXLEN = 4096

LATE_POLICIES = ("retract", "drop")


@dataclasses.dataclass
class StreamStats:
    """Session counters (edges ingested, queries served, closure
    refreshes, subscription ticks)."""

    edges_ingested: int = 0
    queries_served: int = 0
    closure_refreshes: int = 0
    closure_incremental_refreshes: int = 0
    subscription_ticks: int = 0
    auto_advances: int = 0

    def summary(self) -> Dict[str, float]:
        return {
            "edges_ingested": self.edges_ingested,
            "queries_served": self.queries_served,
            "closure_refreshes": self.closure_refreshes,
            "closure_incremental_refreshes": self.closure_incremental_refreshes,
            "subscription_ticks": self.subscription_ticks,
            "auto_advances": self.auto_advances,
        }


@dataclasses.dataclass(frozen=True)
class IngestReceipt:
    """What one ``ingest`` call did: the post-batch epoch, the batch size,
    and the batch's touched-key set — the unique uint32 node keys whose
    sketch ROWS the batch wrote.  ``None`` means "no usable delta": the
    batch carried negative weights (not additions-only), overflowed the
    row-width tracking cap, or the session had already stopped tracking
    (a prior non-additive mutation with no closure sync since).  The
    subscription plane feeds non-``None`` sets to the incremental closure
    refresh; ``None`` forces the next refresh to rebuild from scratch.

    Fused-ingest sessions (``ingest_backend="fused"``) report the delta as
    ``touched_rows`` instead: the (d, w_r) bool row-bucket bitmap the
    one-pass kernel emitted on device — no host unique pass at all.
    ``touched_keys`` is ``None`` for those receipts."""

    epoch: int
    n_edges: int
    touched_keys: Optional[np.ndarray]
    touched_rows: Optional[jax.Array] = None
    # Event-time plane (None / 0 for arrival-ordered sessions): the
    # batch's event-time span, the session watermark after folding it,
    # how many edges the lateness policy dropped/retracted, how many
    # slice advances the watermark drove, and the batch's durable WAL
    # commit seq (None when the session has no WAL).
    event_time_min: Optional[float] = None
    event_time_max: Optional[float] = None
    watermark: Optional[float] = None
    late_dropped: int = 0
    late_retracted: int = 0
    auto_advances: int = 0
    wal_seq: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RecoveryReport:
    """What :meth:`GraphStream.recover` did: the checkpoint step it
    restored (None = no checkpoint, full-genesis replay), how many WAL
    mutations it replayed, and the session epoch / WAL position after."""

    step: Optional[int]
    mutations_replayed: int
    epoch: int
    wal_seq: int


def _preset(name: str) -> SketchConfig:
    from repro.configs import glava

    presets = {
        "smoke": glava.SMOKE,
        "base": glava.BASE,
        "web": glava.WEB,
        "nonsquare": glava.NONSQUARE,
    }
    if name not in presets:
        raise ValueError(f"unknown preset {name!r} (want {sorted(presets)})")
    return presets[name]


def _bytes_limit(device) -> Optional[int]:
    """The memory a device holds arrays in, where its backend reports it
    (TPU and GPU do, the CPU does not)."""
    return (device.memory_stats() or {}).get("bytes_limit")


def _empty_sharded(config: SketchConfig, key, mesh) -> GLavaSketch:
    """An empty sketch whose counter rows are sharded over ``mesh``'s model
    axis, allocated by one jitted constructor with those output shardings:
    each device writes only its own rows, no device ever holds a whole-size
    copy.  The hash family and flow registers are replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    make = functools.partial(GLavaSketch.empty, config)
    shapes = jax.eval_shape(make, key)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    rows, replicated = NamedSharding(mesh, P(None, "model", None)), NamedSharding(mesh, P())
    shardings = [rows if x is shapes.counters else replicated for x in leaves]
    flat = jax.jit(
        lambda k: jax.tree_util.tree_leaves(make(k)), out_shardings=shardings
    )(key)
    sketch = jax.tree_util.tree_unflatten(treedef, flat)
    if config.is_square:  # one family for rows and columns, as on one device
        sketch = dataclasses.replace(sketch, col_hash=sketch.row_hash)
    return sketch


def row_shards(config: SketchConfig, devices) -> int:
    """The placement rule of :class:`GraphStream` (see its docstring): how
    many of ``devices`` a session's counters are row-sharded over, 1 for a
    session that stays on one device."""
    limits = [_bytes_limit(d) for d in devices]
    n = len(devices)
    if n == 1 or None in limits or 2 * config.space_bytes() <= min(limits):
        return 1
    if config.width_rows % n:
        raise ValueError(
            f"{config.space_bytes()} B of counters do not fit one device "
            f"({min(limits)} B), and their {config.width_rows} rows do not "
            f"divide over the {n} local devices"
        )
    return n


class GraphStream:
    """One graph-stream session: a summary plus its ingest/query engines.

    Construct via :meth:`open`.  All mutation bumps the sketch *epoch*,
    which tags the query engine's transitive-closure cache so reach
    queries amortize one closure per quiescent period.

    Placement: a session opened without ``mesh=`` stays on one device while
    its counters (d · w_r · w_c · 4 bytes) take at most half of the
    smallest local device's memory (``memory_stats()["bytes_limit"]``),
    which leaves room for a second whole copy; so does any session whose
    devices report no memory (the CPU), a windowed or ``fused`` one, and
    any on a host of one device.  Otherwise its counters are row-sharded
    over all n local devices on a 1 × n ``("data", "model")`` mesh,
    allocated sharded, with the hash family and flow registers replicated;
    n must divide w_r.  A session on a mesh ingests through the donated
    jit boundary (``auto``: the Pallas kernel per row shard) and answers
    edge queries shard-locally (one ``pmin`` over ``model``)."""

    def __init__(
        self,
        config: SketchConfig,
        *,
        seed: int = 0,
        window_slices: Optional[int] = None,
        ingest_backend: str = "auto",
        query_backend: str = "auto",
        checkpoint_dir: Optional[str] = None,
        keep: int = 3,
        mesh: Optional[jax.sharding.Mesh] = None,
        double_buffer: bool = True,
        max_inflight: int = 2,
        preagg: str = "auto",
        wal_dir: Optional[str] = None,
        wal_fsync_every: int = 1,
        slice_width: Optional[float] = None,
        max_lateness: Optional[float] = None,
        late_policy: str = "retract",
        events_policy: str = "drop_oldest",
    ):
        if mesh is not None and window_slices:
            raise ValueError("windowed + distributed sessions are not supported yet")
        # Event-time plane: slice_width maps event times onto the window
        # ring; max_lateness bounds out-of-orderness (how far behind the
        # per-source maximum the watermark trails).
        if late_policy not in LATE_POLICIES:
            raise ValueError(
                f"unknown late_policy {late_policy!r} (want one of {LATE_POLICIES})"
            )
        self._late_policy = late_policy
        self._tracker: Optional[WatermarkTracker] = None
        self._slice_width: Optional[float] = None
        self._lead = 0
        self._head_slice: Optional[int] = None
        # Host mirror of the ring's current-slot index: slot(b) for an
        # absolute slice b is (b - head_slice + ring_pos) % K, an invariant
        # because the head and the ring only ever advance together.
        self._ring_pos = 0
        if max_lateness is not None and slice_width is None:
            raise ValueError("max_lateness needs slice_width= (event-time slicing)")
        if slice_width is not None:
            if not window_slices:
                raise ValueError("slice_width needs window_slices= (a sliding window)")
            slice_width = float(slice_width)
            if not (slice_width > 0.0) or not math.isfinite(slice_width):
                raise ValueError(f"slice_width must be finite and > 0, got {slice_width}")
            lateness = float(max_lateness) if max_lateness is not None else 0.0
            self._tracker = WatermarkTracker(lateness)
            self._slice_width = slice_width
            # Head slices the ring must keep open AHEAD of the watermark:
            # an in-bound edge (t >= W) from the watermark-defining source
            # sits at most max_lateness past W, i.e. <= lead slices ahead.
            self._lead = int(math.ceil(lateness / slice_width))
            if self._lead + 1 > window_slices:
                raise ValueError(
                    f"max_lateness={lateness:g} spans {self._lead} slices of "
                    f"width {slice_width:g} — it must fit inside the "
                    f"window ring (window_slices={window_slices}); widen the "
                    f"slices or deepen the window"
                )
        self._wal = (
            WriteAheadLog(wal_dir, fsync_every=wal_fsync_every)
            if wal_dir is not None
            else None
        )
        self._replaying = False
        self._last_restore_meta: Dict = {}
        self.config = config
        with TraceAnnotation("glava.open.place") as span:
            if mesh is None and not window_slices and ingest_backend != "fused":
                devices = jax.local_devices()
                n = row_shards(config, devices)
                if n > 1:
                    mesh = jax.sharding.Mesh(
                        np.asarray(devices).reshape(1, n), ("data", "model")
                    )
            if mesh is not None:
                # The estimators run on the sharded counters as on one
                # device: Auto axes let the compiler partition them, where
                # Explicit axes (jax.make_mesh's default) would demand an
                # output sharding for every gather.
                mesh = jax.sharding.Mesh(
                    mesh.devices,
                    mesh.axis_names,
                    axis_types=(jax.sharding.AxisType.Auto,) * mesh.devices.ndim,
                )
            shards = 1 if mesh is None else mesh.shape["model"]
            span.set_metadata(bytes_per_device=config.space_bytes() // shards)
            if window_slices:
                self._window: Optional[SlidingWindowSketch] = (
                    SlidingWindowSketch.empty(config, window_slices, jax.random.key(seed))
                )
                self._sketch: Optional[GLavaSketch] = None
            else:
                self._window = None
                key = jax.random.key(seed)
                self._sketch = (
                    GLavaSketch.empty(config, key)
                    if mesh is None
                    else _empty_sharded(config, key, mesh)
                )
        if mesh is not None and query_backend in (None, "auto"):
            # The compiler cannot partition a Mosaic kernel: the register
            # families run as XLA gathers, and the edge family shard-locally
            # (QueryEngine with a mesh).
            query_backend = "jnp"
        # "fused" is a session-level mode, not an IngestEngine backend: the
        # one-pass kernel updates counters + registers + touched bitmap
        # together, which only a plain local session can consume.
        self._fused = ingest_backend == "fused"
        if self._fused and (mesh is not None or window_slices):
            raise ValueError("fused ingest needs a plain local session")
        self.ingest_backend = (
            "fused" if self._fused else resolve_backend(ingest_backend)
        )
        # Host-side pre-aggregation of duplicate (src, dst) pairs before
        # dispatch ("auto" honours REPRO_INGEST_PREAGG, else batches >=
        # PREAGG_MIN_BATCH) — the heavy-tail ingest fast path.
        self._preagg = preagg
        self.engine = QueryEngine(query_backend, mesh=mesh)
        self.stats = StreamStats()
        self._mesh = mesh
        self._shards = shards
        self._epoch = 0
        # Standing-query plane: registered subscriptions, the session-wide
        # event feed, and the touched-key accumulator feeding the
        # incremental closure refresh (None = "not additions-only since the
        # last closure sync; full rebuild required").
        self._subs: Dict[int, Subscription] = {}
        self._next_sub_id = 0
        self._event_log = EventFeed(EVENT_LOG_MAXLEN, events_policy)
        self._touched: Optional[List[np.ndarray]] = []
        self._touched_count = 0
        self._monitor_subs: Dict[Tuple[int, float], Subscription] = {}
        # Double-buffered ingest: JAX dispatch is async, so staging the next
        # host batch overlaps the device accumulating the previous one; the
        # deque bounds how many un-materialized updates may be in flight.
        self._max_inflight = max_inflight if double_buffer else 0
        self._inflight: collections.deque = collections.deque()
        backend = self.ingest_backend
        # Donate the live summary through the jit boundary: the update is a
        # scatter-add into the (d, w_r, w_c) counters, so XLA writes them in
        # place instead of allocating a full copy per batch.  Two wrinkles:
        # square sketches alias col_hash to row_hash, and donating the same
        # buffer twice is an XLA error — so the boundary dispatches over the
        # DEDUPLICATED leaf tuple and rebuilds the pytree on both sides.
        # And the double-buffer queue must not hold the counters themselves
        # (they become the donated, hence deleted, inputs of the next
        # dispatch), so the update also returns a tiny completion token the
        # queue blocks on instead.
        live0 = self._window if self._window is not None else self._sketch
        leaves0, treedef = jax.tree_util.tree_flatten(live0)
        seen: Dict[int, int] = {}
        slots = []       # leaf position -> unique-buffer slot
        uniq_idx = []    # unique-buffer slot -> first leaf position
        for i, leaf in enumerate(leaves0):
            j = seen.setdefault(id(leaf), len(uniq_idx))
            if j == len(uniq_idx):
                uniq_idx.append(i)
            slots.append(j)
        self._live_treedef = treedef
        self._uniq_leaf_idx = tuple(uniq_idx)
        slots = tuple(slots)

        if self._fused:

            def _update(uniq, s, d, w):
                live = jax.tree_util.tree_unflatten(
                    treedef, [uniq[j] for j in slots]
                )
                new, touched = live.update_fused(s, d, w)
                return jax.tree_util.tree_leaves(new), jnp.sum(w), touched

        else:

            def _update(uniq, s, d, w):
                live = jax.tree_util.tree_unflatten(
                    treedef, [uniq[j] for j in slots]
                )
                # In-jit pre-aggregation stays off HERE: the session already
                # collapses heavy-tail batches host-side (below), so a
                # second device sort would be pure overhead.
                new = live.update(s, d, w, backend=backend, preagg="off")
                return jax.tree_util.tree_leaves(new), jnp.sum(w)

        self._jit_update = jax.jit(_update, donate_argnums=0)

        def _update_pre(uniq, s, d, w, su, sw, du, dw):
            live = jax.tree_util.tree_unflatten(treedef, [uniq[j] for j in slots])
            new = live.update_preaggregated(
                s, d, w, su, sw, du, dw, backend=backend
            )
            return jax.tree_util.tree_leaves(new), jnp.sum(w)

        # The host-collapsed fast path's donated boundary: distinct pairs
        # feed the counter scatter, per-endpoint marginal totals feed the
        # flow registers.  Arrays arrive padded to power-of-two buckets
        # (pad_bucket) so variable collapse sizes cost a bounded trace
        # ladder, not a retrace per batch.
        self._jit_update_pre = jax.jit(_update_pre, donate_argnums=0)

        if mesh is not None:
            from repro.core.distributed import distributed_ingest

            # The mesh's donated boundary, raw or host-collapsed batches
            # alike: each device updates its row shard in place.
            def _update_mesh(uniq, s, d, w, *marginals):
                live = jax.tree_util.tree_unflatten(
                    treedef, [uniq[j] for j in slots]
                )
                new = distributed_ingest(
                    mesh, live, s, d, w,
                    backend=backend, preagg_marginals=marginals or None,
                )
                return jax.tree_util.tree_leaves(new), jnp.sum(w)

            self._jit_update_mesh = jax.jit(_update_mesh, donate_argnums=0)
            self._replicated = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()
            )

        # Window expiry boundary: advancing the ring is pure data movement
        # over the (K, d, w_r, w_c) slices, so donating the window lets XLA
        # zero the expiring slice in place instead of copying the whole
        # ring per advance — the same dedup-dispatch shape as _jit_update.
        if self._window is not None:

            def _advance(uniq):
                live = jax.tree_util.tree_unflatten(
                    treedef, [uniq[j] for j in slots]
                )
                return jax.tree_util.tree_leaves(live.advance())

            self._jit_advance = jax.jit(_advance, donate_argnums=0)

            # Event-time routing boundary: fold a batch into an ARBITRARY
            # ring slot (late-but-in-bound edges land in the slice their
            # event time belongs to).  The slot is a traced int32 scalar,
            # so ONE compiled update serves all K slices; the ring is
            # donated exactly like _jit_update.
            def _update_slice(uniq, s, d, w, slot):
                live = jax.tree_util.tree_unflatten(
                    treedef, [uniq[j] for j in slots]
                )
                new = live.update_at(slot, s, d, w, backend=backend)
                return jax.tree_util.tree_leaves(new), jnp.sum(w)

            self._jit_update_slice = jax.jit(_update_slice, donate_argnums=0)
        else:
            self._jit_advance = None
            self._jit_update_slice = None
        self._ckpt = None
        if checkpoint_dir is not None:
            from repro.checkpoint.manager import CheckpointManager

            self._ckpt = CheckpointManager(checkpoint_dir, keep=keep)

    # -- construction ---------------------------------------------------------

    @classmethod
    def open(
        cls,
        config: Union[SketchConfig, str, None] = None,
        *,
        epsilon: Optional[float] = None,
        delta: Optional[float] = None,
        **kwargs,
    ) -> "GraphStream":
        """Open a session from a :class:`SketchConfig`, a preset name
        ("smoke" / "base" / "web" / "nonsquare"), or a target (ε, δ) pair
        sized per paper Thm 1 / Lemma 5.2.  Remaining kwargs are forwarded
        to the constructor (seed, window_slices, ingest_backend,
        query_backend, checkpoint_dir, mesh, ...)."""
        if isinstance(config, str):
            config = _preset(config)
        elif config is None:
            if epsilon is None or delta is None:
                raise ValueError("open() needs a config, a preset, or (epsilon, delta)")
            config = SketchConfig.for_error(epsilon, delta)
        elif not isinstance(config, SketchConfig):
            raise TypeError(f"config must be SketchConfig or preset name, got {config!r}")
        return cls(config, **kwargs)

    # -- costlint sizing hooks -------------------------------------------------

    @classmethod
    def cost_probe_update(
        cls,
        *,
        width: int = 64,
        depth: int = 2,
        batch: int = 64,
        negative: bool = False,
    ):
        """The REAL donated ingest jit boundary instantiated at a
        parameterized (w, d, B) — the sizing hook costlint compiles at a
        geometric size ladder to fit scaling exponents.  ``negative=True``
        probes the turnstile-delete path (same boundary, negative weights).
        Returns ``(jit_fn, args, counters_shape)``."""
        gs = cls.open(
            SketchConfig(depth=depth, width_rows=width, width_cols=width),
            ingest_backend="scatter",
            query_backend="jnp",
        )
        leaves = jax.tree_util.tree_leaves(gs._sketch)
        uniq = tuple(leaves[i] for i in gs._uniq_leaf_idx)
        src = jnp.arange(batch, dtype=jnp.uint32)
        dst = src + jnp.uint32(batch)
        w = jnp.full((batch,), -1.0 if negative else 1.0, jnp.float32)
        return gs._jit_update, (uniq, src, dst, w), tuple(gs._sketch.counters.shape)

    @classmethod
    def cost_probe_advance(
        cls, *, width: int = 64, depth: int = 2, slices: int = 4
    ):
        """The donated window-advance boundary at a parameterized (w, d, K).
        Returns ``(jit_fn, args, slices_shape)``."""
        gs = cls.open(
            SketchConfig(depth=depth, width_rows=width, width_cols=width),
            window_slices=slices,
            ingest_backend="scatter",
            query_backend="jnp",
        )
        leaves = jax.tree_util.tree_leaves(gs._window)
        uniq = tuple(leaves[i] for i in gs._uniq_leaf_idx)
        return gs._jit_advance, (uniq,), tuple(gs._window.slices.shape)

    @classmethod
    def cost_probe_update_slice(
        cls, *, width: int = 64, depth: int = 2, slices: int = 4, batch: int = 64
    ):
        """The donated event-time slice-routing boundary at a parameterized
        (w, d, K, B) — one batch folded into one traced ring slot.
        Returns ``(jit_fn, args, slices_shape)``."""
        gs = cls.open(
            SketchConfig(depth=depth, width_rows=width, width_cols=width),
            window_slices=slices,
            ingest_backend="scatter",
            query_backend="jnp",
        )
        leaves = jax.tree_util.tree_leaves(gs._window)
        uniq = tuple(leaves[i] for i in gs._uniq_leaf_idx)
        src = jnp.arange(batch, dtype=jnp.uint32)
        dst = src + jnp.uint32(batch)
        w = jnp.ones((batch,), jnp.float32)
        slot = jnp.array(0, jnp.int32)
        return (
            gs._jit_update_slice,
            (uniq, src, dst, w, slot),
            tuple(gs._window.slices.shape),
        )

    # -- state ---------------------------------------------------------------

    @property
    def epoch(self) -> int:
        """Mutation counter; tags the engine's closure cache."""
        return self._epoch

    @property
    def watermark(self) -> Optional[float]:
        """The event-time low watermark (None on arrival-ordered sessions;
        -inf before the first timestamped batch)."""
        return None if self._tracker is None else self._tracker.watermark

    @property
    def late_dropped(self) -> int:
        """Too-late edges dropped by ``late_policy="drop"`` (monotone)."""
        return 0 if self._tracker is None else self._tracker.late_dropped

    @property
    def late_retracted(self) -> int:
        """Too-late edges backed out via the turnstile-delete path by
        ``late_policy="retract"`` (monotone)."""
        return 0 if self._tracker is None else self._tracker.late_retracted

    @property
    def events_dropped(self) -> int:
        """Session-feed events lost to the overflow policy (monotone); the
        per-subscription counters live on ``Subscription.events_dropped``."""
        return self._event_log.dropped

    @property
    def wal_seq(self) -> Optional[int]:
        """The WAL's last durable record seq (None without a WAL)."""
        return None if self._wal is None else self._wal.last_seq

    @property
    def sketch(self) -> GLavaSketch:
        """The live summary (window sessions materialize the window sum)."""
        self.flush()
        return self._live()

    def _live(self) -> GLavaSketch:
        return self._window.window_sketch() if self._window else self._sketch

    def error_bound(self, family: str = "edge") -> ErrorBound:
        """The (ε, δ) annotation this session attaches to ``family`` results."""
        return error_bound_for(family, self.config)

    # -- ingest ---------------------------------------------------------------

    def _dispatch_update(self, live, s, d, w):
        """One donated ingest dispatch: live pytree -> (new live, token,
        touched-row bitmap or None).  Fused sessions get the bitmap from
        the one-pass kernel; plain sessions return None."""
        leaves = jax.tree_util.tree_leaves(live)
        uniq = tuple(leaves[i] for i in self._uniq_leaf_idx)
        if self._fused:
            new_leaves, token, touched = self._jit_update(uniq, s, d, w)
        else:
            new_leaves, token = self._jit_update(uniq, s, d, w)
            touched = None
        new = jax.tree_util.tree_unflatten(self._live_treedef, new_leaves)
        return new, token, touched

    def _donated(self, jit_fn, live, staged):
        """One dispatch through a donated boundary that returns (leaves,
        token): live pytree -> (new live, token)."""
        leaves = jax.tree_util.tree_leaves(live)
        uniq = tuple(leaves[i] for i in self._uniq_leaf_idx)
        new_leaves, token = jit_fn(uniq, *staged)
        return jax.tree_util.tree_unflatten(self._live_treedef, new_leaves), token

    def _dispatch_update_pre(self, live, staged):
        """One donated dispatch of a host-collapsed batch: the PreaggBatch's
        pairs, weights and marginals, bucket-padded and on the device."""
        return self._donated(self._jit_update_pre, live, staged)

    def _kernel_steps(self, slots: int) -> int:
        """Grid steps the ingest kernel runs for a batch of ``slots``
        entries on each device, one call per direction: a host integer from
        the shapes.  On a mesh every row shard runs the grid of its rows
        and its stream shard's entries, out-of-shard entries included."""
        from repro.kernels.ingest.kernel import grid_steps

        cfg = self.config
        calls = 1 if cfg.directed else 2
        rows, per_device = cfg.width_rows, slots
        if self._mesh is not None:
            rows //= self._shards
            per_device //= self._mesh.size // self._shards
        return calls * grid_steps(cfg.depth, rows, cfg.width_cols, per_device)

    def ingest(
        self, src, dst, weights=None, *, timestamps=None, source=None
    ) -> IngestReceipt:
        """Fold one edge batch into the summary.  ``src``/``dst`` are label
        batches (str or int — encoded here by the key codec); returns as
        soon as the device accepts the batch (double-buffered; call
        :meth:`flush` or any query to synchronize) — UNLESS a subscription
        comes due on this mutation, in which case the batch lands and the
        standing queries re-evaluate before returning.

        ``timestamps`` is the per-edge EVENT-TIME column (float seconds,
        any epoch).  On an event-time session (opened with ``slice_width=``
        / ``max_lateness=``) it is required: the watermark tracker folds the
        batch, auto-advances the sliding window when the watermark crosses
        a slice boundary, routes late-but-in-bound edges into the slice
        their event time belongs to, and drops or retracts too-late edges
        per ``late_policy``.  ``source`` names the emitting stream for the
        per-source low-watermark merge (one slow source holds the session
        watermark back).

        Returns an :class:`IngestReceipt` carrying the batch's touched-key
        set (the rows it wrote) — the delta the incremental closure refresh
        consumes — plus the event-time fields (watermark, late counts, WAL
        seq) when those planes are active."""
        with TraceAnnotation("glava.ingest", epoch=self._epoch + 1) as span:
            with TraceAnnotation("glava.ingest.encode"):
                args = self._encode(src, dst, weights, timestamps, source)
            span.set_metadata(edges=int(args[0].shape[0]))
            return self._ingest_encoded(*args)

    def _encode(self, src, dst, weights, timestamps, source):
        """Labels to uint32 keys, weights and event times to their dtypes,
        the source label to its key: ``_ingest_encoded``'s arguments."""
        s_np = np.atleast_1d(encode_labels(src))
        d_np = np.atleast_1d(encode_labels(dst))
        if s_np.shape != d_np.shape:
            raise ValueError(
                f"src/dst shape mismatch: {s_np.shape} vs {d_np.shape}"
            )
        n_edges = int(s_np.shape[0])
        w_np = (
            np.ones(n_edges, np.float32)
            if weights is None
            else np.asarray(weights, np.float32)
        )
        ts_np = None
        if timestamps is not None:
            ts_np = np.atleast_1d(np.asarray(timestamps, np.float64))
            if ts_np.shape != s_np.shape:
                raise ValueError(
                    f"timestamps/src shape mismatch: {ts_np.shape} vs {s_np.shape}"
                )
            if ts_np.size and not np.all(np.isfinite(ts_np)):
                raise ValueError("event timestamps must be finite")
        elif self._tracker is not None:
            raise ValueError(
                "event-time session (opened with slice_width=/max_lateness=) "
                "requires timestamps= on every ingest"
            )
        source_key = (
            DEFAULT_SOURCE if source is None else int(encode_label(source))
        )
        return s_np, d_np, w_np, ts_np, source_key

    def _ingest_encoded(
        self,
        s_np: np.ndarray,
        d_np: np.ndarray,
        w_np: np.ndarray,
        ts_np: Optional[np.ndarray],
        source_key: int,
    ) -> IngestReceipt:
        """Post-codec ingest: the path WAL replay re-enters (keys are
        already uint32, the source label is already hashed).  Appends to
        the WAL FIRST — before any device dispatch — so an acknowledged
        batch is always recoverable."""
        n_edges = int(s_np.shape[0])
        wal_seq = None
        if self._wal is not None and not self._replaying:
            with TraceAnnotation("glava.ingest.wal"):
                wal_seq = self._wal.append_edges(
                    s_np, d_np, w_np, ts_np, source_key=source_key
                )
        ev_min = ev_max = None
        if ts_np is not None and n_edges:
            ev_min, ev_max = float(ts_np.min()), float(ts_np.max())
        if self._tracker is not None:
            return self._ingest_eventtime(
                s_np, d_np, w_np, ts_np, source_key,
                ev_min=ev_min, ev_max=ev_max, wal_seq=wal_seq,
            )
        additive = not bool(np.any(w_np < 0))
        # Heavy-tail fast path: collapse duplicate (src, dst) pairs on the
        # host (we are already host-side for label encoding), so the device
        # scatters one slot per distinct pair and the flow registers one
        # slot per distinct endpoint.  Exact for signed weights.
        pre = None
        if resolve_preagg(self._preagg, batch=n_edges):
            with TraceAnnotation("glava.ingest.preagg") as span:
                pre = preaggregate_host(s_np, d_np, w_np)
                span.set_metadata(
                    pairs=int(pre.src.size),
                    sources=int(pre.src_unique.size),
                    destinations=int(pre.dst_unique.size),
                )
        # Only pay the host-side unique scan while a touched-key delta can
        # still be consumed; once tracking is poisoned (prior delete /
        # overflow, no closure sync since) the set is discarded anyway and
        # the hot ingest path skips it entirely.  The collapsed batch gives
        # the unique sources for free; fused sessions skip all of this —
        # their delta is the kernel's device-emitted bitmap.
        touched = None
        if self._touched is not None and additive and not self._fused:
            with TraceAnnotation("glava.ingest.touched"):
                if pre is not None:
                    if self.config.directed:
                        touched = pre.src_unique
                    else:
                        touched = np.unique(
                            np.concatenate([pre.src_unique, pre.dst_unique])
                        )
                    if touched.size > self.config.width_rows:
                        touched = None
                else:
                    touched = touched_row_keys(
                        s_np,
                        None if self.config.directed else d_np,
                        cap=self.config.width_rows,
                    )
        with TraceAnnotation("glava.ingest.transfer") as span:
            # Bucket padding of a collapsed batch (zero weights are the
            # identity: counters never hold -0.0) bounds the shapes the
            # update compiles for.  Fused sessions take the pairs alone.
            if pre is None:
                staged = (s_np, d_np, w_np)
            else:
                staged = (pre.src, pre.dst, pre.weights)
                if not self._fused:
                    staged += (
                        pre.src_unique, pre.src_totals,
                        pre.dst_unique, pre.dst_totals,
                    )
                staged = tuple(map(pad_bucket, staged))
            if self._mesh is None:
                staged = tuple(jnp.asarray(a) for a in staged)
            else:
                staged = tuple(jax.device_put(staged, self._replicated))
            span.set_metadata(slots=int(staged[0].shape[0]))
        touched_rows = None
        with TraceAnnotation("glava.ingest.dispatch") as span:
            if self.ingest_backend == "pallas":
                span.set_metadata(kernel_steps=self._kernel_steps(staged[0].shape[0]))
            live = self._window if self._window is not None else self._sketch
            if self._mesh is not None:
                span.set_metadata(shards=self._shards)
                new, token = self._donated(self._jit_update_mesh, live, staged)
            elif pre is not None and not self._fused:
                new, token = self._dispatch_update_pre(live, staged)
            else:
                new, token, touched_rows = self._dispatch_update(live, *staged)
            if self._window is not None:
                self._window = new
            else:
                self._sketch = new
            self._inflight.append(token)
        if len(self._inflight) > self._max_inflight:
            with TraceAnnotation("glava.ingest.backpressure"):
                while len(self._inflight) > self._max_inflight:
                    jax.block_until_ready(self._inflight.popleft())
        self.stats.edges_ingested += n_edges
        self._epoch += 1
        if self._fused:
            self._note_touched(touched_rows if additive else None)
        else:
            self._note_touched(touched)
        receipt = IngestReceipt(
            epoch=self._epoch,
            n_edges=n_edges,
            touched_keys=touched,
            touched_rows=touched_rows if additive else None,
            event_time_min=ev_min,
            event_time_max=ev_max,
            wal_seq=wal_seq,
        )
        self._after_mutation()
        return receipt

    def _dispatch_update_slice(self, s_np, d_np, w_np, slot: int) -> None:
        """One donated event-time dispatch into ring slot ``slot``.  Arrays
        are padded to power-of-two buckets (zero weights are the identity)
        so variable per-slice group sizes cost a bounded trace ladder."""
        s = jnp.asarray(pad_bucket(s_np))
        d = jnp.asarray(pad_bucket(d_np))
        w = jnp.asarray(pad_bucket(w_np))
        leaves = jax.tree_util.tree_leaves(self._window)
        uniq = tuple(leaves[i] for i in self._uniq_leaf_idx)
        new_leaves, token = self._jit_update_slice(
            uniq, s, d, w, jnp.asarray(slot, jnp.int32)
        )
        self._window = jax.tree_util.tree_unflatten(
            self._live_treedef, new_leaves
        )
        self._inflight.append(token)

    def _ingest_eventtime(
        self,
        s_np: np.ndarray,
        d_np: np.ndarray,
        w_np: np.ndarray,
        ts_np: np.ndarray,
        source_key: int,
        *,
        ev_min: Optional[float],
        ev_max: Optional[float],
        wal_seq: Optional[int],
    ) -> IngestReceipt:
        """Event-time ingest: watermark fold -> auto-advance -> slice
        routing -> late-edge policy, all driven by the batch's event-time
        column.  Deterministic given the mutation sequence, which is what
        makes WAL replay bit-identical."""
        K = self._window.n_slices
        width = self._slice_width
        late_dropped = late_retracted = auto_adv = 0
        watermark = None
        additive = not bool(np.any(w_np < 0))
        late_mask = None
        floor_slot = 0
        if n_edges := int(s_np.shape[0]):
            # Lateness is judged against the watermark PROMISED before this
            # batch arrived — the batch's own maximum must not retroactively
            # declare its earlier edges late, or an in-order batch spanning
            # more than max_lateness would retract its own head.
            promised = self._tracker.watermark
            watermark = self._tracker.observe(source_key, ev_max)
            b = slices_of(ts_np, width)
            late_mask = ts_np < promised
            # New ring head: the watermark keeps `lead` slices open past
            # itself; an in-bound burst ahead of a lagging source can push
            # the head further.  Monotone by construction.
            target = slice_of(watermark, width) + self._lead
            if not late_mask.all():
                target = max(target, int(b[~late_mask].max()))
            prev = self._head_slice if self._head_slice is not None else target
            target = max(target, prev)
            auto_adv = target - prev
            self._head_slice = target
            for _ in range(auto_adv):
                self._advance_once()
            self.stats.auto_advances += auto_adv
            # Oldest live slice after the advances; in-bound-by-watermark
            # edges that still land below the ring (a fast source far ahead
            # of a slow one) are operationally late too.  Ring slots are
            # addressed RELATIVE to the head — the ring's current slot need
            # not start congruent to the first head slice.
            slot_off = (self._ring_pos - self._head_slice) % K
            floor_slice = self._head_slice - K + 1
            floor_slot = int((floor_slice + slot_off) % K)
            late_mask = late_mask | (b < floor_slice)
            n_late = int(late_mask.sum())
            if n_late and self._late_policy == "drop":
                keep = ~late_mask
                s_np, d_np, w_np, b = s_np[keep], d_np[keep], w_np[keep], b[keep]
                late_dropped = n_late
                self._tracker.late_dropped += n_late
            elif n_late:
                # Retract path: the whole batch lands (late edges clamped
                # to the oldest live slice), then the late subset is backed
                # out through the turnstile-delete path — same slot,
                # negative weights.
                b = np.where(late_mask, floor_slice, b)
                late_retracted = n_late
                self._tracker.late_retracted += n_late
            touched = None
            if self._touched is not None and additive and late_retracted == 0:
                touched = touched_row_keys(
                    s_np,
                    None if self.config.directed else d_np,
                    cap=self.config.width_rows,
                )
            slots = (b + slot_off) % K
            for slot in np.unique(slots).astype(np.int32):
                m = slots == slot
                self._dispatch_update_slice(s_np[m], d_np[m], w_np[m], int(slot))
            if late_retracted and self._late_policy == "retract":
                m = late_mask
                self._dispatch_update_slice(
                    s_np[m], d_np[m], -w_np[m], floor_slot
                )
                additive = False  # the retraction is a turnstile delete
        else:
            touched = np.zeros(0, np.uint32) if self._touched is not None else None
        while len(self._inflight) > self._max_inflight:
            jax.block_until_ready(self._inflight.popleft())
        self.stats.edges_ingested += n_edges
        self._epoch += 1
        self._note_touched(touched if additive else None)
        receipt = IngestReceipt(
            epoch=self._epoch,
            n_edges=n_edges,
            touched_keys=touched if additive else None,
            event_time_min=ev_min,
            event_time_max=ev_max,
            watermark=watermark,
            late_dropped=late_dropped,
            late_retracted=late_retracted,
            auto_advances=auto_adv,
            wal_seq=wal_seq,
        )
        self._after_mutation()
        return receipt

    def delete(
        self, src, dst, weights=None, *, timestamps=None, source=None
    ) -> IngestReceipt:
        """Turnstile deletion: negative-weight ingest (paper Section 6.1.1).
        Not additions-only, so the receipt's touched set is ``None`` and any
        cached reachability closure rebuilds from scratch on next use.
        Event-time sessions route the retraction into the slice the
        original edge's ``timestamps`` place it in."""
        if weights is None:
            weights = np.ones(len(np.atleast_1d(np.asarray(src))), np.float32)
        return self.ingest(
            src, dst, -np.asarray(weights), timestamps=timestamps, source=source
        )

    def flush(self) -> None:
        """Block until every dispatched ingest batch has landed on device."""
        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())

    # -- queries --------------------------------------------------------------

    def query(self, *queries) -> Union[QueryResult, List[QueryResult]]:
        """Answer queries against the live summary.

        Accepts a single :class:`Query` (returns one :class:`QueryResult`),
        several Query arguments, or one :class:`QueryBatch` (returns a
        request-ordered result list).  The planner fuses the batch into at
        most one engine dispatch per family."""
        single = len(queries) == 1 and isinstance(queries[0], Query)
        if len(queries) == 1 and isinstance(queries[0], QueryBatch):
            batch = queries[0]
        else:
            batch = QueryBatch(queries)
        if len(batch) == 0:
            # Nothing to answer: do not flush, plan, or touch the engine.
            return []
        self.flush()
        if any(q.family == "reach" for q in batch):
            # Sync the closure cache from the session's touched-key delta so
            # one-shot reach pulls ride the same incremental refresh as
            # standing subscriptions instead of re-squaring the closure.
            self._ensure_closure()
        results = execute(self.engine, self._live(), batch, epoch=self._epoch)
        self._count_served(results)
        self._sync_engine_stats()
        return results[0] if single else results

    # -- standing queries (subscriptions) -------------------------------------

    def subscribe(
        self,
        *queries,
        every: int = 1,
        on_result: Optional[Callable[[SubscriptionEvent], None]] = None,
        alarm: Optional[Callable[[List[QueryResult]], bool]] = None,
        name: Optional[str] = None,
        max_pending: int = DEFAULT_MAX_PENDING,
        overflow: str = "drop_oldest",
    ) -> Subscription:
        """Register a standing query batch: a :class:`QueryBatch` (or Query
        arguments, like :meth:`query`) compiled ONCE by the planner and
        re-evaluated automatically after every ``every``-th mutation
        (ingest / delete / advance_window / merge), emitting timestamped
        :class:`SubscriptionEvent`\\ s through ``Subscription.poll()``, the
        session-wide :meth:`events` feed, and the optional ``on_result``
        callback.  ``alarm`` is a predicate over the request-ordered result
        list whose value rides on each event (threshold monitors).

        Re-evaluation is INCREMENTAL: flow/heavy families read the
        maintained registers, edge/subgraph plans replay their fused
        jit-cached dispatches, and reach subscriptions refresh the cached
        transitive closure from the rows touched since the last tick
        (``QueryEngine.refresh_closure``) instead of re-squaring — one full
        closure build per additions-only stream, N incremental refreshes."""
        if len(queries) == 1 and isinstance(queries[0], QueryBatch):
            batch = queries[0]
        else:
            batch = QueryBatch(queries)
        for q in batch:
            if q.family == "heavy":
                validate_theta(q.theta)
        sub = Subscription(
            self,
            self._next_sub_id,
            batch,
            every=every,
            on_result=on_result,
            alarm=alarm,
            name=name,
            max_pending=max_pending,
            overflow=overflow,
        )
        self._next_sub_id += 1
        self._subs[sub.id] = sub
        return sub

    @property
    def subscriptions(self) -> Tuple[Subscription, ...]:
        """The active subscriptions, registration-ordered."""
        return tuple(self._subs.values())

    def events(self) -> Iterator[SubscriptionEvent]:
        """Drain the session-wide event feed (all subscriptions, emission
        order).  Non-blocking: yields the pending events and stops."""
        while self._event_log:
            yield self._event_log.popleft()

    def _unsubscribe(self, sub: Subscription) -> None:
        self._subs.pop(sub.id, None)
        if sub.plan.has_reach:
            # The cancelled plan may be the only closure consumer; session
            # teardown/reuse paths (and the fleet's slot recycling) must not
            # find a stale closure that a later epoch tag could collide with.
            self.engine.invalidate()

    def _note_touched(self, batch_delta) -> None:
        """Accumulate one batch's touched-row delta for the next closure
        sync — a unique key array (plain sessions) or a (d, w_r) bool
        device bitmap (fused sessions); ``None`` (non-additive batch) or
        overflowing the row width forces the next sync to rebuild from
        scratch."""
        if self._touched is None:
            return
        if batch_delta is None:
            self._touched = None
            self._touched_count = 0
            return
        self._touched.append(batch_delta)
        if getattr(batch_delta, "ndim", 1) == 2:
            return  # bitmap: bounded by (d, w_r), no overflow cap needed
        self._touched_count += int(batch_delta.size)
        if self._touched_count > self.config.width_rows:
            self._touched = None
            self._touched_count = 0

    def _ensure_closure(self) -> None:
        """Bring the engine's closure cache up to the current epoch — by
        touched-row refresh when the history since the last sync is
        additions-only, else by full rebuild."""
        delta = None
        if self._touched is not None:
            if not self._touched:
                delta = np.zeros(0, np.uint32)
            elif getattr(self._touched[0], "ndim", 1) == 2:
                # Fused sessions: OR the per-batch device bitmaps (cheap
                # device ops), sync once for the refresh.
                bitmap = self._touched[0]
                for b in self._touched[1:]:
                    bitmap = bitmap | b
                delta = np.asarray(bitmap)
            else:
                delta = np.unique(np.concatenate(self._touched)).astype(
                    np.uint32
                )
        self.engine.refresh_closure(self._live(), delta, self._epoch)
        self._touched = []
        self._touched_count = 0

    def _after_mutation(self) -> None:
        """Re-evaluate every subscription that came due on this mutation."""
        due = [
            s for s in list(self._subs.values()) if s.active and s._note_mutation()
        ]
        if not due:
            return
        with TraceAnnotation("glava.tick", epoch=self._epoch, subscriptions=len(due)):
            with TraceAnnotation("glava.tick.flush"):
                self.flush()
            if any(s.plan.has_reach for s in due):
                with TraceAnnotation("glava.tick.closure") as span:
                    full_before = self.engine.closure_refreshes
                    self._ensure_closure()
                    full = self.engine.closure_refreshes > full_before
                    span.set_metadata(kind="full" if full else "incremental")
            sketch = self._live()
            for sub in due:
                with TraceAnnotation("glava.tick.plan", subscription=sub.id):
                    results = sub.plan.run(self.engine, sketch, epoch=self._epoch)
                with TraceAnnotation("glava.tick.emit"):
                    # Stamped once the plan has returned its host answers.
                    event = SubscriptionEvent(
                        subscription_id=sub.id,
                        name=sub.name,
                        tick=sub.ticks + 1,
                        epoch=self._epoch,
                        timestamp=time.time(),
                        results=tuple(results),
                        alarm=None if sub.alarm is None else bool(sub.alarm(results)),
                    )
                    if sub._deliver(event):
                        # Dedup'd re-emissions (exactly-once replay floor)
                        # still advance the subscription's progress, but
                        # never re-enter the feeds or callbacks.
                        self._event_log.push(event)
                    self.stats.subscription_ticks += 1
                    self._count_served(results)
            self._sync_engine_stats()

    def _count_served(self, results) -> None:
        for r in results:
            v = r.value
            self.stats.queries_served += (
                int(np.size(v[0])) if isinstance(v, tuple) else int(np.size(v))
            )

    def _sync_engine_stats(self) -> None:
        self.stats.closure_refreshes = self.engine.closure_refreshes
        self.stats.closure_incremental_refreshes = (
            self.engine.closure_incremental_refreshes
        )

    def monitor(self, src, dst, weights, watch, theta: float) -> bool:
        """Paper Section 4.2's real-time monitor as a thin wrapper over a
        threshold subscription: a standing ``Query.heavy(watch, θ)`` with an
        ``alarm`` predicate on the in-flow bit, registered once per
        (watch, θ) and evaluated right after this batch is ingested.  θ is
        the fraction of the total stream weight F̃ (``0 < θ <= 1``,
        validated).  Returns the alarm decision; the subscription keeps
        monitoring subsequent ingests (events via :meth:`events`)."""
        theta = validate_theta(theta)
        key = (int(np.uint32(encode_labels(watch))), theta)
        sub = self._monitor_subs.get(key)
        if sub is None or not sub.active:
            sub = self.subscribe(
                Query.heavy(watch, theta),
                every=1,
                alarm=lambda results: bool(np.asarray(results[0].value[0])),
                name=f"monitor:{key[0]}@{theta:g}",
            )
            self._monitor_subs[key] = sub
        self.ingest(src, dst, weights)
        sub.poll()  # the wrapper consumes its events; last_event remains
        return bool(sub.last_event.alarm)

    def pagerank(self, damping: float = 0.85, iters: int = 32) -> np.ndarray:
        """Run PageRank directly on the summary-as-a-graph (Section 3.3
        Remark): returns (d, w) bucket ranks."""
        self.flush()
        return np.asarray(queries_mod.sketch_pagerank(self._live(), damping, iters))

    # -- convenience wrappers (vectorized; used by the serving engine) --------

    def edge_frequency(self, src, dst) -> np.ndarray:
        return np.atleast_1d(self.query(Query.edge(src, dst)).value)

    def in_flow(self, keys) -> np.ndarray:
        return np.atleast_1d(self.query(Query.in_flow(keys)).value)

    def out_flow(self, keys) -> np.ndarray:
        return np.atleast_1d(self.query(Query.out_flow(keys)).value)

    def heavy_hitters(self, keys, theta: float) -> np.ndarray:
        in_heavy, _ = self.query(Query.heavy(keys, theta)).value
        return np.atleast_1d(in_heavy)

    def reachable(self, src, dst) -> np.ndarray:
        return np.atleast_1d(self.query(Query.reach(src, dst)).value)

    def subgraph_weight(self, src, dst) -> float:
        return float(self.query(Query.subgraph(src, dst)).value)

    # -- lifecycle ------------------------------------------------------------

    def advance_window(self) -> None:
        """Move the sliding window to the next time slice (expiring the
        oldest slice); no-op for non-windowed sessions.  Counts as a
        mutation for subscriptions; expiry removes edges, so any cached
        reachability closure rebuilds from scratch on next use.

        On an event-time session this also moves the ring head one slice
        forward (an explicit advance DECLARES a new open slice; the
        watermark keeps driving automatic ones).  Explicit advances are
        WAL-logged; watermark-driven ones are not — replay re-derives them
        from the logged event times."""
        if self._window is None:
            return
        if self._wal is not None and not self._replaying:
            self._wal.append_advance()
        if self._head_slice is not None:
            self._head_slice += 1
        self._advance_once()

    def _advance_once(self) -> None:
        """One ring advance through the donated boundary: expiry + epoch
        bump + subscription tick.  Shared by explicit ``advance_window``
        and the watermark-driven automatic path (which is NOT WAL-logged)."""
        self.flush()
        leaves = jax.tree_util.tree_leaves(self._window)
        uniq = tuple(leaves[i] for i in self._uniq_leaf_idx)
        new_leaves = self._jit_advance(uniq)
        self._window = jax.tree_util.tree_unflatten(
            self._live_treedef, new_leaves
        )
        self._ring_pos = (self._ring_pos + 1) % self._window.n_slices
        self._epoch += 1
        self._note_touched(None)
        self._after_mutation()

    def merge(self, other: "GraphStream") -> "GraphStream":
        """Merge another session's summary into this one (linearity; the
        paper's distributed merge-by-add).  Both must share a hash family —
        open them with the same config + seed."""
        if self._window is not None or other._window is not None:
            raise ValueError("merge() runs on non-windowed sessions")
        self.flush()
        other.flush()
        if not self._sketch.same_family(other._sketch):
            raise ValueError(
                "cannot merge sketches with different hash families "
                "(open both sessions with the same config and seed)"
            )
        if self._wal is not None and not self._replaying:
            # The merged-in state never went through this WAL: log a
            # barrier replay refuses to cross, and checkpoint() right
            # after so recovery never needs to.
            self._wal.append_merge_barrier()
        self._sketch = self._sketch.merge(other._sketch)
        self.stats.edges_ingested += other.stats.edges_ingested
        self._epoch += 1
        self._note_touched(None)  # foreign rows everywhere: full rebuild
        self._after_mutation()
        return self

    def _sub_key(self, sub: Subscription) -> str:
        return sub_progress_key(sub)

    def checkpoint(self, step: Optional[int] = None) -> int:
        """Durably save the session state (requires ``checkpoint_dir``).
        Returns the step the checkpoint was saved under.

        With a WAL attached, the checkpoint also records its durable WAL
        position (``wal_seq``), the watermark-tracker state, and each
        active subscription's tick progress — everything :meth:`recover`
        needs for exactly-once replay — then rotates the WAL segment and
        drops segments every retained checkpoint already covers."""
        if self._ckpt is None:
            raise ValueError("open the session with checkpoint_dir= to checkpoint")
        self.flush()
        step = self._epoch if step is None else step
        state = self._window if self._window is not None else self._sketch
        meta: Dict = {"epoch": self._epoch}
        if self._wal is not None:
            self._wal.sync()
            meta["wal_seq"] = self._wal.last_seq
        if self._tracker is not None:
            meta["watermark"] = self._tracker.state()
            meta["head_slice"] = self._head_slice
        subs = {
            self._sub_key(s): {"ticks": s.ticks, "pending": s._mutations_pending}
            for s in self._subs.values()
            if s.active
        }
        if subs:
            meta["subs"] = subs
        self._ckpt.save(step, state, metadata=meta)
        if self._wal is not None:
            # Rotation keyed to the checkpoint step: the next mutation
            # opens a fresh segment, so no segment straddles the boundary
            # and GC can reason per whole segment.
            self._wal.rotate()
            covered = None
            for s in self._ckpt.all_steps():
                try:
                    seq = int(self._ckpt.read_metadata(s).get("wal_seq", 0))
                except Exception:
                    seq = 0  # unreadable manifest: assume it covers nothing
                covered = seq if covered is None else min(covered, seq)
            if covered:
                self._wal.gc(covered)
        return step

    def restore(self, step: Optional[int] = None) -> int:
        """Restore session state from the checkpoint directory (latest step
        by default).  Handles pre-register checkpoints via the fill-missing
        schema-evolution path.  Returns the restored step."""
        if self._ckpt is None:
            raise ValueError("open the session with checkpoint_dir= to restore")
        self.flush()
        like = self._window if self._window is not None else self._sketch
        state, meta = self._ckpt.restore(step, like=like, fill_missing=True)
        if meta.get("filled_leaves"):
            # Registers absent from an old checkpoint: rebuild from counters.
            if isinstance(state, GLavaSketch):
                state = state.with_counters(state.counters)
            else:
                state = dataclasses.replace(
                    state,
                    row_flows=jnp.sum(state.slices, axis=3),
                    col_flows=jnp.sum(state.slices, axis=2),
                )
        if self._window is not None:
            self._window = state
            # Re-sync the host ring-position mirror with the restored ring
            # (the head-relative slot mapping depends on it).
            self._ring_pos = int(np.asarray(state.current))
        else:
            self._sketch = state
        self._epoch = int(meta.get("epoch", meta["step"]))
        if self._tracker is not None:
            wm_state = meta.get("watermark")
            if wm_state is not None:
                self._tracker = WatermarkTracker.from_state(wm_state)
                head = meta.get("head_slice")
                self._head_slice = None if head is None else int(head)
            else:
                # Pre-event-time checkpoint: start the tracker fresh.
                self._tracker = WatermarkTracker(self._tracker.max_lateness)
                self._head_slice = None
        subs_meta = meta.get("subs") or {}
        for sub in self._subs.values():
            m = subs_meta.get(self._sub_key(sub))
            if m is not None:
                sub.ticks = int(m["ticks"])
                sub._mutations_pending = int(m["pending"])
        self.engine.invalidate()  # any cached closure predates the restore
        self._touched = []
        self._touched_count = 0
        self._last_restore_meta = meta
        return int(meta["step"])

    def recover(self, step: Optional[int] = None) -> RecoveryReport:
        """Crash recovery (requires ``wal_dir``): restore the newest usable
        checkpoint — falling back past a corrupt one, or starting from the
        empty summary when none exists — then replay the WAL suffix through
        the normal mutation path (no re-append).  Standing subscriptions
        registered BEFORE calling this re-evaluate during replay exactly as
        the pre-crash session did: ticks resume from the checkpointed
        progress, and events a consumer already processed are deduplicated
        by (subscription, tick) via :meth:`Subscription.seek` — together,
        exactly-once delivery.  The post-recovery event sequence is
        bit-identical to the uninterrupted run (property-tested)."""
        if self._wal is None:
            raise ValueError("open the session with wal_dir= to recover")
        restored_step = None
        after_seq = 0
        if self._ckpt is not None:
            try:
                restored_step = self.restore(step)
                after_seq = int(self._last_restore_meta.get("wal_seq", 0))
            except FileNotFoundError:
                restored_step = None  # genesis replay over the empty summary
        self._replaying = True
        replayed = 0
        try:
            for mut in self._wal.replay(after_seq=after_seq):
                if isinstance(mut, EdgeMutation):
                    with TraceAnnotation(
                        "glava.ingest", epoch=self._epoch + 1, edges=int(mut.src.size)
                    ):
                        self._ingest_encoded(
                            mut.src, mut.dst, mut.weights, mut.timestamps,
                            mut.source_key,
                        )
                elif isinstance(mut, AdvanceMutation):
                    self.advance_window()
                else:  # MergeMutation — state entered outside this log
                    raise RuntimeError(
                        f"WAL suffix crosses a merge barrier (seq {mut.seq}): "
                        f"the merged-in summary never went through this log. "
                        f"checkpoint() immediately after merge() so recovery "
                        f"never needs to replay past it"
                    )
                replayed += 1
        finally:
            self._replaying = False
        self.flush()
        return RecoveryReport(
            step=restored_step,
            mutations_replayed=replayed,
            epoch=self._epoch,
            wal_seq=self._wal.last_seq,
        )

    def summary(self) -> Dict[str, float]:
        """Flushed session counters."""
        self.flush()
        out = self.stats.summary()
        out["events_dropped"] = self.events_dropped
        if self._tracker is not None:
            out["watermark"] = self._tracker.watermark
            out["late_dropped"] = self._tracker.late_dropped
            out["late_retracted"] = self._tracker.late_retracted
        return out
