"""FleetQueryEngine — every query family batched across the tenant axis.

Each family function is the fleet twin of its :mod:`repro.core.queries`
estimator: queries carry a per-query ``slots`` lane alongside the key
lanes, the gather picks up the tenant as one more advanced index, and the
window axis (K slices) is summed ON THE GATHERED CELLS — O(K·d·Q) work,
never a T-wide reduction — so answers are bit-identical to running the
plain estimator on that tenant's window-summed ``GLavaSketch`` (fp32
integer addition is order-independent in the exact regime).  One jit per
family serves every tenant mix: the slot lane is data, not structure, so
permuting tenant ids across calls cannot retrace (the fleet no-retrace
contract).

Reachability keeps the per-tenant epoch-tagged closure cache, but builds
and refreshes are BATCHED: stale tenants' window-summed counter stacks go
through one ``transitive_closure`` call (already batched over leading
dims) or one vmapped ``closure_refresh``, padded to a power-of-two stack
depth so the jit cache holds a short ladder of shapes.  The cache is
keyed by SLOT, and per-tenant epochs restart at 0 for every slot
occupant — so every residency change (eviction, admission, session
close, reach-subscription cancel) must ``drop_closure(slot)`` or a
readmitted tenant could be served the previous occupant's closure at a
colliding epoch (the stale-closure fix this PR ships with a regression
test)."""
from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reach
from repro.core.hashing import affine_hash_np
from repro.core.queries import undirected_selfloop_correction
from repro.core.query_engine import (
    CLOSURE_REFRESH_FRAC,
    CLOSURE_REFRESH_PAD_T,
    CLOSURE_STALENESS_BUDGET,
    DEFAULT_CHUNK_Q,
    DEFAULT_PAD_Q,
    padded_len,
)
from repro.fleet.stack import FleetSketch


# ---------------------------------------------------------------------------
# Fleet family functions (slot-indexed twins of repro.core.queries)
# ---------------------------------------------------------------------------


def _window_cells(state: FleetSketch, slots, r, c):
    """(K, d, Q) counter cells at per-query (slot, row, col)."""
    k, d = state.counters.shape[1], state.counters.shape[2]
    k_idx = jnp.arange(k)[:, None, None]
    d_idx = jnp.arange(d)[None, :, None]
    return state.counters[slots[None, None, :], k_idx, d_idx, r[None], c[None]]


def fleet_edge_query(state: FleetSketch, slots, src, dst):
    """f̃_e(a, b) per (tenant, edge) query — min over d of window-summed cells."""
    r, c = state.row_hash(src), state.col_hash(dst)
    est = jnp.min(jnp.sum(_window_cells(state, slots, r, c), axis=0), axis=0)
    if not state.config.directed:
        est = undirected_selfloop_correction(est, src, dst)
    return est


def _register_gather(register, slots, h):
    """(T, K, d, w) register → (Q,) min-d of window-summed per-query gathers."""
    k, d = register.shape[1], register.shape[2]
    k_idx = jnp.arange(k)[:, None, None]
    d_idx = jnp.arange(d)[None, :, None]
    vals = register[slots[None, None, :], k_idx, d_idx, h[None]]  # (K, d, Q)
    return jnp.min(jnp.sum(vals, axis=0), axis=0)


def fleet_in_flow(state: FleetSketch, slots, keys):
    return _register_gather(state.col_flows, slots, state.col_hash(keys))


def fleet_out_flow(state: FleetSketch, slots, keys):
    return _register_gather(state.row_flows, slots, state.row_hash(keys))


def fleet_flow(state: FleetSketch, slots, keys):
    if state.config.directed:
        return fleet_in_flow(state, slots, keys) + fleet_out_flow(
            state, slots, keys
        )
    return fleet_out_flow(state, slots, keys)


def fleet_stream_totals(state: FleetSketch, slots):
    """Per-query F̃ (Q,) — min over d of the queried tenant's row-flow mass.
    Register-served, and the slot gather comes FIRST: the reduction runs on
    the (Q, K, d, w_r) gathered rows, so the cost scales with the query
    chunk, never a T-wide scan of the fleet stack."""
    return jnp.min(jnp.sum(state.row_flows[slots], axis=(1, 3)), axis=1)


def fleet_heavy_rel_vec(state: FleetSketch, slots, keys, thetas):
    """Relative-θ heavy check against the QUERY'S OWN tenant total F̃."""
    cut = thetas.astype(jnp.float32) * fleet_stream_totals(state, slots).astype(
        jnp.float32
    )
    return (
        fleet_in_flow(state, slots, keys) > cut,
        fleet_out_flow(state, slots, keys) > cut,
    )


def fleet_subgraph_batch(state: FleetSketch, slots, src, dst, mask):
    """n masked subgraph queries, each against its own tenant's window."""
    r = state.row_hash(src)  # (d, n, k)
    c = state.col_hash(dst)
    kk = state.counters.shape[1]
    k_idx = jnp.arange(kk)[:, None, None, None]
    d_idx = jnp.arange(r.shape[0])[None, :, None, None]
    cells = jnp.sum(
        state.counters[slots[None, None, :, None], k_idx, d_idx, r[None], c[None]],
        axis=0,
    )  # (d, n, k)
    live = mask[None, :, :]
    present = jnp.all(jnp.where(live, cells > 0, True), axis=2)
    wsum = jnp.sum(jnp.where(live, cells, 0.0), axis=2)
    return jnp.min(jnp.where(present, wsum, 0.0), axis=0)


def fleet_reach_pre(state: FleetSketch, closures, pos, src, dst):
    """Batched r̃(a, b) against a stacked (S, d, w, w) closure plane;
    ``pos`` maps each query to its tenant's stack position."""
    r = state.row_hash(src)
    c = state.row_hash(dst)
    d_idx = jnp.arange(r.shape[0])[:, None]
    return jnp.all(closures[pos[None, :], d_idx, r, c], axis=0)


def fleet_closure_build(counters, sel):
    """Batched full closure of the selected tenants' window-summed
    adjacencies — ``transitive_closure`` is already batched over leading
    dims, so S stale tenants cost one device call, no vmap needed."""
    return reach.transitive_closure(jnp.sum(counters[sel], axis=1))


def fleet_closure_refresh(closures, counters, sel, rows):
    """Batched incremental refresh: vmapped ``closure_refresh`` over the
    (S, d, w, w) closure stack / selected window-summed counters / per-
    tenant touched-row plans."""
    return jax.vmap(reach.closure_refresh)(
        closures, jnp.sum(counters[sel], axis=1), rows
    )


_FLEET_FAMILIES: Dict[str, Callable] = {
    "edge": fleet_edge_query,
    "in_flow": fleet_in_flow,
    "out_flow": fleet_out_flow,
    "flow": fleet_flow,
    "heavy_rel_vec": fleet_heavy_rel_vec,
    "subgraph_batch": fleet_subgraph_batch,
    "reach_pre": fleet_reach_pre,
    "closure": fleet_closure_build,
    "closure_refresh": fleet_closure_refresh,
}


def _pad_pow2(seq: List) -> List:
    """Pad a non-empty list to the next power of two by repeating its first
    element — closure stacks see a short ladder of jit shapes, and the
    repeated entry's rebuild/refresh is idempotent."""
    n = len(seq)
    m = 1 << max(0, n - 1).bit_length() if n > 1 else 1
    return list(seq) + [seq[0]] * (m - n)


class FleetQueryEngine:
    """Per-family jit caching + query padding + the slot-keyed, epoch-tagged
    batched closure cache — the QueryEngine surface, fleet-wide."""

    def __init__(
        self,
        pad_q: int = DEFAULT_PAD_Q,
        chunk_q: int = DEFAULT_CHUNK_Q,
        closure_staleness_budget: int = CLOSURE_STALENESS_BUDGET,
        closure_refresh_frac: float = CLOSURE_REFRESH_FRAC,
    ):
        self.pad_q = pad_q
        self.chunk_q = max(chunk_q, pad_q)
        self.closure_staleness_budget = closure_staleness_budget
        self.closure_refresh_frac = closure_refresh_frac
        self._jits: Dict[str, Callable] = {}
        # slot -> (closure (d, w, w) bool, epoch); per-slot staleness count.
        self._closures: Dict[int, Tuple[jax.Array, int]] = {}
        self._since_full: Dict[int, int] = {}
        self.closure_builds = 0
        self.closure_incremental_refreshes = 0
        self.dispatches: collections.Counter = collections.Counter()

    # -- jit cache -----------------------------------------------------------

    def _fn(self, family: str) -> Callable:
        fn = self._jits.get(family)
        if fn is None:
            fn = jax.jit(_FLEET_FAMILIES[family])
            self._jits[family] = fn
        return fn

    def _cache_size(self) -> int:
        """Total traced signatures across all family jits — the fleet
        no-retrace contract asserts this stays flat under tenant-id
        permutations."""
        total = 0
        for fn in self._jits.values():
            sz = getattr(fn, "_cache_size", None)
            if callable(sz):
                total += sz()
        return total

    @staticmethod
    def family_probe(
        family: str,
        *,
        tenants: int = 4,
        width: int = 64,
        depth: int = 2,
        n_queries: int = 32,
        touched: int = 2,
    ):
        """Costlint sizing hook: the fleet family estimator + args at a
        parameterized (T, w, d, Q, S) — compiled across a geometric ladder
        to prove register families are O(d·Q) with exponent ≈ 0 in T and
        closure maintenance is O(S·w²), never a T-wide scan.  ``touched``
        is S, the stale-tenant stack depth for the closure families.
        Returns ``(fn, args, counters_shape)``."""
        from repro.core.sketch import SketchConfig

        cfg = SketchConfig(depth=depth, width_rows=width, width_cols=width)
        state = FleetSketch.empty(cfg, tenants, jax.random.key(0))
        slots = jnp.arange(n_queries, dtype=jnp.int32) % tenants
        keys = jnp.arange(n_queries, dtype=jnp.uint32)
        shape = tuple(state.counters.shape)
        if family == "edge":
            args = (state, slots, keys, keys + jnp.uint32(1))
        elif family in ("in_flow", "out_flow", "flow"):
            args = (state, slots, keys)
        elif family == "heavy_rel_vec":
            thetas = jnp.full((n_queries,), 0.5, jnp.float32)
            args = (state, slots, keys, thetas)
        elif family == "closure":
            sel = jnp.arange(touched, dtype=jnp.int32) % tenants
            return fleet_closure_build, (state.counters, sel), shape
        elif family == "closure_refresh":
            sel = jnp.arange(touched, dtype=jnp.int32) % tenants
            closures = fleet_closure_build(state.counters, sel)
            rows = jnp.tile(
                state.row_hash(keys[: min(8, n_queries)])[None],
                (touched, 1, 1),
            )
            return (
                fleet_closure_refresh,
                (closures, state.counters, sel, rows),
                shape,
            )
        else:
            raise ValueError(f"no cost probe for fleet family {family!r}")
        return _FLEET_FAMILIES[family], args, shape

    # -- padding/chunking (same discipline as QueryEngine._run_padded) -------

    def padded_len(self, q: int) -> int:
        """Key slots :meth:`_run_padded` dispatches for ``q`` keys."""
        return padded_len(q, self.pad_q, self.chunk_q)

    def _run_padded(self, family: str, head, keys, tail=()):
        self.dispatches[family] += 1
        fn = self._fn(family)
        q = keys[0].shape[0]
        outs = []
        for lo in range(0, max(q, 1), self.chunk_q):
            hi = min(q, lo + self.chunk_q)
            part = [k[lo:hi] for k in keys]
            n = hi - lo
            pad = (-n) % self.pad_q
            if pad:
                # Slot/pos lanes pad with 0 — they gather slot 0, and the
                # padded answers are sliced away below.
                part = [jnp.pad(k, (0, pad)) for k in part]
            out = fn(*head, *part, *tail)
            outs.append(
                jax.tree_util.tree_map(lambda o: o[:n], out) if pad else out
            )
        if len(outs) == 1:
            return outs[0]
        return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *outs)

    # -- query families ------------------------------------------------------

    def edge(self, state: FleetSketch, slots, src, dst):
        return self._run_padded("edge", (state,), (slots, src, dst))

    def in_flow(self, state: FleetSketch, slots, keys):
        return self._run_padded("in_flow", (state,), (slots, keys))

    def out_flow(self, state: FleetSketch, slots, keys):
        return self._run_padded("out_flow", (state,), (slots, keys))

    def flow(self, state: FleetSketch, slots, keys):
        return self._run_padded("flow", (state,), (slots, keys))

    def heavy_rel_vec(self, state: FleetSketch, slots, keys, thetas):
        return self._run_padded(
            "heavy_rel_vec",
            (state,),
            (slots, keys, jnp.asarray(thetas, jnp.float32)),
        )

    def subgraph_batch(self, state: FleetSketch, slots, src, dst, mask):
        # Subgraph batches jit at their exact (n, k) shape — zero-padding
        # the edge axis would change absent-edge semantics (same rule as
        # QueryEngine.subgraph_batch).
        self.dispatches["subgraph_batch"] += 1
        return self._fn("subgraph_batch")(state, slots, src, dst, mask)

    # -- batched closure plane ----------------------------------------------

    def drop_closure(self, slot: int) -> None:
        """Forget one slot's closure — REQUIRED on every slot occupancy
        change (evict / admit / close / reach-subscription cancel): epochs
        restart per occupant, so a stale entry could otherwise satisfy the
        next occupant's epoch tag."""
        self._closures.pop(slot, None)
        self._since_full.pop(slot, None)

    def invalidate(self) -> None:
        self._closures.clear()
        self._since_full.clear()

    def refresh_closures(self, state: FleetSketch, items) -> None:
        """Bring many tenants' closures up to their epochs in at most one
        full-build dispatch plus one incremental-refresh dispatch.

        ``items`` is ``[(slot, delta, epoch)]`` with ``delta`` the unique
        touched-key array accumulated since the slot's cached epoch, or
        ``None`` for "unknown / not additions-only" (deletes, window
        advance, fault-in) which forces a full rebuild — the same
        escalation ladder as ``QueryEngine.refresh_closure`` (frac /
        staleness-budget fallbacks, empty-delta retag)."""
        w_r = state.config.width_rows
        build: List[Tuple[int, int]] = []
        refresh: List[Tuple[int, np.ndarray, int]] = []
        for slot, delta, epoch in items:
            cached = self._closures.get(slot)
            if cached is not None and cached[1] == epoch:
                continue
            if (
                cached is None
                or delta is None
                or self._since_full.get(slot, 0) >= self.closure_staleness_budget
            ):
                build.append((slot, epoch))
                continue
            delta = np.atleast_1d(np.asarray(delta))
            if delta.size > self.closure_refresh_frac * w_r:
                build.append((slot, epoch))
                continue
            if delta.size == 0:
                # Nothing touched: counters unchanged, only retag.
                self._closures[slot] = (cached[0], epoch)
                continue
            refresh.append((slot, delta, epoch))
        if build:
            self._build(state, build)
        if refresh:
            self._refresh(state, refresh)

    def _build(self, state: FleetSketch, items) -> None:
        sel = jnp.asarray(
            np.asarray(_pad_pow2([s for s, _ in items]), np.int32)
        )
        closures = self._fn("closure")(state.counters, sel)
        self.dispatches["closure"] += 1
        for i, (slot, epoch) in enumerate(items):
            self._closures[slot] = (closures[i], epoch)
            self._since_full[slot] = 0
            self.closure_builds += 1

    def _refresh(self, state: FleetSketch, items) -> None:
        a = np.asarray(state.row_hash.a).reshape(-1)
        b = np.asarray(state.row_hash.b).reshape(-1)
        w_r = state.config.width_rows
        t_max = max(delta.size for _, delta, _ in items)
        t_pad = t_max + (-t_max) % CLOSURE_REFRESH_PAD_T
        # Row plans on the host via the exact hash twin; padding with row 0
        # is idempotent (an untouched row restates known paths).
        rows_np = np.zeros((len(items), a.shape[0], t_pad), np.int32)
        for i, (_, delta, _) in enumerate(items):
            rows_np[i, :, : delta.size] = affine_hash_np(
                delta.astype(np.uint32, copy=False)[None, :],
                a[:, None],
                b[:, None],
                w_r,
            )
        idx = _pad_pow2(list(range(len(items))))
        slots = [items[j][0] for j in idx]
        sel = jnp.asarray(np.asarray(slots, np.int32))
        closures = jnp.stack([self._closures[s][0] for s in slots])
        rows = jnp.asarray(rows_np[np.asarray(idx)])
        out = self._fn("closure_refresh")(closures, state.counters, sel, rows)
        self.dispatches["closure_refresh"] += 1
        for i, (slot, _, epoch) in enumerate(items):
            self._closures[slot] = (out[i], epoch)
            self._since_full[slot] = self._since_full.get(slot, 0) + 1
            self.closure_incremental_refreshes += 1

    def reach(
        self,
        state: FleetSketch,
        slots,
        src,
        dst,
        epochs: Dict[int, int],
        touched: Optional[Dict[int, Optional[np.ndarray]]] = None,
    ):
        """Batched r̃(a, b) with a per-query tenant lane: ensure every
        distinct tenant's closure is at its epoch (one batched build and/or
        refresh), stack the fresh closures, and answer all queries in one
        gather dispatch."""
        slots_np = np.asarray(slots)
        uniq = np.unique(slots_np)
        self.refresh_closures(
            state,
            [
                (int(s), (touched or {}).get(int(s)), epochs[int(s)])
                for s in uniq
            ],
        )
        stack_slots = _pad_pow2([int(s) for s in uniq])
        closures = jnp.stack([self._closures[s][0] for s in stack_slots])
        pos = jnp.asarray(np.searchsorted(uniq, slots_np).astype(np.int32))
        return self._run_padded(
            "reach_pre",
            (state, closures),
            (pos, jnp.asarray(src), jnp.asarray(dst)),
        )
