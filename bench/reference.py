"""The plain reference, and the comparison that decides ``correct``.

It replays every edge a run ingested, in the order the run ingested them,
as exact float64 sums, and answers the standing queries the way gLava's
estimators are defined (paper §3-4): an edge is the least of its d counter
cells, a flow the least of its d register buckets, a node is heavy when
its flow exceeds theta times the stream's total weight, and ``u`` reaches
``v`` when every one of the d bucket graphs (cell > 0 is an arc, every
bucket reaches itself) has a path ``h(u) -> h(v)``.  It imports nothing
of the program: it derives the deployment's hash family from the session
seed by the stated recipe and hashes exactly, in 64-bit integers.

The control is the same reference with its counters and registers held in
bfloat16, the precision below the configuration's float32: each batch's
per-cell sum is added to a bfloat16 counter and rounded.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import ml_dtypes
import numpy as np

from bench.traffic import Pool, QuerySet

MERSENNE_P = (1 << 31) - 1
CHUNK_EDGES = 1 << 21


def family_params(key, depth: int):
    """(a, b) of one affine family ((a*x + b) mod p) mod w, drawn as stated:
    a ~ U[1, p-1], b ~ U[0, p-1] from two halves of ``key``."""
    import jax
    import jax.numpy as jnp

    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (depth,), 1, MERSENNE_P, dtype=jnp.uint32)
    b = jax.random.randint(kb, (depth,), 0, MERSENNE_P, dtype=jnp.uint32)
    return np.asarray(a, np.uint64), np.asarray(b, np.uint64)


def hash_ids(a: np.ndarray, b: np.ndarray, width: int, ids: np.ndarray) -> np.ndarray:
    """(d, n) bucket of every id under each of the d hashes."""
    k = np.asarray(ids).astype(np.uint64) % np.uint64(MERSENNE_P)
    h = (a[:, None] * k[None, :] + b[:, None]) % np.uint64(MERSENNE_P)
    return (h % np.uint64(width)).astype(np.int32)


class Hashes:
    """The deployment's row and column hashes.  A session opened with seed
    ``s`` splits ``key(s)`` into a row and a column key; a square sketch
    uses the row family for both."""

    def __init__(self, config: Dict, session_seed: int):
        import jax

        sk = config["sketch"]
        self.depth = int(sk["depth"])
        self.wr, self.wc = int(sk["width_rows"]), int(sk["width_cols"])
        if not sk.get("directed", True):
            raise ValueError("the reference answers directed sketches only")
        kr, kc = jax.random.split(jax.random.key(session_seed))
        self._row = family_params(kr, self.depth)
        self._col = self._row if self.wr == self.wc else family_params(kc, self.depth)

    def row(self, ids: np.ndarray) -> np.ndarray:
        return hash_ids(*self._row, self.wr, ids)

    def col(self, ids: np.ndarray) -> np.ndarray:
        return hash_ids(*self._col, self.wc, ids)


def bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


@dataclasses.dataclass
class Tracked:
    """Per-batch sums of one set of tracked cells or buckets, one array per depth."""

    keys: List[np.ndarray]   # sorted tracked ids per depth
    sums: List[np.ndarray]   # (n_batches, len(keys[i])) exact per-batch sums
    sums16: List[np.ndarray]  # the same over bfloat16-rounded weights


def _track(ids_per_depth: Sequence[np.ndarray]) -> List[np.ndarray]:
    return [np.unique(ids) for ids in ids_per_depth]


class Replay:
    """Exact per-batch sums, for one query set, over the edges a run ingested."""

    def __init__(self, hashes: Hashes, pool: Pool, batches: List[tuple], qs: QuerySet):
        self.h = h = hashes
        self.qs = qs
        d = h.depth
        er, ec = h.row(qs.edge_src), h.col(qs.edge_dst)
        edge_ids = [er[i].astype(np.int64) * h.wc + ec[i] for i in range(d)]
        in_col, heavy_col, heavy_row = h.col(qs.in_flow), h.col(qs.heavy), h.row(qs.heavy)
        col_ids = [np.concatenate([in_col[i], heavy_col[i]]) for i in range(d)]
        row_ids = [heavy_row[i] for i in range(d)]
        self.cells = _track(edge_ids)
        self.cols = _track(col_ids)
        self.rows = _track(row_ids)
        nb = len(batches)
        cell_s = [np.zeros((nb, k.size)) for k in self.cells]
        cell_s16 = [np.zeros((nb, k.size)) for k in self.cells]
        col_s = [np.zeros((nb, k.size)) for k in self.cols]
        col_s16 = [np.zeros((nb, k.size)) for k in self.cols]
        row_s = [np.zeros((nb, k.size)) for k in self.rows]
        row_s16 = [np.zeros((nb, k.size)) for k in self.rows]
        total = np.zeros(nb)
        total16 = np.zeros(nb)
        col_lut = [self._lut(k, h.wc) for k in self.cols]
        row_lut = [self._lut(k, h.wr) for k in self.rows]
        for b0, b1, seg, idx in _chunks(pool, batches):
            rows, cols = h.row(pool.src[idx]), h.col(pool.dst[idx])
            w = pool.weight[idx].astype(np.float64)
            w16 = bf16(w)
            n = b1 - b0
            total[b0:b1] = np.bincount(seg, w, minlength=n)
            total16[b0:b1] = np.bincount(seg, w16, minlength=n)
            for i in range(d):
                r, c = rows[i], cols[i]
                cell = r.astype(np.int64) * h.wc + c
                keys = self.cells[i]
                pos = np.clip(np.searchsorted(keys, cell), 0, keys.size - 1)
                hit = keys[pos] == cell
                flat = seg[hit] * keys.size + pos[hit]
                size = n * keys.size
                cell_s[i][b0:b1] = np.bincount(flat, w[hit], size).reshape(n, -1)
                cell_s16[i][b0:b1] = np.bincount(flat, w16[hit], size).reshape(n, -1)
                for lut, tracked, out, out16, bucket in (
                    (col_lut[i], self.cols[i], col_s[i], col_s16[i], c),
                    (row_lut[i], self.rows[i], row_s[i], row_s16[i], r),
                ):
                    slot = lut[bucket]
                    hit = slot >= 0
                    flat = seg[hit] * tracked.size + slot[hit]
                    size = n * tracked.size
                    out[b0:b1] = np.bincount(flat, w[hit], size).reshape(n, -1)
                    out16[b0:b1] = np.bincount(flat, w16[hit], size).reshape(n, -1)
        self.cell = Tracked(self.cells, cell_s, cell_s16)
        self.col = Tracked(self.cols, col_s, col_s16)
        self.row = Tracked(self.rows, row_s, row_s16)
        self.total, self.total16 = total, total16
        self.edge_pos = [np.searchsorted(self.cells[i], edge_ids[i]) for i in range(d)]
        self.in_pos = [np.searchsorted(self.cols[i], in_col[i]) for i in range(d)]
        self.heavy_in_pos = [np.searchsorted(self.cols[i], heavy_col[i]) for i in range(d)]
        self.heavy_out_pos = [np.searchsorted(self.rows[i], row_ids[i]) for i in range(d)]

    @staticmethod
    def _lut(keys: np.ndarray, width: int) -> np.ndarray:
        lut = np.full(width, -1, np.int64)
        lut[keys] = np.arange(keys.size)
        return lut

    def answers(self, at: np.ndarray, control: bool = False) -> Dict[str, np.ndarray]:
        """Answers after each batch index in ``at`` (ascending), exact or,
        with ``control``, from bfloat16 counters."""
        acc = _bf16_running if control else _running

        def vals(tr: Tracked, pos: List[np.ndarray]) -> np.ndarray:
            per_depth = [
                acc(tr.sums16[i] if control else tr.sums[i], at)[:, pos[i]]
                for i in range(len(pos))
            ]
            return np.min(np.stack(per_depth), axis=0)

        total = acc((self.total16 if control else self.total)[:, None], at)[:, 0]
        edge = vals(self.cell, self.edge_pos)
        in_flow = vals(self.col, self.in_pos)
        heavy_in = vals(self.col, self.heavy_in_pos)
        heavy_out = vals(self.row, self.heavy_out_pos)
        cut = self.qs.theta * total[:, None]
        return dict(
            edge=edge, in_flow=in_flow, heavy_in_flow=heavy_in,
            heavy_out_flow=heavy_out, cut=cut,
            heavy_in=heavy_in > cut, heavy_out=heavy_out > cut,
        )


def _running(sums: np.ndarray, at: np.ndarray) -> np.ndarray:
    return np.cumsum(sums, axis=0)[at]


def _bf16_running(sums: np.ndarray, at: np.ndarray) -> np.ndarray:
    out = np.zeros((at.size, sums.shape[1]))
    acc = np.zeros(sums.shape[1])
    j = 0
    for k in range(int(at.max()) + 1 if at.size else 0):
        acc = bf16(acc + sums[k])
        while j < at.size and at[j] == k:
            out[j] = acc
            j += 1
    return out


def _chunks(pool: Pool, batches: List[tuple]):
    """(first batch, end batch, segment per edge, pool row per edge) over
    runs of consecutive batches of about CHUNK_EDGES edges."""
    b0 = 0
    while b0 < len(batches):
        b1, n = b0, 0
        while b1 < len(batches) and (n == 0 or n + batches[b1][1] <= CHUNK_EDGES):
            n += batches[b1][1]
            b1 += 1
        idx = np.concatenate([pool.index(s, m) for s, m in batches[b0:b1]])
        seg = np.repeat(np.arange(b1 - b0), [m for _, m in batches[b0:b1]])
        yield b0, b1, seg, idx
        b0 = b1


def reach_answers(
    hashes: Hashes, pool: Pool, batches: List[tuple], qs: QuerySet, at: np.ndarray
) -> np.ndarray:
    """(len(at), Q) bool: ``reach_src[j]`` reaches ``reach_dst[j]`` after
    each batch index in ``at`` (ascending), by breadth-first search over
    each depth's bucket graph, all Q sources at once as bit sets."""
    h = hashes
    idx = np.concatenate([pool.index(s, m) for s, m in batches])
    ends = np.cumsum([m for _, m in batches])
    q = qs.reach_src.size
    words = (q + 63) // 64
    bit = np.left_shift(np.uint64(1), (np.arange(q) % 64).astype(np.uint64))
    out = np.ones((at.size, q), bool)
    rows, cols = h.row(pool.src[idx]), h.col(pool.dst[idx])
    src_b, dst_b = h.row(qs.reach_src), h.row(qs.reach_dst)
    for i in range(h.depth):
        u_all = rows[i].astype(np.int64)
        v_all = cols[i].astype(np.int64)
        arcs, first = np.unique(u_all * h.wc + v_all, return_index=True)
        reach = np.zeros((h.wr, words), np.uint64)
        srcb, dstb = src_b[i], dst_b[i]
        for j in range(q):
            reach[srcb[j], j // 64] |= bit[j]
        for e, k in enumerate(at):
            live = arcs[first < ends[k]]
            u, v = live // h.wc, live % h.wc
            order = np.argsort(v, kind="stable")
            u, v = u[order], v[order]
            starts = np.flatnonzero(np.r_[True, v[1:] != v[:-1]])
            heads = v[starts]
            while True:
                pushed = np.bitwise_or.reduceat(reach[u], starts, axis=0)
                new = reach.copy()
                new[heads] |= pushed
                if np.array_equal(new, reach):
                    break
                reach = new
            got = (reach[dstb, np.arange(q) // 64] & bit) != 0
            out[e] &= got
    return out


@dataclasses.dataclass
class Check:
    """One compared number and its limit (a number passes at or below it)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got.astype(np.float64) - want) / np.maximum(np.abs(want), 1.0)))


def heavy_wrong(got: np.ndarray, want: np.ndarray, flow: np.ndarray, cut: np.ndarray, band: float) -> int:
    """Heavy bits that differ from the reference where the reference's flow
    lies further than ``band`` (relative) from the cut: inside the band a
    float32 sum may round either way."""
    outside = np.abs(flow - cut) > band * np.maximum(cut, 1.0)
    return int(np.sum((got != want) & outside))


def compare(
    hashes: Hashes,
    pool: Pool,
    batches: List[tuple],
    qs: QuerySet,
    events: List[tuple],
    limits: Dict[str, float],
    missing: int,
    reach_sample: np.ndarray,
    control: bool = False,
) -> List[Check]:
    """The compared numbers of one run.  ``events`` lists ``(batch index,
    answers)`` of every event the window delivered; ``reach_sample`` picks
    which of them the reach reference re-derives.  With ``control`` the
    answers compared are the bfloat16 reference's."""
    edge = flow = 0.0
    heavy = reach_bad = 0
    band = 2.0 * limits["flow_rel_err"]
    if events:
        at = np.array([k for k, _ in events])
        rep = Replay(hashes, pool, batches, qs)
        ref = rep.answers(at)
        got = rep.answers(at, control=True) if control else {
            f: np.stack([a[f] for _, a in events]) for f in ("edge", "in_flow", "heavy_in", "heavy_out")
        }
        edge = rel_err(got["edge"], ref["edge"])
        flow = rel_err(got["in_flow"], ref["in_flow"])
        heavy += heavy_wrong(got["heavy_in"], ref["heavy_in"], ref["heavy_in_flow"], ref["cut"], band)
        heavy += heavy_wrong(got["heavy_out"], ref["heavy_out"], ref["heavy_out_flow"], ref["cut"], band)
        if qs.reach_src.size and not control:
            pick = np.unique(reach_sample[reach_sample < len(events)])
            want = reach_answers(hashes, pool, batches, qs, at[pick])
            have = np.stack([events[j][1]["reach"] for j in pick])
            reach_bad += int(np.sum(have != want))
    checks = [
        Check("events_missing", float(missing), 0.0),
        Check("edge_rel_err", edge, limits["edge_rel_err"]),
        Check("flow_rel_err", flow, limits["flow_rel_err"]),
        Check("heavy_wrong", float(heavy), 0.0),
    ]
    if qs.reach_src.size:
        checks.append(Check("reach_wrong", float(reach_bad), 0.0))
    return checks
