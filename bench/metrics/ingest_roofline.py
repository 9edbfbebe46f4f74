"""Ingest's share of its roofline: the least time any correct ingest of the
traced batches could take on this chip, over the device time of the
session's ingest programs in the traced slice.

Least time = bytes / peak HBM bandwidth, with bytes counted by the
benchmark from the batches it sent (``bench/work.py``): it is the same
number whether a dense kernel, a scatter or a sort does the work.  The
ingest programs are the session's jitted update entry points, whose XLA
modules are named ``jit__update*``."""
from bench import work


def is_ingest(name: str) -> bool:
    return "_update" in name


def read(run):
    t = run.trace
    if t is None or not run.traced:
        return None
    device_s = t.module_seconds(is_ingest)
    if device_s <= 0:
        return None
    total = sum(work.ingest_bytes(work.batch_work(run.pool, b.start, b.n), run.depth) for b in run.traced)
    return 100.0 * (total / run.peaks["hbm_bytes_per_s"]) / device_s
