"""Ingest's share of its roofline on the web share's chips: the least time
any correct ingest of the traced batches could take on all of them, over
the device time of the session's ingest programs on one of them.

Least time = bytes / (peak HBM bandwidth * chips), with bytes counted by the
benchmark from the batches it sent at the configuration's depth
(``bench/work.py``): the same number whatever implements ingest, each chip
carrying its share.  The device time is the per-chip mean of the modules
named ``jit__update*`` (``bench/trace.py`` averages over the chips that
ran), so the share cannot pass 100%."""
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
    return 100.0 * (total / (run.peaks["hbm_bytes_per_s"] * t.devices)) / device_s
