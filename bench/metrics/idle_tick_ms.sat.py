"""Device-idle milliseconds per batch while the program's standing-query
tick was the innermost span open: ``glava.tick`` and its children (flush
wait, closure sync, each subscription's plan with its per-family dispatches
and fetches, event emission).  Batches are the ``glava.ingest`` spans that
start inside the traced slice (``bench/program_spans.py``)."""
from bench import program_spans


def read(run):
    a = program_spans.for_run(run)
    if a is None:
        return None
    return 1e3 * a.idle_tick_s / a.batches
