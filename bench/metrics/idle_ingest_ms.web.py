"""Device-idle milliseconds per batch, per chip, while the program's ingest
path was the innermost span open on the web share: ``glava.ingest`` and its
children (label codec, host pre-aggregation, touched keys, transfer to the
chips, dispatch, back-pressure), the standing-query tick inside it left
out.  Batches are the ``glava.ingest`` spans that start inside the traced
slice (``bench/program_spans.py``)."""
from bench import program_spans


def read(run):
    a = program_spans.for_run(run)
    if a is None:
        return None
    return 1e3 * a.idle_ingest_s / a.batches
