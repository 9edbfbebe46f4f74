"""Share of the traced slice in which no operation ran on the web share's
chips: 100 * (1 - busy / slice), busy averaged over the chips that ran
anything (``bench/trace.py``)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
