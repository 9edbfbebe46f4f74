"""Share of the traced slice in which no operation ran on the device, in a
saturating cell: 100 * (1 - busy / slice), from the device trace."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
