"""Device milliseconds per batch, per chip, of the program's query programs
in the traced slice of the web share: the XLA modules named
``jit_glava_query_<family>`` (the shard-local edge gather with its pmin,
the register gathers, their padding), over the batches cut while the trace
ran."""


def is_query(name: str) -> bool:
    return "glava_query_" in name


def read(run):
    t = run.trace
    if t is None or not run.traced:
        return None
    device_s = t.module_seconds(is_query)
    if device_s <= 0:
        return None
    return 1e3 * device_s / len(run.traced)
