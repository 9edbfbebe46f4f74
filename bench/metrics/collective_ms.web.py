"""Device milliseconds per batch, per chip, of the collectives in the traced
slice of the web share: operations of the all-reduce, all-gather and
collective-permute kinds (their async start and done halves included),
over the batches cut while the trace ran.  The edge query's pmin moves
d * Q values a batch; a gather of counters across chips would show here as
milliseconds."""
import re

KINDS = ("all-reduce", "all-gather", "collective-permute")
# An operation is named by its HLO text, ``%name = shape kind(operands...)``,
# or by its name alone: the kind is the first word after ``=`` that opens a
# parenthesis, else the name.  Operands that refer to a collective do not
# count.
_OP = re.compile(r"^%?([\w.\-]+)(?:\s*=.*?\s([a-z][\w\-]*)\()?")


def op_kind(name: str) -> str:
    m = _OP.match(name)
    if m is None:
        return ""
    return m.group(2) or m.group(1)


def is_collective(name: str) -> bool:
    return op_kind(name).startswith(KINDS)


def read(run):
    t = run.trace
    if t is None or not run.traced:
        return None
    return 1e3 * sum(s for name, s in t.ops.items() if is_collective(name)) / len(run.traced)
