"""Pass 1 — the jaxpr contract checker.

Generalizes the ad-hoc ``_walk_jaxprs`` helper that used to live inside
``tests/test_query_engine.py`` into a rule engine: each entry point in
:data:`repro.analysis.contracts.ENTRY_POINTS` is traced with
``jax.make_jaxpr`` and its declared contracts are checked against every
(sub-)jaxpr, including the bodies of ``scan``/``while``/``cond``/
``pallas_call``/``shard_map`` equations.  The donation contract inspects
the *lowering* instead (donation is applied at lowering time — it never
shows up in the jaxpr), and the retrace contracts drive the live engines
(see ``contracts.DYNAMIC_CHECKS``).
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Tuple

from repro.analysis.contracts import (
    DYNAMIC_CHECKS,
    ENTRY_POINTS,
    EntryPoint,
    TracedEntry,
    Violation,
)

# Primitives that move data or control to the host mid-computation.  Any of
# these inside a hot-path jaxpr serializes the async dispatch pipeline.
HOST_CALLBACK_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "outside_call",
        "host_callback_call",
        "infeed",
        "outfeed",
        "device_put",
    }
)

# Cross-device collectives: legal ONLY under shard_map (outside one they
# either fail at run time on a mesh or silently run replicated).
COLLECTIVE_PRIMITIVES = frozenset(
    {
        "psum",
        "pmin",
        "pmax",
        "pmean",
        "all_gather",
        "all_to_all",
        "ppermute",
        "pshuffle",
        "reduce_scatter",
        "psum_scatter",
    }
)

# 64-bit/complex128 avals double HBM traffic; the sketch plane is float32 /
# uint32 end to end and jax's x64 flag is off, so any wide aval is a
# promotion bug (e.g. a Python float snuck in as weak float64).
WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})

REDUCTION_PREFIX = "reduce_"

SHARD_MAP_PRIMITIVES = frozenset({"shard_map", "pjit_shard_map"})


def walk_jaxprs(jaxpr, in_shard_map: bool = False) -> Iterator[Tuple[object, bool]]:
    """Yield ``(jaxpr, in_shard_map)`` for a jaxpr and every sub-jaxpr
    reachable through equation params (scan/while/cond bodies, pallas_call
    kernels, shard_map bodies, nested pjit calls), tracking whether the
    walk is currently inside a ``shard_map`` region."""
    yield jaxpr, in_shard_map
    for eqn in jaxpr.eqns:
        inner = in_shard_map or eqn.primitive.name in SHARD_MAP_PRIMITIVES
        for param in eqn.params.values():
            yield from _walk_param(param, inner)


def _walk_param(param, in_shard_map: bool) -> Iterator[Tuple[object, bool]]:
    if hasattr(param, "jaxpr"):  # ClosedJaxpr
        yield from walk_jaxprs(param.jaxpr, in_shard_map)
    elif hasattr(param, "eqns"):  # raw Jaxpr
        yield from walk_jaxprs(param, in_shard_map)
    elif isinstance(param, (tuple, list)):
        for item in param:
            yield from _walk_param(item, in_shard_map)


def _trace(entry: TracedEntry):
    import jax

    return jax.make_jaxpr(entry.fn)(*entry.args)


# ---------------------------------------------------------------------------
# per-contract checkers — each takes the traced closed jaxpr and the entry
# ---------------------------------------------------------------------------


def check_no_host_callback(closed, entry: TracedEntry, name: str) -> List[Violation]:
    out = []
    for jaxpr, _ in walk_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in HOST_CALLBACK_PRIMITIVES:
                out.append(
                    Violation(
                        rule="no-host-callback",
                        subject=name,
                        message=(
                            f"host-transfer primitive {eqn.primitive.name!r} "
                            "in a hot-path jaxpr"
                        ),
                        pass_name="jaxpr",
                    )
                )
    return out


def check_no_wide_dtype(closed, entry: TracedEntry, name: str) -> List[Violation]:
    out = []
    seen = set()
    for jaxpr, _ in walk_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            for var in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(var, "aval", None)
                dtype = getattr(aval, "dtype", None)
                if dtype is None:
                    continue
                dname = str(dtype)
                if dname in WIDE_DTYPES and (eqn.primitive.name, dname) not in seen:
                    seen.add((eqn.primitive.name, dname))
                    out.append(
                        Violation(
                            rule="no-wide-dtype",
                            subject=name,
                            message=(
                                f"{dname} aval produced around primitive "
                                f"{eqn.primitive.name!r} — weak-type/x64 "
                                "promotion on the hot path"
                            ),
                            pass_name="jaxpr",
                        )
                    )
    return out


def check_no_counter_reduction(
    closed, entry: TracedEntry, name: str
) -> List[Violation]:
    shape = entry.counters_shape
    if shape is None:
        return []
    out = []
    for jaxpr, _ in walk_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if not eqn.primitive.name.startswith(REDUCTION_PREFIX):
                continue
            for var in eqn.invars:
                aval = getattr(var, "aval", None)
                if aval is not None and tuple(getattr(aval, "shape", ())) == shape:
                    out.append(
                        Violation(
                            rule="no-counter-reduction",
                            subject=name,
                            message=(
                                f"{eqn.primitive.name!r} consumes the full "
                                f"{shape} counter tensor — register-served "
                                "families must stay O(d·Q) gathers"
                            ),
                            pass_name="jaxpr",
                        )
                    )
    return out


def check_collectives_under_shard_map(
    closed, entry: TracedEntry, name: str
) -> List[Violation]:
    out = []
    for jaxpr, in_shard_map in walk_jaxprs(closed.jaxpr):
        if in_shard_map:
            continue
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
                out.append(
                    Violation(
                        rule="collectives-under-shard-map",
                        subject=name,
                        message=(
                            f"collective {eqn.primitive.name!r} outside any "
                            "shard_map region"
                        ),
                        pass_name="jaxpr",
                    )
                )
    return out


def check_donation_applied(entry: TracedEntry, name: str) -> List[Violation]:
    """Donation never appears in the jaxpr — it is applied when the jit is
    LOWERED.  A donated-but-unusable buffer (shape/dtype mismatch with every
    output, or donation silently dropped) keeps the full per-batch sketch
    copy alive, so we assert the lowering actually aliases inputs into
    outputs (``tf.aliasing_output`` on the entry computation)."""
    if entry.jit_fn is None:
        return [
            Violation(
                rule="donation-applied",
                subject=name,
                message="entry declares donation contract but exposes no jit_fn",
                pass_name="jaxpr",
            )
        ]
    lowered = entry.jit_fn.lower(*entry.args)
    text = lowered.as_text()
    if "tf.aliasing_output" not in text:
        return [
            Violation(
                rule="donation-applied",
                subject=name,
                message=(
                    "lowering carries no tf.aliasing_output attribute — "
                    "sketch buffers are NOT donated through the jit "
                    "boundary (each batch pays a full counter-tensor copy)"
                ),
                pass_name="jaxpr",
            )
        ]
    return []


_CHECKERS = {
    "no-host-callback": check_no_host_callback,
    "no-wide-dtype": check_no_wide_dtype,
    "no-counter-reduction": check_no_counter_reduction,
    "collectives-under-shard-map": check_collectives_under_shard_map,
}


def check_entry_point(ep: EntryPoint) -> List[Violation]:
    try:
        entry = ep.build()
    except Exception as exc:  # a broken fixture is itself a finding
        return [
            Violation(
                rule="entry-point-broken",
                subject=ep.name,
                message=f"fixture failed to build: {type(exc).__name__}: {exc}",
                pass_name="jaxpr",
            )
        ]
    out: List[Violation] = []
    jaxpr_contracts = [c for c in ep.contracts if c in _CHECKERS]
    if jaxpr_contracts:
        try:
            closed = _trace(entry)
        except Exception as exc:
            return [
                Violation(
                    rule="entry-point-broken",
                    subject=ep.name,
                    message=f"trace failed: {type(exc).__name__}: {exc}",
                    pass_name="jaxpr",
                )
            ]
        for contract in jaxpr_contracts:
            out.extend(_CHECKERS[contract](closed, entry, ep.name))
    if "donation-applied" in ep.contracts:
        out.extend(check_donation_applied(entry, ep.name))
    return out


def run_jaxpr_pass(
    entry_points: Optional[Iterable[EntryPoint]] = None,
    *,
    dynamic: bool = True,
) -> List[Violation]:
    """Check every registered entry point; then run the dynamic retrace
    detectors against the live engines."""
    out: List[Violation] = []
    for ep in entry_points if entry_points is not None else ENTRY_POINTS:
        out.extend(check_entry_point(ep))
    if dynamic and entry_points is None:
        for check_name, check in DYNAMIC_CHECKS.items():
            try:
                out.extend(check())
            except Exception as exc:
                out.append(
                    Violation(
                        rule="entry-point-broken",
                        subject=check_name,
                        message=f"dynamic check crashed: {type(exc).__name__}: {exc}",
                        pass_name="jaxpr",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# test-facing helper (API-compatible replacement for the old private copy
# in tests/test_query_engine.py)
# ---------------------------------------------------------------------------


def reduces_full_counters(fn, counters_shape: Tuple[int, ...], *args) -> bool:
    """True iff tracing ``fn(*args)`` yields any reduction primitive whose
    operand has exactly ``counters_shape`` — i.e. the full counter tensor is
    reduced instead of being served from the flow registers."""
    entry = TracedEntry(fn=fn, args=args, counters_shape=tuple(counters_shape))
    closed = _trace(entry)
    return bool(check_no_counter_reduction(closed, entry, "<adhoc>"))
