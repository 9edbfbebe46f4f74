"""Pallas kernels for the sketch's hot spots, each as kernel.py (the
``pallas_call``), ops.py (padding and dispatch) and ref.py (the jnp oracle).

Whether a kernel runs compiled or in the Pallas interpreter is decided here
and nowhere else: every wrapper takes ``interpret=None``, which means
"compiled on a TPU, interpreted everywhere else".  Tests pass ``True`` to run
a kernel body on the CPU, and the compile rehearsals pass ``False``.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """An explicit ``interpret`` wins; ``None`` follows the platform."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
