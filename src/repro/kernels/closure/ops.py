"""jit'd wrapper: full transitive closure by repeated Pallas squaring."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.closure.kernel import TILE, closure_step_pallas


def transitive_closure(
    adj, include_self: bool = True, interpret: Optional[bool] = None
):
    """(..., w, w) weighted adjacency -> boolean closure, via the Pallas
    blocked-squaring kernel.  Batched over leading dims (the d sketches)."""
    w = adj.shape[-1]
    pad = (-w) % TILE
    a = (adj > 0).astype(jnp.float32)
    if include_self:
        a = jnp.clip(a + jnp.eye(w, dtype=jnp.float32), 0.0, 1.0)
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, pad)])
    step = lambda m: closure_step_pallas(m, interpret=interpret)
    for _ in range(a.ndim - 2):
        step = jax.vmap(step)
    n_steps = max(1, math.ceil(math.log2(max(2, w))))
    for _ in range(n_steps):
        a = step(a)
    out = a[..., :w, :w]
    return out > 0
