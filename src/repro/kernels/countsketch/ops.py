"""jit'd wrapper: hash + pad + Pallas CountSketch."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.countsketch.kernel import CHUNK_N, TILE_W, countsketch_pallas


def countsketch(vec, hash_family, interpret: Optional[bool] = None):
    """Compress a flat vector with a HashFamily -> (d, w) table.  Exactly
    matches repro.train.compression._sketch (tested)."""
    n = vec.shape[0]
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = hash_family(idx).astype(jnp.int32)
    s = hash_family.signs(idx).astype(jnp.int32)
    pad_n = (-n) % CHUNK_N
    if pad_n:
        vec = jnp.pad(vec.astype(jnp.float32), (0, pad_n))
        h = jnp.pad(h, ((0, 0), (0, pad_n)))
        s = jnp.pad(s, ((0, 0), (0, pad_n)), constant_values=1)
    w = hash_family.w
    pad_w = (-w) % TILE_W
    out = countsketch_pallas(
        vec.astype(jnp.float32), h, s, width=w + pad_w, interpret=interpret
    )
    return out[:, :w]
