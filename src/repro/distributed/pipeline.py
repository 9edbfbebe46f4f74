"""Pipeline parallelism (experimental, DESIGN.md Section 4): GPipe-style
microbatch pipelining over a ``pipe`` mesh axis with explicit
``collective_permute`` activation transfers, expressed under shard_map.

The default path for the assigned shapes is TP×FSDP(×EP) — at these depths
the PP bubble (S−1)/(M+S−1) loses to EP+FSDP — but PP is the right tool for
>10k-chip deployments where a single layer no longer fits a TP group, so the
schedule ships as a first-class, tested module.

Semantics: ``pipeline_apply(stage_fn, stage_params, x, mesh)`` computes
    y = stage_fn(p_{S-1}, stage_fn(p_{S-2}, … stage_fn(p_0, x)))
with stage s resident on pipe-rank s, microbatches streamed GPipe-style:
tick t has rank s working on microbatch t−s (bubble at the ends).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params,          # pytree, each leaf (S, ...) — stage-major
    x: jax.Array,          # (M, mb, D) microbatched input
    mesh: jax.sharding.Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run the S-stage pipeline over M microbatches.  Returns (M, mb, D)."""
    s_stages = mesh.shape[axis]
    m, mb, d = x.shape
    n_ticks = m + s_stages - 1
    fwd_pairs = [(i, (i + 1) % s_stages) for i in range(s_stages)]

    def body(params_local, x_local):
        # params_local: this rank's stage params (leaves (1, ...))
        params_local = jax.tree.map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        # every rank holds the full microbatch queue but only rank 0 injects
        # (x is replicated along `axis` by the in_spec)
        out_acc = jnp.zeros((m, mb, d), x_local.dtype)
        recv = jnp.zeros((mb, d), x_local.dtype)

        def tick(t, carry):
            recv, out_acc = carry
            # stage input: rank 0 takes microbatch t from the queue (if any),
            # others take what arrived from the left neighbor
            inject = jax.lax.dynamic_slice_in_dim(
                x_local, jnp.clip(t, 0, m - 1), 1, axis=0
            )[0]
            stage_in = jnp.where(rank == 0, inject, recv)
            stage_out = stage_fn(params_local, stage_in)
            # last rank commits microbatch (t - (S-1)) when it is valid
            mb_idx = t - (s_stages - 1)
            valid_out = (rank == s_stages - 1) & (mb_idx >= 0) & (mb_idx < m)
            out_acc = jax.lax.cond(
                valid_out,
                lambda acc: jax.lax.dynamic_update_slice_in_dim(
                    acc, stage_out[None], jnp.clip(mb_idx, 0, m - 1), axis=0
                ),
                lambda acc: acc,
                out_acc,
            )
            # ship activations rightward for the next tick
            recv = jax.lax.ppermute(stage_out, axis, fwd_pairs)
            return recv, out_acc

        recv, out_acc = jax.lax.fori_loop(0, n_ticks, tick, (recv, out_acc))
        # only the last rank's accumulator is the real output: broadcast it
        out_acc = jnp.where(rank == s_stages - 1, out_acc, 0.0)
        return jax.lax.psum(out_acc, axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """(B, D) -> (M, B/M, D)."""
    b = x.shape[0]
    assert b % n_micro == 0
    return x.reshape(n_micro, b // n_micro, *x.shape[1:])


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: (S-1)/(M+S-1) — the quantity that makes EP+FSDP win at
    the assigned depths (DESIGN.md Section 4)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
