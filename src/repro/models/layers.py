"""Transformer building blocks: norms, RoPE, GQA attention (full / sliding
window / decode-with-cache), SwiGLU MLP, and capacity-based MoE.

Everything is a pure function over explicit param pytrees (pjit-friendly);
layer params are stacked on a leading L axis and consumed with ``lax.scan``
to keep HLO size independent of depth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dtype)


def layer_norm_nonparam(x: jax.Array, eps: float = 1e-5):
    """OLMo's non-parametric LayerNorm: standardize, no scale/bias."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dtype)


def apply_norm(kind: str, x: jax.Array, weight: Optional[jax.Array]):
    if kind == "rmsnorm":
        return rms_norm(x, weight)
    if kind == "layernorm_nonparam":
        return layer_norm_nonparam(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)  # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def gqa_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,  # (B, Skv, Hkv, Dh)
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    sliding_window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Grouped-query attention.  q head h attends kv head h // (Hq//Hkv).

    ``q_offset``: absolute position of q[0] (decode: the cache length).
    ``kv_valid_len``: number of valid cache slots (decode with ring/partial
    caches); None means all of Skv is valid.
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    groups = hq // hkv
    qg = q.reshape(b, sq, hkv, groups, dh)
    scale = 1.0 / np.sqrt(dh)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # (B, Hkv, G, Sq, Skv)

    q_pos = jnp.arange(sq)[:, None] + q_offset  # (Sq, 1) absolute
    k_pos = jnp.arange(skv)[None, :]            # (1, Skv) cache slot index
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    mask_b = jnp.broadcast_to(mask, (b, 1, 1, sq, skv))
    if kv_valid_len is not None:
        valid = k_pos < jnp.reshape(kv_valid_len, (-1, 1, 1, 1, 1))
        mask_b = mask_b & valid
    logits = jnp.where(mask_b, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, dh)


def chunked_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,
    *,
    causal: bool,
    sliding_window: Optional[int] = None,
    q_chunk: int = 512,
    window_slicing: bool = False,
) -> jax.Array:
    """Query-chunked attention with rematerialized chunk bodies.

    Peak live memory is one (B, Hkv, G, q_chunk, Skv) fp32 logits block
    instead of the full S² score tensor; ``jax.checkpoint`` on the chunk body
    keeps backward memory at the same bound (probs are recomputed, not
    stored).

    ``window_slicing`` (§Perf lever for SWA archs): each query chunk attends
    only a dynamic (window + q_chunk)-wide KV slice instead of all of Skv —
    attention FLOPs drop Skv/(window+q_chunk)-fold (7.1× on mixtral
    prefill_32k).  Baseline (False) computes the masked dense blocks."""
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / np.sqrt(dh)
    nq = -(-sq // q_chunk)
    pad = nq * q_chunk - sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(b, nq, q_chunk, hkv, g, dh).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, Hkv, G, qc, Dh)

    sliced = (
        window_slicing
        and sliding_window is not None
        and skv > sliding_window + q_chunk
    )
    if sliced:
        # front-pad so every chunk's (window + qc) slice is in-bounds; real
        # kv position = slice_start + offset - window
        win = sliding_window
        kp = jnp.pad(k, ((0, 0), (win, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (win, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def one_chunk(args):
        qi, qblk = args
        q_pos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
        if sliced:
            start = qi * q_chunk  # in padded coords: covers q_lo-win .. q_hi
            kb = jax.lax.dynamic_slice_in_dim(kp, start, win + q_chunk, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, start, win + q_chunk, axis=1)
            k_pos = start + jnp.arange(win + q_chunk)[None, :] - win
        else:
            kb, vb = k, v
            k_pos = jnp.arange(skv)[None, :]
        logits = jnp.einsum(
            "bhgqd,bkhd->bhgqk", qblk.astype(jnp.float32), kb.astype(jnp.float32)
        ) * scale
        mask = k_pos >= 0
        if causal:
            mask &= k_pos <= q_pos
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bhgqd", probs.astype(vb.dtype), vb)

    out = jax.lax.map(one_chunk, (jnp.arange(nq), qb))  # (nq, B, Hkv, G, qc, Dh)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * q_chunk, hq, dh)
    return out[:, :sq]


def decode_attention(
    q: jax.Array,        # (B, 1, Hq, Dh)
    cache_k: jax.Array,  # (B, Skv, Hkv, Dh) — k already rotated at write time
    cache_v: jax.Array,
    cache_len: jax.Array,  # (B,) or scalar: number of valid slots
) -> jax.Array:
    """One-token decode against a (possibly ring) KV cache.  Ring caches pass
    cache_len == capacity once full; ordering inside the ring is irrelevant
    for plain (non-ALiBi) attention since k carries its own rotation."""
    return gqa_attention(
        q,
        cache_k,
        cache_v,
        causal=False,
        kv_valid_len=cache_len,
    )


def swa_attention_halo(
    q: jax.Array,  # (B, S, Hq, Dh) sharded (dp, model, ·, ·)
    k: jax.Array,  # (B, S, Hkv, Dh)
    v: jax.Array,
    *,
    sliding_window: int,
    mesh,
    q_chunk: int = 512,
) -> jax.Array:
    """SWA attention with HALO EXCHANGE instead of a full KV gather (§Perf
    iteration 4 on mixtral prefill_32k).

    With seq sharded tp-ways, a window-w query shard only needs keys from
    itself + ceil(w / s_loc) left neighbors.  A traced-start dynamic_slice
    on the sharded seq axis makes GSPMD all-gather K/V entirely (measured
    2.4 GB × 56 layers per chip); here each shard ppermutes its K/V shard
    rightward n_halo times (n_halo × 134 MB on the same cell) and attends
    locally.  Requires w < S·(tp-1)/tp — otherwise it degenerates to full
    attention and the caller should use the dense path."""
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tp = mesh.shape["model"]
    s_total = q.shape[1]
    s_loc = s_total // tp
    n_halo = min(-(-sliding_window // s_loc), tp - 1)
    fwd = [(i, (i + 1) % tp) for i in range(tp)]

    def body(q_loc, k_loc, v_loc):
        b_loc = q_loc.shape[0]
        rank = jax.lax.axis_index("model")
        ks, vs = [k_loc], [v_loc]
        ck, cv = k_loc, v_loc
        for _ in range(n_halo):
            ck = jax.lax.ppermute(ck, "model", fwd)
            cv = jax.lax.ppermute(cv, "model", fwd)
            ks.insert(0, ck)
            vs.insert(0, cv)
        k_ext = jnp.concatenate(ks, axis=1)  # ((n_halo+1)·s_loc, …)
        v_ext = jnp.concatenate(vs, axis=1)
        # global positions: my q rows start at rank·s_loc; k_ext starts
        # n_halo shards earlier (ring wrap-around rows get k_pos < 0 → masked)
        q_start = rank * s_loc
        k_pos = q_start - n_halo * s_loc + jnp.arange((n_halo + 1) * s_loc)

        hq, dh = q_loc.shape[2], q_loc.shape[3]
        hkv = k_loc.shape[2]
        g = hq // hkv
        scale = 1.0 / np.sqrt(dh)
        nq = s_loc // q_chunk
        qb = q_loc.reshape(b_loc, nq, q_chunk, hkv, g, dh).transpose(1, 0, 3, 4, 2, 5)

        @jax.checkpoint
        def one_chunk(args):
            qi, qblk = args
            q_pos = q_start + qi * q_chunk + jnp.arange(q_chunk)[:, None]
            logits = jnp.einsum(
                "bhgqd,bkhd->bhgqk",
                qblk.astype(jnp.float32),
                k_ext.astype(jnp.float32),
            ) * scale
            mask = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos)
            mask &= k_pos[None, :] > q_pos - sliding_window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhgqk,bkhd->bhgqd", probs.astype(v_ext.dtype), v_ext)

        out = jax.lax.map(one_chunk, (jnp.arange(nq), qb))
        return out.transpose(1, 0, 4, 2, 3, 5).reshape(b_loc, s_loc, hq, dh)

    spec = P(dp, "model", None, None)
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


@dataclasses.dataclass(frozen=True)
class MoEArgs:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    dense_residual: bool = False  # Arctic: dense FFN in parallel with MoE
    aux_loss_coef: float = 0.01
    # sharding strategy: "expert" (EP over model axis) or "ffn" (TP inside
    # each expert; used when n_experts doesn't divide the model axis)
    partition: str = "expert"
    # PartitionSpec for the (E, C, D) dispatch/combine buffers — without a
    # constraint GSPMD replicates them (measured: +32 GB/chip on mixtral
    # train_4k).  Set by the step factory from the live mesh.
    dispatch_pspec: Optional[Any] = None
    # shard_map EP dispatch (§Perf hillclimb): explicit all-to-all token
    # exchange instead of GSPMD-resolved gather/scatter (which all-gathers
    # the full token buffer and all-reduces the combine — measured 100×
    # collective overhead on arctic).  Requires a mesh and seq % model == 0,
    # so only the train/prefill paths enable it.
    shard_dispatch: bool = False
    mesh: Optional[Any] = None  # jax.sharding.Mesh (hashable; config stays static)


def moe_capacity(n_tokens: int, args: MoEArgs) -> int:
    c = int(np.ceil(n_tokens * args.top_k / args.n_experts * args.capacity_factor))
    return max(4, int(np.ceil(c / 4)) * 4)


def moe_block(
    x: jax.Array,  # (T, D)
    router_w: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,    # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    args: MoEArgs,
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based top-k MoE with gather dispatch / scatter combine.

    No giant one-hot einsum: dispatch is an (E, C) index table + gather, so
    compiled FLOPs ≈ active-expert FLOPs × capacity factor (keeps the
    MODEL_FLOPS/HLO_FLOPS roofline ratio honest — DESIGN.md Section 4).
    Returns (output (T, D), aux_loss scalar).
    """
    t, d = x.shape
    e, k = args.n_experts, args.top_k
    c = moe_capacity(t, args)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch/GShard style).
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = args.aux_loss_coef * e * jnp.sum(me * ce)

    # Slot assignment: rank of each (token, k) within its expert.
    e_flat = expert_idx.reshape(-1)                                # (T*K,)
    gate_flat = gate_vals.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)            # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]  # (T*K,)
    keep = slot < c
    token_id = jnp.arange(t * k) // k

    # (E, C) dispatch tables; dropped tokens scatter to expert index E (OOB →
    # dropped by XLA scatter semantics), unfilled slots point at the zero row.
    e_safe = jnp.where(keep, e_flat, e)
    slot_safe = jnp.clip(slot, 0, c - 1)
    table = jnp.full((e, c), t, jnp.int32).at[e_safe, slot_safe].set(token_id)
    gates = jnp.zeros((e, c), jnp.float32).at[e_safe, slot_safe].set(gate_flat)

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[table]  # (E, C, D) gather

    def _csp(a):
        if args.dispatch_pspec is not None:
            return jax.lax.with_sharding_constraint(a, args.dispatch_pspec)
        return a

    xe = _csp(xe)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xe, w_up
    )
    ye = _csp(jnp.einsum("ecf,efd->ecd", h, w_down))  # (E, C, D)
    ye = ye * gates[..., None].astype(ye.dtype)

    y = jnp.zeros((t + 1, d), x.dtype).at[table.reshape(-1)].add(
        ye.reshape(-1, d)
    )[:t]
    return y, aux


# ---------------------------------------------------------------------------
# shard_map EP dispatch (§Perf): explicit all-to-all instead of GSPMD gather
# ---------------------------------------------------------------------------


def _route_local(x, router_w, e, k, cap_factor, aux_coef):
    """Route LOCAL tokens -> ((E, C_loc) token table, gates, aux).  Pure
    per-device math, no collectives."""
    t, d = x.shape
    c = moe_capacity(t, MoEArgs(n_experts=e, top_k=k, capacity_factor=cap_factor))
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = aux_coef * e * jnp.sum(me * ce)
    e_flat = expert_idx.reshape(-1)
    gate_flat = gate_vals.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = slot < c
    token_id = jnp.arange(t * k) // k
    e_safe = jnp.where(keep, e_flat, e)
    slot_safe = jnp.clip(slot, 0, c - 1)
    table = jnp.full((e, c), t, jnp.int32).at[e_safe, slot_safe].set(token_id)
    gates = jnp.zeros((e, c), jnp.float32).at[e_safe, slot_safe].set(gate_flat)
    return table, gates, aux


def moe_ffn_sharded(
    x: jax.Array,  # (B, S, D) activations, sharded (dp, model, ·)
    router_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    args: MoEArgs,
) -> Tuple[jax.Array, jax.Array]:
    """Expert FFN with EXPLICIT collectives under shard_map.

    partition="expert" (EP): local route → local gather → all_to_all(model)
    tokens→experts → expert matmuls → reverse all_to_all → local combine.
    Per-layer wire: 2× the (E, C_loc, D) buffer + the FSDP weight gather —
    vs GSPMD's all-gather of the FULL (T, D) token buffer + an all-reduce of
    the (T, D) combine (measured 100× more bytes on arctic train_4k).

    partition="ffn" (TP inside experts, mixtral): no token exchange — every
    device computes all experts on its local tokens with the F/tp weight
    shard, then one psum of the (E, C_loc, D) partial combine.
    """
    from jax.sharding import PartitionSpec as P

    mesh = args.mesh
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tp = mesh.shape["model"]
    e, k = args.n_experts, args.top_k

    if args.partition == "expert":

        def body(xb, rw, wg, wu, wd):
            b_loc, s_loc, d = xb.shape
            xl = xb.reshape(b_loc * s_loc, d)
            # FSDP gather of this shard's experts (transpose = reduce-scatter)
            wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)  # (E/tp, D, F)
            wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True)  # (E/tp, F, D)
            table, gates, aux = _route_local(
                xl, rw, e, k, args.capacity_factor, args.aux_loss_coef
            )
            c_loc = table.shape[1]
            x_pad = jnp.concatenate([xl, jnp.zeros((1, d), xl.dtype)], axis=0)
            xe = x_pad[table]                                   # (E, C_loc, D)
            # tokens -> expert owners
            xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1, tiled=True)
            gt = jax.lax.all_to_all(
                gates[..., None], "model", split_axis=0, concat_axis=1, tiled=True
            )[..., 0]                                           # (E/tp, tp*C_loc)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
                "ecd,edf->ecf", xe, wu
            )
            ye = jnp.einsum("ecf,efd->ecd", h, wd)
            ye = ye * gt[..., None].astype(ye.dtype)
            # expert outputs -> token owners
            ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0, tiled=True)
            y = jnp.zeros((b_loc * s_loc + 1, d), xb.dtype).at[
                table.reshape(-1)
            ].add(ye.reshape(-1, d))[: b_loc * s_loc]
            aux = jax.lax.pmean(aux, ("model",) + dp)
            return y.reshape(b_loc, s_loc, d), aux

        wspec_in = P("model", dp, None)    # (E→model, D→dp/FSDP, F)
        wspec_dn = P("model", None, dp)    # (E→model, F, D→dp)
    else:  # "ffn": Megatron-style TP inside each expert (E < tp, mixtral)

        def body(xb, rw, wg, wu, wd):
            # tokens are sharded over model (SP); the F-contraction psum
            # requires every model-peer to process the SAME token set →
            # gather the model-axis token shards first, compute the F/tp
            # partial for all of them, psum, then slice back (Megatron SP).
            b_loc, s_loc, d = xb.shape
            t_loc = b_loc * s_loc
            xl = xb.reshape(t_loc, d)
            xl = jax.lax.all_gather(xl, "model", axis=0, tiled=True)  # (tp·t, D)
            wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)  # (E, D, F/tp)
            wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True)  # (E, F/tp, D)
            table, gates, aux = _route_local(
                xl, rw, e, k, args.capacity_factor, args.aux_loss_coef
            )  # identical on every model peer (same inputs)
            x_pad = jnp.concatenate([xl, jnp.zeros((1, d), xl.dtype)], axis=0)
            xe = x_pad[table]                                   # (E, C, D)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
                "ecd,edf->ecf", xe, wu
            )
            ye = jnp.einsum("ecf,efd->ecd", h, wd)              # partial over F
            ye = ye * gates[..., None].astype(ye.dtype)
            # combine FIRST (still partial over F), then psum_scatter: each
            # peer only needs its own t_loc token rows, so reducing the
            # (tp·t_loc, D) combine costs tp× less wire than psum-ing the
            # (E, C, D) expert buffer (§Perf iteration 3: 3.77 GB -> 0.76 GB
            # per layer on mixtral prefill_32k).
            y = jnp.zeros((xl.shape[0] + 1, d), xb.dtype).at[
                table.reshape(-1)
            ].add(ye.reshape(-1, d))[: xl.shape[0]]
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=0, tiled=True)
            aux = jax.lax.pmean(aux, ("model",) + dp)
            return y.reshape(b_loc, s_loc, d), aux

        wspec_in = P(None, dp, "model")
        wspec_dn = P(None, "model", dp)

    act_spec = P(dp, "model", None)
    y, aux = shard_map(
        body,
        mesh=mesh,
        in_specs=(act_spec, P(None, None), wspec_in, wspec_in, wspec_dn),
        out_specs=(act_spec, P()),
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)
    return y, aux
