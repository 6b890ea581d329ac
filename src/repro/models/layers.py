"""Shared neural building blocks: RMSNorm, RoPE, GQA attention (full /
sliding-window / cached-decode), gated MLP, and capacity-based MoE.

All functions are pure; parameters are plain dict pytrees.  Activations are
annotated with logical sharding axes (see shardlib) so the same code runs
unsharded on CPU and pjit-sharded on the production mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.shardlib import active_rules, shard

# --------------------------------------------------------------------- norm


def rms_norm(x, w, eps: float = 1e-5):
    """RMSNorm with the variance reduction in f32.

    Deliberately structured so the only f32 consumer of ``x`` is inside the
    (fused) square-mean reduction: an elementwise f32 copy of x would make
    XLA store the layer-scan residual stack in f32 — 2x the activation
    memory of the whole backward pass (measured: +21 GiB/device at the
    llama3.2-3b train_4k shape).  The scale is applied in the compute dtype.
    """
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    scale = (jax.lax.rsqrt(var + eps) * w).astype(x.dtype)  # (..., D)
    return x * scale


# --------------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x, positions, theta: float):
    """x: (..., S, n, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention


def _qk_scale(head_dim: int) -> float:
    return head_dim ** -0.5


def gqa_attention(
    q,  # (B, S, nh, hd)
    k,  # (B, T, nkv, hd)
    v,  # (B, T, nkv, hd)
    *,
    causal_offset: Optional[int] = 0,
    window: int = 0,
    q_positions=None,   # (B, S) absolute positions of queries; default arange
    kv_valid=None,      # (B, T) bool mask of valid cache slots (decode)
    kv_positions=None,  # (B, T) absolute positions of cache slots (ring SWA)
    scale: Optional[float] = None,  # score scale; None -> 1/sqrt(head_dim)
):
    """Grouped-query attention with optional causal/sliding-window masking.

    Training/prefill: T == S, causal mask, window applied if nonzero.
    Decode: S == 1, ``kv_valid``/``kv_positions`` describe the cache.
    """
    b, s, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    qpk = nh // nkv
    qg = q.reshape(b, s, nkv, qpk, hd)

    logits = jnp.einsum(
        "bsngh,btnh->bngst", qg, k, preferred_element_type=jnp.float32
    ) * (scale or _qk_scale(hd))  # (B, nkv, qpk, S, T)

    if q_positions is None:
        q_pos = jnp.arange(s)[None, :] + (causal_offset or 0)
        q_pos = jnp.broadcast_to(q_pos, (b, s))
    else:
        q_pos = q_positions
    if kv_positions is None:
        k_pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    else:
        k_pos = kv_positions

    mask = k_pos[:, None, :] <= q_pos[:, :, None]  # (B, S, T) causal
    if window:
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v)
    return out.reshape(b, s, nh, hd)


def chunked_gqa_attention(
    q, k, v,
    *,
    window: int = 0,
    q_positions=None,
    kv_positions=None,
    kv_valid=None,
    chunk_q: int = 512,
    chunk_k: int = 1024,
    scale: Optional[float] = None,
):
    """Flash-style chunked attention in pure JAX (lax.scan online softmax).

    Same semantics as ``gqa_attention`` but with O(S*chunk) memory instead
    of O(S*T): mandatory for the 4k-train / 32k-prefill shapes, where the
    full (B, H, S, T) logits tensor would not fit HBM.  On TPU the Pallas
    ``flash_prefill`` kernel replaces this; this is the shardable jnp form
    the dry-run lowers (XLA keeps the scan as a while loop, so HLO size and
    live memory stay bounded).
    """
    b, s, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    qpk = nh // nkv
    # context parallelism (§Perf O4): when the launcher maps the logical
    # "q_chunks" axis to a mesh axis, q chunks are computed as a vmapped
    # batch (shardable) instead of a sequential scan, and nq is forced to
    # a multiple of that axis degree.  This is the attention sharding for
    # archs whose head count does not divide the model axis (llama3.2 24H,
    # llava 56H, starcoder2 36H, whisper 6H): logits stay device-local,
    # only the (B,S,nh,hd) output is re-gathered once per layer.
    cp_degree = 0
    ctx = active_rules()
    if ctx is not None:
        mesh, rules = ctx
        ax = rules.get("q_chunks")
        if ax is not None:
            cp_degree = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                cp_degree *= mesh.shape[a]
    # snap chunk sizes to divisors of s/t (e.g. whisper's 1500-frame
    # encoder output): s // (s // c) is the smallest divisor-chunk >= c
    cq = s // max(1, s // min(chunk_q, s))
    ck = t // max(1, t // min(chunk_k, t))
    while s % cq:
        cq += 1
    while t % ck:
        ck += 1
    nq, nk = s // cq, t // ck
    if cp_degree > 1:
        # force nq to a multiple of the context-parallel degree
        nq2 = ((max(nq, cp_degree) + cp_degree - 1) // cp_degree) * cp_degree
        while nq2 <= s and s % nq2:
            nq2 += cp_degree
        if nq2 <= s:
            nq = nq2
            cq = s // nq
        else:
            cp_degree = 0  # cannot split this length: fall back to scan

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    if kv_valid is None:
        kv_valid = jnp.ones((b, t), bool)

    qg = q.reshape(b, nq, cq, nkv, qpk, hd)
    kg = k.reshape(b, nk, ck, nkv, hd)
    vg = v.reshape(b, nk, ck, nkv, hd)
    # pin the scanned K/V layout HERE, outside the chunk loops: without this
    # SPMD re-gathers each (q,k) chunk pair inside the innermost loop when
    # the cache output layout differs from the attention layout (measured
    # 640 GiB of all-gather at dbrx prefill_32k — §Perf iteration 2)
    kg = shard(kg, "batch", None, None, "kv_heads", "head_dim")
    vg = shard(vg, "batch", None, None, "kv_heads", "head_dim")
    qp = q_positions.reshape(b, nq, cq)
    kp = kv_positions.reshape(b, nk, ck)
    kva = kv_valid.reshape(b, nk, ck)
    scale = scale or _qk_scale(hd)

    def one_q_chunk(carry, qs):
        q_c, qp_c = qs          # (B,cq,nkv,qpk,hd), (B,cq)

        @jax.checkpoint
        def one_k_chunk(acc, ks):
            m, l, o = acc
            k_c, v_c, kp_c, kva_c = ks
            s_ = jnp.einsum(
                "bqngh,bknh->bngqk", q_c, k_c,
                preferred_element_type=jnp.float32,
            ) * scale                               # (B,nkv,qpk,cq,ck)
            msk = (kp_c[:, None, :] <= qp_c[:, :, None]) & kva_c[:, None, :]
            if window:
                msk &= kp_c[:, None, :] > (qp_c[:, :, None] - window)
            s_ = jnp.where(msk[:, None, None], s_, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
            p = jnp.where(jnp.isfinite(s_), jnp.exp(s_ - safe[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            o = alpha[..., None] * o + jnp.einsum(
                "bngqk,bknh->bngqh", p.astype(v_c.dtype), v_c
            ).astype(jnp.float32)
            return (m_new, l, o), None

        init = (
            jnp.full((b, nkv, qpk, cq), -jnp.inf, jnp.float32),
            jnp.zeros((b, nkv, qpk, cq), jnp.float32),
            jnp.zeros((b, nkv, qpk, cq, hd), jnp.float32),
        )
        mv = lambda a: jnp.moveaxis(a, 1, 0)
        (m, l, o), _ = jax.lax.scan(
            one_k_chunk, init, (mv(kg), mv(vg), mv(kp), mv(kva))
        )
        out = o / jnp.maximum(l, 1e-30)[..., None]
        # (B,nkv,qpk,cq,hd) -> (B,cq,nh,hd)
        out = jnp.moveaxis(out, 3, 1).reshape(b, cq, nh, hd)
        return carry, out.astype(q.dtype)

    if cp_degree > 1:
        # context-parallel path: q chunks as a vmapped (shardable) batch
        qg = shard(qg, "batch", "q_chunks", None, None, None, None)
        qp_s = shard(qp, "batch", "q_chunks", None)

        def per_chunk(q_c, qp_c):
            _, out = one_q_chunk(None, (q_c, qp_c))
            return out

        outs = jax.vmap(per_chunk, in_axes=(1, 1), out_axes=1)(qg, qp_s)
        outs = shard(outs, "batch", "q_chunks", None, None, None)
        return outs.reshape(b, s, nh, hd)

    mvq = lambda a: jnp.moveaxis(a, 1, 0)
    _, outs = jax.lax.scan(one_q_chunk, None, (mvq(qg), mvq(qp)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, nh, hd)


def attention_any(
    q, k, v, *, window=0, q_positions=None, kv_positions=None,
    kv_valid=None, full_threshold: int = 2048, scale=None,
):
    """Dispatch: full-matrix attention for small S*T, chunked otherwise."""
    s, t = q.shape[1], k.shape[1]
    if s * t <= full_threshold * full_threshold or s == 1:
        return gqa_attention(
            q, k, v, window=window, q_positions=q_positions,
            kv_positions=kv_positions, kv_valid=kv_valid, scale=scale,
        )
    return chunked_gqa_attention(
        q, k, v, window=window, q_positions=q_positions,
        kv_positions=kv_positions, kv_valid=kv_valid, scale=scale,
    )


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def init_attention(key, d_model: int, dims: AttnDims, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d_model ** -0.5
    nh, nkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    return {
        "wq": (jax.random.normal(k1, (d_model, nh, hd)) * scale).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, nkv, hd)) * scale).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, nkv, hd)) * scale).astype(dtype),
        "wo": (jax.random.normal(k4, (nh, hd, d_model)) * scale).astype(dtype),
    }


def attention_qkv(p, x, positions, theta: float, use_rope: bool):
    """Project and (optionally) rotate. x: (B,S,D) -> q,k,v."""
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    ctxr = active_rules()
    if ctxr is not None and ctxr[1].get("head_dim_proj") is not None:
        # context-parallel mode (§Perf O4/iter.5): pin the PROJECTION
        # outputs head_dim-sharded first — otherwise SPMD replicates the
        # whole qkv matmul on every model shard (2.8x per-device FLOPs) —
        # then the plain annotations below insert one explicit activation
        # all-gather per layer at the attention boundary.
        q = shard(q, "batch", "seq", "heads", "head_dim_proj")
        k = shard(k, "batch", "seq", "kv_heads", "head_dim_proj")
        v = shard(v, "batch", "seq", "kv_heads", "head_dim_proj")
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    if use_rope:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def attention_out(p, o):
    y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    return shard(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------- mlp


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": (jax.random.normal(k1, (d_model, d_ff)) * d_model**-0.5).astype(dtype),
        "w3": (jax.random.normal(k2, (d_model, d_ff)) * d_model**-0.5).astype(dtype),
        "w2": (jax.random.normal(k3, (d_ff, d_model)) * d_ff**-0.5).astype(dtype),
    }


def gated_mlp(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w1"])) * jnp.einsum(
        "bsd,df->bsf", x, p["w3"]
    )
    h = shard(h, "batch", "seq", "ffn")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    return shard(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------- moe


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype):
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(k0, (d_model, n_experts)) * d_model**-0.5
                   ).astype(jnp.float32),
        "w1": (jax.random.normal(k1, (n_experts, d_model, d_ff))
               * d_model**-0.5).astype(dtype),
        "w3": (jax.random.normal(k2, (n_experts, d_model, d_ff))
               * d_model**-0.5).astype(dtype),
        "w2": (jax.random.normal(k3, (n_experts, d_ff, d_model))
               * d_ff**-0.5).astype(dtype),
    }


def moe_mlp(p, x, *, top_k: int, capacity_factor: float = 1.25,
            group_size: int = 1024):
    """GShard-style capacity-based top-k MoE, GROUPED for long sequences.

    Tokens are processed in groups of <= ``group_size`` along the sequence
    (the GSPMD MoE trick): the dispatch one-hot is (B, G, g, E, C) with
    C = ceil(g * top_k / E * capacity_factor), so memory scales with the
    group, not the full sequence.  The dispatch/combine einsums lower to
    all-to-alls when experts are sharded over the 'model' mesh axis.
    Overflowing tokens fall through the residual (standard capacity drop).
    Returns (output, aux) where aux carries the load-balancing loss term.
    """
    b, s, d = x.shape
    e = p["router"].shape[-1]
    g = s // max(1, s // min(group_size, s))   # divisor-snapped group size
    while s % g:
        g += 1
    ng = s // g
    cap = max(1, int(g * top_k / e * capacity_factor))

    xg = x.reshape(b, ng, g, d)
    gate_logits = jnp.einsum(
        "bngd,de->bnge", xg.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(gate_logits, axis=-1)        # (B,G,g,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)   # (B,G,g,K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # position of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (B,G,g,K,E)
    flat = onehot.reshape(b, ng, g * top_k, e)
    pos_in_expert = jnp.cumsum(flat, axis=2) * flat - 1.0
    pos_in_expert = pos_in_expert.reshape(b, ng, g, top_k, e)
    fits = (pos_in_expert >= 0) & (pos_in_expert < cap)

    pos_clip = jnp.clip(pos_in_expert, 0, cap - 1).astype(jnp.int32)
    disp = (
        jax.nn.one_hot(pos_clip, cap, dtype=x.dtype)
        * (onehot * fits)[..., None].astype(x.dtype)
    ).sum(axis=3)                                        # (B,G,g,E,C)
    comb = (
        jax.nn.one_hot(pos_clip, cap, dtype=jnp.float32)
        * (onehot * fits * gate_vals[..., None]).astype(jnp.float32)[..., None]
    ).sum(axis=3).astype(x.dtype)

    xe = jnp.einsum("bngd,bngec->bnecd", xg, disp)       # (B,G,E,C,D)
    # expert-parallel archs shard E ("experts"->model, "ffn"->None);
    # few-expert archs shard F instead ("experts"->None, "ffn"->model) —
    # the rules guarantee the two never both map to "model".  Without the
    # "ffn" hint SPMD all-gathers the full F-sharded expert weights every
    # layer (measured 56 GiB/step at mixtral long_500k — §Perf iter. 3).
    xe = shard(xe, "batch", None, "experts", None, None)
    h = jax.nn.silu(jnp.einsum("bnecd,edf->bnecf", xe, p["w1"])) * jnp.einsum(
        "bnecd,edf->bnecf", xe, p["w3"]
    )
    h = shard(h, "batch", None, "experts", None, "ffn")
    ye = jnp.einsum("bnecf,efd->bnecd", h, p["w2"])
    y = jnp.einsum("bnecd,bngec->bngd", ye, comb)
    y = y.reshape(b, s, d)
    y = shard(y, "batch", "seq", "embed")

    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(onehot.sum(3), axis=(0, 1, 2))   # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1, 2))            # (E,)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux
