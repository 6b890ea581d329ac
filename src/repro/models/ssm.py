"""State-space and recurrent sequence mixers: Mamba2 (SSD) and xLSTM blocks.

TPU adaptation notes (DESIGN.md §3): these are implemented with
``jax.lax.scan`` over the sequence (training/prefill) and an O(1) functional
state update (decode).  The mLSTM additionally has the *parallel* quadratic
form used for training — mathematically equivalent to its recurrence and
MXU-friendly (it is a decay-masked attention), matching how the xLSTM paper
trains on accelerators.

State layouts (per layer; a model's cache stacks them layer first):
  mamba2:  h: (B, H, P, N)   conv: (B, W-1, d_conv_channels)
  mlstm:   C: (B, H, hd, hd)  n: (B, H, hd)  m: (B, H)
  slstm:   c,n,h: (B, H, hd)  m: (B, H)
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.shardlib import shard

# ------------------------------------------------------------------- mamba2


def mamba2_dims(d_model: int, d_state: int):
    d_inner = 2 * d_model
    p = 64                       # head dim (Mamba2 default)
    h = d_inner // p             # ssm heads
    return d_inner, p, h, d_state


def init_mamba2(key, d_model: int, d_state: int, conv_width: int, dtype):
    d_inner, p, h, n = mamba2_dims(d_model, d_state)
    ks = jax.random.split(key, 6)
    scale = d_model ** -0.5
    return {
        # fused input projection, out by in: rows [z, x, B, C, dt].  The
        # model width stays minor, so a TPU keeps an output width that is
        # no multiple of 128 (8512 for granite-4.0-h) in the layout the
        # matmul reads, with no relayout of the weights per call
        "w_in": (jax.random.normal(ks[0], (2 * d_inner + 2 * n + h, d_model))
                 * scale).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_width, d_inner + 2 * n))
                   * 0.1).astype(dtype),
        "conv_b": jnp.zeros((d_inner + 2 * n,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "w_out": (jax.random.normal(ks[2], (d_inner, d_model))
                  * d_inner ** -0.5).astype(dtype),
        "norm_w": jnp.ones((d_inner,), jnp.float32),
    }


def _mamba2_sizes(p):
    """(d_inner, heads, head size, d_state) of a Mamba2 block's weights."""
    d_inner = p["w_out"].shape[0]
    h = p["a_log"].shape[0]
    n = (p["w_in"].shape[0] - 2 * d_inner - h) // 2
    return d_inner, h, d_inner // h, n


def _mamba2_project(p, x, conv_state=None, n_valid=None):
    """Shared projection+conv for train/prefill/decode.

    x: (B, S, D).  Returns z, xs, bv, cv, dt and the new conv state: the
    last W-1 conv inputs, or with ``n_valid`` (B,) each row's last W-1
    inputs before its first padded position.
    """
    d_inner, h, _, n = _mamba2_sizes(p)

    zxbc = jnp.einsum("bsd,ed->bse", x, p["w_in"])
    z = zxbc[..., :d_inner]
    xbc = zxbc[..., d_inner : d_inner + d_inner + 2 * n]
    dt = zxbc[..., -h:]

    w = p["conv_w"].shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], w - 1, xbc.shape[-1]), xbc.dtype)
    else:
        pad = conv_state
    xbc_pad = jnp.concatenate([pad, xbc], axis=1)
    if n_valid is None:
        new_conv_state = xbc_pad[:, -(w - 1):, :]
    else:
        at = n_valid[:, None] + jnp.arange(w - 1)
        new_conv_state = jnp.take_along_axis(xbc_pad, at[..., None], axis=1)
    # causal depthwise conv via stacked shifts (w is small, 4)
    conv = sum(
        xbc_pad[:, i : i + xbc.shape[1], :] * p["conv_w"][i]
        for i in range(w)
    ) + p["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[..., :d_inner]
    bv = conv[..., d_inner : d_inner + n]
    cv = conv[..., d_inner + n :]
    return z, xs, bv, cv, dt, new_conv_state


def mamba2_forward(p, x, state=None, conv_state=None):
    """Full-sequence form. x: (B,S,D) -> (y, (ssm_state, conv_state))."""
    b, s, _ = x.shape
    _, h, pdim, _ = _mamba2_sizes(p)

    z, xs, bv, cv, dt, new_conv = _mamba2_project(p, x, conv_state)
    xs = xs.reshape(b, s, h, pdim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])  # (H,)
    decay = jnp.exp(a * dt)   # (B,S,H)

    n = bv.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, pdim, n), jnp.float32)

    def step(carry, inp):
        hst = carry
        x_t, b_t, c_t, dt_t, dec_t = inp
        # outer product update: h = dec*h + dt * x ⊗ B
        upd = jnp.einsum("bhp,bn->bhpn", x_t * dt_t[..., None], b_t)
        hst = hst * dec_t[..., None, None] + upd
        y_t = jnp.einsum("bhpn,bn->bhp", hst, c_t)
        return hst, y_t

    xs_t = jnp.moveaxis(xs.astype(jnp.float32), 1, 0)        # (S,B,H,P)
    bv_t = jnp.moveaxis(bv.astype(jnp.float32), 1, 0)        # (S,B,N)
    cv_t = jnp.moveaxis(cv.astype(jnp.float32), 1, 0)
    dt_t = jnp.moveaxis(dt, 1, 0)                            # (S,B,H)
    dec_t = jnp.moveaxis(decay, 1, 0)
    state, ys = jax.lax.scan(step, state, (xs_t, bv_t, cv_t, dt_t, dec_t))
    y = jnp.moveaxis(ys, 0, 1)                               # (B,S,H,P)
    return _mamba2_out(p, x, y, xs, z), (state, new_conv)


def _mamba2_out(p, x, y, xs, z):
    """D skip, gated RMSNorm (Mamba2 style) and the output projection of
    the scan's ``y`` (B,S,H,P)."""
    b, s = y.shape[:2]
    y = y + xs.astype(jnp.float32) * p["d_skip"][..., None]
    y = y.reshape(b, s, -1).astype(x.dtype)
    y32 = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y32 = y32 * jax.lax.rsqrt(
        jnp.mean(y32 * y32, axis=-1, keepdims=True) + 1e-5
    ) * p["norm_w"]
    out = jnp.einsum("bse,ed->bsd", y32.astype(x.dtype), p["w_out"])
    return shard(out, "batch", "seq", "embed")


def mamba2_forward_chunked(p, x, state=None, conv_state=None,
                           chunk: int = 512, n_valid=None):
    """Chunkwise SSD form (Mamba2 paper §6): O(L*chunk) memory, quadratic
    only within a chunk, exact same math as the per-step recurrence.

    Per head (scalar decay a, per-step dt): with lam_t = exp(a*dt_t),
    cum_t = sum_{j<=t} log lam_j (<= 0, so every exp below is stable):

      y_t   = Lam_t (C_t . H_0) + sum_{j<=t} e^{cum_t-cum_j} (C_t.B_j) u_j
      H_out = Lam_L H_0 + sum_j e^{cum_L-cum_j} u_j (x) B_j

    The per-step scan form (``mamba2_forward``) is kept as the oracle and
    decode path; backward through THIS form only stores per-chunk boundary
    states (the BPTT residuals of the step form — one (B,H,P,N) state per
    token — cannot fit HBM at 4k).

    ``n_valid`` (B,): a padded batch, row b holding ``n_valid[b]`` real
    positions.  A padded position gets dt = 0, so its decay is 1 and its
    update 0, and the state out is exactly the state at ``n_valid``; the
    conv state out is each row's last W-1 real inputs.  The chunk is the
    largest divisor of S not above ``chunk``.
    """
    b, s, _ = x.shape
    _, h, pdim, _ = _mamba2_sizes(p)

    z, xs, bv, cv, dt, new_conv = _mamba2_project(p, x, conv_state, n_valid)
    xs = xs.reshape(b, s, h, pdim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    if n_valid is not None:
        real = jnp.arange(s)[None, :] < n_valid[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"])
    log_lam = a * dt                                              # (B,S,H) <=0

    n = bv.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, pdim, n), jnp.float32)

    nc = -(-s // chunk)
    while s % nc:
        nc += 1
    c = s // nc

    u = (xs.astype(jnp.float32) * dt[..., None])                  # (B,S,H,P)
    ug = jnp.moveaxis(u.reshape(b, nc, c, h, pdim), 1, 0)
    bg = jnp.moveaxis(bv.astype(jnp.float32).reshape(b, nc, c, n), 1, 0)
    cg = jnp.moveaxis(cv.astype(jnp.float32).reshape(b, nc, c, n), 1, 0)
    lg = jnp.moveaxis(log_lam.reshape(b, nc, c, h), 1, 0)

    @jax.checkpoint
    def one_chunk(hst, inp):
        u_c, b_c, c_c, l_c = inp
        cum = jnp.cumsum(l_c, axis=1)                             # (B,c,H)
        lam = jnp.exp(cum)
        # intra-chunk decay-weighted "attention": (B,H,c,c).  The exponent
        # is positive (-> inf) in the masked upper triangle; clamp it with
        # a where BEFORE exp or the backward pass turns 0*inf into NaN.
        expo = cum[:, :, None, :] - cum[:, None, :, :]            # t,j
        causal = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]
        ratio = jnp.exp(jnp.where(causal, expo, 0.0))
        cb = jnp.einsum("btn,bjn->btj", c_c, b_c)                 # (B,c,c)
        g = jnp.where(causal, cb[..., None] * ratio, 0.0)
        y_intra = jnp.einsum("btjh,bjhp->bthp", g, u_c)
        y_inter = lam[..., None] * jnp.einsum("btn,bhpn->bthp", c_c, hst)
        # chunk-final state
        wj = jnp.exp(cum[:, -1:, :] - cum)                        # (B,c,H)
        upd = jnp.einsum("bjhp,bjn,bjh->bhpn", u_c, b_c, wj)
        hst = hst * jnp.exp(cum[:, -1])[..., None, None] + upd
        return hst, y_intra + y_inter

    state, ys = jax.lax.scan(one_chunk, state, (ug, bg, cg, lg))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, pdim)
    return _mamba2_out(p, x, y, xs, z), (state, new_conv)


def mamba2_decode(p, x1, state, conv_state):
    """One-token decode. x1: (B,1,D)."""
    return mamba2_forward(p, x1, state=state, conv_state=conv_state)


# -------------------------------------------------------------------- mlstm


def init_mlstm(key, d_model: int, n_heads: int, head_dim: int, dtype):
    ks = jax.random.split(key, 6)
    scale = d_model ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads, head_dim)) * scale
               ).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_heads, head_dim)) * scale
               ).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_heads, head_dim)) * scale
               ).astype(dtype),
        "w_if": (jax.random.normal(ks[3], (d_model, n_heads, 2)) * scale
                 ).astype(jnp.float32),
        "b_if": jnp.array([[0.0, 3.0]] * n_heads, jnp.float32),  # forget open
        "wo": (jax.random.normal(ks[4], (n_heads, head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
        "norm_w": jnp.ones((n_heads, head_dim), jnp.float32),
    }


def _mlstm_gates(p, x):
    g = jnp.einsum("bsd,dhg->bshg", x.astype(jnp.float32), p["w_if"]) + p["b_if"]
    i_raw = g[..., 0]                                  # (B,S,H)
    log_f = -jax.nn.softplus(-g[..., 1])               # log sigmoid
    return i_raw, log_f


def mlstm_parallel(p, x):
    """Parallel (training/prefill) form: decay-masked attention.

    h_i = sum_{j<=i} exp(D_ij - m_i) (q_i.k_j/sqrt(d)) v_j / n_i
    D_ij = cumsum(log_f)_i - cumsum(log_f)_j + i_raw_j
    """
    b, s, d_model = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "heads", "head_dim")
    v = shard(v, "batch", "seq", "heads", "head_dim")
    hd = q.shape[-1]
    i_raw, log_f = _mlstm_gates(p, x)
    fcum = jnp.cumsum(log_f, axis=1)                   # (B,S,H)
    dmat = fcum[:, :, None, :] - fcum[:, None, :, :] + i_raw[:, None, :, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    dmat = jnp.where(causal[None, :, :, None], dmat, -jnp.inf)  # (B,S,S,H)
    m = jnp.max(dmat, axis=2, keepdims=True)           # (B,S,1,H)
    dexp = jnp.exp(dmat - m)
    scores = jnp.einsum("bshk,bthk->bsth", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd) * dexp
    norm = jnp.maximum(
        jnp.abs(scores.sum(axis=2)), jnp.exp(-m[:, :, 0, :])
    )                                                   # (B,S,H)
    hvec = jnp.einsum("bsth,bthk->bshk", scores.astype(x.dtype), v)
    hvec = hvec / norm[..., None].astype(x.dtype)
    hvec = rms_head_norm(hvec, p["norm_w"])
    out = jnp.einsum("bshk,hkd->bsd", hvec, p["wo"])
    return shard(out, "batch", "seq", "embed")


def rms_head_norm(h, w):
    h32 = h.astype(jnp.float32)
    y = h32 * jax.lax.rsqrt(jnp.mean(h32 * h32, axis=-1, keepdims=True) + 1e-5)
    return (y * w).astype(h.dtype)


def mlstm_forward(p, x, state=None):
    """Recurrent full-sequence form: lax.scan of the stabilized step.

    Linear in S with O(H * hd^2) state — the form used for long sequences
    (training at 4k and prefill at 32k+); ``mlstm_parallel`` is its
    quadratic-memory equivalent kept for short sequences and as the oracle
    in the equivalence property test.
    Returns (y (B,S,D), final_state).
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    hd = q.shape[-1]
    i_raw, log_f = _mlstm_gates(p, x)
    if state is None:
        state = mlstm_init_state(p, b)

    def step(carry, inp):
        c, n, m = carry
        q_t, k_t, v_t, i_t, f_t = inp
        m_new = jnp.maximum(f_t + m, i_t)
        alpha = jnp.exp(f_t + m - m_new)
        beta = jnp.exp(i_t - m_new)
        kf = k_t.astype(jnp.float32) / math.sqrt(hd)
        c = c * alpha[..., None, None] + beta[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", kf, v_t.astype(jnp.float32)
        )
        n = n * alpha[..., None] + beta[..., None] * kf
        num = jnp.einsum("bhk,bhkv->bhv", q_t.astype(jnp.float32), c)
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhk,bhk->bh", q_t.astype(jnp.float32), n)),
            jnp.exp(-m_new),
        )
        return (c, n, m_new), (num / den[..., None])

    mv = lambda a: jnp.moveaxis(a, 1, 0)
    state, hs = jax.lax.scan(
        step, state, (mv(q), mv(k), mv(v), mv(i_raw), mv(log_f))
    )
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    h = rms_head_norm(h, p["norm_w"])
    out = jnp.einsum("bshk,hkd->bsd", h, p["wo"])
    return shard(out, "batch", "seq", "embed"), state


def mlstm_forward_chunked(p, x, state=None, chunk: int = 256):
    """Chunkwise-parallel mLSTM (the xLSTM training form): quadratic only
    within a chunk, recurrent state handed across chunks; exactly equal to
    the per-step recurrence (``mlstm_forward``) but BPTT-feasible — the
    step form would store a (B,H,hd,hd) matrix state per TOKEN in backward.

    Stabilized like the paper's App. formulas: with F_t = cumsum(log f),
    D_tj = F_t - F_j + i_j (j<=t), m_t = max(F_t + m0, max_j D_tj):

      num_t = e^{F_t+m0-m_t} (q_t.C0) + sum_j e^{D_tj-m_t} (q_t.k_j/√d) v_j
      den_t = max(|e^{F_t+m0-m_t} (q_t.n0) + sum_j e^{D_tj-m_t} (q_t.k_j/√d)|,
                  e^{-m_t})
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    hd = q.shape[-1]
    i_raw, log_f = _mlstm_gates(p, x)
    if state is None:
        state = mlstm_init_state(p, b)

    c = s // max(1, s // min(chunk, s))
    while s % c:
        c += 1
    nc = s // c
    mv = lambda a: jnp.moveaxis(
        a.reshape(b, nc, c, *a.shape[2:]), 1, 0
    )
    # only k carries the 1/sqrt(d) scale (matching the recurrent form,
    # where C accumulates k/sqrt(d) (x) v and q contracts unscaled)
    qg, kg, vg = mv(q.astype(jnp.float32)), \
        mv(k.astype(jnp.float32) / math.sqrt(hd)), mv(v.astype(jnp.float32))
    ig, fg = mv(i_raw), mv(log_f)

    @jax.checkpoint
    def one_chunk(carry, inp):
        c0, n0, m0 = carry
        q_c, k_c, v_c, i_c, f_c = inp       # (B,c,H,hd) / (B,c,H)
        fcum = jnp.cumsum(f_c, axis=1)      # F_t
        d = fcum[:, :, None, :] - fcum[:, None, :, :] + i_c[:, None, :, :]
        causal = jnp.tril(jnp.ones((c, c), bool))
        d = jnp.where(causal[None, :, :, None], d, -jnp.inf)  # (B,t,j,H)
        m_intra = jnp.max(d, axis=2)                          # (B,t,H)
        m_inter = fcum + m0[:, None, :]
        m_t = jnp.maximum(m_intra, m_inter)
        w = jnp.exp(d - m_t[:, :, None, :])                   # (B,t,j,H)
        inter = jnp.exp(m_inter - m_t)                        # (B,t,H)

        qk = jnp.einsum("bthk,bjhk->btjh", q_c, k_c)
        num = jnp.einsum("btjh,btjh,bjhk->bthk", qk, w, v_c) + inter[
            ..., None
        ] * jnp.einsum("bthk,bhkv->bthv", q_c, c0)
        den_sum = jnp.einsum("btjh,btjh->bth", qk, w) + inter * jnp.einsum(
            "bthk,bhk->bth", q_c, n0
        )
        den = jnp.maximum(jnp.abs(den_sum), jnp.exp(-m_t))
        h_c = num / den[..., None]

        # chunk-final state (t = L)
        m_new = m_t[:, -1]
        wj = jnp.exp(fcum[:, -1:, :] - fcum + i_c - m_new[:, None, :])
        c_new = jnp.exp(m_inter[:, -1] - m_new)[..., None, None] * c0 + \
            jnp.einsum("bjh,bjhk,bjhv->bhkv", wj, k_c, v_c)
        n_new = jnp.exp(m_inter[:, -1] - m_new)[..., None] * n0 + \
            jnp.einsum("bjh,bjhk->bhk", wj, k_c)
        return (c_new, n_new, m_new), h_c

    state, hs = jax.lax.scan(one_chunk, state, (qg, kg, vg, ig, fg))
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, *hs.shape[3:]).astype(x.dtype)
    h = rms_head_norm(h, p["norm_w"])
    out = jnp.einsum("bshk,hkd->bsd", h, p["wo"])
    return shard(out, "batch", "seq", "embed"), state


def mlstm_init_state(p, batch: int):
    n_heads, hd = p["norm_w"].shape
    return (
        jnp.zeros((batch, n_heads, hd, hd), jnp.float32),  # C
        jnp.zeros((batch, n_heads, hd), jnp.float32),      # n
        jnp.full((batch, n_heads), -1e30, jnp.float32),    # m (running max)
    )


def mlstm_decode(p, x1, state):
    """One-token recurrent step.  x1: (B,1,D)."""
    c, n, m = state
    b = x1.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x1, p["wq"])[:, 0]
    k = jnp.einsum("bsd,dhk->bshk", x1, p["wk"])[:, 0]
    v = jnp.einsum("bsd,dhk->bshk", x1, p["wv"])[:, 0]
    hd = q.shape[-1]
    i_raw, log_f = _mlstm_gates(p, x1)
    i_raw, log_f = i_raw[:, 0], log_f[:, 0]            # (B,H)
    m_new = jnp.maximum(log_f + m, i_raw)
    alpha = jnp.exp(log_f + m - m_new)
    beta = jnp.exp(i_raw - m_new)
    kf = k.astype(jnp.float32) / math.sqrt(hd)
    c = c * alpha[..., None, None] + beta[..., None, None] * jnp.einsum(
        "bhk,bhv->bhkv", kf, v.astype(jnp.float32)
    )
    n = n * alpha[..., None] + beta[..., None] * kf
    num = jnp.einsum("bhk,bhkv->bhv", q.astype(jnp.float32), c)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhk,bhk->bh", q.astype(jnp.float32), n)),
        jnp.exp(-m_new),
    )
    hvec = (num / den[..., None]).astype(x1.dtype)
    hvec = rms_head_norm(hvec, p["norm_w"])
    out = jnp.einsum("bhk,hkd->bd", hvec, p["wo"])[:, None, :]
    return out, (c, n, m_new)


# -------------------------------------------------------------------- slstm


def init_slstm(key, d_model: int, n_heads: int, head_dim: int, dtype):
    ks = jax.random.split(key, 4)
    scale = d_model ** -0.5
    return {
        # fused z,i,f,o input projections: (D, H, hd, 4)
        "w_in": (jax.random.normal(ks[0], (d_model, n_heads, head_dim, 4))
                 * scale).astype(dtype),
        # recurrent per-head projections (block-diagonal R): (H, hd, hd, 4)
        "r": (jax.random.normal(ks[1], (n_heads, head_dim, head_dim, 4))
              * head_dim ** -0.5).astype(jnp.float32),
        "b": jnp.zeros((n_heads, head_dim, 4), jnp.float32),
        "wo": (jax.random.normal(ks[2], (n_heads, head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
        "norm_w": jnp.ones((n_heads, head_dim), jnp.float32),
    }


def slstm_init_state(p, batch: int):
    n_heads, hd = p["norm_w"].shape
    z = jnp.zeros((batch, n_heads, hd), jnp.float32)
    return (z, z, z, jnp.full((batch, n_heads, hd), -1e30, jnp.float32))


def _slstm_step(p, carry, u_t):
    """u_t: (B,H,hd,4) pre-activations from the input projection."""
    c, n, h_prev, m = carry
    rec = jnp.einsum("bhk,hkjg->bhjg", h_prev, p["r"])
    pre = u_t + rec + p["b"]
    z = jnp.tanh(pre[..., 0])
    i_raw = pre[..., 1]
    log_f = -jax.nn.softplus(-pre[..., 2])             # sigmoid forget
    o = jax.nn.sigmoid(pre[..., 3])
    m_new = jnp.maximum(log_f + m, i_raw)
    alpha = jnp.exp(log_f + m - m_new)
    beta = jnp.exp(i_raw - m_new)
    c = alpha * c + beta * z
    n = alpha * n + beta
    h = o * c / jnp.maximum(n, 1e-6)
    return (c, n, h, m_new), h


def slstm_forward(p, x, state=None):
    """x: (B,S,D) -> (y, state); lax.scan over the sequence."""
    b, s, _ = x.shape
    u = jnp.einsum("bsd,dhkg->bshkg", x.astype(jnp.float32),
                   p["w_in"].astype(jnp.float32))
    if state is None:
        state = slstm_init_state(p, b)
    u_t = jnp.moveaxis(u, 1, 0)                        # (S,B,H,hd,4)
    state, hs = jax.lax.scan(
        lambda cr, ut: _slstm_step(p, cr, ut), state, u_t
    )
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)         # (B,S,H,hd)
    h = rms_head_norm(h, p["norm_w"])
    out = jnp.einsum("bshk,hkd->bsd", h, p["wo"])
    return shard(out, "batch", "seq", "embed"), state


def slstm_decode(p, x1, state):
    y, state = slstm_forward(p, x1, state)
    return y, state
