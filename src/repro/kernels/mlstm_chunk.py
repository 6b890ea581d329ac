"""Chunkwise mLSTM sequence mixer — Pallas TPU kernel.

The training hot-spot of the xLSTM architecture (xlstm-350m in the assigned
pool): the matrix-memory recurrence

    C_t = f_t C_{t-1} + i_t (k_t/√d) v_tᵀ ,  h_t = (q_t·C_t) / max(|q_t·n_t|, e^{-m_t})

computed in its chunkwise-parallel form (quadratic only within a chunk,
O(hd²) recurrent state handed across chunks).  TPU mapping: the chunk axis
is a SEQUENTIAL grid dimension; the (hd, hd) matrix state C, the normalizer
n and the stabilizer m live in VMEM scratch across grid steps — the same
carried-accumulator pattern as flash attention, but the carry is the
model's recurrent state rather than softmax statistics.  All intra-chunk
math is (c × c) and (c × hd) MXU work.

Layouts:
  q, k, v: (B, H, S, hd)   i_raw, log_f: (B, H, S)
  out:     (B, H, S, hd)
  Grid (B, H, S/c) with the chunk axis 'arbitrary' (sequential).

The pure-jnp oracle is ``repro.models.ssm.mlstm_forward`` (the exact
per-step recurrence); equivalence of the chunkwise math is additionally
property-tested at the model level (tests/test_model_consistency.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    q_ref,    # (1, 1, c, hd)
    k_ref,    # (1, 1, c, hd)
    v_ref,    # (1, 1, c, hd)
    i_ref,    # (1, 1, c)
    f_ref,    # (1, 1, c)
    o_ref,    # (1, 1, c, hd)
    c_state,  # (hd, hd) f32 scratch
    n_state,  # (1, hd)  f32 scratch
    m_state,  # (1, 1)   f32 scratch
    *,
    chunk: int,
    scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        c_state[...] = jnp.zeros_like(c_state)
        n_state[...] = jnp.zeros_like(n_state)
        m_state[...] = jnp.full_like(m_state, -1e30)

    q = q_ref[0, 0].astype(jnp.float32)                  # (c, hd)
    k = k_ref[0, 0].astype(jnp.float32) * scale
    v = v_ref[0, 0].astype(jnp.float32)
    i_raw = i_ref[0, 0].astype(jnp.float32)              # (c,)
    log_f = f_ref[0, 0].astype(jnp.float32)

    m0 = m_state[0, 0]
    fcum = jnp.cumsum(log_f)                             # F_t
    # D_tj = F_t - F_j + i_j   (j <= t), else -inf
    d = fcum[:, None] - fcum[None, :] + i_raw[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    d = jnp.where(causal, d, -jnp.inf)
    m_intra = jnp.max(d, axis=1)                         # (c,)
    m_inter = fcum + m0
    m_t = jnp.maximum(m_intra, m_inter)
    w = jnp.exp(d - m_t[:, None])                        # (c, c)
    inter = jnp.exp(m_inter - m_t)                       # (c,)

    qk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # (c, c)
    num = jax.lax.dot_general(
        qk * w, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + inter[:, None] * jax.lax.dot_general(
        q, c_state[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # (c, hd)
    den_sum = jnp.sum(qk * w, axis=1) + inter * jnp.sum(
        q * n_state[0][None, :], axis=1
    )
    den = jnp.maximum(jnp.abs(den_sum), jnp.exp(-m_t))
    o_ref[0, 0] = (num / den[:, None]).astype(o_ref.dtype)

    # chunk-final state handoff
    m_new = m_t[chunk - 1]
    wj = jnp.exp(fcum[chunk - 1] - fcum + i_raw - m_new)  # (c,)
    decay = jnp.exp(m_inter[chunk - 1] - m_new)
    c_state[...] = decay * c_state[...] + jax.lax.dot_general(
        k * wj[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    n_state[0, :] = decay * n_state[0, :] + jnp.sum(k * wj[:, None], axis=0)
    m_state[0, 0] = m_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk_kernel(
    q,       # (B, H, S, hd)
    k,
    v,
    i_raw,   # (B, H, S)
    log_f,   # (B, H, S)
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    b, h, s, hd = q.shape
    if s % chunk:
        raise ValueError(f"S={s} must be divisible by chunk={chunk}")
    nc = s // chunk
    grid = (b, h, nc)
    kernel = functools.partial(_kernel, chunk=chunk, scale=hd ** -0.5)
    qkv_spec = pl.BlockSpec((1, 1, chunk, hd),
                            lambda b_, h_, j_: (b_, h_, j_, 0))
    gate_spec = pl.BlockSpec((1, 1, chunk),
                             lambda b_, h_, j_: (b_, h_, j_))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, i_raw, log_f)
