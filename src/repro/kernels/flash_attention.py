"""Causal (optionally sliding-window) GQA flash attention — Pallas TPU
kernel for the prefill path.

Standard two-level online-softmax tiling adapted to the TPU memory
hierarchy: q tiles of (block_q, hd) stay resident in VMEM while (block_k,
hd) K/V tiles stream in; the kv-block grid axis is sequential ('arbitrary')
so m/l/acc scratch carries across kv tiles; causal (and SWA) tiles that
cannot contribute are skipped entirely with pl.when — for window W the work
drops from O(S^2) to O(S*W), which is what makes the dense archs' long-
context serving variant honest (DESIGN.md §4).

Layouts:
  q: (B, nh, S, hd) -> grid (B, nh, S/bq, S/bk)
  k/v: (B, n_kv, S, hd), kv head = q head // qpk
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    q_ref,   # (1, 1, bq, hd)
    k_ref,   # (1, 1, bk, hd)
    v_ref,   # (1, 1, bk, hd)
    o_ref,   # (1, 1, bq, hd)
    m_ref,   # (bq, 1)
    l_ref,   # (bq, 1)
    acc_ref, # (bq, hd)
    *,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    window: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # causal: this kv block contributes iff k_start <= q_end
    in_causal = k_start <= q_start + block_q - 1
    # SWA: skip blocks entirely left of every query's window
    in_window = (window == 0) | (k_start + block_k - 1 > q_start - window)

    @pl.when(in_causal & in_window)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (bq, bk)
        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_ids <= q_ids
        if window:
            mask &= k_ids > q_ids - window
        s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # rows with all -inf (fully masked) keep m = -inf; guard exp
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(
            jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0
        )
        p = jnp.where(
            jnp.isfinite(s), jnp.exp(s - safe_m[:, None]), 0.0
        )
        l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q,   # (B, nh, S, hd), pre-scaled by hd**-0.5
    k,   # (B, n_kv, S, hd)
    v,
    *,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    b, nh, s, hd = q.shape
    n_kv = k.shape[1]
    qpk = nh // n_kv
    if s % block_q or s % block_k:
        raise ValueError(f"S={s} must be divisible by block sizes")
    nq, nk = s // block_q, s // block_k

    grid = (b, nh, nq, nk)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_kv_blocks=nk,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b_, h_, iq_, ik_: (b_, h_, iq_, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b_, h_, iq_, ik_: (b_, h_ // qpk, ik_, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b_, h_, iq_, ik_: (b_, h_ // qpk, ik_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b_, h_, iq_, ik_: (b_, h_, iq_, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out
