"""Paged GQA decode attention — Pallas TPU kernel.

The serving hot-spot of a vLLM-style engine: one new query token per
sequence attends to that sequence's KV cache, which lives in a PAGED pool
(pages of ``block_size`` tokens) indexed by a per-sequence block table.
This is the TPU adaptation of vLLM's PagedAttention (DESIGN.md §3): instead
of GPU pointer-chasing, the grid walks the block table via scalar prefetch
and DMAs (page, kv_head)-tiles HBM->VMEM, accumulating an online softmax
over pages.

Layouts (token-major pages, MXU/VPU aligned: page tiles are
(block_size, head_dim) with head_dim in {64, 80, 128, 256}):

  q:            (B, n_kv, qpk, hd)   qpk = q heads per kv head
  k_pages:      (n_pages, block_size, n_kv, hd)
  v_pages:      (n_pages, block_size, n_kv, hd)
  block_tables: (B, max_pages) int32  (entries beyond the length clamped 0)
  lengths:      (B,) int32            context length per sequence
  out:          (B, n_kv, qpk, hd)

Grid: (B, n_kv, max_pages); the page axis is 'arbitrary' (sequential) so
the m/l/acc scratch carries across pages; the output block is revisited and
written once on the final page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    # scalar-prefetch operands
    block_tables_ref,   # (B, max_pages) int32, SMEM
    lengths_ref,        # (B,) int32, SMEM
    # array operands (VMEM tiles per BlockSpec)
    q_ref,              # (1, 1, qpk, hd)
    k_ref,              # (1, block_size, 1, hd)
    v_ref,              # (1, block_size, 1, hd)
    o_ref,              # (1, 1, qpk, hd)
    # scratch
    m_ref,              # (qpk, 1) f32
    l_ref,              # (qpk, 1) f32
    acc_ref,            # (qpk, hd) f32
    *,
    block_size: int,
    max_pages: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (qpk, hd)
        k = k_ref[0, :, 0].astype(jnp.float32)         # (bs, hd)
        v = v_ref[0, :, 0].astype(jnp.float32)         # (bs, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # (qpk, bs)
        token_ids = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        )
        s = jnp.where(token_ids < length, s, -jnp.inf)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)                 # (qpk,)
        p = jnp.exp(s - m_new[:, None])                 # (qpk, bs)
        l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(j == max_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "interpret")
)
def paged_attention(
    q,              # (B, n_kv, qpk, hd), already scaled by hd**-0.5
    k_pages,        # (n_pages, block_size, n_kv, hd)
    v_pages,
    block_tables,   # (B, max_pages) int32
    lengths,        # (B,) int32
    *,
    block_size: int = 16,
    interpret: bool = False,
):
    b, n_kv, qpk, hd = q.shape
    max_pages = block_tables.shape[1]
    # clamp table entries so masked-out pages still index a real page
    tables = jnp.clip(block_tables, 0, k_pages.shape[0] - 1).astype(jnp.int32)

    grid = (b, n_kv, max_pages)

    def q_map(b_, h_, j_, tables_, lengths_):
        return (b_, h_, 0, 0)

    def kv_map(b_, h_, j_, tables_, lengths_):
        return (tables_[b_, j_], 0, h_, 0)

    def o_map(b_, h_, j_, tables_, lengths_):
        return (b_, h_, 0, 0)

    kernel = functools.partial(
        _kernel, block_size=block_size, max_pages=max_pages
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, qpk, hd), q_map),
                pl.BlockSpec((1, block_size, 1, hd), kv_map),
                pl.BlockSpec((1, block_size, 1, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, qpk, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((qpk, 1), jnp.float32),
                pltpu.VMEM((qpk, 1), jnp.float32),
                pltpu.VMEM((qpk, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tables, lengths.astype(jnp.int32), q, k_pages, v_pages)
    return out
