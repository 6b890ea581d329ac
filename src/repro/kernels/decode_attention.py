"""One decode step's GQA attention over the layer-stacked KV cache, as a
Pallas TPU kernel that reads each slot's live rows where they lie.

The serving engine's cache holds K and V as rows, ``(L, B, T, W)`` with
``W = n_kv * head_dim``, and the positions of those rows as ``kv_pos``
``(L, B, T)``.  For one layer (a scalar-prefetched index into ``L``), slot
``b`` attends only the rows ``[0, min(pos_b + 1, T))``, in blocks of
``block`` rows: on a full cache row = position, so rows past ``pos`` are
causally masked anyway; on a sliding-window ring every row is valid once
``pos >= T`` and none past ``pos`` before that.  Inside the blocks it reads,
the masking rule is ``gqa_attention``'s: ``kv_pos >= 0``, ``kv_pos <=
pos`` and, with a window, ``kv_pos > pos - window``.

Grouped heads without lane slicing: each query head is zero outside its KV
group's ``head_dim`` columns of the row, so one ``(nh, W) x (W, rows)``
product gives every head's scores and one ``(nh, rows) x (rows, W)``
product every head's output, of which each head keeps its group's
columns.  A 640-wide row (head_dim 80) is then never cut at a lane that is
not a multiple of 128.  The kernel spreads the ``(nh, head_dim)`` queries
over the row and gathers each head's columns back with products by a 0/1
``(head_dim, W)`` matrix, exact since each sum holds one nonzero term, so
that XLA runs no tiling or gather around each call.

Grid ``(B,)``, sequential: step ``b`` loops over slot ``b``'s live blocks,
DMAing K and V blocks from HBM into two VMEM buffers; the block after the
last of slot ``b`` is slot ``b + 1``'s first, so the copies stream across
slots with no gap.  Scores and the online softmax are float32, and P.V
accumulates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the score a masked row gets (``gqa_attention``'s), finite so that a
#: block with no valid row leaves the running maximum finite
MASKED = -1e30


def block_rows(t: int) -> int:
    """The rows a block holds for a cache of ``t`` rows: the largest of
    512, 256 and 128 that divides ``t``, else ``t`` (one block)."""
    return next((tb for tb in (512, 256, 128) if t % tb == 0), t)


def live_blocks(pos, t: int, block: int, xp=jnp):
    """The blocks a slot at position ``pos`` reads:
    ``ceil(min(pos + 1, t) / block)``, at least one.  ``xp`` is ``numpy``
    for the host's count of the rows read."""
    rows = xp.minimum(pos + 1, t)
    return xp.maximum((rows + block - 1) // block, 1)


def _kernel(layer_ref, pos_ref,                      # scalar prefetch
            q_ref, spread_ref, group_ref, kp_ref, k_hbm, v_hbm,  # inputs
            o_ref,                                   # output
            kbuf, vbuf, sem, seen_ref, m_ref, l_ref, acc_ref,  # scratch
            *, block: int, t: int, window: int, scale: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]

    def copies(slot, j, buf):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, slot, rows],
                                      kbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, slot, rows],
                                      vbuf.at[buf], sem.at[1, buf]))

    @pl.when(b == 0)
    def _first():
        seen_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    pos = pos_ref[b]
    n = live_blocks(pos, t, block)
    first = seen_ref[0]           # blocks of the slots before this one
    m_ref[...] = jnp.full_like(m_ref, MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    spread = spread_ref[...]      # (hd, W): the identity once a group
    group = group_ref[...]        # (nh, W): 1 in each head's group columns
    q = (jnp.dot(q_ref[0], spread, preferred_element_type=jnp.float32)
         * group).astype(spread.dtype)

    def step(j, carry):
        buf = (first + j) % 2

        @pl.when(j + 1 < n)
        def _next_block():
            for c in copies(b, j + 1, 1 - buf):
                c.start()

        @pl.when((j + 1 == n) & (b + 1 < n_slots))
        def _next_slot():
            for c in copies(b + 1, 0, 1 - buf):
                c.start()

        for c in copies(b, j, buf):
            c.wait()
        k = kbuf[buf]
        v = vbuf[buf]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                         # (nh, block)
        # slot b's positions: a dynamic row of the (B, block) tile, picked
        # by a select, since Mosaic loads no unaligned dynamic sublane
        kp = kp_ref[:, pl.ds(pl.multiple_of(j * block, block), block)]
        row = jax.lax.broadcasted_iota(jnp.int32, kp.shape, 0)
        kp = jnp.max(jnp.where(row == b, kp, jnp.iinfo(jnp.int32).min),
                     axis=0, keepdims=True)               # (1, block)
        valid = (kp >= 0) & (kp <= pos)
        if window:
            valid &= kp > pos - window
        s = jnp.where(valid, s, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n, step, 0)
    seen_ref[0] = first + n
    o = (acc_ref[...] / l_ref[...] * group).astype(o_ref.dtype)
    o_ref[0] = jax.lax.dot_general(
        o, spread, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)                                 # (nh, hd)


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "block", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_pos, layer, pos, *,
                     scale: float, window: int = 0, block: int = 0,
                     interpret: bool = False):
    """Attention of one query token a slot over the live rows of layer
    ``layer`` of the stacked cache.

    ``q``: (B, nh, hd); ``k_cache``/``v_cache``: (L, B, T, n_kv*hd);
    ``kv_pos``: (L, B, T); ``layer``: int32 scalar; ``pos``: (B,) the
    query's position.  ``block`` 0 takes ``block_rows(T)``.  Returns
    (B, nh, hd) in ``q``'s dtype.
    """
    bsz, nh, hd = q.shape
    _, _, t, width = k_cache.shape
    n_kv = width // hd
    block = block or block_rows(t)
    if t % block:
        raise ValueError(f"a block of {block} rows does not tile {t}")
    spread = np.tile(np.eye(hd), (1, n_kv))
    group = np.arange(width)[None, :] // hd == np.arange(nh)[:, None] // (
        nh // n_kv)
    kernel = functools.partial(_kernel, block=block, t=t, window=window,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz,),
            in_specs=[
                pl.BlockSpec((1, nh, hd), lambda b, li, p: (b, 0, 0)),
                pl.BlockSpec((hd, width), lambda b, li, p: (0, 0)),
                pl.BlockSpec((nh, width), lambda b, li, p: (0, 0)),
                pl.BlockSpec((None, bsz, t), lambda b, li, p: (li[0], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, nh, hd), lambda b, li, p: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), k_cache.dtype),
                pltpu.VMEM((2, block, width), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((nh, 1), jnp.float32),
                pltpu.VMEM((nh, 1), jnp.float32),
                pltpu.VMEM((nh, width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, nh, hd), k_cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      q.astype(k_cache.dtype), jnp.asarray(spread, k_cache.dtype),
      jnp.asarray(group, k_cache.dtype), kv_pos, k_cache, v_cache)
    return out.astype(q.dtype)
