"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to False: the kernels compile to Mosaic for the
TPU.  Callers on a backend without Mosaic (the CPU tests) pass
``interpret=True`` explicitly.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged


def paged_gqa_decode(
    q,              # (B, nh, hd) one query token per sequence
    k_pages,        # (P, block_size, n_kv, hd)
    v_pages,
    block_tables,   # (B, max_pages) int32
    lengths,        # (B,) int32
    *,
    block_size: int = 16,
    interpret: bool = False,
):
    """Paged decode attention; returns (B, nh, hd)."""
    b, nh, hd = q.shape
    n_kv = k_pages.shape[2]
    qpk = nh // n_kv
    qg = (q * hd ** -0.5).reshape(b, n_kv, qpk, hd)
    out = _paged(
        qg, k_pages, v_pages, block_tables, lengths,
        block_size=block_size,
        interpret=interpret,
    )
    return out.reshape(b, nh, hd)


def flash_prefill(
    q,   # (B, S, nh, hd)
    k,   # (B, S, n_kv, hd)
    v,
    *,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Causal (optionally SWA) prefill attention; returns (B, S, nh, hd)."""
    hd = q.shape[-1]
    qt = jnp.swapaxes(q * hd ** -0.5, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(
        qt, kt, vt,
        window=window, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)
