"""Unified model zoo: one functional Model class covering all assigned
architecture families (dense GQA / SWA, MoE, VLM decoder, audio enc-dec,
xLSTM, Mamba2+shared-attention hybrid, Mamba2 and attention layers at
fixed positions with per-layer MLPs).

Design choices:
  * layers are STACKED and iterated with jax.lax.scan — the HLO contains one
    layer body regardless of depth, so compiles stay fast at published
    depths;
  * caches carry an explicit per-slot position tensor ``kv_pos`` (B, T);
    full caches and SWA ring buffers share one attention masking rule
    (valid = kv_pos >= 0, causal = kv_pos <= q_pos, window optional);
  * every cache leaf holds the layer axis first and the batch axis second
    (``(L, B, ...)``), recurrent state as well as K/V, so the engine's
    slot writes, gathers and swaps index ``[:, slot]`` for every kind;
  * every architecture exposes the same three entry points:
      forward(params, batch)           -> logits   (the consistency tests'
                                                    full-sequence pass)
      prefill(params, batch, cache_len)-> (logits, cache)   (serving)
      decode(params, cache, tokens, pos)-> (logits, cache)  (serving)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as decode_kernels
from repro.models import ssm
from repro.models.config import ModelConfig
from repro.models.layers import (
    AttnDims,
    apply_rope,
    attention_any,
    attention_out,
    attention_qkv,
    gated_mlp,
    gqa_attention,
    init_attention,
    init_mlp,
    init_moe,
    moe_mlp,
    rms_norm,
)
from repro.models.shardlib import active_rules, shard


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def kv_rows(kv):
    """(..., n_kv, head_dim) keys or values -> (..., n_kv*head_dim) cache
    rows.  The cache keeps rows, not heads: at the published widths the
    row is a multiple of 128 wide, so the TPU's (8,128) tiles pad nothing,
    where a 64- or 80-wide head pads to 128."""
    return kv.reshape(*kv.shape[:-2], kv.shape[-2] * kv.shape[-1])


def kv_heads(rows, n_kv: int):
    """(..., n_kv*head_dim) cache rows -> (..., n_kv, head_dim) heads."""
    return rows.reshape(*rows.shape[:-1], n_kv, rows.shape[-1] // n_kv)


def update_cache(cache_kv, new_kv, pos):
    """cache_kv: (B,T,F); new_kv: (B,S,F); pos: (B,) write offsets."""

    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p, 0))

    return jax.vmap(upd)(cache_kv, new_kv, pos)


def update_pos_masked(kv_pos, pos, s, lens):
    """Write positions ``arange(pos, pos+s)`` into each row of ``kv_pos``
    (B,T), with positions at or beyond a row's true length written as -1
    (invalid slot), so padded chunk tails never become attendable cache
    entries."""

    def upd(kp, p, ln):
        new = p + jnp.arange(s, dtype=kp.dtype)
        new = jnp.where(new < ln, new, jnp.array(-1, kp.dtype))
        return jax.lax.dynamic_update_slice(kp, new, (p,))

    return jax.vmap(upd)(kv_pos, pos, lens)


def decode_kernel(width: int):
    """The Pallas decode attention (``repro.kernels.decode_attention``) for
    cache rows ``width`` lanes wide, or None for the XLA path.  It engages
    on a TPU with no sharding rules installed, for rows a multiple of 128
    wide; the CPU, the mesh-sharded paths and prefill take
    ``gqa_attention``."""
    if (jax.default_backend() == "tpu" and active_rules() is None
            and width % 128 == 0):
        return decode_kernels.decode_attention
    return None


def attn_rows_read(width: int, pos, t: int) -> int:
    """The rows of one layer's K cache that decode attention reads for
    slots at positions ``pos`` (a host array of any shape) in a cache of
    ``t`` rows: each slot's live blocks with the kernel, all ``t`` rows on
    the XLA path."""
    pos = np.asarray(pos)
    if decode_kernel(width) is None:
        return pos.size * t
    block = decode_kernels.block_rows(t)
    return int(decode_kernels.live_blocks(pos, t, block, np).sum()) * block


def decode_attention(p_attn, h, pos, layer, k_cache, v_cache, kv_pos,
                     cfg: ModelConfig, ring: bool):
    """One decode step's self-attention at ``layer`` of the layer-stacked
    caches, which it updates in place.

    ``k_cache``/``v_cache``: (L,B,T,n_kv*head_dim); ``kv_pos``: (L,B,T).
    Each slot's new key, value and position land at ``(layer, slot,
    row)``, where ``row`` is ``pos`` on a full cache and ``pos % T`` on a
    sliding-window ring; the query then attends over ``layer``'s slice,
    through ``decode_kernel`` where it engages: it reads each slot's rows
    up to ``pos`` from the whole stacked cache as they lie.  Only B rows
    are written, so a layer scan that carries the caches keeps one buffer
    instead of rebuilding the stack every step.
    """
    b, t = kv_pos.shape[1], kv_pos.shape[2]
    q, k_new, v_new = attention_qkv(
        p_attn, h, pos[:, None], cfg.rope_theta, cfg.use_rope
    )
    at = (layer, jnp.arange(b), pos % t if ring else pos)
    # "clip" clamps a row past the cache as dynamic_update_slice would
    k_cache = k_cache.at[at].set(kv_rows(k_new[:, 0]).astype(k_cache.dtype),
                                 mode="clip")
    v_cache = v_cache.at[at].set(kv_rows(v_new[:, 0]).astype(v_cache.dtype),
                                 mode="clip")
    kv_pos = kv_pos.at[at].set(pos.astype(kv_pos.dtype), mode="clip")
    kernel = decode_kernel(k_cache.shape[-1])
    if kernel is not None:
        att = kernel(q[:, 0], k_cache, v_cache, kv_pos, layer, pos,
                     scale=cfg.qk_scale, window=cfg.sliding_window)
        return (attention_out(p_attn, att[:, None]), k_cache, v_cache,
                kv_pos)
    kp = kv_pos[layer]
    att = gqa_attention(
        q,
        kv_heads(k_cache[layer], cfg.n_kv_heads),
        kv_heads(v_cache[layer], cfg.n_kv_heads),
        window=cfg.sliding_window,
        q_positions=pos[:, None],
        kv_positions=kp,
        kv_valid=kp >= 0,
        scale=cfg.qk_scale,
    )
    return attention_out(p_attn, att), k_cache, v_cache, kv_pos


#: the SSD chunk of the ``mamba2_hybrid`` stack's chunked Mamba2 scan
#: (granite-4.0-h's ``mamba_chunk_size``); it tiles the scan and leaves
#: its result as it is
SSD_CHUNK = 256


def layer_pattern(layer_types) -> tuple[int, tuple]:
    """The period of a per-layer mixer list, and one period's runs of like
    layers: ``((kind, count, first layer, first layer of that kind), ...)``
    with both offsets counted from the period's start."""
    n = len(layer_types)
    period = next(p for p in range(1, n + 1) if n % p == 0 and all(
        layer_types[i] == layer_types[i % p] for i in range(n)))
    runs, seen = [], {}
    for i, kind in enumerate(layer_types[:period]):
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1) + runs[-1][2:]
        else:
            runs.append((kind, 1, i, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return period, tuple(runs)


def _put(stack, row, i):
    """``stack`` with row ``i`` (traced) replaced: a dynamic-update-slice,
    which XLA applies in place to a carried buffer."""
    return jax.lax.dynamic_update_index_in_dim(
        stack, row.astype(stack.dtype), i, 0
    )


def _take(tree, i):
    """Row ``i`` (traced) of every leaf of a layer-stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree
    )


# ===========================================================================
# dense / moe / vlm decoder blocks
# ===========================================================================


def init_dense_block(key, cfg: ModelConfig, dtype):
    ka, km = jax.random.split(key)
    dims = AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": init_attention(ka, cfg.d_model, dims, dtype),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.is_moe:
        p["moe"] = init_moe(km, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype)
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, dtype)
    return p


def dense_block_train(p, x, positions, cfg: ModelConfig, attn_mask_lens=None):
    """Full-sequence causal block (training / prefill compute).

    Returns (x, (k, v, moe_aux)) so prefill can collect the cache.
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = attention_qkv(p["attn"], h, positions, cfg.rope_theta, cfg.use_rope)
    kv_valid = None
    if attn_mask_lens is not None:
        t = x.shape[1]
        kv_valid = jnp.arange(t)[None, :] < attn_mask_lens[:, None]
    att = attention_any(
        q, k, v,
        window=cfg.sliding_window,
        q_positions=positions,
        kv_positions=positions,
        kv_valid=kv_valid,
        scale=cfg.qk_scale,
    )
    x = x + attention_out(p["attn"], att)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.float32(0.0)
    if cfg.is_moe:
        y, aux = moe_mlp(p["moe"], h2, top_k=cfg.top_k,
                         capacity_factor=cfg.capacity_factor)
    else:
        y = gated_mlp(p["mlp"], h2)
    return x + y, (k, v, aux)


def dense_block_chunk(p, x, pos, positions, lens, k_cache, v_cache, kv_pos,
                      cfg: ModelConfig):
    """S-token chunk step against a (non-ring) KV cache: the chunked-prefill
    generalization of ``dense_block_decode``.

    ``pos``: (B,) write offsets of the chunk; ``positions``: (B,S) absolute
    query positions (``pos + arange(S)``); ``lens``: (B,) true prompt
    lengths.  Chunk K/V is written into the cache first, then queries
    attend over the whole cache — the causal rule ``kv_pos <= q_pos`` masks
    future tokens *within* the chunk and ``kv_pos >= 0`` masks unwritten
    slots and padded tails, so the result matches full-sequence prefill.
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    att, k_cache, v_cache, kv_pos = attention_chunk(
        p["attn"], h, pos, positions, lens, k_cache, v_cache, kv_pos, cfg
    )
    x = x + att
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_mlp(p["moe"], h2, top_k=cfg.top_k,
                       capacity_factor=cfg.capacity_factor)
    else:
        y = gated_mlp(p["mlp"], h2)
    return x + y, k_cache, v_cache, kv_pos


def attention_chunk(p_attn, h, pos, positions, lens, k_cache, v_cache,
                    kv_pos, cfg: ModelConfig):
    """The self-attention of ``dense_block_chunk`` on the normed input
    ``h``: the chunk's K/V and lens-masked positions are written into the
    layer's cache, then its queries attend over the whole cache."""
    q, k_new, v_new = attention_qkv(
        p_attn, h, positions, cfg.rope_theta, cfg.use_rope
    )
    k_cache = update_cache(k_cache, kv_rows(k_new), pos)
    v_cache = update_cache(v_cache, kv_rows(v_new), pos)
    kv_pos = update_pos_masked(kv_pos, pos, h.shape[1], lens)
    att = attention_any(
        q,
        kv_heads(k_cache, cfg.n_kv_heads),
        kv_heads(v_cache, cfg.n_kv_heads),
        window=cfg.sliding_window,
        q_positions=positions,
        kv_positions=kv_pos,
        kv_valid=kv_pos >= 0,
        scale=cfg.qk_scale,
    )
    return attention_out(p_attn, att), k_cache, v_cache, kv_pos


def dense_block_decode(p, x, pos, layer, k_cache, v_cache, kv_pos,
                       cfg: ModelConfig, ring: bool):
    """One-token decode step of block ``layer`` against the layer-stacked
    (possibly ring) KV cache, updated in place (``decode_attention``)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    att, k_cache, v_cache, kv_pos = decode_attention(
        p["attn"], h, pos, layer, k_cache, v_cache, kv_pos, cfg, ring
    )
    x = x + att
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_mlp(p["moe"], h2, top_k=cfg.top_k,
                       capacity_factor=cfg.capacity_factor)
    else:
        y = gated_mlp(p["mlp"], h2)
    return x + y, k_cache, v_cache, kv_pos


# ===========================================================================
# the Model
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def ragged_prefill(self) -> bool:
        """``prefill_chunked`` takes a batch of prompts padded to one
        length, with ``lens``, and gives each row exactly what it gives the
        prompt alone (the engine then batches and buckets prefills)."""
        return self.cfg.kind in ("dense", "moe", "vlm", "mamba2_hybrid")

    @property
    def recurrent_state(self) -> bool:
        """The cache holds per-sequence recurrent state besides K/V."""
        return self.cfg.kind in ("ssm", "hybrid", "mamba2_hybrid")

    # ------------------------------------------------------------ init

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = _dtype(cfg)
        keys = jax.random.split(key, 8)
        params: dict[str, Any] = {
            "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model))
                      * 0.02).astype(dtype),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (
                jax.random.normal(keys[1], (cfg.d_model, cfg.vocab))
                * cfg.d_model ** -0.5
            ).astype(dtype)
        if cfg.position == "learned":
            params["pos_emb"] = (
                jax.random.normal(keys[2], (cfg.max_position, cfg.d_model))
                * 0.02
            ).astype(dtype)

        if cfg.kind in ("dense", "moe", "vlm"):
            lkeys = jax.random.split(keys[3], cfg.n_layers)
            params["blocks"] = jax.vmap(
                lambda k: init_dense_block(k, cfg, dtype)
            )(lkeys)
        elif cfg.kind == "encdec":
            ekeys = jax.random.split(keys[3], cfg.n_enc_layers)
            dkeys = jax.random.split(keys[4], cfg.n_layers)
            params["enc_blocks"] = jax.vmap(
                lambda k: init_dense_block(k, cfg, dtype)
            )(ekeys)
            params["dec_blocks"] = jax.vmap(
                lambda k: self._init_decoder_block(k, dtype)
            )(dkeys)
            params["enc_pos"] = (
                jax.random.normal(keys[5], (cfg.n_audio_frames, cfg.d_model))
                * 0.02
            ).astype(dtype)
            params["enc_final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        elif cfg.kind == "ssm":
            n_pairs = cfg.n_layers // cfg.slstm_every
            pkeys = jax.random.split(keys[3], n_pairs)
            params["xlstm_pairs"] = jax.vmap(
                lambda k: self._init_xlstm_pair(k, dtype)
            )(pkeys)
        elif cfg.kind == "hybrid":
            n_super = cfg.n_layers // cfg.attn_every
            mkeys = jax.random.split(keys[3], n_super)
            params["super_blocks"] = jax.vmap(
                lambda k: self._init_mamba_group(k, dtype)
            )(mkeys)
            # zamba2's single SHARED attention+MLP block
            params["shared_attn"] = init_dense_block(keys[4], cfg, dtype)
        elif cfg.kind == "mamba2_hybrid":
            n_mamba = cfg.layer_types.count("mamba")
            n_attn = cfg.layer_types.count("attention")
            dims = AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
            params["layers"] = jax.vmap(lambda k: {
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                "mlp": init_mlp(k, cfg.d_model, cfg.d_ff, dtype),
            })(jax.random.split(keys[3], cfg.n_layers))
            params["mamba"] = jax.vmap(lambda k: ssm.init_mamba2(
                k, cfg.d_model, cfg.ssm_state, cfg.conv_width, dtype
            ))(jax.random.split(keys[4], n_mamba))
            params["attn"] = jax.vmap(
                lambda k: init_attention(k, cfg.d_model, dims, dtype)
            )(jax.random.split(keys[5], n_attn))
        else:
            raise ValueError(f"unknown kind {cfg.kind}")
        return params

    def _init_decoder_block(self, key, dtype):
        cfg = self.cfg
        ka, kc, km = jax.random.split(key, 3)
        dims = AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
        return {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": init_attention(ka, cfg.d_model, dims, dtype),
            "ln_x": jnp.ones((cfg.d_model,), jnp.float32),
            "xattn": init_attention(kc, cfg.d_model, dims, dtype),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "mlp": init_mlp(km, cfg.d_model, cfg.d_ff, dtype),
        }

    def _init_xlstm_pair(self, key, dtype):
        cfg = self.cfg
        km, ks = jax.random.split(key)
        return {
            "ln_m": jnp.ones((cfg.d_model,), jnp.float32),
            "mlstm": ssm.init_mlstm(km, cfg.d_model, cfg.n_heads,
                                    cfg.head_dim, dtype),
            "ln_s": jnp.ones((cfg.d_model,), jnp.float32),
            "slstm": ssm.init_slstm(ks, cfg.d_model, cfg.n_heads,
                                    cfg.head_dim, dtype),
        }

    def _init_mamba_group(self, key, dtype):
        cfg = self.cfg
        gkeys = jax.random.split(key, cfg.attn_every)
        return {
            "ln": jnp.ones((cfg.attn_every, cfg.d_model), jnp.float32),
            "mamba": jax.vmap(
                lambda k: ssm.init_mamba2(k, cfg.d_model, cfg.ssm_state,
                                          cfg.conv_width, dtype)
            )(gkeys),
        }

    # ------------------------------------------------------------ embed

    def _embed(self, params, tokens, positions):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.position == "learned":
            x = x + jnp.take(params["pos_emb"], positions, axis=0)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        return shard(x, "batch", "seq", "embed")

    def head_matrix(self, params):
        return (
            params["embed"].T if self.cfg.tie_embeddings
            else params["lm_head"]
        )

    def _logits(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._head(params, x)

    def _head(self, params, x):
        logits = jnp.einsum("bsd,dv->bsv", x, self.head_matrix(params))
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        return shard(logits, "batch", "seq", "vocab")

    # ------------------------------------------------------------ forward

    def forward(self, params, batch: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence forward over the token positions: (logits, moe
        aux).  The consistency tests hold prefill and decode to it."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s_tok = tokens.shape
        aux = jnp.float32(0.0)

        if cfg.kind == "encdec":
            enc = batch["embeds"].astype(_dtype(cfg))
            enc = enc + params["enc_pos"][None, : enc.shape[1]]
            enc = self._run_encoder(params, enc)
            positions = jnp.broadcast_to(jnp.arange(s_tok)[None], (b, s_tok))
            x = self._embed(params, tokens, positions)
            x, aux = self._run_decoder(params, x, positions, enc)
        elif cfg.kind == "vlm" and "embeds" in batch:
            img = batch["embeds"].astype(_dtype(cfg))
            n_img = img.shape[1]
            positions = jnp.broadcast_to(
                jnp.arange(n_img + s_tok)[None], (b, n_img + s_tok)
            )
            x_tok = jnp.take(params["embed"], tokens, axis=0)
            x = jnp.concatenate([img, x_tok], axis=1)
            x = shard(x, "batch", "seq", "embed")
            x, aux = self._run_stack(params, x, positions)
            x = x[:, n_img:]
        else:
            positions = jnp.broadcast_to(jnp.arange(s_tok)[None], (b, s_tok))
            x = self._embed(params, tokens, positions)
            x, aux = self._run_stack(params, x, positions)
        return self._logits(params, x), aux

    def _run_stack(self, params, x, positions):
        cfg = self.cfg
        if cfg.kind in ("dense", "moe", "vlm"):
            def body(carry, lp):
                h, aux = carry
                h, (_, _, a) = dense_block_train(lp, h, positions, cfg)
                return (h, aux + a), None

            (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                       params["blocks"])
            return x, aux
        if cfg.kind == "ssm":
            def body(carry, lp):
                h = carry
                hm = rms_norm(h, lp["ln_m"], cfg.norm_eps)
                y, _ = ssm.mlstm_forward_chunked(lp["mlstm"], hm)
                h = h + y
                hs = rms_norm(h, lp["ln_s"], cfg.norm_eps)
                y2, _ = ssm.slstm_forward(lp["slstm"], hs)
                return h + y2, None

            x, _ = jax.lax.scan(body, x, params["xlstm_pairs"])
            return x, jnp.float32(0.0)
        if cfg.kind == "hybrid":
            shared = params["shared_attn"]

            def body(carry, lp):
                h = carry

                def mamba_one(hc, mp_ln):
                    mp, ln = mp_ln
                    hin = rms_norm(hc, ln, cfg.norm_eps)
                    y, _ = ssm.mamba2_forward_chunked(mp, hin)
                    return hc + y, None

                h, _ = jax.lax.scan(mamba_one, h, (lp["mamba"], lp["ln"]))
                h, _ = dense_block_train(shared, h, positions, cfg)[0], None
                return h, None

            x, _ = jax.lax.scan(body, x, params["super_blocks"])
            return x, jnp.float32(0.0)
        if cfg.kind == "mamba2_hybrid":
            def mixer(kind, w, h, idx, carry):
                if kind == "mamba":
                    y, _ = ssm.mamba2_forward_chunked(w, h, chunk=SSD_CHUNK)
                    return y, carry
                q, k, v = attention_qkv(w, h, positions, cfg.rope_theta,
                                        cfg.use_rope)
                att = attention_any(q, k, v, q_positions=positions,
                                    kv_positions=positions,
                                    scale=cfg.qk_scale)
                return attention_out(w, att), carry

            x, _ = self._hybrid_stack(params, x, None, mixer)
            return x, jnp.float32(0.0)
        raise ValueError(cfg.kind)

    def _hybrid_stack(self, params, x, carry, mixer):
        """The ``mamba2_hybrid`` layer stack: a scan over the periods of
        ``layer_types``, and inside one period a scan over each run of like
        layers, so the program holds one body per run, not one per layer.
        Each layer, with its own weights:

            x = x + r * mixer(rmsnorm(x) * ln1)      Mamba2 or attention
            x = x + r * mlp(rmsnorm(x) * ln2)        SwiGLU

        with r the residual multiplier.  ``mixer(kind, weights, h, index,
        carry) -> (y, carry)`` receives the layer's index among the layers
        of its kind: its row of ``params["mamba"]`` or ``params["attn"]``,
        and of the cache leaves the carry holds."""
        cfg = self.cfg
        period, runs = layer_pattern(cfg.layer_types)
        per = {k: cfg.layer_types[:period].count(k)
               for k in ("mamba", "attention")}
        weights = {"mamba": params.get("mamba"),
                   "attention": params.get("attn")}
        scope = {"mamba": "mamba2", "attention": "attention"}
        r = cfg.residual_multiplier

        def period_body(c, p):
            for kind, n, l0, k0 in runs:
                def layer(c, i, kind=kind, l0=l0, k0=k0):
                    x, carry = c
                    lp = _take(params["layers"], p * period + l0 + i)
                    idx = p * per[kind] + k0 + i
                    with jax.named_scope(scope[kind]):
                        y, carry = mixer(
                            kind, _take(weights[kind], idx),
                            rms_norm(x, lp["ln1"], cfg.norm_eps), idx, carry,
                        )
                    x = x + y * r
                    with jax.named_scope("mlp"):
                        y = gated_mlp(lp["mlp"],
                                      rms_norm(x, lp["ln2"], cfg.norm_eps))
                    return (x + y * r, carry), None

                c, _ = jax.lax.scan(layer, c, jnp.arange(n))
            return c, None

        (x, carry), _ = jax.lax.scan(
            period_body, (x, carry),
            jnp.arange(len(cfg.layer_types) // period),
        )
        return x, carry

    def _run_encoder(self, params, enc):
        cfg = self.cfg
        b, f, _ = enc.shape
        positions = jnp.broadcast_to(jnp.arange(f)[None], (b, f))

        def body(h, lp):
            # bidirectional: no causal mask -> use kv_valid trick with a
            # huge q_pos so every key passes the causal comparison
            hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
            q, k, v = attention_qkv(lp["attn"], hn, positions,
                                    cfg.rope_theta, False)
            att = gqa_attention(
                q, k, v,
                q_positions=jnp.full((b, f), f + 1, jnp.int32),
                kv_positions=positions,
            )
            h = h + attention_out(lp["attn"], att)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            return h + gated_mlp(lp["mlp"], h2), None

        enc, _ = jax.lax.scan(body, enc, params["enc_blocks"])
        return rms_norm(enc, params["enc_final_norm"], cfg.norm_eps)

    def _run_decoder(self, params, x, positions, enc):
        cfg = self.cfg
        b, f = enc.shape[0], enc.shape[1]
        enc_pos = jnp.broadcast_to(jnp.arange(f)[None], (b, f))

        def body(h, lp):
            hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
            q, k, v = attention_qkv(lp["attn"], hn, positions,
                                    cfg.rope_theta, cfg.use_rope)
            att = attention_any(q, k, v, q_positions=positions,
                                kv_positions=positions)
            h = h + attention_out(lp["attn"], att)
            hx = rms_norm(h, lp["ln_x"], cfg.norm_eps)
            qx, kx, vx = (
                jnp.einsum("bsd,dnh->bsnh", hx, lp["xattn"]["wq"]),
                jnp.einsum("bsd,dnh->bsnh", enc, lp["xattn"]["wk"]),
                jnp.einsum("bsd,dnh->bsnh", enc, lp["xattn"]["wv"]),
            )
            xat = attention_any(
                qx, kx, vx,
                q_positions=jnp.full_like(positions, f + 1),
                kv_positions=enc_pos,
            )
            h = h + attention_out(lp["xattn"], xat)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            return h + gated_mlp(lp["mlp"], h2), None

        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        return x, jnp.float32(0.0)

    # ------------------------------------------------------------ serve

    def init_cache(self, params, batch: int, cache_len: int) -> dict:
        """Allocate an empty decode cache (kv_pos = -1 -> invalid)."""
        cfg = self.cfg
        dtype = _dtype(cfg)
        t = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        kv = lambda n: jnp.zeros((n, batch, t, cfg.n_kv_heads * cfg.head_dim),
                                 dtype)
        pos = lambda n: jnp.full((n, batch, t), -1, jnp.int32)
        if cfg.kind in ("dense", "moe", "vlm"):
            return {"k": kv(cfg.n_layers), "v": kv(cfg.n_layers),
                    "kv_pos": pos(cfg.n_layers)}
        if cfg.kind == "encdec":
            nl = cfg.n_layers
            f = cfg.n_audio_frames
            cross = jnp.zeros((nl, batch, f, cfg.n_kv_heads, cfg.head_dim),
                              dtype)
            return {"k": kv(nl), "v": kv(nl), "kv_pos": pos(nl),
                    "cross_k": cross, "cross_v": cross,
                    "enc_len": jnp.zeros((batch,), jnp.int32)}
        if cfg.kind == "ssm":
            n_pairs = cfg.n_layers // cfg.slstm_every
            nh, hd = cfg.n_heads, cfg.head_dim
            z = lambda *s: jnp.zeros((n_pairs, batch, *s), jnp.float32)
            return {
                "mlstm_c": z(nh, hd, hd), "mlstm_n": z(nh, hd),
                "mlstm_m": jnp.full((n_pairs, batch, nh), -1e30, jnp.float32),
                "slstm_c": z(nh, hd), "slstm_n": z(nh, hd),
                "slstm_h": z(nh, hd),
                "slstm_m": jnp.full((n_pairs, batch, nh, hd), -1e30,
                                    jnp.float32),
            }
        if cfg.kind in ("hybrid", "mamba2_hybrid"):
            # one row of state per Mamba2 layer, one of K/V per attention
            if cfg.kind == "hybrid":
                n_mamba = n_attn = cfg.n_layers // cfg.attn_every
                n_mamba *= cfg.attn_every
            else:
                n_mamba = cfg.layer_types.count("mamba")
                n_attn = cfg.layer_types.count("attention")
            d_inner, pdim, h, n = ssm.mamba2_dims(cfg.d_model, cfg.ssm_state)
            return {
                "mamba_h": jnp.zeros((n_mamba, batch, h, pdim, n),
                                     jnp.float32),
                "mamba_conv": jnp.zeros(
                    (n_mamba, batch, cfg.conv_width - 1, d_inner + 2 * n),
                    dtype,
                ),
                "k": kv(n_attn), "v": kv(n_attn), "kv_pos": pos(n_attn),
            }
        raise ValueError(cfg.kind)

    def prefill(self, params, batch: dict, cache_len: int):
        """Process the full prompt; returns (last-position logits, cache).

        batch: {"tokens": (B,S), optional "embeds", optional "lens": (B,)}.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        lens = batch.get("lens", jnp.full((b,), s, jnp.int32))
        cache = self.init_cache(params, b, cache_len)

        if cfg.kind in ("dense", "moe", "vlm"):
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x = self._embed(params, tokens, positions)
            if cfg.kind == "vlm" and "embeds" in batch:
                img = batch["embeds"].astype(_dtype(cfg))
                x = jnp.concatenate([img, jnp.take(params["embed"], tokens,
                                                   axis=0)], axis=1)
                x = shard(x, "batch", "seq", "embed")
                s = x.shape[1]
                positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
                lens = lens + img.shape[1]  # prompt = image tokens + text

            def body(carry, lp):
                h = carry
                h, (k, v, _) = dense_block_train(lp, h, positions, cfg,
                                                 attn_mask_lens=lens)
                return h, (k, v)

            x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
            cache = self._fill_kv(cache, ks, vs, lens, s)
            logits = self._logits(params, _gather_last(x, lens))
            return logits, cache

        if cfg.kind == "encdec":
            enc = batch["embeds"].astype(_dtype(cfg))
            enc = enc + params["enc_pos"][None, : enc.shape[1]]
            enc = self._run_encoder(params, enc)
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x = self._embed(params, tokens, positions)
            f = enc.shape[1]
            enc_pos = jnp.broadcast_to(jnp.arange(f)[None], (b, f))

            def body(carry, lp):
                h = carry
                hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
                q, k, v = attention_qkv(lp["attn"], hn, positions,
                                        cfg.rope_theta, cfg.use_rope)
                att = attention_any(q, k, v, q_positions=positions,
                                    kv_positions=positions)
                h = h + attention_out(lp["attn"], att)
                hx = rms_norm(h, lp["ln_x"], cfg.norm_eps)
                kx = jnp.einsum("bsd,dnh->bsnh", enc, lp["xattn"]["wk"])
                vx = jnp.einsum("bsd,dnh->bsnh", enc, lp["xattn"]["wv"])
                qx = jnp.einsum("bsd,dnh->bsnh", hx, lp["xattn"]["wq"])
                xat = attention_any(
                    qx, kx, vx,
                    q_positions=jnp.full_like(positions, f + 1),
                    kv_positions=enc_pos,
                )
                h = h + attention_out(lp["xattn"], xat)
                h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
                return h + gated_mlp(lp["mlp"], h2), (k, v, kx, vx)

            x, (ks, vs, kxs, vxs) = jax.lax.scan(body, x,
                                                 params["dec_blocks"])
            cache = self._fill_kv(cache, ks, vs, lens, s)
            cache["cross_k"], cache["cross_v"] = kxs, vxs
            logits = self._logits(params, _gather_last(x, lens))
            return logits, cache

        if cfg.kind == "ssm":
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x = self._embed(params, tokens, positions)

            def body(carry, lp):
                h = carry
                hm = rms_norm(h, lp["ln_m"], cfg.norm_eps)
                y, m_state = ssm.mlstm_forward_chunked(lp["mlstm"], hm)
                h = h + y
                hs = rms_norm(h, lp["ln_s"], cfg.norm_eps)
                y2, sl_state = ssm.slstm_forward(lp["slstm"], hs)
                return h + y2, (m_state, sl_state)

            x, (m_states, sl_states) = jax.lax.scan(body, x,
                                                    params["xlstm_pairs"])
            cache["mlstm_c"], cache["mlstm_n"], cache["mlstm_m"] = m_states
            cache["slstm_c"], cache["slstm_n"] = sl_states[0], sl_states[1]
            cache["slstm_h"], cache["slstm_m"] = sl_states[2], sl_states[3]
            logits = self._logits(params, _gather_last(x, lens))
            return logits, cache

        if cfg.kind == "hybrid":
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x = self._embed(params, tokens, positions)
            shared = params["shared_attn"]

            def body(carry, lp):
                h = carry

                def mamba_one(hc, mp_ln):
                    mp, ln = mp_ln
                    hin = rms_norm(hc, ln, cfg.norm_eps)
                    y, st = ssm.mamba2_forward_chunked(mp, hin)
                    return hc + y, st

                h, m_states = jax.lax.scan(mamba_one, h,
                                           (lp["mamba"], lp["ln"]))
                h, (k, v, _) = dense_block_train(shared, h, positions, cfg,
                                                 attn_mask_lens=lens)
                return h, (m_states, k, v)

            x, (m_states, ks, vs) = jax.lax.scan(body, x,
                                                 params["super_blocks"])
            cache = self._fill_kv(cache, ks, vs, lens, s)
            # (n_super, attn_every, B, ...) -> the cache's (layer, B, ...)
            cache["mamba_h"], cache["mamba_conv"] = (
                st.reshape(-1, *st.shape[2:]) for st in m_states
            )
            logits = self._logits(params, _gather_last(x, lens))
            return logits, cache

        if cfg.kind == "mamba2_hybrid":
            return self._hybrid_prefill(params, tokens, lens, cache_len, s)

        raise ValueError(cfg.kind)

    def prefill_chunked(self, params, batch: dict, cache_len: int,
                        chunk: int):
        """Chunked prefill: process the prompt ``chunk`` tokens at a time.

        Same signature contract as :meth:`prefill` (returns last-position
        logits and a decode cache) but bounds per-step activation memory to
        ``B x chunk`` instead of ``B x S`` — the serving engine's
        ``prefill_chunk`` knob maps directly onto this, so long prompts are
        *actually* processed in chunk-sized slices rather than merely
        accounted as multiple iterations.

        ``mamba2_hybrid`` carries its SSM and conv state from slice to
        slice, and a padded position leaves a row's state as it was
        (``ssm.mamba2_forward_chunked``'s ``n_valid``), so a padded batch
        with ``lens`` prefills every row exactly.

        Falls back to the one-shot :meth:`prefill` when chunking cannot
        help or would change the result: prompts that fit in one chunk,
        the other recurrent families (ssm, the shared-block hybrid),
        encdec, MoE (GShard capacity routing is sequence-length dependent,
        so per-chunk capacities drop different tokens than one-shot),
        VLM image batches, and ring (sliding-window) caches smaller than
        the prompt.
        """
        cfg = self.cfg
        s = batch["tokens"].shape[1]
        if cfg.kind == "mamba2_hybrid":
            b = batch["tokens"].shape[0]
            lens = batch.get("lens", jnp.full((b,), s, jnp.int32))
            return self._hybrid_prefill(params, batch["tokens"], lens,
                                        cache_len, chunk)
        ring = bool(cfg.sliding_window) and min(
            cache_len, cfg.sliding_window
        ) < cache_len
        if (
            s <= chunk
            or cfg.kind not in ("dense", "vlm")
            or "embeds" in batch
            or ring
        ):
            return self.prefill(params, batch, cache_len=cache_len)

        tokens = batch["tokens"]
        b = tokens.shape[0]
        lens = batch.get("lens", jnp.full((b,), s, jnp.int32))
        cache = self.init_cache(params, b, cache_len)
        k_cache, v_cache, kv_pos = cache["k"], cache["v"], cache["kv_pos"]
        hidden = []
        for c0 in range(0, s, chunk):
            toks_c = tokens[:, c0:c0 + chunk]
            sc = toks_c.shape[1]
            positions = jnp.broadcast_to(
                jnp.arange(c0, c0 + sc)[None], (b, sc)
            )
            pos0 = jnp.full((b,), c0, jnp.int32)
            x = self._embed(params, toks_c, positions)

            def body(carry, xs, positions=positions, pos0=pos0):
                h = carry
                lp, kc, vc, kp = xs
                h, kc, vc, kp = dense_block_chunk(
                    lp, h, pos0, positions, lens, kc, vc, kp, cfg
                )
                return h, (kc, vc, kp)

            x, (k_cache, v_cache, kv_pos) = jax.lax.scan(
                body, x, (params["blocks"], k_cache, v_cache, kv_pos)
            )
            hidden.append(x)
        x = jnp.concatenate(hidden, axis=1)
        cache = dict(cache, k=k_cache, v=v_cache, kv_pos=kv_pos)
        logits = self._logits(params, _gather_last(x, lens))
        return logits, cache

    def _hybrid_prefill(self, params, tokens, lens, cache_len: int,
                        chunk: int):
        """``mamba2_hybrid`` prefill of a padded batch (B,S) with true
        lengths ``lens``, ``chunk`` positions at a time: each slice runs the
        whole stack, its Mamba2 layers starting from the state and conv
        inputs the previous slice left, its attention layers writing their
        lens-masked K/V (``attention_chunk``)."""
        cfg = self.cfg
        b, s = tokens.shape
        # K/V rows for the prompt's own S positions, padded to cache_len at
        # the end: no query attends past the prompt
        cache = self.init_cache(params, b, s)
        carry = tuple(cache[k] for k in ("mamba_h", "mamba_conv", "k", "v",
                                         "kv_pos"))
        hidden = []
        for c0 in range(0, s, chunk):
            toks_c = tokens[:, c0:c0 + chunk]
            sc = toks_c.shape[1]
            positions = jnp.broadcast_to(
                jnp.arange(c0, c0 + sc)[None], (b, sc)
            )
            pos0 = jnp.full((b,), c0, jnp.int32)
            n_valid = jnp.clip(lens - c0, 0, sc)

            def mixer(kind, w, h, idx, carry, positions=positions,
                      pos0=pos0, n_valid=n_valid):
                mh, mc, kc, vc, kp = carry
                if kind == "mamba":
                    y, (st, cv) = ssm.mamba2_forward_chunked(
                        w, h, mh[idx], mc[idx], chunk=SSD_CHUNK,
                        n_valid=n_valid,
                    )
                    return y, (_put(mh, st, idx), _put(mc, cv, idx),
                               kc, vc, kp)
                y, k1, v1, p1 = attention_chunk(
                    w, h, pos0, positions, lens, kc[idx], vc[idx], kp[idx],
                    cfg,
                )
                return y, (mh, mc, _put(kc, k1, idx), _put(vc, v1, idx),
                           _put(kp, p1, idx))

            x = self._embed(params, toks_c, positions)
            x, carry = self._hybrid_stack(params, x, carry, mixer)
            hidden.append(x)
        x = jnp.concatenate(hidden, axis=1)
        cache = dict(zip(("mamba_h", "mamba_conv", "k", "v", "kv_pos"),
                         carry))
        pad = [(0, 0), (0, 0), (0, cache_len - s)]
        cache["k"] = jnp.pad(cache["k"], pad + [(0, 0)])
        cache["v"] = jnp.pad(cache["v"], pad + [(0, 0)])
        cache["kv_pos"] = jnp.pad(cache["kv_pos"], pad, constant_values=-1)
        return self._logits(params, _gather_last(x, lens)), cache

    def prefill_slice(self, params, cache: dict, tokens, slot, start, total):
        """One bounded prefill slice of a SINGLE batch slot against a live
        decode cache — the serving engine's fused prefill-in-window unit.

        ``tokens``: (S,) int32 chunk of the prompt (zero-padded past the
        prompt's end); ``slot``/``start``/``total``: traced int32 scalars —
        the cache row being prefilled, the slice's absolute write offset,
        and the full prompt length.  Follows ``dense_block_chunk``'s rule
        per layer: write the slice's K/V first (positions at or beyond
        ``total`` masked to -1; writes use explicit scatter-with-drop, so
        an out-of-range ``slot``/index never clamp-corrupts a neighbour
        the way ``dynamic_update_slice`` would), then attend the queries
        over the slot's whole cache with ``kv_pos <= q_pos`` masking the
        chunk-internal future and ``kv_pos >= 0`` the unwritten rows.

        Returns ``(logits (V,), cache)`` where the logits are taken at the
        prompt's final position clipped into this slice — i.e. the
        first-token distribution when this slice completes the prompt, and
        garbage otherwise.  Supports the full-cache attention families
        (dense / moe / vlm token prompts); callers gate ring (sliding
        window smaller than the cache) layouts out, as chunked writes
        cannot reproduce a ring wrap.
        """
        cfg = self.cfg
        if cfg.kind not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"prefill_slice: unsupported model kind {cfg.kind!r}"
            )
        s = tokens.shape[0]
        idx = start + jnp.arange(s, dtype=jnp.int32)
        positions = idx[None, :]
        x = self._embed(params, tokens[None, :], positions)

        def body(carry, xs):
            h, kc, vc, kp = carry
            lp, layer = xs
            hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
            q, k_new, v_new = attention_qkv(
                lp["attn"], hn, positions, cfg.rope_theta, cfg.use_rope
            )
            at = (layer, slot, idx)
            kc = kc.at[at].set(kv_rows(k_new[0]).astype(kc.dtype),
                               mode="drop")
            vc = vc.at[at].set(kv_rows(v_new[0]).astype(vc.dtype),
                               mode="drop")
            kp = kp.at[at].set(
                jnp.where(idx < total, idx, -1).astype(kp.dtype), mode="drop"
            )
            kp_row = kp[layer, slot][None]
            att = attention_any(
                q,
                kv_heads(kc[layer, slot][None], cfg.n_kv_heads),
                kv_heads(vc[layer, slot][None], cfg.n_kv_heads),
                window=cfg.sliding_window,
                q_positions=positions,
                kv_positions=kp_row,
                kv_valid=kp_row >= 0,
                scale=cfg.qk_scale,
            )
            h = h + attention_out(lp["attn"], att)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            if cfg.is_moe:
                y, _ = moe_mlp(lp["moe"], h2, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor)
            else:
                y = gated_mlp(lp["mlp"], h2)
            return (h + y, kc, vc, kp), None

        (x, ks, vs, kps), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"], cache["kv_pos"]),
            (params["blocks"], jnp.arange(cfg.n_layers)),
        )
        cache = dict(cache, k=ks, v=vs, kv_pos=kps)
        last = jnp.clip(total - 1 - start, 0, s - 1)
        logits = self._logits(params, x[:, last][:, None, :])
        return logits[0, 0], cache

    def _fill_kv(self, cache, ks, vs, lens, s):
        """Copy prefill K/V (L,B,S,n,h) into the cache's first S slots."""
        cfg = self.cfg
        t = cache["k"].shape[2]
        ks, vs = kv_rows(ks), kv_rows(vs)
        if cfg.sliding_window and t < s:
            # ring buffer smaller than the prompt: keep the last t tokens
            ks, vs = ks[:, :, -t:], vs[:, :, -t:]
            kvp = jnp.arange(s - t, s, dtype=jnp.int32)
            kvp = jnp.broadcast_to(kvp[None, None], ks.shape[:3])
        else:
            pad = t - ks.shape[2]
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0)))
            kvp = jnp.pad(
                jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, None],
                                 (ks.shape[0], ks.shape[1], s)),
                ((0, 0), (0, 0), (0, pad)), constant_values=-1,
            )
        # mask out slots beyond each row's true prompt length
        valid = kvp < lens[None, :, None]
        kvp = jnp.where(valid, kvp, -1)
        cache["k"], cache["v"], cache["kv_pos"] = ks, vs, kvp
        return cache

    def decode(self, params, cache: dict, tokens, pos):
        """One decode step.  tokens: (B,1) int32; pos: (B,) positions of the
        new token.  Returns (logits (B,1,V), updated cache).

        The attention caches ride the layer scan's carry and each layer
        writes only its B new rows (``decode_attention``), so a window
        that scans this step and donates the cache holds one copy of it.
        """
        cfg = self.cfg
        b = tokens.shape[0]
        x = self._embed(params, tokens, pos[:, None])
        if cfg.kind == "mamba2_hybrid":
            return self._hybrid_decode(params, cache, x, pos)
        ring = bool(cfg.sliding_window) and (
            "k" in cache and cache["k"].shape[2] == cfg.sliding_window
        )
        if "k" in cache:
            # the layer scan's carry, and the layer each iteration writes
            init = (x, cache["k"], cache["v"], cache["kv_pos"])
            layers = jnp.arange(cache["k"].shape[0])

        if cfg.kind in ("dense", "moe", "vlm"):
            def body(carry, xs):
                h, kc, vc, kp = carry
                lp, layer = xs
                return dense_block_decode(lp, h, pos, layer, kc, vc, kp,
                                          cfg, ring), None

            (x, ks, vs, kps), _ = jax.lax.scan(
                body, init, (params["blocks"], layers)
            )
            cache = dict(cache, k=ks, v=vs, kv_pos=kps)
            return self._logits(params, x), cache

        if cfg.kind == "encdec":
            f = cache["cross_k"].shape[2]
            enc_pos = jnp.broadcast_to(jnp.arange(f)[None], (b, f))

            def body(carry, xs):
                h, kc, vc, kp = carry
                lp, ckx, cvx, layer = xs
                att, kc, vc, kp = decode_attention(
                    lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), pos,
                    layer, kc, vc, kp, cfg, ring,
                )
                h2 = h + att
                hx = rms_norm(h2, lp["ln_x"], cfg.norm_eps)
                qx = jnp.einsum("bsd,dnh->bsnh", hx, lp["xattn"]["wq"])
                xat = gqa_attention(
                    qx, ckx, cvx,
                    q_positions=jnp.full((b, 1), f + 1, jnp.int32),
                    kv_positions=enc_pos,
                )
                h2 = h2 + attention_out(lp["xattn"], xat)
                hm = rms_norm(h2, lp["ln2"], cfg.norm_eps)
                h2 = h2 + gated_mlp(lp["mlp"], hm)
                return (h2, kc, vc, kp), None

            (x, ks, vs, kps), _ = jax.lax.scan(
                body, init,
                (params["dec_blocks"], cache["cross_k"], cache["cross_v"],
                 layers),
            )
            cache = dict(cache, k=ks, v=vs, kv_pos=kps)
            return self._logits(params, x), cache

        if cfg.kind == "ssm":
            def body(carry, xs):
                h = carry
                lp, mc, mn, mm, sc, sn, sh, sm = xs
                hm = rms_norm(h, lp["ln_m"], cfg.norm_eps)
                y, (mc, mn, mm) = ssm.mlstm_decode(lp["mlstm"], hm,
                                                   (mc, mn, mm))
                h = h + y
                hs = rms_norm(h, lp["ln_s"], cfg.norm_eps)
                y2, (sc, sn, sh, sm) = ssm.slstm_decode(lp["slstm"], hs,
                                                        (sc, sn, sh, sm))
                return h + y2, (mc, mn, mm, sc, sn, sh, sm)

            x, states = jax.lax.scan(
                body, x,
                (params["xlstm_pairs"], cache["mlstm_c"], cache["mlstm_n"],
                 cache["mlstm_m"], cache["slstm_c"], cache["slstm_n"],
                 cache["slstm_h"], cache["slstm_m"]),
            )
            cache = dict(
                cache,
                mlstm_c=states[0], mlstm_n=states[1], mlstm_m=states[2],
                slstm_c=states[3], slstm_n=states[4], slstm_h=states[5],
                slstm_m=states[6],
            )
            return self._logits(params, x), cache

        if cfg.kind == "hybrid":
            shared = params["shared_attn"]

            def body(carry, xs):
                h, kc, vc, kp = carry
                lp, mh, mconv, layer = xs

                def mamba_one(hc, packed):
                    mp, ln, st, cv = packed
                    hin = rms_norm(hc, ln, cfg.norm_eps)
                    y, (st, cv) = ssm.mamba2_decode(mp, hin, st, cv)
                    return hc + y, (st, cv)

                h, (mh, mconv) = jax.lax.scan(
                    mamba_one, h, (lp["mamba"], lp["ln"], mh, mconv)
                )
                h, kc, vc, kp = dense_block_decode(shared, h, pos, layer, kc,
                                                   vc, kp, cfg, ring)
                return (h, kc, vc, kp), (mh, mconv)

            # the cache's (layer, B, ...) state as (n_super, attn_every,
            # B, ...) for the two-level scan, and back
            group = lambda st: st.reshape(-1, cfg.attn_every, *st.shape[1:])
            (x, ks, vs, kps), (mh, mconv) = jax.lax.scan(
                body, init,
                (params["super_blocks"], group(cache["mamba_h"]),
                 group(cache["mamba_conv"]), layers),
            )
            cache = dict(cache, mamba_h=mh.reshape(cache["mamba_h"].shape),
                         mamba_conv=mconv.reshape(cache["mamba_conv"].shape),
                         k=ks, v=vs, kv_pos=kps)
            return self._logits(params, x), cache

        raise ValueError(cfg.kind)

    def _hybrid_decode(self, params, cache: dict, x, pos):
        """One ``mamba2_hybrid`` decode step.  Every cache leaf rides the
        layer scans' carry: a Mamba2 layer reads its row of ``mamba_h`` and
        ``mamba_conv`` and writes the new state back in place, an attention
        layer writes its B new K/V rows (``decode_attention``), so a window
        that scans this step and donates the cache holds one copy of it."""
        cfg = self.cfg

        def mixer(kind, w, h, idx, carry):
            mh, mc, kc, vc, kp = carry
            if kind == "mamba":
                y, (st, cv) = ssm.mamba2_decode(w, h, mh[idx], mc[idx])
                return y, (_put(mh, st, idx), _put(mc, cv, idx), kc, vc, kp)
            y, kc, vc, kp = decode_attention(w, h, pos, idx, kc, vc, kp, cfg,
                                             ring=False)
            return y, (mh, mc, kc, vc, kp)

        names = ("mamba_h", "mamba_conv", "k", "v", "kv_pos")
        x, carry = self._hybrid_stack(
            params, x, tuple(cache[k] for k in names), mixer
        )
        return self._logits(params, x), dict(cache, **dict(zip(names, carry)))


def _gather_last(x, lens):
    """x: (B,S,D); lens: (B,) true lengths -> (B,1,D) at position lens-1."""
    b = x.shape[0]
    idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    return x[jnp.arange(b), idx][:, None, :]
