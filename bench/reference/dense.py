"""The dense GQA decoder family: sizes, weights, plain reference and counts.

A configuration file names its family (``"family": "dense"``) and the
harness loads ``bench/reference/<family>.py`` by that name.  A family module
gives what the harness needs of a model and nothing of the program:

* ``Dims.from_config(cfg)``: the sizes, hashable (static in ``jax.jit``),
  with ``vocab`` and ``dtype``;
* ``program_fields(dims)``: the program's ``ModelConfig`` fields at those
  sizes, by name;
* ``make_params(dims, key)``: random weights in the program's layout;
* ``token_gaps(dims, quant, params, tokens, targets)``: the plain
  reference's gap of each target token;
* ``decode_flops``, ``decode_bytes`` and ``prefill_flops``: the work of the
  served tokens, from shapes and live positions.

The reference is written from the published description of a llama-style
decoder and the equations the program serves, with no kernel, cache or
batching and no import of the program:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q, k, v = h Wq, h Wk, h Wv        rotary embedding on q, k
                x = x + attention(q, k, v) Wo     causal, GQA, 1/sqrt(hd),
                                                  window if the model has one
                h = rmsnorm(x) * ln2
                x = x + (silu(h W1) * (h W3)) W2
    logits = (rmsnorm(x) * final_norm) head

The rotary embedding rotates the two halves of each head (``x1, x2``) by
``theta ** (-2i / hd)`` per position.  Departures from each published model
(scalar multipliers the program does not serve, a window that is never
reached) are listed in its configuration file.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16.  One sequence runs at a time and the
weights are widened to float32 one layer at a time inside the layer scan, so
the reference fits beside the served model's bfloat16 weights.

``quant="fp8"`` is the control: the same pass with every operand of every
matrix product rounded to float8 (e4m3, one scale per tensor), the step
below the bfloat16 the configurations serve.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

#: bytes of one element of the served dtypes
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


# ------------------------------------------------------------------ sizes

@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    sliding_window: int      # 0: full attention
    dtype: str               # the served dtype of the weight matrices

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        """The sizes under the keys of the published ``config.json``."""
        d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            n_layers=cfg["num_hidden_layers"],
            d_model=d,
            n_heads=nh,
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // nh,
            d_ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            tie_embeddings=bool(cfg["tie_word_embeddings"]),
            sliding_window=int(cfg.get("sliding_window") or 0),
            dtype=cfg["torch_dtype"],
        )

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def matmul_params_per_layer(self) -> int:
        """Weights one token multiplies through in one layer."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff


def program_fields(dims: Dims) -> dict:
    """The program's ``ModelConfig`` fields that set these sizes."""
    return dict(
        n_layers=dims.n_layers, d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab=dims.vocab, rope_theta=dims.rope_theta, norm_eps=dims.norm_eps,
        tie_embeddings=dims.tie_embeddings,
        sliding_window=dims.sliding_window, dtype=dims.dtype,
    )


# ---------------------------------------------------------------- weights

@functools.partial(jax.jit, static_argnums=(0,))
def make_params(dims: Dims, key) -> dict:
    """The whole weight tree in one jitted call, on the device: laid out as
    the program's ``Model.init`` lays out a dense decoder (stacked over
    layers), at its scales, in the served dtype.  The RMSNorm scales are
    drawn round 1 rather than set to 1, so that a path that skips one shows
    in the comparison."""
    dt = jnp.dtype(dims.dtype)
    L, D, F, V = dims.n_layers, dims.d_model, dims.d_ff, dims.vocab
    nh, nkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    keys = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def norm_scale(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, 0.75, 1.25)

    params = {
        "embed": normal((V, D), 0.02),
        "final_norm": norm_scale((D,)),
        "blocks": {
            "ln1": norm_scale((L, D)),
            "attn": {
                "wq": normal((L, D, nh, hd), D ** -0.5),
                "wk": normal((L, D, nkv, hd), D ** -0.5),
                "wv": normal((L, D, nkv, hd), D ** -0.5),
                "wo": normal((L, nh, hd, D), D ** -0.5),
            },
            "ln2": norm_scale((L, D)),
            "mlp": {
                "w1": normal((L, D, F), D ** -0.5),
                "w3": normal((L, D, F), D ** -0.5),
                "w2": normal((L, F, D), F ** -0.5),
            },
        },
    }
    if not dims.tie_embeddings:
        params["lm_head"] = normal((D, V), D ** -0.5)
    return params


# -------------------------------------------------------------- reference

def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor; back in float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, quant):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, n, hd), rotated by position."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(dims: Dims, quant, x, lp):
    t = x.shape[0]
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"], dims.norm_eps)
    q = _rope(_mm("td,dnh->tnh", h, a["wq"], quant), dims.rope_theta)
    k = _rope(_mm("td,dnh->tnh", h, a["wk"], quant), dims.rope_theta)
    v = _mm("td,dnh->tnh", h, a["wv"], quant)
    k = jnp.repeat(k, dims.q_per_kv, axis=1)
    v = jnp.repeat(v, dims.q_per_kv, axis=1)
    s = _mm("qnh,knh->nqk", q, k, quant) * dims.head_dim ** -0.5
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = ki <= qi
    if dims.sliding_window:
        mask &= ki > qi - dims.sliding_window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("nqk,knh->qnh", p, v, quant)
    x = x + _mm("qnh,nhd->qd", o, a["wo"], quant)
    h = _rmsnorm(x, lp["ln2"], dims.norm_eps)
    m = lp["mlp"]
    g = jax.nn.silu(_mm("td,df->tf", h, m["w1"], quant))
    u = _mm("td,df->tf", h, m["w3"], quant)
    return x + _mm("tf,fd->td", g * u, m["w2"], quant), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def logits(dims: Dims, quant: str, params: dict, tokens):
    """(T, V) float32 logits of one sequence ``tokens`` (T,)."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, dims, quant), x,
                        params["blocks"])
    x = _rmsnorm(x, params["final_norm"], dims.norm_eps)
    head = params["embed"].T if dims.tie_embeddings else params["lm_head"]
    return _mm("td,dv->tv", x, head, quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def token_gaps(dims: Dims, quant: str, params: dict, tokens, targets):
    """Per position i: how far ``targets[i]``'s logit lies below the best
    logit after ``tokens[:i+1]`` (0 where the target is the argmax), and
    that argmax.  ``targets[i]`` is the token served after position i."""
    lg = logits(dims, quant, params, tokens)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(lg, axis=-1).astype(jnp.int32)


# ----------------------------------------------------------------- counts
#
# What the algorithm needs, not what a program happens to do: a decode
# token reads the keys and values of the context it attends over, never the
# whole ``cache_len`` reservation, and the weights are read once per decode
# step however many slots are live.  A program that reads less than this
# cannot exist, so a share of the roofline computed from these counts
# cannot pass 100% unless the time is short of the work.

def head_params(dims: Dims) -> int:
    return dims.d_model * dims.vocab


def weight_bytes(dims: Dims) -> int:
    """Bytes of every weight a decode step reads: the layers' matrices, the
    output head and the RMSNorm scales (float32)."""
    mats = dims.n_layers * dims.matmul_params_per_layer() + head_params(dims)
    norms = (2 * dims.n_layers + 1) * dims.d_model
    return mats * ITEMSIZE[dims.dtype] + norms * 4


def kv_bytes_per_position(dims: Dims) -> int:
    """Keys and values of one cached position, over all layers."""
    return (2 * dims.n_layers * dims.n_kv_heads * dims.head_dim
            * ITEMSIZE[dims.dtype])


def attended(dims: Dims, ctx):
    """Positions a query at position ``ctx`` attends over (itself included),
    capped by the model's window."""
    n = np.asarray(ctx, np.int64) + 1
    return np.minimum(n, dims.sliding_window) if dims.sliding_window else n


def decode_flops(dims: Dims, ctx) -> float:
    """Model FLOPs of decode tokens fed at positions ``ctx`` (an int or an
    array): every matrix product, the output head, and attention over the
    attended positions."""
    n = attended(dims, ctx)
    dense = 2 * (dims.n_layers * dims.matmul_params_per_layer()
                 + head_params(dims))
    attn = 4 * dims.n_layers * dims.n_heads * dims.head_dim * n
    return float(np.sum(dense + attn))


def decode_bytes(dims: Dims, ctx, steps: int) -> float:
    """Bytes ``steps`` decode steps must move to feed tokens at positions
    ``ctx``: the weights once per step, and per token the keys and values
    it attends over plus its own new key and value."""
    n = attended(dims, ctx)
    kv = kv_bytes_per_position(dims)
    return float(steps * weight_bytes(dims) + np.sum(n * kv + kv))


def prefill_flops(dims: Dims, prompt_len) -> float:
    """Model FLOPs of prefilling prompts of lengths ``prompt_len``: every
    matrix product for every prompt token, causal attention, and the output
    head at the last position (where the first token is sampled)."""
    p = np.atleast_1d(np.asarray(prompt_len, np.int64))
    w = np.minimum(p, dims.sliding_window) if dims.sliding_window else p
    # sum over query positions i < p of min(i + 1, window)
    pairs = w * (w + 1) // 2 + (p - w) * w
    dense = 2 * dims.n_layers * dims.matmul_params_per_layer() * p
    attn = 4 * dims.n_layers * dims.n_heads * dims.head_dim * pairs
    return float(np.sum(dense + attn + 2 * head_params(dims)))
