"""The Mamba-2 / attention hybrid family (granite-4.0-h): sizes, weights,
plain reference and counts.

A configuration file names this family (``"family": "mamba2_hybrid"``) and
the harness loads this module by that name.  It gives what
``reference/dense.py`` gives (``Dims.from_config``, ``program_fields``,
``make_params``, ``token_gaps``, ``decode_flops``, ``decode_bytes``,
``prefill_flops``) and imports nothing of the program.

The reference is written from the published description of
``granitemoehybrid`` (Hugging Face transformers) and of Mamba-2 (Dao and Gu,
arXiv:2405.21060), with no kernel, cache, chunking or batching:

    x = embed[tokens] * embedding_multiplier
    per layer, its mixer given by layer_types:
        h = rmsnorm(x) * ln1
        mamba:      z, xBC, dt = h W_in
                    xBC = silu(causal depthwise conv(xBC) + conv_b)
                    x_, B, C = split(xBC)          n_groups 1: B, C shared
                    dt = softplus(dt + dt_bias);  A = -exp(A_log)
                    per position t, per head:
                        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
                        y_t = S_t C_t + D x_t
                    y = rmsnorm(y * silu(z)) * norm_w;  m = y W_out
        attention:  q, k, v = h Wq, h Wk, h Wv     no position encoding
                    m = softmax(q k^T * attention_multiplier, causal) v Wo
        x = x + residual_multiplier * m
        h = rmsnorm(x) * ln2
        x = x + residual_multiplier * (silu(h W1) * (h W3)) W2
    logits = (rmsnorm(x) * final_norm) embed^T / logits_scaling

The state-space layer is the per-step recurrence above, one position at a
time, never the chunked form the program computes.  Every matrix product
runs at ``Precision.HIGHEST`` in float32; the layers run in a scan whose
body picks the layer's kind, and each layer's weights are widened to
float32 inside it, so the reference fits beside the served model.

``quant="fp8"`` is the control: the same pass with every operand of every
matrix product rounded to float8 (e4m3, one scale per tensor), the step
below the bfloat16 the configuration serves.  The recurrence, like the
program's state, stays in float32.
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

MAMBA, ATTENTION = "mamba", "attention"


# ------------------------------------------------------------------ sizes

@dataclasses.dataclass(frozen=True)
class Dims:
    layer_types: tuple       # "mamba" or "attention", per layer
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    d_conv: int
    norm_eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    dtype: str               # the served dtype of the weight matrices

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        """The sizes under the keys of the published ``config.json``."""
        d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
        if cfg["position_embedding_type"] != "nope":
            raise ValueError("the family serves attention with no position "
                             "encoding only")
        if cfg["mamba_n_groups"] != 1 or cfg["num_local_experts"]:
            raise ValueError("the family serves n_groups 1 and no experts")
        if not cfg["tie_word_embeddings"] or not cfg["mamba_conv_bias"]:
            raise ValueError("the family serves tied embeddings and a conv "
                             "bias")
        dims = cls(
            layer_types=tuple(cfg["layer_types"]),
            d_model=d,
            n_heads=nh,
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // nh,
            d_ff=cfg["shared_intermediate_size"],
            vocab=cfg["vocab_size"],
            ssm_heads=cfg["mamba_n_heads"],
            ssm_head_dim=cfg["mamba_d_head"],
            d_state=cfg["mamba_d_state"],
            d_conv=cfg["mamba_d_conv"],
            norm_eps=float(cfg["rms_norm_eps"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            dtype=cfg["torch_dtype"],
        )
        if (len(dims.layer_types) != cfg["num_hidden_layers"]
                or dims.d_inner != cfg["mamba_expand"] * d):
            raise ValueError("layer_types or the Mamba-2 widths disagree "
                             "with the config")
        if cfg["mamba_expand"] != 2 or dims.ssm_head_dim != 64:
            raise ValueError("the program serves Mamba-2's expansion 2 and "
                             "head size 64")
        return dims

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attn(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.d_state

    @property
    def in_proj(self) -> int:
        """Width of the fused input projection: z, xBC, dt."""
        return self.d_inner + self.conv_channels + self.ssm_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)

    def mamba_params(self) -> int:
        """Matrix weights of one Mamba-2 layer (projections in and out)."""
        return self.d_model * self.in_proj + self.d_inner * self.d_model

    def matmul_params(self) -> int:
        """Weights one token multiplies through in the layer stack."""
        return (self.n_layers * 3 * self.d_model * self.d_ff
                + self.n_mamba * self.mamba_params()
                + self.n_attn * self.attn_params())


def program_fields(dims: Dims) -> dict:
    """The program's ``ModelConfig`` fields that set these sizes."""
    return dict(
        n_layers=dims.n_layers, d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab=dims.vocab, norm_eps=dims.norm_eps, tie_embeddings=True,
        position="none", attn_scale=dims.attention_multiplier,
        ssm_state=dims.d_state, conv_width=dims.d_conv,
        layer_types=dims.layer_types,
        embedding_multiplier=dims.embedding_multiplier,
        residual_multiplier=dims.residual_multiplier,
        logits_scaling=dims.logits_scaling, dtype=dims.dtype,
    )


# ---------------------------------------------------------------- weights

@functools.partial(jax.jit, static_argnums=(0,))
def make_params(dims: Dims, key) -> dict:
    """The whole weight tree in one jitted call, on the device, laid out as
    the program's ``Model.init`` lays out a ``mamba2_hybrid`` stack: per
    layer norms and MLP stacked over all layers, Mamba-2 weights stacked
    over the Mamba-2 layers, attention weights over the attention layers.
    The RMSNorm scales are drawn round 1, ``dt_bias`` as Mamba-2 draws it
    (softplus of it between 0.001 and 0.1) and ``A_log`` over log 1..16,
    so that a path that skips one shows in the comparison.  The embedding
    is drawn at the program's 0.02 over the embedding multiplier, so that
    what enters the residual stream has the program's scale: at 0.02 the
    tied head reads the input token back out of the stream, the greedy
    model repeats its last token, and float8 puts another token first
    nowhere (147 of 150 served tokens repeated the one before at a
    256-wide cut of this configuration on the CPU, against none at this
    scale, where float8 changed the first token at half the positions)."""
    dt = jnp.dtype(dims.dtype)
    L, M, A = dims.n_layers, dims.n_mamba, dims.n_attn
    D, F, V = dims.d_model, dims.d_ff, dims.vocab
    nh, nkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    H, E, C = dims.ssm_heads, dims.d_inner, dims.conv_channels
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    step = jnp.exp(uniform((M, H), np.log(1e-3), np.log(1e-1)))
    return {
        "embed": normal((V, D), 0.02 / dims.embedding_multiplier),
        "final_norm": uniform((D,), 0.75, 1.25),
        "layers": {
            "ln1": uniform((L, D), 0.75, 1.25),
            "ln2": uniform((L, D), 0.75, 1.25),
            "mlp": {
                "w1": normal((L, D, F), D ** -0.5),
                "w3": normal((L, D, F), D ** -0.5),
                "w2": normal((L, F, D), F ** -0.5),
            },
        },
        "mamba": {
            "w_in": normal((M, dims.in_proj, D), D ** -0.5),
            "conv_w": normal((M, dims.d_conv, C), 0.3),
            "conv_b": normal((M, C), 0.1),
            "a_log": jnp.log(uniform((M, H), 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "d_skip": uniform((M, H), 0.5, 1.5),
            "w_out": normal((M, E, D), E ** -0.5),
            "norm_w": uniform((M, E), 0.75, 1.25),
        },
        "attn": {
            "wq": normal((A, D, nh, hd), D ** -0.5),
            "wk": normal((A, D, nkv, hd), D ** -0.5),
            "wv": normal((A, D, nkv, hd), D ** -0.5),
            "wo": normal((A, nh, hd, D), D ** -0.5),
        },
    }


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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mamba(dims: Dims, quant, h, p):
    """One Mamba-2 mixer over a whole sequence h (T, D), by its per-step
    recurrence."""
    t = h.shape[0]
    E, N, H, P = dims.d_inner, dims.d_state, dims.ssm_heads, dims.ssm_head_dim
    zxbcdt = _mm("td,ed->te", h, p["w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :E], zxbcdt[:, E:E + dims.conv_channels],
                  zxbcdt[:, E + dims.conv_channels:])
    # causal depthwise conv: out[t] = sum_i xbc[t - (W-1) + i] * w[i]
    w = dims.d_conv
    padded = jnp.concatenate([jnp.zeros((w - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + t] * p["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, b, c = xbc[:, :E].reshape(t, H, P), xbc[:, E:E + N], xbc[:, E + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (T, H)
    a = -jnp.exp(p["a_log"])                                   # (H,)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, b, c, dt))
    y = (y + x * p["d_skip"][:, None]).reshape(t, E)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm_w"], dims.norm_eps)
    return _mm("te,ed->td", y, p["w_out"], quant)


def _attention(dims: Dims, quant, h, p):
    """One causal GQA attention over a whole sequence, no position
    encoding, scores scaled by the attention multiplier."""
    t = h.shape[0]
    q = _mm("td,dnh->tnh", h, p["wq"], quant)
    k = jnp.repeat(_mm("td,dnh->tnh", h, p["wk"], quant), dims.q_per_kv, 1)
    v = jnp.repeat(_mm("td,dnh->tnh", h, p["wv"], quant), dims.q_per_kv, 1)
    s = _mm("qnh,knh->nqk", q, k, quant) * dims.attention_multiplier
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    o = _mm("nqk,knh->qnh", jax.nn.softmax(s, axis=-1), v, quant)
    return _mm("qnh,nhd->qd", o, p["wo"], quant)


def _layer(dims: Dims, quant, params, x, i):
    """Layer i: its mixer (by kind), then its MLP, each on a scaled
    residual branch."""
    kinds = jnp.asarray([t == ATTENTION for t in dims.layer_types], jnp.int32)
    rank = np.zeros(dims.n_layers, np.int32)       # index within its kind
    for kind in (MAMBA, ATTENTION):
        at = [j for j, t in enumerate(dims.layer_types) if t == kind]
        rank[at] = np.arange(len(at))
    j = jnp.asarray(rank)[i]
    take = lambda tree: _f32(jax.tree.map(lambda a: a[j], tree))
    lp = _f32(jax.tree.map(lambda a: a[i], params["layers"]))
    r = dims.residual_multiplier
    h = _rmsnorm(x, lp["ln1"], dims.norm_eps)
    mix = jax.lax.switch(kinds[i], [
        lambda h: _mamba(dims, quant, h, take(params["mamba"])),
        lambda h: _attention(dims, quant, h, take(params["attn"])),
    ], h)
    x = x + r * mix
    h = _rmsnorm(x, lp["ln2"], dims.norm_eps)
    m = lp["mlp"]
    g = jax.nn.silu(_mm("td,df->tf", h, m["w1"], quant))
    u = _mm("td,df->tf", h, m["w3"], quant)
    return x + r * _mm("tf,fd->td", g * u, m["w2"], quant), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def logits(dims: Dims, quant: str, params: dict, tokens):
    """(T, V) float32 logits of one sequence ``tokens`` (T,)."""
    x = params["embed"][tokens].astype(jnp.float32) * dims.embedding_multiplier
    x, _ = jax.lax.scan(functools.partial(_layer, dims, quant, params), x,
                        jnp.arange(dims.n_layers))
    x = _rmsnorm(x, params["final_norm"], dims.norm_eps)
    return _mm("td,vd->tv", x, params["embed"], quant) / dims.logits_scaling


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
# What the algorithm needs, not what a program happens to do: a decode step
# reads the weights once however many slots are live; each live slot reads
# and writes its recurrent state (the SSM state in float32 and the conv
# inputs) in every Mamba-2 layer, and reads the keys and values of the
# context it attends over in every attention layer plus writes its own.

def head_params(dims: Dims) -> int:
    return dims.d_model * dims.vocab


def weight_bytes(dims: Dims) -> int:
    """Bytes of every weight a decode step reads: the matrices, the output
    head (the tied embedding), the conv weights and biases, and the
    float32 vectors (norm scales, A_log, dt_bias, D)."""
    it = ITEMSIZE[dims.dtype]
    conv = dims.n_mamba * (dims.d_conv + 1) * dims.conv_channels
    f32 = ((2 * dims.n_layers + 1) * dims.d_model
           + dims.n_mamba * (3 * dims.ssm_heads + dims.d_inner))
    return (dims.matmul_params() + head_params(dims) + conv) * it + f32 * 4


def state_bytes(dims: Dims) -> int:
    """One sequence's recurrent state over all Mamba-2 layers: the float32
    SSM state and the last d_conv - 1 conv inputs."""
    ssm = dims.ssm_heads * dims.ssm_head_dim * dims.d_state * 4
    conv = (dims.d_conv - 1) * dims.conv_channels * ITEMSIZE[dims.dtype]
    return dims.n_mamba * (ssm + conv)


def kv_bytes_per_position(dims: Dims) -> int:
    """Keys and values of one cached position, over the attention layers."""
    return (2 * dims.n_attn * dims.n_kv_heads * dims.head_dim
            * ITEMSIZE[dims.dtype])


def ssm_flops_per_token(dims: Dims) -> int:
    """The recurrence of one position in one Mamba-2 layer: the state's
    decay and outer-product update, and the readout by C."""
    return 4 * dims.ssm_heads * dims.ssm_head_dim * dims.d_state


def decode_flops(dims: Dims, ctx) -> float:
    """Model FLOPs of decode tokens fed at positions ``ctx`` (an int or an
    array): every matrix product, the output head, the recurrence, and
    attention over the attended positions."""
    n = np.asarray(ctx, np.int64) + 1
    dense = (2 * (dims.matmul_params() + head_params(dims))
             + dims.n_mamba * ssm_flops_per_token(dims))
    attn = 4 * dims.n_attn * dims.n_heads * dims.head_dim * n
    return float(np.sum(dense + attn))


def decode_bytes(dims: Dims, ctx, steps: int) -> float:
    """Bytes ``steps`` decode steps must move to feed tokens at positions
    ``ctx``: the weights once per step, and per token its recurrent state
    read and written, the keys and values it attends over and its own new
    key and value."""
    n = np.asarray(ctx, np.int64) + 1
    kv = kv_bytes_per_position(dims)
    per_token = 2 * state_bytes(dims) + n * kv + kv
    return float(steps * weight_bytes(dims) + np.sum(per_token))


def prefill_flops(dims: Dims, prompt_len) -> float:
    """Model FLOPs of prefilling prompts of lengths ``prompt_len``: every
    matrix product and the recurrence for every prompt token, causal
    attention, and the output head at the last position."""
    p = np.atleast_1d(np.asarray(prompt_len, np.int64))
    pairs = p * (p + 1) // 2
    dense = (2 * dims.matmul_params()
             + dims.n_mamba * ssm_flops_per_token(dims)) * p
    attn = 4 * dims.n_attn * dims.n_heads * dims.head_dim * pairs
    return float(np.sum(dense + attn + 2 * head_params(dims)))
