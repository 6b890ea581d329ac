"""granite-4.0-h-micro [mamba2_hybrid] — 36 Mamba-2 layers and 4 NoPE GQA
attention layers, each layer with its own SwiGLU MLP
[hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid].

40L d_model=2048; attention 32H (GQA kv=8) head_dim 64 with no position
encoding at layers 5, 15, 25, 35; Mamba-2 64 heads x 64, d_state 128,
n_groups 1, conv 4 with bias, expand 2, chunk 256; MLP width 8192
(``shared_intermediate_size``; no routed experts); vocab 100352, tied
embeddings; granite's multipliers: embedding 12, attention 0.015625,
residual 0.22, logits 8.
"""

from repro.models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    kind="mamba2_hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    position="none",
    attn_scale=0.015625,
    ssm_state=128,
    conv_width=4,
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
)

LONG_CONTEXT_OVERRIDES = {}  # mamba state is O(1); 4 attention layers
