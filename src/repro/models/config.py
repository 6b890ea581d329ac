"""Model configuration for the assigned architecture pool.

One ``ModelConfig`` describes any architecture the framework can build:
dense GQA transformers, SWA variants, MoE, encoder-decoder (audio), VLM
decoders, xLSTM stacks, and Mamba2+attention hybrids.  Every assigned
architecture in ``repro/configs/`` instantiates this dataclass with the
exact numbers from its source paper / model card.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    kind: str                      # dense | moe | ssm | hybrid | mamba2_hybrid
                                   # | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # attention
    head_dim: int = 0              # 0 -> d_model // n_heads
    rope_theta: float = 500_000.0
    position: str = "rope"         # rope | learned (absolute) | none
    attn_scale: float = 0.0        # query-key scale; 0 -> head_dim ** -0.5
    sliding_window: int = 0        # 0 -> full attention
    max_position: int = 1_048_576  # for learned positions / rope cache

    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25

    # SSM / hybrid
    ssm_state: int = 0             # Mamba2 state size per head
    conv_width: int = 4            # Mamba2 short conv
    attn_every: int = 0            # hybrid: one shared attn block every k
    # mamba2_hybrid: each layer's sequence mixer, "mamba" or "attention",
    # every layer with its own weights and its own MLP
    layer_types: tuple = ()
    # xLSTM: ratio of mLSTM blocks per sLSTM block (7:1 in the paper's
    # xLSTM[7:1]; we alternate per `slstm_every`)
    slstm_every: int = 2

    # encoder-decoder (audio)
    n_enc_layers: int = 0
    n_audio_frames: int = 1500     # whisper 30 s @ 50 Hz after conv stub

    # VLM
    n_image_tokens: int = 0        # anyres patch embeddings (stub frontend)

    # granite's scalar multipliers: embeddings times
    # ``embedding_multiplier``, logits divided by ``logits_scaling``, and
    # (mamba2_hybrid only) each residual branch times ``residual_multiplier``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # misc
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types must name every layer")
        if self.position not in ("rope", "learned", "none"):
            raise ValueError(f"unknown position encoding {self.position!r}")
        if self.residual_multiplier != 1.0 and self.kind != "mamba2_hybrid":
            raise ValueError("only the mamba2_hybrid stack serves a "
                             "residual_multiplier")

    # ---------------------------------------------------------------- props

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def qk_scale(self) -> float:
        """The constant attention scores are multiplied by."""
        return self.attn_scale or self.head_dim ** -0.5

    @property
    def use_rope(self) -> bool:
        return self.position == "rope"

    def reduced(self, **overrides) -> "ModelConfig":
        """Smoke-test variant: same family, tiny dims (CPU-runnable)."""
        small = dict(
            n_layers=2,
            d_model=min(self.d_model, 128),
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_kv_heads, 2),
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            head_dim=32,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            attn_every=min(self.attn_every, 2) if self.attn_every else 0,
            # one layer of each kind, in the published order
            layer_types=tuple(dict.fromkeys(self.layer_types)),
            n_enc_layers=2 if self.n_enc_layers else 0,
            n_audio_frames=16 if self.n_enc_layers else 1500,
            n_image_tokens=8 if self.n_image_tokens else 0,
            sliding_window=min(self.sliding_window, 64)
            if self.sliding_window
            else 0,
            max_position=4096,
            dtype="float32",
            name=self.name + "-smoke",
        )
        # keep kv heads consistent with heads
        if small["n_heads"] % small["n_kv_heads"]:
            small["n_kv_heads"] = 1
        small.update(overrides)
        return dataclasses.replace(self, **small)
