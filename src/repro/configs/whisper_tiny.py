"""whisper-tiny [audio] — enc-dec, conv frontend stubbed [arXiv:2212.04356].

4L d_model=384 6H (kv=6, MHA) d_ff=1536 vocab=51865.  The mel+conv frontend
is a stub: the batch's ``embeds`` input supplies precomputed
frame embeddings (B, 1500, 384).  Whisper uses learned absolute positions
(position="learned"); max_position is stretched to 32k positions (the model
card caps decode at 448).
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="whisper-tiny",
    kind="encdec",
    n_layers=4,            # decoder layers
    n_enc_layers=4,
    d_model=384,
    n_heads=6,
    n_kv_heads=6,
    d_ff=1536,
    vocab=51865,
    position="learned",
    max_position=32_776,
    n_audio_frames=1500,
)
