"""Per-architecture smoke tests.

For each registered arch: instantiate the REDUCED variant of the same
family (2 layers, d_model <= 512, <= 4 experts) and run one forward and one
prefill-then-decode step on CPU, asserting output shapes and the absence
of NaNs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.models import Model

# full-zoo forward sweeps dominate tier-1 runtime; run via `pytest -m slow`
pytestmark = pytest.mark.slow

B, S = 2, 16


def make_batch(cfg, key):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab).astype(
            jnp.int32
        )
    }
    if cfg.kind == "encdec":
        batch["embeds"] = (
            jax.random.normal(key, (B, cfg.n_audio_frames, cfg.d_model)) * 0.1
        )
    if cfg.kind == "vlm":
        batch["embeds"] = (
            jax.random.normal(key, (B, cfg.n_image_tokens, cfg.d_model)) * 0.1
        )
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_forward(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.n_experts <= 4
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    logits, aux = model.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_serve_path(arch):
    """prefill + one decode step: shapes + no NaN."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    n_off = cfg.n_image_tokens if cfg.kind == "vlm" else 0
    logits, cache = model.prefill(params, dict(batch), cache_len=n_off + S + 8)
    assert logits.shape == (B, 1, cfg.vocab)
    assert not np.isnan(np.asarray(logits, np.float32)).any()
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), n_off + S, jnp.int32)
    logits2, cache = model.decode(params, cache, tok, pos)
    assert logits2.shape == (B, 1, cfg.vocab)
    assert not np.isnan(np.asarray(logits2, np.float32)).any()
