"""Every registered architecture traces its serving entry points.

For each entry of ``repro.configs.ALL_ARCHS``, at the reduced size of its
family, ``jax.eval_shape`` traces ``Model.init``, ``init_cache``,
``prefill``, ``decode`` and ``forward``: no weights are made and nothing
runs, so the whole zoo takes a few seconds.  The checks are shapes only:
the logits of each entry point, and that ``prefill`` hands back a cache
with ``init_cache``'s tree and leaf shapes, so the engine can write it
into its slots.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.models import Model

B, S, CACHE_LEN = 2, 16, 64


def _shapes(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_arch_traces_serving_entry_points(arch):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    n_embeds = {"encdec": cfg.n_audio_frames,
                "vlm": cfg.n_image_tokens}.get(cfg.kind)
    if n_embeds is not None:
        batch["embeds"] = jax.ShapeDtypeStruct((B, n_embeds, cfg.d_model),
                                               jnp.float32)

    empty = jax.eval_shape(lambda p: model.init_cache(p, B, CACHE_LEN),
                           params)
    logits, cache = jax.eval_shape(
        lambda p, b: model.prefill(p, b, CACHE_LEN), params, batch)
    assert logits.shape == (B, 1, cfg.vocab)
    assert jax.tree.structure(cache) == jax.tree.structure(empty)
    assert _shapes(cache) == _shapes(empty)

    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    logits, stepped = jax.eval_shape(model.decode, params, cache, tok, pos)
    assert logits.shape == (B, 1, cfg.vocab)
    assert _shapes(stepped) == _shapes(empty)

    logits, _ = jax.eval_shape(model.forward, params, batch)
    assert logits.shape == (B, S, cfg.vocab)
