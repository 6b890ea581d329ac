"""The Pallas decode attention (``repro.kernels.decode_attention``) against
the XLA path it replaces on a TPU (``gqa_attention`` over the layer's
slice), in interpret mode on the CPU.

Pinned here:

  * **kernel parity** — at granite-3-2b's head layout (8 KV heads of 64,
    32 query heads), h2o-danube-1.8b's (8 of 80: a 640-wide row) and
    granite-4.0-h-micro's (NoPE, scale 0.015625), on a full cache with
    slots at positions 0, block - 1, block, T - 1 and past T (the row
    ``decode_attention`` clips to T - 1), with stale rows of an earlier
    occupant past ``pos`` and unwritten rows, and on a sliding-window ring
    before and after it wraps;
  * **model parity** — one ``Model.decode`` step of a tiny ``dense`` and a
    tiny ``mamba2_hybrid`` configuration, with the kernel chosen (the
    module's selector patched) and without: logits within bf16 rounding,
    every cache leaf bit-identical; and a decode window in which one slot
    decodes frozen serves the same tokens;
  * **the count of rows read** — ``ServeEngine``'s ``attn_rows_read`` and
    ``attn_rows_reserved`` over windows in which slots finish mid-window,
    against a count from the requests' own lengths.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_scheduler
from repro.engine import ServeEngine
from repro.engine import engine as E
from repro.kernels import decode_attention as K
from repro.models import Model
from repro.models import transformer as T
from repro.models.layers import gqa_attention

#: (n_kv, head_dim, n_heads, scale): the three benchmark configurations'
#: attention layouts
LAYOUTS = {
    "granite-3-2b": (8, 64, 32, 64 ** -0.5),
    "h2o-danube-1.8b": (8, 80, 32, 80 ** -0.5),
    "granite-4.0-h-micro": (8, 64, 32, 0.015625),
}
LAYERS, LAYER, ROWS, BLOCK = 3, 1, 64, 16
#: the engine's reservation in the count test, four of its 32-row blocks
CACHE_LEN = 128


def full_cache():
    """Slots at 0, block-1, block, T-1 and T+6.  Slot 1's rows past its
    position hold a longer earlier occupant's positions, slot 2's were
    never written; slot 4 is past the cache, so its row T-1 holds its own
    position (the clipped write)."""
    pos = np.array([0, BLOCK - 1, BLOCK, ROWS - 1, ROWS + 6])
    kp = np.tile(np.arange(ROWS), (len(pos), 1))
    kp[2, BLOCK + 1:] = -1
    kp[0, 1:] = -1
    kp[4, ROWS - 1] = pos[4]
    return pos, kp, 0


def ring_cache(pos):
    """A sliding-window ring of T rows (window T): row r holds the latest
    position p <= pos with p % T == r, or -1 if there is none."""
    pos = np.asarray(pos)
    r = np.arange(ROWS)
    latest = pos[:, None] - (pos[:, None] - r) % ROWS
    return pos, np.where(latest >= 0, latest, -1), ROWS


CACHES = {
    "full": full_cache,
    "ring-before-wrap": lambda: ring_cache([0, 7, BLOCK, 40, ROWS - 1]),
    "ring-after-wrap": lambda: ring_cache(
        [ROWS, ROWS + BLOCK - 1, ROWS + BLOCK, 2 * ROWS + 5, 5 * ROWS - 1]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cache", list(CACHES))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_matches_gqa_attention(layout, cache, dtype):
    n_kv, hd, nh, scale = LAYOUTS[layout]
    rng = np.random.default_rng(16)
    pos, kp, window = CACHES[cache]()
    b, w = len(pos), n_kv * hd
    dt = jnp.dtype(dtype)
    k = jnp.asarray(rng.standard_normal((LAYERS, b, ROWS, w)), dt)
    v = jnp.asarray(rng.standard_normal((LAYERS, b, ROWS, w)), dt)
    q = jnp.asarray(rng.standard_normal((b, nh, hd)), dt)
    kv_pos = jnp.asarray(np.broadcast_to(kp, (LAYERS, b, ROWS)), jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)

    got = K.decode_attention(q, k, v, kv_pos, jnp.int32(LAYER), pos,
                             scale=scale, window=window, block=BLOCK,
                             interpret=True)
    want = gqa_attention(
        q[:, None], T.kv_heads(k[LAYER], n_kv), T.kv_heads(v[LAYER], n_kv),
        window=window, q_positions=pos[:, None], kv_positions=kv_pos[LAYER],
        kv_valid=kv_pos[LAYER] >= 0, scale=scale,
    )[:, 0]
    assert got.shape == want.shape and got.dtype == want.dtype
    # float32: the order of the sums; bfloat16: the probabilities rounded
    # before P.V, normalised (XLA) or not (kernel)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_live_blocks_bound():
    """Rows [0, min(pos + 1, T)) in whole blocks, at least one."""
    pos = np.array([0, BLOCK - 1, BLOCK, ROWS - 1, ROWS + 6])
    assert K.live_blocks(pos, ROWS, BLOCK, np).tolist() == [1, 1, 2, 4, 4]
    assert K.block_rows(2048) == 512 and K.block_rows(384) == 128
    assert K.block_rows(96) == 96


def with_kernel(monkeypatch):
    """Choose the kernel, in interpret mode, wherever a cache is read."""
    kernel = functools.partial(K.decode_attention, interpret=True)
    monkeypatch.setattr(T, "decode_kernel", lambda width: kernel)


def tiny(arch, name, **kw):
    """A bf16 CPU cut of ``arch`` under a name of its own, so that no
    jitted program of another test is reused with the other selector."""
    cfg = get_config(arch).reduced(dtype="bfloat16", vocab=256, **kw)
    model = Model(dataclasses.replace(cfg, name=cfg.name + "-" + name))
    return model, model.init(jax.random.PRNGKey(3))


def prefilled(model, params, lens, t=64):
    """A cache of ``len(lens)`` slots prefilled with prompts of ``lens``."""
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 256, (len(lens), max(lens))),
                         jnp.int32)
    _, cache = model.prefill_chunked(
        params, {"tokens": tokens, "lens": jnp.asarray(lens, jnp.int32)},
        cache_len=t, chunk=16)
    return cache


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-4.0-h-micro"])
def test_model_decode_step_with_and_without_kernel(arch, monkeypatch):
    """The kernel only reads the cache, so every leaf that no attention
    output feeds is bit-identical: all of them in a one-layer dense stack
    and in the hybrid's cut, a Mamba-2 layer then an attention layer (a
    later layer's new rows would differ by the attention's rounding)."""
    kw = {"n_layers": 1} if arch == "granite-3-2b" else {}
    model, params = tiny(arch, "decode-step", **kw)
    assert model.cfg.n_layers == 1 or model.cfg.layer_types == (
        "mamba", "attention")
    lens = [5, 17, 30]
    cache = prefilled(model, params, lens)
    tokens = jnp.asarray([[3], [9], [27]], jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    want, want_cache = model.decode(params, cache, tokens, pos)
    with_kernel(monkeypatch)
    got, got_cache = model.decode(params, cache, tokens, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)
    for name in want_cache:
        np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                      np.asarray(want_cache[name]),
                                      err_msg=name)


def test_window_with_a_frozen_slot_serves_the_same_tokens(monkeypatch):
    """Three window steps in which slot 1 has no budget left: it decodes
    frozen at its position, rewriting that row, while the others
    advance."""
    lens = [5, 17, 30]
    state = jnp.asarray([[3, 9, 27], lens, [4, 0, 4]], jnp.int32)
    served = {}
    for name in ("xla", "kernel"):
        if name == "kernel":
            with_kernel(monkeypatch)
        model, params = tiny("granite-3-2b", "frozen-slot-" + name)
        served[name] = E._decode_window_jit(
            model, 3, params, prefilled(model, params, lens), state + 0)
    (_, state_x, toks_x), (_, state_k, toks_k) = served.values()
    np.testing.assert_array_equal(np.asarray(toks_k), np.asarray(toks_x))
    np.testing.assert_array_equal(np.asarray(state_k), np.asarray(state_x))
    assert np.asarray(state_k)[1].tolist() == [8, 17, 33]


def _serve(model, params, n_agents=4):
    from benchmarks.perf_engine import synth_agents

    eng = ServeEngine(model, params, make_scheduler("justitia", 256.0),
                      pool_tokens=512, max_batch=4, cache_len=CACHE_LEN,
                      prefill_chunk=16)
    for a in synth_agents(5, n_agents):
        eng.submit_agent(a)
    windows = []
    real = eng._count_attn_rows

    def count(k, snapshot, pf):
        windows.append((k, eng.slot_pos.copy(),
                        {s: r.max_new_tokens - r.generated
                         for s, r in snapshot}))
        real(k, snapshot, pf)

    eng._count_attn_rows = count
    while eng.busy or eng.pending or eng._resumes:
        if not eng.busy:
            eng.now = max(eng.now, int(eng._next_wake(eng.now)))
        eng.step()
    return eng, windows


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_engine_counts_the_rows_decode_attention_reads(kernel, monkeypatch):
    """Per window and step, a slot at position p reads ceil(min(p + 1, T)
    / block) blocks with the kernel and T rows without; a slot whose
    budget ran out mid-window stays at its last position."""
    block, t, b = 32, CACHE_LEN, 4
    monkeypatch.setattr(K, "block_rows", lambda rows: block)
    if kernel:
        with_kernel(monkeypatch)
    model, params = tiny("granite-3-2b", "counter-" + str(kernel))
    eng, windows = _serve(model, params)
    read = reserved = 0
    finished_mid_window = False
    for k, slot_pos, rem in windows:
        for i in range(k):
            for slot in range(b):
                left = rem.get(slot, 0)
                finished_mid_window |= 0 < left < k and i == left
                p = slot_pos[slot] + min(i, left)
                rows = min(p + 1, t)
                read += -(-rows // block) * block if kernel else t
        reserved += k * b * t
    assert finished_mid_window
    assert eng.metrics["attn_rows_reserved"] == reserved
    assert eng.metrics["attn_rows_read"] == read
    assert (read < reserved) == kernel
