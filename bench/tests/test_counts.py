"""FLOP and byte counts of the dense family, from shapes and live token
positions."""

import dataclasses

import numpy as np
import pytest

import run
import stats

counts = run.load_module(run.BENCH / "reference", "dense")
Dims = counts.Dims


@pytest.fixture
def granite():
    return Dims.from_config(run.load_config("granite-3-2b"))


@pytest.mark.parametrize("n", [1, 17, 300, 1500])
def test_decode_reads_the_live_context_only(granite, n):
    """A token fed at position n - 1 reads n positions of keys and values
    (itself included) and writes its own: never the cache reservation."""
    kv = counts.kv_bytes_per_position(granite)
    extra = counts.decode_bytes(granite, n - 1, steps=1) - counts.weight_bytes(
        granite)
    assert extra == (n + 1) * kv


def test_kv_bytes_by_hand(granite):
    # 40 layers x K and V x 8 heads x 64 x 2 bytes = 80 KiB per position
    assert counts.kv_bytes_per_position(granite) == 80 * 1024


def test_weights_read_once_per_step(granite):
    one = counts.decode_bytes(granite, np.array([10, 20]), steps=1)
    two = counts.decode_bytes(granite, np.array([10, 20]), steps=2)
    assert two - one == counts.weight_bytes(granite)


def test_window_caps_attended_positions():
    d = Dims(2, 8, 2, 1, 4, 16, 10, 1e4, 1e-5, True, 5, "bfloat16")
    assert counts.attended(d, 2) == 3
    assert counts.attended(d, 100) == 5


def test_flops_by_hand():
    d = Dims(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-5,
             tie_embeddings=True, sliding_window=0, dtype="bfloat16")
    per_layer = 8 * 4 * (2 * 2 + 2 * 1) + 3 * 8 * 16      # 192 + 384
    assert d.matmul_params_per_layer() == per_layer
    # decode at position 3 attends 4 positions
    want = 2 * (2 * per_layer + 8 * 10) + 4 * 2 * 2 * 4 * 4
    assert counts.decode_flops(d, 3) == want
    # a 3-token prompt: 1 + 2 + 3 query-key pairs, head at the last token
    want = 2 * 2 * per_layer * 3 + 4 * 2 * 2 * 4 * 6 + 2 * 8 * 10
    assert counts.prefill_flops(d, [3]) == want
    w = dataclasses.replace(d, sliding_window=2)
    # pairs with a window of 2: 1 + 2 + 2
    assert counts.prefill_flops(w, [3]) == want - 4 * 2 * 2 * 4 * 1


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert stats.roofline_seconds(1000.0, 50.0, peak) == 10.0
    assert stats.roofline_seconds(100.0, 50.0, peak) == 5.0
