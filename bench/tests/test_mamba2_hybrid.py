"""The ``mamba2_hybrid`` family through the harness, at a CPU size:
``run.run_cell`` serves the granite-4.0-h-micro configuration with its
widths cut, checks it against the family's plain reference and reads the
metrics, with the chip check skipped as in ``conftest.tiny``."""

import json

import numpy as np
import pytest

import devtrace
import run
from conftest import BENCH, DATA

CONFIG = "granite-4.0-h-micro"

#: widths cut to a CPU size; the layer pattern keeps both kinds twice
SMALL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=128, intermediate_size=128, vocab_size=256,
    mamba_n_heads=2, mamba_d_state=16,
    layer_types=["mamba", "attention", "mamba"] * 2, num_hidden_layers=6,
    engine={"max_batch": 4, "cache_len": 512, "pool_tokens": 2048,
            "prefill_chunk": 128, "max_window": 8, "block_size": 16},
)
#: the logit-gap limit at this size, from four seeds on the CPU: the
#: program's widest gap 1.2e-4, the float8 control's narrowest 9.4e-4
LIMIT = 3e-4


@pytest.fixture
def tiny_hybrid(monkeypatch):
    import jax

    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(run, "load_peaks", lambda: {
        jax.devices()[0].device_kind: {"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11}})
    cfg = dict(run.load_config(CONFIG), **SMALL)
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {"name": CONFIG + ".agents-backlog", "config": CONFIG,
            "traffic": "tiny-mix", "chips": 1}

    def serve(seed, limit, trace=False):
        c = dict(cfg, check=dict(cfg["check"], logit_gap_limit=limit))
        return run.run_cell(bench, cell, c, mix, seed, 3.0, trace)

    return serve


def test_a_run_is_correct_and_reads_its_metrics(tiny_hybrid, monkeypatch):
    """A traced run: bf16 served against the float32 reference within the
    limit, every fault count 0, and the prefill counters read."""
    real = devtrace.extract

    def with_device(path):
        ex = real(path)
        (win,) = [s for s in ex["spans"] if s[0] == "traced"]
        half = [win[1], win[2] // 2]
        ex["devices"]["/device:TPU:0"] = {
            "ops": [half], "programs": [["jit__prefill_write_jit", *half]]}
        return ex

    monkeypatch.setattr(devtrace, "extract", with_device)
    res = tiny_hybrid(4100000125, limit=LIMIT, trace=True)
    assert res["correct"], res["checks"]
    assert res["diagnostics"]["checked_tokens"] > 0
    m = res["metrics"]
    assert 0.0 <= m["prefill_pad_frac"]["value"] < 1.0
    assert m["prefill_token_us"]["value"] > 0.0
    assert m["host_gap_ms"]["value"] > 0.0
    assert m["prefill_pass_ms"]["value"] > 0.0


def test_the_limit_tells_a_wrong_state(tiny_hybrid, monkeypatch):
    """A served path that drops each row's conv inputs between prefill
    slices and decode steps reads not correct.  The engine's programs are
    cached by model, so the caches are cleared around the planted fault."""
    import jax

    from repro.models import ssm

    project = ssm._mamba2_project

    def forget(p, x, conv_state=None, n_valid=None):
        return project(p, x, None, n_valid)

    monkeypatch.setattr(ssm, "_mamba2_project", forget)
    jax.clear_caches()
    try:
        res = tiny_hybrid(4100000126, limit=LIMIT)
    finally:
        jax.clear_caches()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_counts_hold_the_state_of_each_live_slot():
    cfg = run.load_config(CONFIG)
    family = run.load_family(cfg)
    dims = family.Dims.from_config(cfg)
    assert (dims.n_mamba, dims.n_attn, dims.d_inner) == (36, 4, 4096)
    # 36 layers of a 64 x 64 x 128 float32 state and 3 x 4352 bf16 inputs
    assert family.state_bytes(dims) == 36 * (64 * 64 * 128 * 4
                                             + 3 * 4352 * 2)
    kv = family.kv_bytes_per_position(dims)
    assert kv == 8 * 1024
    one = family.decode_bytes(dims, np.array([99]), steps=1)
    assert one == (family.weight_bytes(dims) + 2 * family.state_bytes(dims)
                   + 100 * kv + kv)
    # 3.19 B weights at bf16, as the model card's size gives
    assert 6.3e9 < family.weight_bytes(dims) < 6.5e9
