"""The benchmark's own tests (``pytest bench/tests``): its modules import
each other by name from ``bench/``, and the program from ``src/``."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import json  # noqa: E402

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny(monkeypatch):
    """Serve a CPU-sized model through ``run.run_cell``, the chip check
    skipped: ``tiny(seed, seconds, limit=..., control=...)`` returns the result
    dict."""
    import jax

    import run

    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(run, "load_peaks", lambda: {
        jax.devices()[0].device_kind: {"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11}})
    cfg = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny-mix",
            "chips": 1}

    def serve(seed, seconds=3.0, limit=None, trace=False, control=False):
        c = dict(cfg, check=dict(cfg["check"], logit_gap_limit=limit))
        return run.run_cell(bench, cell, c, mix, seed, seconds, trace,
                            control)

    return serve
