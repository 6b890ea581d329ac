"""``run.py`` refuses to measure anything but a TPU, and needs the program."""

import os
import shutil
import subprocess
import sys

import pytest

import run

CMD = [sys.executable, "bench/run.py", "--workload",
       "granite-3-2b.agents-burst", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def call(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_with_no_result_on_a_cpu():
    p = call(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = call(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no-such-cell")


@pytest.mark.parametrize("config", ["granite-3-2b", "h2o-danube-1.8b"])
def test_a_configuration_names_its_family_module(config):
    """The harness finds a configuration's sizes, weights, reference and
    counts in ``reference/<family>.py``, by the name the file gives."""
    cfg = run.load_config(config)
    family = run.load_family(cfg)
    assert family is run.load_family(cfg)
    dims = family.Dims.from_config(cfg)
    assert dims.vocab == cfg["vocab_size"]
    for name in ("program_fields", "make_params", "token_gaps",
                 "decode_flops", "decode_bytes", "prefill_flops"):
        assert callable(getattr(family, name)), name
