"""The control comes out not correct: the reference computed in float8,
put in the program's place, reads gaps above the limit.

At the CPU-sized configuration of ``data/tiny.json`` and on three seeds:
the widest gap of the tokens the float8 reference puts first, under the
float32 reference, exceeds the tiny configuration's limit (the chip's
readings for the real cells are in PERF.md).
"""

import json

import pytest

from conftest import DATA

LIMIT = json.loads((DATA / "tiny.json").read_text())["check"][
    "logit_gap_limit"]


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4000000999])
def test_float8_control_fails_the_limit(tiny, seed):
    res = tiny(seed, limit=LIMIT, control=True)
    assert res["control"]["control_gap"] > LIMIT
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
