"""``attn_read_frac`` on hand-made runs: the engine's attention row
counters over the window, and silence where a program keeps none."""

import dataclasses

import pytest

from test_stats import make_run, read


def with_counters(run, opened, closed):
    served = dataclasses.replace(
        run.served,
        counters_open=dict(run.served.counters_open, **opened),
        counters_close=dict(run.served.counters_close, **closed))
    return dataclasses.replace(run, served=served)


def test_share_of_reserved_rows_read():
    # 12 steps of 4 slots x 2048 rows; the slots read 512 to 1,536 rows
    r = with_counters(make_run(),
                      {"attn_rows_read": 5_000, "attn_rows_reserved": 8_192},
                      {"attn_rows_read": 5_000 + 40_960,
                       "attn_rows_reserved": 8_192 + 12 * 4 * 2048})
    assert read("attn_read_frac", r) == pytest.approx(40_960 / 98_304)


def test_silent_without_counters_or_steps():
    assert read("attn_read_frac", make_run()) is None
    idle = with_counters(make_run(),
                         {"attn_rows_read": 7, "attn_rows_reserved": 9},
                         {"attn_rows_read": 7, "attn_rows_reserved": 9})
    assert read("attn_read_frac", idle) is None
