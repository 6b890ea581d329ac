"""Window arithmetic and the metric readers, on hand-worked runs."""

import numpy as np
import pytest

import loadgen
import run
import stats

dense = run.load_module(run.BENCH / "reference", "dense")

DIMS = dense.Dims(2, 8, 2, 1, 4, 16, 10, 1e4, 1e-5, True, 0, "bfloat16")
agents = run.load_module(run.BENCH / "traffic", "agents")


def tiny_traffic():
    """Agent 0 has stages of two requests and one, agents 1 and 2 one
    request each."""
    p = lambda n: np.arange(n, dtype=np.int32) % 10
    return agents.Traffic("open", [
        agents.Agent("EV", [[(p(5), 3), (p(6), 2)], [(p(7), 1)]]),
        agents.Agent("FV", [[(p(8), 4)]]),
        agents.Agent("FV", [[(p(4), 2)]]),
    ], arrivals=[1.0, 8.0, 11.0])


def read(name, r):
    return run.load_module(run.BENCH / "metrics", name).read(r)


def test_percentile_by_hand():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    # rank 0.9 * 9 = 8.1 between 9 and 10
    assert stats.percentile(range(1, 11), 90) == pytest.approx(9.1)
    assert stats.percentile([], 90) is None


def make_run(seconds=10.0):
    """Window [100, 110).  Agent 0 (due 101): stage 0 has requests 0 and 1,
    stage 1 has request 2; agent 1 (due 108) has request 3, ready inside
    the window and streaming past it; agent 2 (due 111) is outside."""
    R = loadgen.Req
    reqs = {
        0: R(0, 0, 0, ready=101.0, admit=101.5, first_token=1,
             tokens=[1, 2, 3], stamps=[102.0, 102.5, 103.0]),
        1: R(0, 0, 1, ready=101.0, admit=102.0, first_token=1,
             tokens=[4, 5], stamps=[103.0, 104.0]),
        2: R(0, 1, 2, ready=104.0, admit=104.0, first_token=1,
             tokens=[6], stamps=[105.0]),
        3: R(1, 0, 3, ready=108.0, admit=109.0, first_token=1,
             tokens=[7, 8, 9, 1], stamps=[109.5, 110.5, 111.5, 112.5]),
        4: R(2, 0, 4, ready=111.0, admit=111.0, first_token=1,
             tokens=[2, 3], stamps=[111.5, 112.0]),
    }
    agents = [
        loadgen.AgentRun(0, 101.0, 101.0, [101.0, 104.0], done=105.0,
                        rids=[0, 1, 2]),
        loadgen.AgentRun(1, 108.0, 108.1, [108.0], done=112.5, rids=[3]),
        loadgen.AgentRun(2, 111.0, 111.0, [111.0], done=112.0, rids=[4]),
    ]
    c0 = {"decode_steps": 0, "windows": 0, "tokens": 0}
    c1 = {"decode_steps": 12, "windows": 4, "tokens": 24}
    served = loadgen.Served(t0=100.0, seconds=seconds, t_close=110.2,
                           t_end=112.5, agents=agents, reqs=reqs,
                           counters_open=c0, counters_close=c1,
                           counters_end=dict(c1, tokens=12),
                           compiles_in_window=0,
                           trace_window=(100.0, 110.2),
                           counters_trace=(c0, c1))
    return stats.Run(served=served, traffic=tiny_traffic(), family=dense,
                     dims=DIMS,
                     engine={"max_batch": 4}, peak={}, setup_s=3.5,
                     memory={"peak_bytes_in_use": 3, "bytes_limit": 4})


def test_end_to_end_by_hand():
    r = make_run()
    # tokens stamped in [100, 110): 3 + 2 + 1 + 1
    assert read("tokens_per_s", r) == pytest.approx(7 / 10)
    # agents due in the window: 0 (jct 4) and 1 (jct 4.5)
    assert read("jct_mean_s", r) == pytest.approx(4.25)
    # ttft over requests 0..3: 1, 2, 1, 1.5 -> p90 at rank 2.7
    assert read("ttft_p90_s", r) == pytest.approx(1.5 + 0.7 * 0.5)
    # tpot over requests with >= 2 tokens: 500, 1000, 1000 ms
    assert read("tpot_p90_ms", r) == pytest.approx(1000.0)
    assert read("setup_s", r) == 3.5


def test_counters_and_queue_wait_by_hand():
    r = make_run()
    assert read("steps_per_window", r) == 3.0
    assert read("slot_occupancy", r) == pytest.approx(24 / (12 * 4))
    # waits 0.5, 1.0, 0.0, 1.0 -> p90 at rank 2.7 of [0, .5, 1, 1]
    assert read("queue_wait_p90_s", r) == pytest.approx(1.0)
    assert read("hbm_peak_frac", r) == 0.75


def test_trace_metrics_stay_silent_without_a_trace():
    r = make_run()
    for name in ("decode_step_ms", "decode_roofline", "step_mfu",
                 "device_idle_frac"):
        assert read(name, r) is None


def test_decode_roofline_by_hand():
    """Tokens of the traced window [100, 110.2) at their fed positions."""

    r = make_run()
    r.trace = {"programs": {"jit__decode_window_jit": 2.0,
                            "jit__prefill_write_jit": 1.0},
               "window_s": 10.2, "inflight_s": 8.0, "idle_inflight_s": 2.0,
               "busy_s": 3.0, "idle_by_span": {}}
    r.peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert read("decode_step_ms", r) == pytest.approx(2.0 / 12 * 1e3)
    assert read("device_idle_frac", r) == 0.25
    plen = {rid: len(r.request_spec(q)[0]) for rid, q in r.served.reqs.items()}
    ctx = np.array([plen[0], plen[0] + 1, plen[0] + 2, plen[1], plen[1] + 1,
                    plen[2], plen[3]])
    t = stats.roofline_seconds(dense.decode_flops(DIMS, ctx),
                               dense.decode_bytes(DIMS, ctx, 12), r.peak)
    assert read("decode_roofline", r) == pytest.approx(100 * t / 2.0)
    # prompts admitted inside the window: requests 0..3
    flops = dense.prefill_flops(DIMS, [plen[i] for i in range(4)]) + \
        dense.decode_flops(DIMS, ctx)
    assert read("step_mfu", r) == pytest.approx(100 * flops / (10.2 * 1e9))
