"""The metric arithmetic: a rate is taken over all the work and the
whole window, a tail over all requests."""

from types import SimpleNamespace

import pytest

from bench import harness, stats


def read(name, run):
    return harness.plugin("metrics", name).read(run)


def test_percentile():
    assert stats.percentile([], 90) is None
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile(list(range(11)), 90) == pytest.approx(9.0)
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)


def test_library_rate_is_over_the_whole_window():
    ops = [{"work": {"bytes": 1e9}}, {"work": {"bytes": 3e9}}]
    # (op, t_call, t_dispatched, t_done); the window runs to the last completion
    calls = [(0, 0.0, 0.1, 1.0), (1, 1.0, 1.1, 2.0), (0, 2.0, 2.1, 4.0)]
    run = SimpleNamespace(records={"calls": calls, "ops": ops}, window=(0.0, 4.0))
    assert read("lib_gbps", run) == pytest.approx(5.0 / 4.0)
    assert read("lib.host_us_per_call", run) == pytest.approx(0.1e6)


def serving_run():
    tokens = {0: [1.5, 2.0, 2.5], 1: [3.0, 9.5, 10.5], 2: [12.0]}
    return SimpleNamespace(
        window=(1.0, 11.0),
        records={"tokens": tokens, "due": {0: 1.0, 1: 2.0, 2: 10.0},
                 "admitted": {0: 1.2, 1: 2.5, 2: 11.0}, "in_window": [0, 1, 2]},
    )


def test_serving_rate_counts_tokens_inside_the_window():
    # 3 + 3 tokens fall in [1, 11): the one at 10.5 counts, 12.0 does not
    assert read("tokens_per_s", serving_run()) == pytest.approx(6 / 10.0)


def test_tails_are_over_all_requests():
    run = serving_run()
    # first tokens 0.5, 1.0 and 2.0 s after due, the late one included
    assert read("ttft_p50_ms", run) == pytest.approx(1000)
    gaps = [500, 500, 6500, 1000]
    assert read("itl_p99_ms", run) == pytest.approx(stats.percentile(gaps, 99))
    assert read("serve.queue_p50_ms", run) == pytest.approx(500)


def test_readers_find_nothing_without_records():
    empty = SimpleNamespace(records={}, trace_result=None, window=(0.0, 1.0), setup_s=3.0)
    for m in ("lib_gbps", "tokens_per_s", "ttft_p50_ms", "itl_p99_ms",
              "lib.kernel_roofline", "lib.idle_share", "serve.step_idle_share"):
        assert read(m, empty) is None, m
    assert read("setup_s", empty) == 3.0


def test_roofline_is_over_every_device_op():
    from bench import trace as tr

    # 819 bytes a call at 819 GB/s: 1 ns of least time each, two calls
    ops = [{"work": {"bytes": 819, "flops": 0}}]
    calls = [(0, 0.0, 0.0, 1.0), (0, 1.0, 1.0, 2.0)]
    # 2 ns in the kernel and 2 ns in an XLA op: the share is 2 / 4, not 2 / 2
    trace = tr.Trace(1.0, {"/device:TPU:0": [("k.1", 0, 1, "kernel"), ("reshape.2", 1, 2, "op"),
                                             ("k.1", 5, 6, "kernel"), ("reshape.2", 6, 7, "op")]},
                     [])
    run = SimpleNamespace(records={"calls": calls, "ops": ops}, trace_result=trace,
                          devices=[0], peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert read("lib.kernel_roofline", run) == pytest.approx(50.0)
