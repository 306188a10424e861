"""The DeepSeekMoE cell on the CPU at a tiny size: driven end to end, its
control, the program it refuses, and the MoE readers against a synthetic
trace whose roofline is known exactly."""

import copy
import json
from types import SimpleNamespace

import pytest

from bench import harness, program_spans
from bench import trace as tr

CELL = "deepseek-moe-16b-7l.code"


@pytest.fixture
def tiny_moe():
    """The MoE cell's configuration and traffic at a size the CPU holds:
    every width cut, 8 held experts of 16 (top-4, one shared), the
    engine's slots and rings too, and a short window."""
    cfg = json.loads(harness.find("configs", "deepseek-moe-16b-7l", ".json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
               vocab_size=256, initializer_range=0.2, n_routed_experts=8,
               num_experts_per_tok=4, n_shared_experts=1)
    cfg["experts_held"] = {"first": 4, "count": 8, "of": 16}
    cfg["assumed"] = {"batch_slots": 4, "s_max": 256, "chunk": 32, "prompt_bucket": 32}
    # the limit at this size, set as on the chip from readings on the CPU:
    # the program read at most 0.059 (seeds 7, 21-24), the control at
    # least 0.109
    cfg["limits"] = {"served_logit_gap": 0.08}
    traffic = json.loads(harness.find("traffic", "code", ".json").read_text())
    traffic.update(rate_per_s=8, lead_in_s=0.5, drain_s=10, max_wave=2, check_tokens=30,
                   trace_seconds=0.5,
                   prompt={"median": 40, "sigma": 0.6, "min": 8, "max": 100},
                   output={"median": 5, "sigma": 0.8, "min": 2, "max": 16})
    return cfg, traffic


def test_moe_cell_runs_correct_and_counts_its_rows(drive, tiny_moe):
    cfg, traffic = tiny_moe
    run = drive(CELL, config=cfg, traffic=traffic)
    assert run.correct, run.checks
    assert run.attempted > 0 and run.failed == 0
    util = harness.plugin("metrics", "moe.routed_row_util").read(run)
    assert 0 < util <= 100


def test_moe_control_reads_wider_than_the_program(drive, tiny_moe):
    cfg, traffic = tiny_moe
    run = drive(CELL, config=cfg, traffic=traffic, seconds=1.0)
    rows = harness.plugin("runners", "serve_moe").readings(run, [21, 22, 23], lambda _m: None)
    for row in rows:
        assert row["unfinished"] == 0, row
        assert row["program_passes"] and row["program"] <= cfg["limits"]["served_logit_gap"], row
        assert not row["control_passes"] and row["control"] > row["limit"], row


def test_a_program_without_the_expert_share_layer_is_refused(tiny_moe, monkeypatch):
    from repro.models import moe

    monkeypatch.delattr(moe, "moe_held")
    with pytest.raises(ImportError):
        harness.plugin("runners", "serve_moe").program_config(tiny_moe[0])


# -- the readers against a synthetic trace ---------------------------------------

OFFSET_NS = 5_000
CFG = {"hidden_size": 256, "moe_intermediate_size": 128, "torch_dtype": "bfloat16"}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
STEPS = [("admit", 1.000, 1.010, []), ("step", 1.020, 1.030, [])]
PROGRAM = [
    ("engine.admit", 1.001, 1.009, {"n": 1, "rows_real": 60, "rows_computed": 64,
                                    "moe_rows_real": 100, "moe_rows_computed": 128,
                                    "moe_groups": 3}),
    ("engine.decode", 1.021, 1.029, {"slots": 2, "kv_rows": 90, "moe_rows_real": 4,
                                     "moe_rows_computed": 32, "moe_groups": 2}),
]


def ns(t):
    return round(t * 1e9) + OFFSET_NS


def moe_run(monkeypatch, program=PROGRAM):
    ops = [("gather_rows_blocked.1", 1.0010, 1.0015, "kernel"),   # feeds the first gmm
           ("grouped_swiglu.2", 1.0020, 1.0040, "kernel"),
           ("gather_combine_blocked.3", 1.0040, 1.0045, "kernel"),
           ("gather_rows_blocked.4", 1.0100, 1.0120, "kernel"),   # a KV unpack: not counted
           ("gather_rows_blocked.5", 1.0210, 1.0212, "kernel"),
           ("fusion.6", 1.0212, 1.0215, "op"),
           ("grouped_swiglu.7", 1.0215, 1.0235, "kernel"),
           ("gather_combine_blocked.8", 1.0235, 1.0236, "kernel")]
    devices = {"/device:TPU:0": [(n, ns(s), ns(e), k) for n, s, e, k in ops]}
    spans = [(k, ns(s), ns(e), k) for k, s, e, _ in STEPS]
    monkeypatch.setattr(program_spans, "recorded", lambda: (list(program), 0))
    return SimpleNamespace(trace_result=tr.Trace(0.05, devices, spans),
                           records={"steps": list(STEPS)}, window=(0.99, 1.05), peaks=PEAKS,
                           config=CFG, bench=harness.BENCH, devices=[0])


def read(name, run):
    return harness.plugin("metrics", name).read(run)


def test_gmm_roofline_is_exact(monkeypatch):
    run = moe_run(monkeypatch)
    d, f = 256, 128
    admit = max(2 * 3 * d * f * 100 / 1e12, (3 * 3 * d * f * 2 + 2 * 100 * d * 2) / 1e9)
    decode = max(2 * 3 * d * f * 4 / 1e12, (2 * 3 * d * f * 2 + 2 * 4 * d * 2) / 1e9)
    assert read("moe.gmm_roofline", run) == pytest.approx(100 * (admit + decode) / 4e-3)


def test_dispatch_roofline_counts_only_the_gathers_that_fed_the_kernel(monkeypatch):
    run = moe_run(monkeypatch)
    least = 4 * (100 + 4) * 256 * 2 / 1e9
    kernels = (0.5 + 0.5 + 0.2 + 0.1) / 1e3
    assert read("moe.dispatch_roofline", run) == pytest.approx(100 * least / kernels)


def test_routed_row_util_reads_the_counts(monkeypatch):
    run = moe_run(monkeypatch)
    run.trace_result = None  # host counters: no trace needed
    assert read("moe.routed_row_util", run) == pytest.approx(100 * 104 / 160)


def test_moe_readers_read_nothing_without_the_counts(monkeypatch):
    plain = [(n, s, e, {k: v for k, v in a.items() if not k.startswith("moe_")})
             for n, s, e, a in PROGRAM]
    run = moe_run(monkeypatch, program=plain)
    for name in ("moe.gmm_roofline", "moe.dispatch_roofline", "moe.routed_row_util"):
        assert read(name, run) is None, name
    run = moe_run(monkeypatch)
    monkeypatch.setattr(program_spans, "recorded", lambda: None)  # no recorder at all
    for name in ("moe.gmm_roofline", "moe.dispatch_roofline", "moe.routed_row_util"):
        assert read(name, run) is None, name


def test_the_saturate_mix_is_the_chat_mix_above_the_knee():
    chat, sat = (harness.load_json("traffic", n) for n in ("chat", "saturate"))
    assert sat["rate_per_s"] == 2.4 and sat["drain_s"] == 90
    same = {k: v for k, v in sat.items() if k not in ("rate_per_s", "drain_s", "loop")}
    assert same == {k: v for k, v in copy.deepcopy(chat).items()
                    if k not in ("rate_per_s", "drain_s", "loop")}
