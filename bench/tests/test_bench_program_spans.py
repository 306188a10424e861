"""The program's spans on the trace's clock, and the four readers built on
them, against a synthetic trace whose idle seconds and roofline are known
exactly."""

import sys
from types import SimpleNamespace

import pytest

from bench import harness, program_spans
from bench import trace as tr

#: trace ns = perf_counter s x 1e9 + OFFSET_NS
OFFSET_NS = -9_000_000_000 + 123_456
CFG = {"num_attention_heads": 4, "num_key_value_heads": 2, "hidden_size": 512,
       "num_hidden_layers": 2, "torch_dtype": "bfloat16"}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
NEW = ("serve.prefill_row_util", "serve.sample_idle_share",
       "serve.cache_write_idle_share", "serve.decode_attn_roofline")


def ns(t):
    return round(t * 1e9) + OFFSET_NS


def read(name, run):
    return harness.plugin("metrics", name).read(run)


# the benchmark's calls (perf_counter s); the first and last were not traced
STEPS = [("step", 9.000, 9.010, []), ("admit", 10.000, 10.010, []),
         ("step", 10.020, 10.030, []), ("step", 10.040, 10.060, []),
         ("step", 10.070, 10.080, [])]

# the program's spans, in order of their ends as the recorder keeps them
PROGRAM = [
    ("engine.decode", 9.002, 9.008, {"slots": 1, "kv_rows": 7}),
    ("engine.write_ragged", 10.002, 10.004, {"n": 1}),
    ("engine.sample", 10.005, 10.008, {"n": 1}),
    ("engine.admit", 10.001, 10.009, {"n": 1, "rows_real": 100, "rows_computed": 512}),
    ("engine.sample", 10.025, 10.028, {"n": 2}),
    ("engine.decode", 10.021, 10.029, {"slots": 2, "kv_rows": 1000}),
    ("engine.sample", 10.048, 10.049, {"n": 0}),
    ("engine.chunk_wave", 10.041, 10.050, {"slots": 1, "rows_real": 200,
                                           "rows_computed": 1024}),
    ("engine.sample", 10.056, 10.058, {"n": 1}),
    ("engine.decode", 10.051, 10.059, {"slots": 1, "kv_rows": 500}),
    ("engine.chunk_wave", 10.071, 10.079, {"slots": 1, "rows_real": 1,
                                           "rows_computed": 1}),
]


def synthetic_trace():
    ops = [("prefill_ragged.1", 10.0015, 10.003, "op"),       # idle 10.003-.004 in the write
           ("copy.2", 10.003, 10.003, "op"),
           ("fusion.3", 10.0045, 10.006, "op"),               # idle 10.006-.008 in the sample
           ("flash_decode.1", 10.022, 10.026, "kernel"),
           ("decode_combine.1", 10.026, 10.0265, "kernel"),   # idle 10.0265-.028
           ("fusion.4", 10.042, 10.0485, "op"),               # idle 10.0485-.049
           ("flash_attention.2", 10.043, 10.045, "kernel"),   # another kernel: not counted
           ("flash_decode.1", 10.052, 10.057, "kernel"),      # idle 10.057-.058
           ("while.5", 10.050, 10.059, "control")]
    devices = {"/device:TPU:0": [(n, ns(s), ns(e), k) for n, s, e, k in ops]}
    spans = [(k, ns(s), ns(e), k) for k, s, e, _ in STEPS[1:4]]
    return tr.Trace(0.1, devices, spans)


def synthetic_run(monkeypatch, dropped=0, program=PROGRAM):
    monkeypatch.setattr(program_spans, "recorded", lambda: (list(program), dropped))
    return SimpleNamespace(trace_result=synthetic_trace(), records={"steps": list(STEPS)},
                           window=(9.5, 10.065), peaks=PEAKS, config=CFG,
                           bench=harness.BENCH, devices=[0])


def test_clock_recovers_a_planted_offset(monkeypatch):
    run = synthetic_run(monkeypatch)
    offset, residual = program_spans.clock(run)
    assert offset == pytest.approx(OFFSET_NS, abs=1)
    assert residual == pytest.approx(0, abs=1)
    # a trace stamped 20 us late on one call: the residual shows it
    late = [(n, s + (20_000 if n == "admit" else 0), e + (20_000 if n == "admit" else 0), lb)
            for n, s, e, lb in run.trace_result.spans]
    run.trace_result = tr.Trace(0.1, run.trace_result.devices, late)
    assert program_spans.clock(run)[1] == pytest.approx(20_000, abs=1)


def test_program_spans_map_into_the_traced_calls(monkeypatch):
    run = synthetic_run(monkeypatch)
    got = program_spans.on_trace(run, ("engine.decode",))
    # the decode before the trace began is left out
    assert [(s, e, a["kv_rows"]) for _, s, e, a in got] == [
        (ns(10.021), ns(10.029), 1000), (ns(10.051), ns(10.059), 500)]


def test_idle_shares_are_exact(monkeypatch):
    run = synthetic_run(monkeypatch)
    # idle in the samples: 2 + 1.5 + 0.5 + 1 ms of the 0.1 s window
    assert read("serve.sample_idle_share", run) == pytest.approx(5.0, abs=1e-6)
    # idle in the one cache write: 1 ms
    assert read("serve.cache_write_idle_share", run) == pytest.approx(1.0, abs=1e-6)


def test_idle_shares_lie_within_the_device_idle_share(monkeypatch):
    run = synthetic_run(monkeypatch)
    total = 100.0 * (1.0 - run.trace_result.busy_s() / run.trace_result.window_s)
    parts = read("serve.sample_idle_share", run) + read("serve.cache_write_idle_share", run)
    assert 0 < parts <= total


def test_decode_roofline_is_exact(monkeypatch):
    run = synthetic_run(monkeypatch)
    work = harness.plugin("work", "flash_decode").work
    # K and V rows of 2 KV heads of 128 in bf16, plus q and out, 2 layers
    assert work(CFG, 2, 1000)["bytes"] == 2 * (1000 * 2 * 2 * 128 * 2 + 2 * 2 * 4 * 128 * 2)
    least = (2 * (1000 * 1024 + 4096) + 2 * (500 * 1024 + 2048)) / 1e9
    kernels = (4.0 + 0.5 + 5.0) / 1e3
    assert read("serve.decode_attn_roofline", run) == pytest.approx(100 * least / kernels)


def test_prefill_row_util_counts_spans_starting_in_the_window(monkeypatch):
    run = synthetic_run(monkeypatch)
    run.trace_result = None  # host counters: no trace needed
    assert read("serve.prefill_row_util", run) == pytest.approx(100 * 300 / 1536)


def test_nothing_is_read_when_window_spans_were_dropped(monkeypatch):
    # the oldest span kept ends inside the window: those pushed out may too
    run = synthetic_run(monkeypatch, dropped=5, program=PROGRAM[1:])
    for name in NEW:
        assert read(name, run) is None, name
    # the oldest kept ends before the window: all pushed out ended before it
    run = synthetic_run(monkeypatch, dropped=5)
    assert read("serve.sample_idle_share", run) == pytest.approx(5.0, abs=1e-6)


def test_nothing_is_read_without_the_recorder(monkeypatch):
    import repro.utils

    # a program without the module: the import fails
    monkeypatch.delattr(repro.utils, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    assert program_spans.recorded() is None
    run = SimpleNamespace(trace_result=synthetic_trace(), records={"steps": list(STEPS)},
                          window=(9.5, 10.065), peaks=PEAKS, config=CFG,
                          bench=harness.BENCH, devices=[0])
    for name in NEW:
        assert read(name, run) is None, name


def test_no_decode_kernel_event_reads_nothing(monkeypatch):
    run = synthetic_run(monkeypatch)
    dev = run.trace_result.devices["/device:TPU:0"]
    run.trace_result.devices["/device:TPU:0"] = [e for e in dev if "decode" not in e[0]]
    assert read("serve.decode_attn_roofline", run) is None


def test_prefill_row_util_end_to_end(drive, tiny_chat):
    from repro.utils import spans

    cfg, traffic = tiny_chat
    run = drive("qwen2-7b-8l.chat", config=cfg, traffic=traffic)
    util = read("serve.prefill_row_util", run)
    assert 0 < util <= 100
    # every admission the server made holds one engine.admit span whose
    # prompt rows are the heads the server saw admitted
    admits = [s for s in spans.spans() if s[0] == "engine.admit"]
    for kind, t0, t1, segs in run.records["steps"]:
        if kind == "admit":
            inside = [a for _, s, e, a in admits if t0 <= s and e <= t1]
            assert len(inside) == 1
            assert inside[0]["rows_real"] == sum(n for n, _, _ in segs)
