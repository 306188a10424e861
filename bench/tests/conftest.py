"""Fixtures of the benchmark's CPU tests: tiny cells driven end to end
without a chip, and the benchmark's directory layout."""

import copy
import json
import time

import pytest

from bench import harness


@pytest.fixture
def tiny_lib():
    """A library mix of every op at a size the CPU holds."""
    return {"ops": [
        {"op": "copy", "shape": [64, 256], "dtype": "float32"},
        {"op": "permute", "shape": [2, 32, 4, 128], "dtype": "bfloat16", "perm": [0, 2, 1, 3]},
        {"op": "permute", "shape": [16, 16, 128], "dtype": "float32", "perm": [2, 1, 0]},
        {"op": "interlace", "n": 4, "length": 2048, "dtype": "float32"},
        {"op": "deinterlace", "n": 4, "length": 2048, "dtype": "float32"},
        {"op": "gather_rows", "shape": [256, 128], "dtype": "bfloat16"},
        {"op": "stencil", "shape": [32, 128], "dtype": "float32",
         "offsets": [[1, 0], [-1, 0], [0, 1], [0, -1]], "weights": [0.25] * 4,
         "repeat": 3, "boundary": "reflect"},
    ]}


@pytest.fixture
def tiny_chat():
    """The chat cell's configuration and traffic at a size the CPU holds:
    every width cut, the engine's slots and rings too, and a short window."""
    cfg = json.loads(harness.find("configs", "qwen2-7b-8l", ".json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
               initializer_range=0.2)
    cfg["assumed"] = {"batch_slots": 4, "s_max": 256, "chunk": 32, "prompt_bucket": 32}
    # the limit at this size, set as on the chip from readings on the CPU:
    # the program read at most 0.063 (seeds 7, 21-26), the control at
    # least 0.111
    cfg["limits"] = {"served_logit_gap": 0.08}
    traffic = json.loads(harness.find("traffic", "chat", ".json").read_text())
    traffic.update(rate_per_s=8, lead_in_s=0.5, drain_s=10, max_wave=2, check_tokens=30,
                   trace_seconds=0.5,
                   prompt={"median": 24, "sigma": 1.0, "min": 4, "max": 100},
                   output={"median": 6, "sigma": 0.8, "min": 2, "max": 24})
    return cfg, traffic


@pytest.fixture
def drive():
    """Run a cell of BENCHMARK.json on the CPU with the given files."""

    def run(workload, *, seed=7, seconds=1.0, trace=False, config=None, traffic=None):
        return harness.execute(workload, seed, seconds, trace, t_process=time.perf_counter(),
                               require_chip=False, cache=False,
                               config=copy.deepcopy(config), traffic=copy.deepcopy(traffic),
                               log=lambda _msg: None)

    return run
