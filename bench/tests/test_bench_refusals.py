"""A run refuses what it cannot measure: no TPU, an unknown device kind,
a dispatch switch that takes kernels off the chip, a checkout without
the program.  A refused run prints no result line."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import harness


def cli(cwd, *extra, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lib-hbm.rearrange", "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_no_tpu_refused():
    proc = cli(harness.ROOT)
    assert proc.returncode != 0 and no_result(proc)
    assert "no TPU" in proc.stderr


def test_dispatch_switch_refused():
    env = dict(os.environ, REPRO_PALLAS_INTERPRET="1")
    proc = cli(harness.ROOT, env=env)
    assert proc.returncode != 0 and no_result(proc)
    assert "REPRO_PALLAS_INTERPRET" in proc.stderr
    with pytest.raises(harness.Refused):
        harness.refuse_env({"REPRO_FLASH_KERNEL": "0"})
    harness.refuse_env({"REPRO_FLASH_KERNEL": "1"})


def test_checkout_without_the_program_refused(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = cli(tmp_path, env=env)
    assert proc.returncode != 0 and no_result(proc)


def test_unknown_device_kind_refused(monkeypatch):
    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99", id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    run = harness.Run(cell=harness.load_cell("lib-hbm.rearrange"), config={}, traffic={},
                      seed=1, seconds=1, trace=False, t_process=0.0)
    with pytest.raises(harness.Refused, match="TPU v99"):
        harness.device_check(run)
    monkeypatch.setattr(jax, "devices", lambda *a: [SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite", id=0)])
    harness.device_check(run)
    assert run.peaks["hbm_bytes_per_s"] == 819e9


def test_unknown_workload_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such.cell")


def test_result_line_keys(drive, tiny_lib):
    run = drive("lib-hbm.rearrange", traffic=tiny_lib, seconds=0.3)
    line = harness.result_line(run)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    json.dumps(line)
