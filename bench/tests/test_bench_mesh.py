"""The sharded reshard op on four virtual CPU devices: one all-to-all
plus the local kernel, bit-exact against a plain transpose; its control
fails, and so does a run with the exchange between devices left out.  (A child process: the device count is fixed when JAX
starts.)"""

import json
import os
import subprocess
import sys

from bench import harness

CHILD = r"""
import json, sys, time
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax
from bench import harness
traffic = harness.load_json("traffic", "ulysses-a2a")
traffic["ops"][0]["shape"] = [2, 64, 28, 128]
cell = harness.Cell(workload={"name": "mesh", "chips": 4}, end_to_end=[], per_layer=[])
run = harness.Run(cell=cell, config=harness.load_json("configs", "lib-4chip"),
                  traffic=traffic, seed=3, seconds=0.3, trace=False,
                  t_process=time.perf_counter(), require_chip=False)
harness.device_check(run)
drv = harness.plugin("runners", "library")
built = drv.build(run, lambda m: None)
text = jax.jit(built[0][1].program).lower(*built[0][1].args).as_text()
drv.run(run, lambda m: None)
rows = drv.readings(run, [4], lambda m: None)
sound = run.correct

# the exchange left out: each device moves its own blocks where the
# all-to-all would have put them, and nothing crosses between devices
def local_only(x, axis_name, split_axis, concat_axis, tiled=False, **_):
    parts = jax.numpy.split(x, jax.lax.axis_size(axis_name), axis=split_axis)
    return jax.numpy.concatenate(parts, axis=concat_axis)

jax.lax.all_to_all = local_only
broken = harness.Run(cell=cell, config=run.config, traffic=traffic, seed=5, seconds=0.3,
                     trace=False, t_process=time.perf_counter(), require_chip=False)
harness.device_check(broken)
drv.run(broken, lambda m: None)
print(json.dumps({"devices": len(run.devices), "correct": sound, "broken_correct": broken.correct,
                  "all_to_all": text.count("all_to_all"), "rows": rows}))
"""


def test_sharded_reshard_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.replace("ROOT", repr(str(harness.ROOT)))
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["correct"]
    assert not out["broken_correct"]
    assert out["all_to_all"] >= 1
    assert out["rows"][0]["program"] == 0 and out["rows"][0]["control"] > 0
