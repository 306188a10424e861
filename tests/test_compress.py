"""int8 ring-collective gradient compression: numerical validation on a
forced 8-device host mesh (subprocess keeps the main process single-dev)."""

import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_int8_ring_allreduce_subprocess():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import ring_allreduce_int8, wire_bytes
import functools

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
# per-device distinct values; replicated layout, each shard sees its own copy
vals = rng.standard_normal((8, 4096)).astype(np.float32)

fn = jax.shard_map(
    functools.partial(ring_allreduce_int8, axis_name="data"),
    mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
x = jnp.asarray(vals.reshape(-1))  # (8*4096,) sharded over data -> each dev one row
out = np.asarray(fn(x)).reshape(8, 4096)
want = vals.mean(axis=0)
# every device must hold (approximately) the mean; int8 -> ~1% error
for d in range(8):
    err = np.abs(out[d] - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 0.05, (d, err)
# wire accounting sanity
wb = wire_bytes(1_000_000, 8)
assert 3.5 < wb["ratio"] <= 4.0
print("RING_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "RING_OK" in r.stdout, r.stderr[-3000:]
