"""Production mesh construction (16x16 single pod / 2x16x16 multi-pod).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required for the dry-run's forced 512-device
initialization to happen first).
"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def ring_perm(n: int, *, reverse: bool = False) -> list[tuple[int, int]]:
    """``ppermute`` pairs for a ring of ``n`` shards.

    Forward (default) sends shard ``i`` -> ``i+1 (mod n)`` — the receiver
    sees its *predecessor's* rows, i.e. this is how a shard obtains its TOP
    halo from the shard above.  ``reverse=True`` sends ``i`` -> ``i-1`` (the
    BOTTOM halo, from the shard below).  Used by the §10 halo exchange.
    """
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def fake_device_env(n: int = 8) -> dict:
    """Environment entries forcing ``n`` host (CPU) devices — the recipe for
    verifying every mesh-aware code path in this repo without a TPU::

        env = {**os.environ, **fake_device_env(8), "PYTHONPATH": "src"}
        subprocess.run([sys.executable, "-m", "pytest", "tests/test_dist_plan.py"],
                       env=env)

    Must reach the child process before jax initializes its backends, which
    is why tests/benchmarks apply it to a *subprocess* rather than mutating
    their own environment.  Any XLA_FLAGS already in this process's
    environment are preserved (prepended-to, not replaced).
    """
    flags = f"--xla_force_host_platform_device_count={int(n)}"
    existing = os.environ.get("XLA_FLAGS", "")
    return {"XLA_FLAGS": f"{flags} {existing}".strip()}


def make_production_mesh(*, multi_pod: bool = False):
    """The production topology: 16x16 (data, model) single pod, or
    2x16x16 (pod, data, model) when ``multi_pod`` — the mesh the launcher
    dry-run compiles against."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate mesh over whatever devices exist (smoke/e2e runs)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def mesh_axes_info(mesh) -> dict:
    """Summarize a mesh as the plain dict the sharding rules consume
    (axis names plus per-axis sizes; missing axes report size 1)."""
    names = mesh.axis_names
    return {
        "model": "model",
        "data": "data",
        "model_size": mesh.shape["model"] if "model" in names else 1,
        "data_size": mesh.shape["data"] if "data" in names else 1,
        "pod_size": mesh.shape["pod"] if "pod" in names else 1,
        "multi_pod": "pod" in names,
    }


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the batch dimension shards over (pod+data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
