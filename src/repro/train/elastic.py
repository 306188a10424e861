"""Elastic scaling + failure handling policy.

At 1000+ node scale, the failure model is: a pod loses hosts, the job is
rescheduled on a different device count, and training must resume from
the last checkpoint with a RESHAPED mesh.  The pieces that make this
work here:

  * checkpoints are mesh-agnostic (host numpy + manifest;
    ``Checkpointer.restore`` device_puts with the NEW mesh's shardings);
  * the data pipeline is stateless (batch = f(seed, step, shard)) so any
    host count re-derives its shard;
  * ``plan_mesh`` picks the largest valid (data, model) factorization of
    whatever devices survive, preferring to shrink the data axis (model
    parallel width is fixed by the checkpointed layout, so data-parallel
    width absorbs the loss);
  * straggler mitigation is structural: all collectives are sized by the
    static sharding (no data-dependent shapes), grad accumulation keeps
    per-device steps uniform, and the synchronous step means one slow
    host delays — never corrupts — the step.  Detection hooks
    (``StepTimer``) flag hosts whose step time exceeds the p99 window so
    the scheduler can evict them at the next checkpoint boundary.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax


def plan_mesh_shape(n_devices: int, model_width: int, *, pods: int = 1):
    """(shape, axes) for a surviving device count.  model_width is fixed
    by the checkpoint layout; data absorbs the change.  Pure function —
    no device state touched (callable from schedulers/tests)."""
    if n_devices % (model_width * pods):
        # drop stragglers to the largest multiple (scheduler evicts extras)
        n_devices = (n_devices // (model_width * pods)) * model_width * pods
    data = n_devices // (model_width * pods)
    if data < 1:
        raise ValueError(
            f"{n_devices} devices cannot host model_width={model_width}"
        )
    shape = (pods, data, model_width) if pods > 1 else (data, model_width)
    axes = ("pod", "data", "model") if pods > 1 else ("data", "model")
    return shape, axes


def plan_mesh(n_devices: int, model_width: int, *, pods: int = 1):
    """Build the mesh for :func:`plan_mesh_shape`'s chosen layout."""
    shape, axes = plan_mesh_shape(n_devices, model_width, pods=pods)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def rescale_batch(global_batch: int, old_data: int, new_data: int) -> int:
    """Keep per-device microbatch constant across a rescale when possible;
    otherwise keep global batch and adjust grad-accum."""
    per_dev = global_batch // old_data
    return per_dev * new_data


@dataclass
class StepTimer:
    """Rolling straggler detector: flags steps beyond k x median."""

    window: int = 50
    k: float = 3.0

    def __post_init__(self):
        self.times: list[float] = []
        self._t0: float | None = None

    def start(self):
        """Mark the beginning of a step."""
        self._t0 = time.monotonic()

    def stop(self) -> bool:
        """Returns True if this step looks like a straggler event."""
        dt = time.monotonic() - self._t0
        self.times.append(dt)
        self.times = self.times[-self.window :]
        med = sorted(self.times)[len(self.times) // 2]
        return len(self.times) >= 10 and dt > self.k * med
