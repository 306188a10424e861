"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
"TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB of HBM
at 819 GB/s.  A device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(LookupError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take for ``work`` (``flops`` at the
    bf16 MXU peak, ``bytes`` at the HBM bandwidth): the larger bound."""
    return max(work.get("flops", 0) / peaks["flops_bf16"],
               work.get("bytes", 0) / peaks["hbm_bytes_per_s"])
