"""Quantized ring collectives for gradient reduction (beyond-paper).

A GSPMD all-reduce moves full-precision bytes.  This module implements
the data-parallel gradient reduction explicitly — shard_map + a ring of
``collective_permute`` hops — quantizing every hop to int8 with a per-
chunk fp32 scale: ~4x fewer bytes on the wire than a bf16/fp32 ring,
with error feedback available at the optimizer level.

  reduce-scatter:  n-1 hops, each hop sends 1/n of the tensor (int8)
  all-gather:      n-1 hops of the reduced shard (int8)

Integration: the trainer's DP reduction can route through
``compressed_allreduce_mean`` under shard_map when
``TrainConfig.compress_grads`` is set; the dry-run's collective-bytes
accounting then charges int8 operand bytes.
This module is numerically validated on a forced multi-device host mesh
in tests/test_compress.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ring_allreduce_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean-all-reduce of ``x`` over ``axis_name`` with int8 ring hops.
    Call inside shard_map.  x: flat (L,) with L % n == 0."""
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    chunks = x.reshape(n, -1).astype(jnp.float32)

    # --- reduce-scatter: after n-1 hops, device d owns sum of chunk (d+1)%n.
    # step s: d sends its partial of chunk (d-s), receives the partial of
    # chunk (d-1-s) and adds its own contribution to it.
    acc = jnp.take(chunks, me, axis=0)
    for s in range(n - 1):
        q, scale = _quant(acc)
        q = jax.lax.ppermute(q, axis_name, fwd)
        scale = jax.lax.ppermute(scale, axis_name, fwd)
        idx = (me - 1 - s) % n
        acc = _dequant(q, scale) + jnp.take(chunks, idx, axis=0)

    own = (me + 1) % n  # chunk id this device now owns (fully reduced)
    acc = acc / n

    # --- all-gather the reduced shards (int8 hops)
    out = jnp.zeros_like(chunks)
    q, scale = _quant(acc)
    cur_q, cur_scale, cur_idx = q, scale, own
    out = out.at[cur_idx].set(_dequant(cur_q, cur_scale))
    for s in range(n - 1):
        cur_q = jax.lax.ppermute(cur_q, axis_name, fwd)
        cur_scale = jax.lax.ppermute(cur_scale, axis_name, fwd)
        cur_idx = (cur_idx + 1) % n  # my predecessor owned (own - 1)
        idx = (own - 1 - s) % n
        out = out.at[idx].set(_dequant(cur_q, cur_scale))
    return out.reshape(x.shape)


def compressed_allreduce_mean(tree, mesh, *, axis: str = "data"):
    """Mean-reduce a pytree of per-device gradients over the data axis via
    the int8 ring.  Leaves are flattened/padded to a ring-divisible size."""
    n = mesh.shape[axis]

    def one(leaf):
        flat = leaf.reshape(-1).astype(jnp.float32)
        pad = (-flat.size) % n
        flat = jnp.pad(flat, (0, pad))

        fn = jax.shard_map(
            functools.partial(ring_allreduce_int8, axis_name=axis),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
        red = fn(flat)
        return red[: leaf.size].reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, tree)


def wire_bytes(n_params: int, n_devices: int, dtype_bytes: int = 4) -> dict:
    """Napkin accounting: ring AR bytes per device, fp32 vs int8 hops."""
    full = 2 * (n_devices - 1) / n_devices * n_params * dtype_bytes
    quant = 2 * (n_devices - 1) / n_devices * n_params * 1  # int8 payload
    return {"fp32_ring": full, "int8_ring": quant, "ratio": full / quant}
