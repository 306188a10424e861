"""Interlace / de-interlace kernels (paper §III-C), TPU-native.

AoS <-> SoA conversion: n arrays of length L interleaved element-wise into
one array of length n*L (and back).  The CUDA version stages 8x8 blocks in
shared memory with n*64 threads so that both the global load and the global
store stay coalesced; the interleaving shuffle happens in shared memory.

TPU version: each source is viewed as (L/128, 128) rows of one lane-width,
and the interleaved output as (L/128, n*128): the n*128 outputs of source
row s are exactly the n lane-rows that interleave the n source rows s.  So:

  load   n lane-aligned (S, 128) blocks     — one per source (coalesced),
  shuffle in VMEM: output lane-column t of a row draws only from source
         lanes [128t/n, 128(t+1)/n), i.e. from ONE vreg column, so it is n
         in-register lane gathers plus a lane-parity select,
  store  one lane-aligned (S, n*128) block  — contiguous in the output.

Shared memory -> VMEM, warp shuffle -> single-vreg lane gather.  Gathers
run in 32 bits (narrower dtypes widen exactly and narrow back), because
Mosaic's lane gather wants indices and data of one bit width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, interpreted, sublanes


def lane_aligned(length: int) -> bool:
    """True when a length-``length`` source has a kernel view: a positive
    whole number of 128-lane rows.  Dispatch routes other lengths to the
    oracle before building a kernel."""
    return length > 0 and length % LANES == 0


def _block_rows(rows: int, n: int, dtype) -> int:
    """Source rows per grid step: about 2048 lane-rows of output per block,
    sublane aligned, or every row when the sources are shorter."""
    sl = sublanes(dtype)
    return min(rows, max(sl, (2048 // max(n, 1)) // sl * sl))


def _wide(dtype):
    """The 32-bit type the lane gathers run in (exact for narrower types)."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _interlace_kernel(n, *refs):
    o_ref = refs[-1]
    rows = o_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    srcs = [r[...].astype(_wide(o_ref.dtype)) for r in refs[:-1]]
    for t in range(n):
        # output lane 128t + u holds source (pos % n) element pos // n
        pos = LANES * t + lane
        idx = pos // n
        col = None
        for k in range(n):
            g = jnp.take_along_axis(srcs[k], idx, axis=1)
            col = g if col is None else jnp.where(pos % n == k, g, col)
        o_ref[:, LANES * t:LANES * (t + 1)] = col.astype(o_ref.dtype)


def _deinterlace_kernel(n, x_ref, *o_refs):
    rows = x_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    cols = [
        x_ref[:, LANES * t:LANES * (t + 1)].astype(_wide(x_ref.dtype))
        for t in range(n)
    ]
    for k, o_ref in enumerate(o_refs):
        # element u of source k sits at interleaved lane u*n + k
        pos = lane * n + k
        idx = pos % LANES
        out = None
        for t in range(n):
            g = jnp.take_along_axis(cols[t], idx, axis=1)
            out = g if out is None else jnp.where(pos // LANES == t, g, out)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def interlace(
    arrays: tuple[jax.Array, ...],
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """n 1-D arrays (L,) -> (n*L,) with out[j*n + k] = arrays[k][j].
    ``L`` must be :func:`lane_aligned`."""
    n = len(arrays)
    L = arrays[0].shape[0]
    for a in arrays:
        if a.shape != (L,) or a.dtype != arrays[0].dtype:
            raise ValueError("interlace requires same-shape/dtype 1-D arrays")
    if not lane_aligned(L):
        raise ValueError(f"L={L} is not a positive multiple of {LANES}")
    dtype = arrays[0].dtype
    rows = L // LANES
    br = _block_rows(rows, n, dtype)
    interpret = interpreted() if interpret is None else interpret
    out2d = pl.pallas_call(
        functools.partial(_interlace_kernel, n),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, LANES), lambda i: (i, 0)) for _ in range(n)],
        out_specs=pl.BlockSpec((br, n * LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n * LANES), dtype),
        interpret=interpret,
    )(*(a.reshape(rows, LANES) for a in arrays))
    return out2d.reshape(n * L)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def deinterlace(
    x: jax.Array,
    n: int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, ...]:
    """(n*L,) -> n arrays (L,): inverse of :func:`interlace`.  ``L`` must
    be :func:`lane_aligned`."""
    if x.ndim != 1 or x.shape[0] % n:
        raise ValueError(f"bad shape {x.shape} for n={n}")
    L = x.shape[0] // n
    if not lane_aligned(L):
        raise ValueError(f"L={L} is not a positive multiple of {LANES}")
    rows = L // LANES
    br = _block_rows(rows, n, x.dtype)
    interpret = interpreted() if interpret is None else interpret
    outs = pl.pallas_call(
        functools.partial(_deinterlace_kernel, n),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, n * LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, LANES), lambda i: (i, 0)) for _ in range(n)],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), x.dtype) for _ in range(n)],
        interpret=interpret,
    )(x.reshape(rows, n * LANES))
    return tuple(o.reshape(L) for o in outs)
