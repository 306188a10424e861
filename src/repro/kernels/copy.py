"""Basic read/write kernels (paper §III-A).

The paper's primitive: stream data through the device at memcpy rate, with
templated access patterns (contiguous, ranged, index-set).  CUDA used 1-D
blocks with 4 elements per thread and automatic gridding; the TPU analogue
is a row-panel copy whose panel size is auto-planned against VMEM so each
grid step issues one large aligned DMA in and one out.

Ranged access keeps the paper's constant-memory trick via scalar prefetch:
the start offset rides in SMEM and feeds the load-side index map.

Index-set access lives in ``gather_scatter.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, cdiv, interpreted, plan_copy_tiles


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _copy_range_kernel(s_ref, x_ref, o_ref):
    del s_ref  # consumed by the index maps
    o_ref[...] = x_ref[...]


_LANE_COLS = (8192, 4096, 2048, 1024, 512, 256, LANES)


def has_view(shape: tuple[int, ...]) -> bool:
    """True when :func:`copy` has a (rows, cols) kernel view of ``shape``:
    any rank >= 2 array, or a 1-D length with a lane-aligned factor.
    Dispatch routes other shapes to the oracle before building a kernel."""
    if len(shape) >= 2:
        return True
    return len(shape) == 1 and any(shape[0] % c == 0 for c in _LANE_COLS)


def _as_2d(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """View x as (rows, cols) with a lane-friendly cols (see has_view)."""
    if x.ndim >= 2:
        return x.reshape(-1, x.shape[-1]), x.shape
    if not has_view(x.shape):
        raise ValueError(f"1-D length {x.shape} has no lane-aligned factor")
    L = x.shape[0]
    cols = next(c for c in _LANE_COLS if L % c == 0)
    return x.reshape(L // cols, cols), x.shape


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def copy(
    x: jax.Array,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Contiguous device-to-device copy through VMEM panels."""
    x2, orig_shape = _as_2d(x)
    R, C = x2.shape
    plan = plan_copy_tiles(R, C, x.dtype)
    br = min(block_rows or plan.block_r, R)

    interpret = interpreted() if interpret is None else interpret
    out = pl.pallas_call(
        _copy_kernel,
        grid=(cdiv(R, br),),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x2)
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("size", "block_rows", "interpret"))
def copy_range(
    x: jax.Array,
    start: jax.Array,
    size: int,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Ranged read: rows [start, start+size) of a 2-D array.

    ``start`` is a *runtime* scalar (int32) delivered to the index map via
    scalar prefetch — the constant-memory analogue.  Row-granular: the
    kernel slides whole row panels; ``start`` need not be panel-aligned
    (the index map adds the row offset in block units after validating
    alignment at the chosen panel size of 1 row — i.e. panels are rows).
    """
    if x.ndim != 2:
        raise ValueError("copy_range expects 2-D (rows, cols)")
    R, C = x.shape
    br = block_rows or 1  # row-granular sliding window
    if size % br:
        raise ValueError(f"size {size} not divisible by block_rows {br}")

    interpret = interpreted() if interpret is None else interpret
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(size // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i, s_ref: (i + s_ref[0], 0))],
        out_specs=pl.BlockSpec((br, C), lambda i, s_ref: (i, 0)),
    )
    start_blocks = (jnp.asarray(start, jnp.int32) // br)[None]
    return pl.pallas_call(
        _copy_range_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((size, C), x.dtype),
        interpret=interpret,
    )(start_blocks, x)
