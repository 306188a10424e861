"""Batched 2-D transpose — the building block of every reorder (paper §III-B).

The paper's 3D Permute kernel handles a permutation as "a set of batched 2D
data movement operations" in the plane spanned by the fastest-changing input
and output dimensions, staged through shared memory with 32x32 tiles so both
the global load and the global store are coalesced.

TPU-native version:
* the (R, C) plane is tiled into (block_r, block_c) VMEM blocks; the
  transpose happens inside VMEM (VREG shuffles by the VPU) — both the
  HBM->VMEM load and the VMEM->HBM store move full lane-aligned tiles,
  the TPU equivalent of "coalesced on read AND write";
* the paper's *diagonalized CUDA-block ordering* (partition-camping
  avoidance) is kept as a selectable grid-walk policy: the (i, j) tile walk
  is remapped to (i, (i + j) % nC) on both sides.  On TPU, HBM channel
  interleaving is handled by hardware, so this is measured as a policy knob
  rather than assumed to help (DESIGN.md §8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    cdiv,
    interpreted,
    plan_transpose_tiles,
    plan_transpose_vec_tiles,
)


def _transpose_kernel(x_ref, o_ref):
    # block shapes: x (1, br, bc) -> o (1, bc, br)
    o_ref[0, :, :] = x_ref[0, :, :].T


def _dim_semantics(n: int, parallel: bool) -> pltpu.CompilerParams:
    kind = pltpu.PARALLEL if parallel else pltpu.ARBITRARY
    return pltpu.CompilerParams(dimension_semantics=(kind,) * n)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "diagonal", "interpret")
)
def transpose2d_batched(
    x: jax.Array,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    diagonal: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, R, C) -> (B, C, R) via VMEM-staged tiled transpose."""
    if x.ndim != 3:
        raise ValueError(f"expected (B, R, C), got {x.shape}")
    B, R, C = x.shape
    plan = plan_transpose_tiles(R, C, x.dtype)
    br = block_r or plan.block_r
    bc = block_c or plan.block_c
    nR, nC = cdiv(R, br), cdiv(C, bc)

    if diagonal and nC > 1:

        def in_map(b, i, j):
            return (b, i, lax.rem(i + j, nC))

        def out_map(b, i, j):
            return (b, lax.rem(i + j, nC), i)

    else:

        def in_map(b, i, j):
            return (b, i, j)

        def out_map(b, i, j):
            return (b, j, i)

    interpret = interpreted() if interpret is None else interpret
    return pl.pallas_call(
        _transpose_kernel,
        grid=(B, nR, nC),
        in_specs=[pl.BlockSpec((1, br, bc), in_map)],
        out_specs=pl.BlockSpec((1, bc, br), out_map),
        out_shape=jax.ShapeDtypeStruct((B, C, R), x.dtype),
        interpret=interpret,
        compiler_params=_dim_semantics(3, parallel=not diagonal),
    )(x)


def _transpose_vec_kernel(x_ref, o_ref):
    # block shapes: x (1, br, bc, bv) -> o (1, bc, br, bv)
    o_ref[0] = jnp.transpose(x_ref[0], (1, 0, 2))


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "block_v", "interpret")
)
def transpose2d_batched_vec(
    x: jax.Array,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    block_v: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, R, C, V) -> (B, C, R, V): batched middle-axes transpose with a
    contiguous vector payload.

    This is the planner's target for the whole (B, S, H, D)-swap family
    (split_heads / merge_heads / space_to_depth after axis collapsing): V is
    the collapsed identity tail, so both the load and the store move runs of
    V contiguous elements — the (R, C) plane transposes whole V-vectors
    instead of scalars, and the lane dim never changes sides.
    """
    if x.ndim != 4:
        raise ValueError(f"expected (B, R, C, V), got {x.shape}")
    B, R, C, V = x.shape
    plan = plan_transpose_vec_tiles(R, C, V, x.dtype)
    br = min(block_r or plan.block_r, R)
    bc = min(block_c or plan.block_c, C)
    bv = min(block_v or plan.block_v, V)
    nR, nC, nV = cdiv(R, br), cdiv(C, bc), cdiv(V, bv)

    def in_map(b, i, j, v):
        return (b, i, j, v)

    def out_map(b, i, j, v):
        return (b, j, i, v)

    interpret = interpreted() if interpret is None else interpret
    return pl.pallas_call(
        _transpose_vec_kernel,
        grid=(B, nR, nC, nV),
        in_specs=[pl.BlockSpec((1, br, bc, bv), in_map)],
        out_specs=pl.BlockSpec((1, bc, br, bv), out_map),
        out_shape=jax.ShapeDtypeStruct((B, C, R, V), x.dtype),
        interpret=interpret,
        compiler_params=_dim_semantics(4, parallel=True),
    )(x)


def transpose2d(
    x: jax.Array,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    diagonal: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """(R, C) -> (C, R)."""
    return transpose2d_batched(
        x[None],
        block_r=block_r,
        block_c=block_c,
        diagonal=diagonal,
        interpret=interpret,
    )[0]
