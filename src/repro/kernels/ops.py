"""Dispatch layer: one public op per kernel, Pallas on TPU / oracle elsewhere.

Dispatch rules
--------------
* Two predicates decide every dispatch in the library, here and in the
  model layers: :func:`use_pallas` ("a Pallas kernel runs") and
  :func:`_interpret` ("it runs in the interpreter").
* On TPU the Pallas kernels own the fast path, compiled.
* Elsewhere the jnp oracles (``ref.py``) are the dispatch target — XLA
  fuses them competitively, and the dry run compiles the XLA path.
* ``REPRO_PALLAS_INTERPRET=1`` sends every op, prefill and decode
  attention included, through its Pallas kernel in the interpreter — this
  is how the test suite validates kernel semantics on CPU.
* Kernels have alignment preconditions (lane divisibility, the TPU tiling
  rule, a panel that fits VMEM).  Each op checks them through an explicit
  predicate of the plan or the kernel module BEFORE it builds a kernel, and
  routes an input that violates them to the oracle — the library never
  fails on an odd shape, it just loses the fast path (same contract as the
  paper's library).  Nothing here catches an error raised while a kernel
  is built, lowered or compiled: a kernel the compiler refuses raises.
"""

from __future__ import annotations

import os
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import affine
from repro.core.index_plan import IndexPlan, plan_index_op
from repro.core.plan import RearrangePlan, plan_affine, plan_rearrange
from repro.kernels import (
    copy as copy_k,
    gather_scatter as gs_k,
    grouped_matmul as gm_k,
    interlace as il_k,
    permute3d as p3_k,
    ref,
    reorder_nd as rnd_k,
    stencil2d as st_k,
    tiling,
)

Array = jax.Array


def use_pallas() -> bool:
    """True when a Pallas kernel runs: on a TPU, or on any platform under
    ``REPRO_PALLAS_INTERPRET=1``."""
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1" or not _interpret()


def _interpret() -> bool:
    """True when a kernel that runs is interpreted: the platform is not a
    TPU (:func:`repro.kernels.tiling.interpreted`)."""
    return tiling.interpreted()


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------


def copy(x: Array) -> Array:
    """Materialized device copy (paper §III-A read/write kernel)."""
    if use_pallas() and copy_k.has_view(x.shape):
        return copy_k.copy(x, interpret=_interpret())
    return ref.copy(x)


def copy_range(x: Array, start, size: int) -> Array:
    """Ranged access: copy ``x[start:start+size]`` along axis 0."""
    if use_pallas() and x.ndim == 2:
        return copy_k.copy_range(x, start, size, interpret=_interpret())
    return ref.copy_range(x, start, size)


def apply_index_plan(
    x: Array, idx: Array, plan: IndexPlan, gates: Array | None = None
) -> Array:
    """Execute an :class:`IndexPlan` on ``x`` with the blocked kernels.

    Every route is at most ONE kernel invocation over HBM:

      noop           -> zeros (empty table / empty rows), no kernel
      gather         -> blocked masked gather (run-detected block copies)
                        — or the seed rowwise kernel when the tuner
                        selected that engine (unmasked gathers only)
      scatter        -> the same gather through the inverted index table
                        (an int32 table op; unmapped rows stay zero)
      gather_combine -> fused gather + weighted combine (needs ``gates``)
      ragged_rows    -> the masked gather route above; -1 sentinels zero
                        the tail rows (the serving engine's ragged-prefill
                        unpack, DESIGN.md §12)
    """
    interp = _interpret()
    if plan.mode == "noop":
        return jnp.zeros((plan.n_out, x.shape[1]), x.dtype)
    if plan.mode == "rowwise":
        return gs_k.gather_rows(x, idx, interpret=interp)
    if plan.semantics == "scatter":
        inv = jnp.full((plan.n_out,), -1, jnp.int32).at[idx].set(
            jnp.arange(plan.n_src, dtype=jnp.int32), mode="drop"
        )
        return gs_k.gather_rows_blocked(
            x, inv, block_r=plan.block_rows, interpret=interp
        )
    if plan.semantics == "gather_combine":
        if gates is None:
            raise ValueError("gather_combine plans need the gates operand")
        return gs_k.gather_combine_blocked(
            x, idx, gates, block_t=plan.block_rows, interpret=interp
        )
    return gs_k.gather_rows_blocked(x, idx, block_r=plan.block_rows, interpret=interp)


def gather_rows(x: Array, idx: Array, *, masked: bool = False, engine: str = "plan") -> Array:
    """Index-set access: rows of ``x`` (axis 0) selected by ``idx``.

    ``masked=True`` enables sentinel semantics (``idx[i] < 0`` -> zero
    row).  ``engine="plan"`` (default) routes through the IndexPlan engine
    (blocked kernel, `core/index_plan.py`); ``engine="rowwise"`` keeps the
    seed one-row-per-grid-step kernel (benchmark baseline, unmasked only).
    """
    if engine not in ("plan", "rowwise"):
        raise ValueError(f"unknown gather_rows engine {engine!r}")
    if engine == "rowwise" and masked:
        raise ValueError("the rowwise engine has no sentinel masking")
    if use_pallas() and x.ndim == 2:
        if engine == "rowwise":
            return gs_k.gather_rows(x, idx, interpret=_interpret())
        plan = plan_index_op(x.shape, x.dtype, idx.shape[0], "gather", masked=masked)
        return apply_index_plan(x, idx, plan)
    if masked:
        return ref.gather_rows_masked(x, idx)
    return ref.gather_rows(x, idx)


def scatter_rows(x: Array, idx: Array, num_out: int | None = None) -> Array:
    """Injective row scatter: ``out[idx[i], :] = x[i, :]``.

    Contract (explicit — the seed version fell back silently):

    * ``idx`` must be injective into ``[0, num_out)``.  Duplicate targets
      leave the duplicated row unspecified (this cannot be validated
      eagerly on traced values); out-of-range targets are dropped.
    * ``num_out`` defaults to ``x.shape[0]`` (permutation scatter).
      ``num_out > x.shape[0]`` is the capacity-scatter case (rows nothing
      maps to — dropped slots — are zero-filled); it routes to the masked
      blocked kernel through the inverted table, the same fast path as the
      permutation case.
    * ``num_out < x.shape[0]`` cannot be injective: raises eagerly.
    * Non-2-D ``x`` has no Pallas fast path and dispatches to the oracle.
    """
    if idx.ndim != 1 or idx.shape[0] != x.shape[0]:
        raise ValueError(
            f"scatter_rows wants 1-D idx over x rows, got {x.shape}, {idx.shape}"
        )
    if num_out is not None and num_out < x.shape[0]:
        raise ValueError(
            f"scatter_rows num_out={num_out} < {x.shape[0]} rows cannot be injective"
        )
    n_out = x.shape[0] if num_out is None else num_out
    if use_pallas() and x.ndim == 2:
        plan = plan_index_op(x.shape, x.dtype, n_out, "scatter", masked=True)
        return apply_index_plan(x, idx, plan)
    return ref.scatter_rows(x, idx, num_out)


def gather_combine(src: Array, back: Array, gates: Array) -> Array:
    """Fused gather + weighted combine (the MoE combine primitive):
    ``out[t] = sum_k gates[t, k] * src[back[t, k]]``, with negative
    ``back`` entries contributing zero.  ONE `pallas_call` on the Pallas
    path (no (T*k, C) gathered intermediate in HBM)."""
    if back.ndim != 2 or gates.shape != back.shape:
        raise ValueError(
            f"gather_combine wants matching (T, k) back/gates, got "
            f"{back.shape}, {gates.shape}"
        )
    if use_pallas() and src.ndim == 2:
        plan = plan_index_op(
            src.shape, src.dtype, back.shape[0], "gather_combine",
            masked=True, top_k=back.shape[1],
        )
        return apply_index_plan(src, back, plan, gates=gates)
    return ref.gather_combine(src, back, gates)


def grouped_swiglu(
    x: Array, w_gate: Array, w_up: Array, w_down: Array, tile_group: Array,
    n_used: Array, *, tm: int,
) -> Array:
    """The MoE expert FFN over expert-sorted rows: each ``tm``-row tile of
    ``x`` through the SwiGLU weights of its group (`kernels.grouped_matmul`;
    ``tile_group`` and ``n_used`` as ``grouped_matmul.tile_groups`` gives
    them).  ONE `pallas_call` where an expert's weights fit VMEM."""
    d, f = w_gate.shape[1:]
    if use_pallas() and gm_k.fits(d, f, tm, x.dtype):
        return gm_k.grouped_swiglu(x, w_gate, w_up, w_down, tile_group, n_used,
                                   tm=tm, interpret=_interpret())
    return ref.grouped_swiglu(x, w_gate, w_up, w_down, tile_group, n_used, tm)


def transpose2d_batched(x: Array, *, diagonal: bool = False) -> Array:
    """(B, R, C) -> (B, C, R) batched 2-D transpose (optionally with the
    paper's diagonalized block walk, DESIGN.md §8)."""
    if use_pallas():
        return p3_k.transpose2d_batched(x, diagonal=diagonal, interpret=_interpret())
    return ref.transpose2d_batched(x)


def apply_plan(x: Array, plan: RearrangePlan) -> Array:
    """Execute a :class:`RearrangePlan` on ``x`` with the Pallas kernels.

    Reshapes to/from the canonical form are metadata-only (adjacent-axis
    merges of a contiguous array), so every route is at most ONE kernel
    invocation over HBM:

      identity  -> pure reshape, zero data movement
      transpose -> batched 2-D transpose (scalar or V-deep elements)
      copy      -> reorder_nd in row-gather mode on the collapsed form
      reorder   -> generic reorder_nd on the collapsed form
      affine    -> generalized reorder_affine driven by the plan's AffineMap
    """
    interp = _interpret()
    if plan.mode == "identity":
        return x.reshape(plan.out_shape)
    if plan.mode == "affine":
        y = rnd_k.reorder_affine(
            x.reshape(plan.canonical_shape),
            plan.amap,
            block_r=plan.block_r,
            block_c=plan.block_c,
            grid_order=plan.grid_order,
            interpret=interp,
        )
        return y.reshape(plan.out_shape)
    if plan.mode == "transpose":
        b, r, c, v = plan.exec_shape
        if v > 1:
            y = p3_k.transpose2d_batched_vec(
                x.reshape(b, r, c, v),
                block_r=plan.block_r,
                block_c=plan.block_c,
                interpret=interp,
                **({"block_v": plan.block_v} if plan.block_v else {}),
            )
        else:
            y = p3_k.transpose2d_batched(
                x.reshape(b, r, c),
                block_r=plan.block_r,
                block_c=plan.block_c,
                interpret=interp,
            )
        return y.reshape(plan.out_shape)
    y = rnd_k.permute_nd(
        x.reshape(plan.canonical_shape),
        plan.canonical_perm,
        block_r=plan.block_r,
        block_c=plan.block_c,
        grid_order=plan.grid_order,
        interpret=interp,
    )
    return y.reshape(plan.out_shape)


def permute(x: Array, perm: Sequence[int], *, grid_order: str = "out") -> Array:
    """N-D transpose through the plan engine: collapse -> route -> cached
    plan -> at most ONE kernel pass (DESIGN.md §3)."""
    perm = tuple(int(p) for p in perm)
    if use_pallas():
        plan = plan_rearrange(x.shape, x.dtype, perm, grid_order=grid_order)
        return apply_plan(x, plan)
    return ref.permute(x, perm)


def _apply_affine(x: Array, amap: affine.AffineMap, out_shape, oracle) -> Array:
    """Shared affine-op dispatch: plan the map (analytic source) and run it
    as ONE kernel pass reshaped to the user-facing ``out_shape`` — or call
    ``oracle()`` where the plan routes there: ``mode="oracle"`` (no
    single-pass lowering), or ``tpu_kernel=False`` when compiling for the
    chip (the kernel breaks the TPU tiling rule)."""
    plan = plan_affine(amap, x.dtype)
    if plan.mode == "oracle" or not (plan.tpu_kernel or _interpret()):
        return oracle()
    return apply_plan(x, plan).reshape(out_shape)


def bit_reversal(x: Array, *, axis: int = 0) -> Array:
    """Bit-reversal reorder along ``axis`` (FFT layouts, paper's reorder
    class): element ``i`` moves to bit-reversed index.  Affine route: the
    axis is digit-split into base-2 digits whose order is reversed — a
    clean digit permutation, ONE pallas_call, no index table.  A
    non-power-of-two axis raises ValueError."""
    axis = axis % max(x.ndim, 1)
    if use_pallas() and x.size:
        amap = affine.bit_reversal_map(x.shape, axis=axis)
        return _apply_affine(
            x, amap, x.shape, lambda: ref.bit_reversal(x, axis=axis)
        )
    return ref.bit_reversal(x, axis=axis)


def strided_gather(x: Array, stride: int, *, phase: int = 0, axis: int = 0) -> Array:
    """Strided window gather ``x[..., phase::stride, ...]`` along ``axis``.

    When ``stride`` divides the axis (and ``phase < stride``) this lowers
    through the affine planner: the axis digit-splits into
    ``(n // stride, stride)`` with the stride digit pinned at ``phase`` —
    a windowed affine map, ONE pallas_call, no materialized slice.  Other
    strides run the oracle."""
    if stride <= 0:
        raise ValueError(f"stride must be positive, got {stride}")
    axis = axis % max(x.ndim, 1)
    splits = x.ndim > 0 and x.shape[axis] % stride == 0 and 0 <= phase < stride
    if use_pallas() and x.size and splits:
        amap = affine.strided_map(x.shape, axis=axis, stride=stride, phase=phase)
        out_shape = x.shape[:axis] + (x.shape[axis] // stride,) + x.shape[axis + 1:]
        return _apply_affine(
            x, amap, out_shape,
            lambda: ref.strided_gather(x, stride, phase=phase, axis=axis),
        )
    return ref.strided_gather(x, stride, phase=phase, axis=axis)


def diagonal_reorder(x: Array) -> Array:
    """Skewed-diagonal reorder ``out[..., i, j] = x[..., i, (i + j) % C]``
    (the paper's diagonal block walk applied to the data).  The affine
    lowering keeps the lane digit resident and applies the per-row modular
    shift in-register — ONE pallas_call, no gather table."""
    if x.ndim < 2:
        raise ValueError("diagonal_reorder wants rank >= 2")
    if use_pallas() and x.size:
        return _apply_affine(
            x, affine.diagonal_map(x.shape), x.shape,
            lambda: ref.diagonal_reorder(x),
        )
    return ref.diagonal_reorder(x)


def shuffle(x: Array, seed: int = 0) -> Array:
    """Table-free seeded row shuffle (axis 0) — the epoch-shuffling
    primitive (ROADMAP item 3; bijective index functions per Mitchell et
    al., arXiv:2106.06161).  The seed draws a mixed-radix digit permutation
    plus per-digit rotations over the row index space: a bijection the
    affine planner lowers as ONE pallas_call with the row map evaluated in
    the scalar core — no O(n) index table in HBM.  The same seed always
    yields the same permutation; the oracle path materializes it as a
    gather table instead."""
    if use_pallas() and x.size and x.ndim >= 1 and x.shape[0] > 1:
        amap = affine.shuffle_map(x.shape[0], payload=x.shape[1:], seed=seed)
        return _apply_affine(x, amap, x.shape, lambda: ref.shuffle(x, seed=seed))
    return ref.shuffle(x, seed=seed)


def reorder_nm(
    x: Array,
    perm: Sequence[int],
    base: Sequence[int] | None = None,
    sizes: Sequence[int] | None = None,
) -> Array:
    """N->M reorder: window select + permute + squeeze (paper §III-B)."""
    if base is None and sizes is None and len(perm) == x.ndim:
        return permute(x, perm)
    nd = x.ndim
    base_l = [0] * nd if base is None else list(base)
    sizes_l = list(x.shape) if sizes is None else list(sizes)
    kept = [int(p) for p in perm]
    kept_set = set(kept)
    for ax in range(nd):
        if ax not in kept_set and sizes_l[ax] != 1:
            raise ValueError(
                f"axis {ax} dropped by perm {perm} must have window size 1, "
                f"got {sizes_l[ax]}"
            )
    full_perm = kept + [ax for ax in range(nd) if ax not in kept_set]
    out_shape = tuple(sizes_l[ax] for ax in kept)
    static_base = all(isinstance(b, (int, np.integer)) for b in base_l)
    if use_pallas() and static_base:
        # fused one-pass form: the window base rides in the kernel's
        # index_map offsets, no materialized slice (DESIGN.md §6).  The base
        # is clamped like dynamic_slice so both paths agree on semantics.
        base_c = tuple(
            min(max(int(b), 0), x.shape[k] - int(sizes_l[k]))
            for k, b in enumerate(base_l)
        )
        sizes_t = tuple(int(s) for s in sizes_l)
        interp = _interpret()
        if rnd_k.window_blocks(
            x.shape, x.dtype, tuple(full_perm), base_c, sizes_t, tpu_rule=not interp
        ) is not None:
            moved = rnd_k.reorder_window(
                x, tuple(full_perm), base_c, sizes_t, interpret=interp
            )
            return moved.reshape(out_shape)
    # runtime (traced) or misaligned base: slice, then permute via kernel
    window = jax.lax.dynamic_slice(x, base_l, sizes_l)
    moved = permute(window, full_perm) if use_pallas() else ref.permute(window, full_perm)
    return moved.reshape(out_shape)


def interlace(arrays: Sequence[Array]) -> Array:
    """Interleave n same-shape arrays along the last axis.  N-D inputs are
    flattened (a metadata reshape) so the whole op is one kernel pass."""
    arrays = list(arrays)
    same = arrays and arrays[0].ndim >= 1 and all(
        a.shape == arrays[0].shape and a.dtype == arrays[0].dtype for a in arrays
    )
    if use_pallas() and same and il_k.lane_aligned(arrays[0].size):
        lead, last = arrays[0].shape[:-1], arrays[0].shape[-1]
        flat = tuple(a.reshape(-1) for a in arrays)
        out = il_k.interlace(flat, interpret=_interpret())
        return out.reshape(*lead, last * len(arrays))
    return ref.interlace(arrays)  # mismatched inputs raise in the oracle


def deinterlace(x: Array, n: int) -> list[Array]:
    """Inverse of :func:`interlace` along the last axis (N-D supported)."""
    if (
        use_pallas() and x.ndim >= 1 and x.shape[-1] % n == 0
        and il_k.lane_aligned(x.size // n)
    ):
        lead, last = x.shape[:-1], x.shape[-1]
        outs = il_k.deinterlace(x.reshape(-1), n, interpret=_interpret())
        return [o.reshape(*lead, last // n) for o in outs]
    return ref.deinterlace(x, n)


def _fused_ok(x: Array, radii, boundary: str, **kw) -> bool:
    """The stencil precondition: the Pallas path is on, the grid is a
    non-empty 2-D array, and the fused pipeline has a panel for it."""
    return (
        use_pallas() and boundary in st_k.BOUNDARIES and x.ndim == 2
        and x.size > 0
        and st_k.fused_panel(*x.shape, x.dtype, tuple(radii), boundary, **kw)
        is not None
    )


def stencil2d(
    x: Array,
    offsets,
    weights,
    *,
    boundary: str = "zero",
) -> Array:
    """Single weighted-sum stencil sweep (any of the four boundary modes)."""
    radius = max((max(abs(dy), abs(dx)) for dy, dx in offsets), default=0)
    if _fused_ok(x, (radius,), boundary):
        return st_k.stencil2d(
            x, offsets, weights, boundary=boundary, interpret=_interpret()
        )
    return ref.stencil2d(x, offsets, weights, boundary=boundary)


def stencil2d_functor(
    x: Array,
    functor: Callable,
    radius: int,
    *,
    boundary: str = "zero",
) -> Array:
    """Single generic-functor stencil sweep (trace-time specialization)."""
    if _fused_ok(x, (int(radius),), boundary):
        return st_k.stencil2d_functor(
            x, functor, radius, boundary=boundary, interpret=_interpret()
        )
    return ref.stencil2d_functor(x, functor, radius, boundary=boundary)


def stencil_program(
    x: Array,
    stages,
    *,
    boundary: str = "zero",
    block_rows: int | None = None,
    aux: Array | None = None,
    fused: bool = True,
    window: tuple | None = None,
) -> Array:
    """Execute a compiled stencil program (tuple of (functor, radius)
    stages — see ``core.stencil.StencilPlan.stages_exec``).

    Fused temporal-blocking kernel on the Pallas path when
    :func:`repro.kernels.stencil2d.fused_panel` has a panel for the grid;
    per-sweep oracle sweeps otherwise (or when the planner routed the
    program to the reference path, ``fused=False``).

    ``window=(row0, global_rows)`` runs the program in global-row-window
    mode (§10 halo exchange): ``x`` is a halo-extended shard whose row 0
    sits at global row ``row0`` (may be traced) of a ``global_rows``-row
    grid.  Boundary conditions then fire at the true grid edges and the
    caller crops the contaminated apron.  ``aux`` is a single-device-only
    feature and cannot be combined with ``window``.
    """
    if window is not None and aux is not None:
        raise ValueError("window mode does not support aux operands")
    row0, global_rows = (None, None) if window is None else window
    radii = tuple(int(r) for _, r in stages)
    if fused and _fused_ok(
        x, radii, boundary, block_rows=block_rows, halo_resident=window is not None
    ):
        return st_k.stencil2d_pipeline(
            x,
            stages,
            boundary=boundary,
            aux=aux,
            block_rows=block_rows,
            row0=row0,
            global_rows=global_rows,
            halo_resident=window is not None,
            interpret=_interpret(),
        )
    if window is not None:
        return ref.stencil_pipeline_window(
            x, stages, boundary=boundary, row0=row0, global_rows=global_rows
        )
    return ref.stencil_pipeline(x, stages, boundary=boundary, aux=aux)
