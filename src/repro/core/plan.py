"""Rearrangement planner: collapse -> route -> cache (DESIGN.md §3).

The planner is the library's 'auto gridding' (paper §III-A: "gridding and
threading configuration is done automatically based on the data size") and
the single dispatch spine for every permute-shaped op:

1. **collapse** — merge contiguous input axes that stay adjacent under the
   permutation (:func:`repro.core.layout.coalesce`), so every reorder
   reduces to its minimal-rank canonical form;
2. **route** — pick the cheapest kernel for the canonical form:
   ``identity`` (pure reshape, no data movement), ``transpose`` (the
   adjacent-swap family -> batched 2-D transpose, `kernels/permute3d.py`),
   ``copy`` (fastest axis preserved -> blocked row gather), or ``reorder``
   (generic fallback, `kernels/reorder_nd.py`);
3. **cache** — plans are memoized on ``(shape, dtype, perm, grid_order)``
   so steady-state training/serving steps pay zero planning overhead
   (repeated calls return the *identical* plan object).

It also reports the predicted HBM traffic and roofline time so callers
(and the benchmarks) can compare achieved vs predicted movement.

``tuned=`` adds the optional fourth step (DESIGN.md §11): the routed
plan's tile neighborhood is enumerated and the autotuner
(:mod:`repro.core.tune`) selects by measurement (TPU) or by the roofline
cost model (deterministic fallback).  The untuned default is bit-identical
to the pre-tuner planner; a tuned plan differs only in tiles / grid
order, never in the computed result.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Sequence

import jax.numpy as jnp

from repro.core import affine, layout, tune
from repro.kernels.tiling import (
    TilePlan,
    VecTilePlan,
    cdiv,
    copy_tile_candidates,
    plan_copy_tiles,
    plan_transpose_tiles,
    plan_transpose_vec_tiles,
    transpose_tile_candidates,
    vec_tile_candidates,
)
from repro.kernels.reorder_nd import affine_tiling_ok
from repro.utils.roofline import HBM_BW, movement_cost_s


@dataclass(frozen=True)
class RearrangePlan:
    """Cached lowering decision for one permutation: the canonical
    (collapsed) form, the kernel route, the chosen tiles, and the predicted
    HBM traffic/roofline (DESIGN.md §3)."""

    mode: str  # identity | copy | transpose | reorder | affine | oracle
    kernel: str  # noop | copy | transpose2d_batched[_vec] | reorder_nd | reorder_affine
    canonical_shape: tuple[int, ...]
    canonical_perm: tuple[int, ...]
    out_shape: tuple[int, ...]  # full-rank output shape
    exec_shape: tuple[int, ...] | None  # (B, R, C, V) for transpose mode
    block_r: int
    block_c: int
    grid_order: str
    bytes_moved: int  # read + write
    roofline_s: float  # bytes / HBM bandwidth (one chip)
    block_v: int | None = None  # lane-depth tile on the _vec route
    plan_source: str = "heuristic"  # heuristic | analytic | tuned
    amap: affine.AffineMap | None = None  # merged map, affine-mode plans
    # False when the kernel breaks the TPU tiling rule (affine mode only):
    # dispatch then runs the oracle on the chip (the interpreter has no rule)
    tpu_kernel: bool = True

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        tiles = f"tiles=({self.block_r},{self.block_c}"
        tiles += f",{self.block_v})" if self.block_v is not None else ")"
        ex = f" exec={self.exec_shape}" if self.exec_shape is not None else ""
        tpu = "kernel" if self.tpu_kernel else "oracle"
        return (
            f"{self.mode}: shape={self.canonical_shape} perm={self.canonical_perm} "
            f"kernel={self.kernel} {tiles}{ex} source={self.plan_source} tpu={tpu} "
            f"{self.bytes_moved/1e6:.2f} MB moved, "
            f"roofline {self.roofline_s*1e6:.1f} us @ {HBM_BW / 1e9:g} GB/s"
        )


def _build_plan(
    shape: tuple[int, ...],
    dtype_name: str,
    perm: tuple[int, ...],
    grid_order: str,
    block_r: int | None = None,
    block_c: int | None = None,
) -> RearrangePlan:
    """Collapse + route one permutation and materialize the plan.

    ``block_r`` / ``block_c`` override the heuristic tiles (the tuner's
    hook); with both ``None`` this is exactly the pre-tuner planner.
    """
    canon = layout.canonicalize(shape, perm)
    itemsize = jnp.dtype(dtype_name).itemsize
    n_elems = 1
    for s in shape:
        n_elems *= int(s)
    out_shape = tuple(shape[p] for p in perm)
    bytes_moved = 2 * n_elems * itemsize  # read once + write once

    exec_shape = None
    block_v = None
    factors = None if canon.mode == "identity" else layout.swap_factors(
        canon.shape, canon.perm
    )
    if n_elems == 0:
        # zero-size array: nothing to move, the output is an empty reshape
        return RearrangePlan(
            mode="identity",
            kernel="noop",
            canonical_shape=canon.shape,
            canonical_perm=canon.perm,
            out_shape=out_shape,
            exec_shape=None,
            block_r=1,
            block_c=1,
            grid_order=grid_order,
            bytes_moved=0,
            roofline_s=0.0,
        )
    if canon.mode == "identity" or canon.rows_axis is None:
        # no movement: the output is a metadata reshape of the input (a
        # caller that must materialize routes through the streaming copy
        # kernel, copy.py, with these tiles)
        mode, kernel = "identity", "noop"
        last = shape[-1] if shape else 1
        tp = plan_copy_tiles(max(n_elems // max(last, 1), 1), last, dtype_name)
        br, bc = tp.block_r, tp.block_c
    elif factors is not None:
        # adjacent-swap family: batched 2-D transpose plane, V-deep elements
        mode = "transpose"
        b, r, c, v = factors
        exec_shape = (b, r, c, v)
        if v > 1:
            kernel = "transpose2d_batched_vec"
            vp = plan_transpose_vec_tiles(r, c, v, dtype_name)
            br, bc = vp.block_r, vp.block_c
            block_v = vp.block_v
        else:
            kernel = "transpose2d_batched"
            tp = plan_transpose_tiles(r, c, dtype_name)
            br, bc = tp.block_r, tp.block_c
    elif canon.mode == "copy":
        # fastest axis preserved: blocked gather of contiguous rows
        mode, kernel = "copy", "reorder_nd"
        tp = plan_copy_tiles(
            canon.shape[canon.rows_axis], canon.shape[canon.cols_axis], dtype_name
        )
        br, bc = tp.block_r, tp.block_c
    else:
        # generic fallback: both fastest axes change, not a single swap
        mode, kernel = "reorder", "reorder_nd"
        tp = plan_transpose_tiles(
            canon.shape[canon.rows_axis], canon.shape[canon.cols_axis], dtype_name
        )
        br, bc = tp.block_r, tp.block_c

    if block_r is not None:
        br = block_r
    if block_c is not None:
        bc = block_c
    source = "heuristic"
    if block_r is None and block_c is None:
        # analytic cross-check (DESIGN.md §14): derive the tile in closed
        # form from the affine lift; when it reproduces the routed tile the
        # plan is stamped `analytic` (the common case — the derivation uses
        # the same formulas on the merged run-lengths).  A mismatch (e.g. a
        # size-1 axis splitting a mergeable run, where the affine merge is
        # coarser than `coalesce`) keeps the authoritative heuristic stamp;
        # the plan itself is identical either way.
        try:
            ex = affine.derive(layout.to_affine(shape, perm), dtype_name,
                               grid_order)
            if (ex.mode == mode and ex.block_r == br and ex.block_c == bc
                    and ex.block_v == block_v and ex.exec_shape == exec_shape):
                source = "analytic"
        except ValueError:
            pass
    return RearrangePlan(
        mode=mode,
        kernel=kernel,
        canonical_shape=canon.shape,
        canonical_perm=canon.perm,
        out_shape=out_shape,
        exec_shape=exec_shape,
        block_r=br,
        block_c=bc,
        grid_order=grid_order,
        bytes_moved=bytes_moved,
        roofline_s=bytes_moved / HBM_BW,
        block_v=block_v,
        plan_source=source,
    )


@functools.lru_cache(maxsize=4096)
def _plan_cached(
    shape: tuple[int, ...], dtype_name: str, perm: tuple[int, ...], grid_order: str
) -> RearrangePlan:
    return _build_plan(shape, dtype_name, perm, grid_order)


def _tile_candidates(
    plan: RearrangePlan, shape: tuple, dtype_name: str, grid_order: str
) -> list[tune.Candidate]:
    """Enumerate the tuner's search space around one routed plan: the
    plan's own tile is the seed (the analytic derivation when the request
    was affine-recognized, the heuristic otherwise) and only its ±1
    neighborhood is enumerated — plus, on the ``reorder_nd`` routes, both
    grid-walk orders.  Cost scores include the padded-block traffic and
    grid-step count so the model can separate candidates that move the
    same useful bytes at different granularity."""
    itemsize = jnp.dtype(dtype_name).itemsize
    n_elems = 1
    for s in shape:
        n_elems *= int(s)
    cands: list[tune.Candidate] = []

    def add(br: int, bc: int, go: str, padded_elems: int, steps: int) -> None:
        label = f"br{br}_bc{bc}_{go}"
        if any(c.label == label for c in cands):
            return
        cands.append(
            tune.Candidate(
                label=label,
                params=(("block_r", br), ("block_c", bc), ("grid_order", go)),
                cost_s=movement_cost_s(2 * padded_elems * itemsize, steps),
            )
        )

    if plan.mode == "transpose":
        b, r, c, v = plan.exec_shape
        if v > 1:
            bv = plan.block_v or plan_transpose_vec_tiles(r, c, v, dtype_name).block_v
            seed_v = VecTilePlan(plan.block_r, plan.block_c, bv,
                                 cdiv(r, plan.block_r), cdiv(c, plan.block_c),
                                 cdiv(v, bv))
            for vp in vec_tile_candidates(r, c, v, dtype_name, seed_v):
                padded = (
                    b
                    * (vp.grid_r * vp.block_r)
                    * (vp.grid_c * vp.block_c)
                    * (vp.grid_v * vp.block_v)
                )
                add(vp.block_r, vp.block_c, grid_order,
                    padded, b * vp.grid_r * vp.grid_c * vp.grid_v)
        else:
            seed = TilePlan(plan.block_r, plan.block_c,
                            cdiv(r, plan.block_r), cdiv(c, plan.block_c))
            for tp in transpose_tile_candidates(r, c, dtype_name, seed):
                padded = b * (tp.grid_r * tp.block_r) * (tp.grid_c * tp.block_c)
                add(tp.block_r, tp.block_c, grid_order,
                    padded, b * tp.grid_r * tp.grid_c)
    else:  # copy / reorder: reorder_nd kernel, both grid-walk orders
        enum = (
            copy_tile_candidates if plan.mode == "copy" else transpose_tile_candidates
        )
        r, c = _movement_plane(plan)
        batch = max(n_elems // max(r * c, 1), 1)
        seed = TilePlan(plan.block_r, plan.block_c,
                        cdiv(r, plan.block_r), cdiv(c, plan.block_c))
        for go in (grid_order, "in" if grid_order == "out" else "out"):
            for tp in enum(r, c, dtype_name, seed):
                padded = batch * (tp.grid_r * tp.block_r) * (tp.grid_c * tp.block_c)
                add(tp.block_r, tp.block_c, go, padded, batch * tp.grid_r * tp.grid_c)
    return cands


def _movement_plane(plan: RearrangePlan) -> tuple[int, int]:
    """The (rows, cols) plane the routed kernel tiles (canonical axes)."""
    canon = layout.canonicalize(plan.canonical_shape, plan.canonical_perm)
    return (
        plan.canonical_shape[canon.rows_axis],
        plan.canonical_shape[canon.cols_axis],
    )


def _runner_factory(shape: tuple, dtype_name: str, perm: tuple, grid_order: str):
    """Measured-mode runner: execute one candidate plan on a deterministic
    sample array (jitted, device-synced by the tuner's timing loop)."""

    def factory(cand: tune.Candidate):
        import jax

        from repro.kernels import ops  # lazy: ops imports this module

        d = cand.param_dict()
        plan = _build_plan(
            shape, dtype_name, perm, d["grid_order"],
            block_r=d["block_r"], block_c=d["block_c"],
        )
        x = tune.sample_array(shape, dtype_name)
        fn = jax.jit(lambda a: ops.apply_plan(a, plan))
        return lambda: fn(x)

    return factory


@functools.lru_cache(maxsize=4096)
def _plan_tuned_cached(
    shape: tuple[int, ...],
    dtype_name: str,
    perm: tuple[int, ...],
    grid_order: str,
    mode: str,
) -> RearrangePlan:
    base = _plan_cached(shape, dtype_name, perm, grid_order)
    if base.mode == "identity":
        return base  # nothing to tune: no data moves
    cands = _tile_candidates(base, shape, dtype_name, grid_order)
    choice = tune.select(
        "rearrange",
        f"shape={shape}|dtype={dtype_name}|perm={perm}|go={grid_order}",
        cands,
        _runner_factory(shape, dtype_name, perm, grid_order),
        mode=mode,
    )
    d = choice.param_dict()
    if (
        d["block_r"] == base.block_r
        and d["block_c"] == base.block_c
        and d["grid_order"] == base.grid_order
    ):
        return base  # seed won: tuned and untuned plans are the SAME object
    out = _build_plan(
        shape, dtype_name, perm, d["grid_order"],
        block_r=d["block_r"], block_c=d["block_c"],
    )
    return replace(out, plan_source="tuned")


def plan_rearrange(
    shape: Sequence[int],
    dtype,
    perm: Sequence[int],
    *,
    grid_order: str = "out",
    tuned: bool | None = None,
) -> RearrangePlan:
    """Plan (and cache) the movement for ``transpose(x, perm)``.

    ``tuned=None`` (default) resolves from ``REPRO_TUNE`` — off unless the
    variable opts in, so default plans are bit-identical to the pre-tuner
    engine.  ``tuned=True`` routes through the autotuner (DESIGN.md §11):
    the tile neighborhood is measured (TPU) or cost-scored (elsewhere) and
    the winner is cached with the same lru identity guarantees.
    """
    perm_t = tuple(int(p) for p in perm)
    if sorted(perm_t) != list(range(len(shape))):
        raise ValueError(f"bad perm {perm_t} for rank {len(shape)}")
    if grid_order not in ("in", "out"):
        raise ValueError(f"grid_order must be 'in' or 'out', got {grid_order!r}")
    if tuned is None:
        tuned = tune.tune_default()
    key = (tuple(int(s) for s in shape), jnp.dtype(dtype).name, perm_t, grid_order)
    if not tuned:
        return _plan_cached(*key)
    return _plan_tuned_cached(*key, tune.resolve_mode())


# ---------------------------------------------------------------------------
# affine plans (DESIGN.md §14): requests arriving as an AffineMap — the new
# ops (bit_reversal, strided/diagonal reorder, seeded shuffle) and anything
# the recognizer lifts.  The tile comes from the closed-form derivation
# (`affine.derive`), so the plan source is `analytic` by construction; the
# tuner only *verifies* the seed against its ±1 neighborhood.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1024)
def _plan_affine_cached(
    amap: affine.AffineMap, dtype_name: str, grid_order: str
) -> RearrangePlan:
    itemsize = jnp.dtype(dtype_name).itemsize
    out_shape = tuple(amap.out_digits)
    n_out = amap.n_out
    if n_out == 0 or amap.n_in == 0:
        return RearrangePlan(
            mode="identity", kernel="noop",
            canonical_shape=amap.in_digits,
            canonical_perm=tuple(range(len(amap.in_digits))),
            out_shape=out_shape, exec_shape=None, block_r=1, block_c=1,
            grid_order=grid_order, bytes_moved=0, roofline_s=0.0,
            plan_source="analytic",
        )
    bytes_moved = 2 * n_out * itemsize
    try:
        ex = affine.derive(amap, dtype_name, grid_order)
    except ValueError:
        # no single-pass lowering (e.g. a rotated lane digit that cannot be
        # resident): the explicit oracle route, on every platform
        return RearrangePlan(
            mode="oracle", kernel="ref",
            canonical_shape=amap.in_digits, canonical_perm=amap.src,
            out_shape=out_shape, exec_shape=None, block_r=1, block_c=1,
            grid_order=grid_order, bytes_moved=bytes_moved,
            roofline_s=bytes_moved / HBM_BW, plan_source="analytic",
            tpu_kernel=False,
        )
    m = ex.amap
    if ex.mode == "transpose":
        kernel = (
            "transpose2d_batched_vec" if ex.block_v is not None
            else "transpose2d_batched"
        )
    else:
        kernel = {
            "identity": "noop", "copy": "reorder_nd",
            "reorder": "reorder_nd", "affine": "reorder_affine",
        }[ex.mode]
    return RearrangePlan(
        mode=ex.mode, kernel=kernel,
        canonical_shape=m.in_digits, canonical_perm=m.src,
        out_shape=out_shape, exec_shape=ex.exec_shape,
        block_r=ex.block_r, block_c=ex.block_c, grid_order=grid_order,
        bytes_moved=bytes_moved, roofline_s=bytes_moved / HBM_BW,
        block_v=ex.block_v, plan_source="analytic",
        amap=m if ex.mode == "affine" else None,
        tpu_kernel=affine_tiling_ok(ex),
    )


def _affine_tile_candidates(
    base: RearrangePlan, dtype_name: str
) -> list[tune.Candidate]:
    """The verification neighborhood for an analytic plan: the derived seed
    ±1 step.  Permutation-class plans reuse the generic enumeration; the
    ``affine``-mode kernel searches its (jr, jc) plane, with the lane block
    pinned when the skewed lane digit is resident."""
    if base.mode != "affine":
        return _tile_candidates(
            base, base.canonical_shape, dtype_name, base.grid_order
        )
    itemsize = jnp.dtype(dtype_name).itemsize
    ex = affine.derive(base.amap, dtype_name, base.grid_order)
    R = base.amap.out_digits[ex.jr] if ex.jr is not None else 1
    C = base.amap.out_digits[ex.jc]
    batch = max(base.amap.n_out // max(R * C, 1), 1)
    seed = TilePlan(base.block_r, base.block_c,
                    cdiv(R, base.block_r), cdiv(C, base.block_c))
    enum = copy_tile_candidates if ex.resident_skew else transpose_tile_candidates
    cands: list[tune.Candidate] = []
    for tp in enum(R, C, dtype_name, seed):
        label = f"br{tp.block_r}_bc{tp.block_c}_{base.grid_order}"
        if any(c.label == label for c in cands):
            continue
        padded = batch * (tp.grid_r * tp.block_r) * (tp.grid_c * tp.block_c)
        cands.append(
            tune.Candidate(
                label=label,
                params=(("block_r", tp.block_r), ("block_c", tp.block_c),
                        ("grid_order", base.grid_order)),
                cost_s=movement_cost_s(
                    2 * padded * itemsize, batch * tp.grid_r * tp.grid_c
                ),
            )
        )
    return cands


def _affine_runner_factory(
    amap: affine.AffineMap, dtype_name: str, grid_order: str
):
    """Measured-mode runner for affine plans (mirrors `_runner_factory`)."""

    def factory(cand: tune.Candidate):
        import jax

        from repro.kernels import ops  # lazy: ops imports this module

        d = cand.param_dict()
        base = _plan_affine_cached(amap, dtype_name, d["grid_order"])
        plan = replace(base, block_r=d["block_r"], block_c=d["block_c"])
        x = tune.sample_array(base.canonical_shape, dtype_name)
        fn = jax.jit(lambda a: ops.apply_plan(a, plan))
        return lambda: fn(x)

    return factory


@functools.lru_cache(maxsize=1024)
def _plan_affine_tuned_cached(
    amap: affine.AffineMap, dtype_name: str, grid_order: str, mode: str
) -> RearrangePlan:
    base = _plan_affine_cached(amap, dtype_name, grid_order)
    if base.mode in ("identity", "oracle"):
        return base  # nothing to tune: no data moves, or no kernel
    cands = _affine_tile_candidates(base, dtype_name)
    key = (
        f"amap={amap.in_digits}->{amap.out_digits}|src={amap.src}|"
        f"base={amap.base}|rot={amap.rot}|skew={amap.skew}{amap.skew_sign}|"
        f"dtype={dtype_name}|go={grid_order}"
    )
    choice = tune.select(
        "rearrange", key, cands,
        _affine_runner_factory(amap, dtype_name, grid_order), mode=mode,
    )
    d = choice.param_dict()
    if (
        d["block_r"] == base.block_r
        and d["block_c"] == base.block_c
        and d["grid_order"] == base.grid_order
    ):
        return base  # analytic seed verified: SAME object as the untuned plan
    return replace(
        base, block_r=d["block_r"], block_c=d["block_c"],
        grid_order=d["grid_order"], plan_source="tuned",
    )


def plan_affine(
    amap: affine.AffineMap,
    dtype,
    *,
    grid_order: str = "out",
    tuned: bool | None = None,
) -> RearrangePlan:
    """Plan (and cache) the movement for one :class:`~repro.core.affine.AffineMap`.

    The affine analogue of :func:`plan_rearrange`: the map is coalesced
    (``affine.merge_runs``), classified, and tiled in closed form by
    :func:`affine.derive` — permutation-class maps land on the existing
    kernel routes, anything with window bases / rotations / skew lands on
    the generalized ``reorder_affine`` kernel.  A map with no single-pass
    lowering plans as ``mode="oracle"``; a kernel that breaks the TPU
    tiling rule plans with ``tpu_kernel=False`` — both shown by
    ``describe()`` and obeyed by the dispatch layer.
    ``tuned`` resolves like :func:`plan_rearrange`; because the seed is the
    derivation itself, tuning is a verification pass over its ±1
    neighborhood.
    """
    if not isinstance(amap, affine.AffineMap):
        raise TypeError(f"plan_affine wants an AffineMap, got {type(amap)}")
    if grid_order not in ("in", "out"):
        raise ValueError(f"grid_order must be 'in' or 'out', got {grid_order!r}")
    if tuned is None:
        tuned = tune.tune_default()
    key = (amap, jnp.dtype(dtype).name, grid_order)
    if not tuned:
        return _plan_affine_cached(*key)
    return _plan_affine_tuned_cached(*key, tune.resolve_mode())


def plan_cache_info():
    """Expose the memo stats (tests / benchmarks)."""
    return _plan_cached.cache_info()


def affine_plan_cache_info():
    """Expose the affine-path memo stats (tests / benchmarks)."""
    return _plan_affine_cached.cache_info()


def tuned_plan_cache_info():
    """Expose the tuned-path memo stats (tests / benchmarks)."""
    return _plan_tuned_cached.cache_info()
