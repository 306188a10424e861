"""Shared tiling helpers for the Pallas rearrangement kernels.

TPU facts encoded here (v5e target):
* native vector register tile is (8, 128) for fp32 — (sublanes, lanes);
  bf16 packs (16, 128), int8 (32, 128).
* VMEM is ~16 MiB/core; the Pallas pipeline double-buffers every operand,
  so the *planner budget* is VMEM_BUDGET/2 per direction.
* DMA efficiency wants >= ~64 KiB per transfer; larger blocks amortize
  better until they crowd out double buffering.

The CUDA paper's 32x32 tile / 32x8 threads / 4-elements-per-thread choices
are the C1060 equivalents of exactly these constraints — see DESIGN.md §2.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------

VMEM_BYTES = 16 * 1024 * 1024
# pipeline double-buffers in + out; keep a conservative working budget
VMEM_BUDGET = VMEM_BYTES // 4

LANES = 128


def sublanes(dtype) -> int:
    """Minimum second-minor tile dim for a dtype (packing)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def hbm_tile_rows(dtype) -> int:
    """Rows of the tile a 2-D ``dtype`` array has in HBM: the least a DMA
    can fetch of it.  XLA lays 32-bit and 16-bit arrays out in 8-row tiles
    (bf16 as ``T(8,128)(2,1)``: 8 rows of packed pairs, not the 16-row
    VMEM tile of :func:`sublanes`), and Mosaic refuses a DMA slice whose
    rows are not whole tiles ("Slice shape along dimension 0 must be
    aligned to tiling (8)").  1-byte types keep their sublane tile."""
    return 8 if jnp.dtype(dtype).itemsize >= 2 else sublanes(dtype)


def round_up(x: int, m: int) -> int:
    """Round ``x`` up to the next multiple of ``m``."""
    return -(-x // m) * m


def cdiv(a: int, b: int) -> int:
    """Ceiling division (grid-step counts)."""
    return -(-a // b)


def pick_block(dim: int, target: int, mult: int) -> int:
    """Block size for one axis: ``target`` rounded to ``mult``, clamped to
    cover ``dim`` with no more padding waste than one partial block."""
    if dim <= mult:
        return dim  # tiny axis: single (possibly sub-tile) block
    b = min(round_up(target, mult), round_up(dim, mult))
    return b


@dataclass(frozen=True)
class TilePlan:
    """Chosen 2-D tile for the (rows, cols) movement plane."""

    block_r: int
    block_c: int
    grid_r: int
    grid_c: int

    @property
    def vmem_bytes_per_buf(self) -> int:
        """Elements per pipeline buffer (multiply by itemsize for bytes)."""
        return self.block_r * self.block_c


def plan_transpose_tiles(
    rows: int, cols: int, dtype, *, target: int | None = None
) -> TilePlan:
    """Tile the (rows, cols) transpose plane.

    Both the load block (br, bc) and the store block (bc, br) must be
    lane/sublane aligned, so *both* dims are rounded to LANES when large
    (a square 256x256 default keeps both sides full-width DMAs — the TPU
    version of "coalesced on read AND write", paper §III-B).
    """
    itemsize = jnp.dtype(dtype).itemsize
    if target is None:
        # in+out double-buffered: 4 buffers of br*bc*itemsize
        target = 256 if itemsize >= 2 else 512
        while 4 * target * target * itemsize > VMEM_BUDGET * 2:
            target //= 2
    br = pick_block(rows, target, LANES if rows >= LANES else sublanes(dtype))
    bc = pick_block(cols, target, LANES if cols >= LANES else sublanes(dtype))
    return TilePlan(br, bc, cdiv(rows, br), cdiv(cols, bc))


@dataclass(frozen=True)
class VecTilePlan:
    """Tile for the (rows, cols) transpose plane when every element carries
    a contiguous V-deep vector payload (collapsed identity tail)."""

    block_r: int
    block_c: int
    block_v: int
    grid_r: int
    grid_c: int
    grid_v: int


def plan_transpose_vec_tiles(rows: int, cols: int, vec: int, dtype) -> VecTilePlan:
    """Tile a batched (B, R, C, V) -> (B, C, R, V) transpose.

    V is the lane axis (it stays minor on both sides, so every DMA is a run
    of V-contiguous elements); R and C only need sublane alignment.  The
    whole payload is kept when it fits; otherwise V is blocked in LANES
    multiples and the (r, c) tile shrinks to respect the VMEM budget.
    """
    itemsize = jnp.dtype(dtype).itemsize
    sl = sublanes(dtype)
    budget_elems = max(VMEM_BUDGET // (2 * itemsize), 1)

    if vec <= LANES:
        bv = vec
    else:
        bv = min(round_up(vec, LANES), max(LANES, budget_elems // (sl * sl) // LANES * LANES))
        if bv > vec:
            bv = vec
    plane_budget = max(budget_elems // max(bv, 1), 1)
    target = max(int(plane_budget ** 0.5), 1)
    br = pick_block(rows, target, sl)
    bc = pick_block(cols, target, sl)
    while br * bc > plane_budget and bc > sl:
        bc = max(sl, bc // 2)
    while br * bc > plane_budget and br > sl:
        br = max(sl, br // 2)
    return VecTilePlan(
        br, bc, bv, cdiv(rows, br), cdiv(cols, bc), cdiv(vec, bv)
    )


def shrink_rows(br: int, bc: int, max_elems: int, sl: int) -> int:
    """Halve the row block until the (br, bc) buffer fits ``max_elems``,
    clamped at the ``sl`` sublane floor: plain halving can land below it
    (bf16 sl=16 with br=24 -> 12), producing an unaligned row block."""
    while br * bc > max_elems and br > sl:
        br = max(sl, br // 2)
    return br


def plan_copy_tiles(rows: int, cols: int, dtype, *, target_rows: int = 512) -> TilePlan:
    """Tile a streaming (rows, cols) copy: cols stay full-width when they
    fit the budget (long contiguous DMAs), rows are blocked."""
    itemsize = jnp.dtype(dtype).itemsize
    sl = sublanes(dtype)
    bc = cols
    max_elems = VMEM_BUDGET // (2 * itemsize)
    br = max(sl, min(round_up(target_rows, sl), max_elems // max(bc, 1)))
    if br > rows:
        br = rows
    br = shrink_rows(br, bc, max_elems, sl)
    return TilePlan(br, bc, cdiv(rows, br), cdiv(cols, bc))


def align_block(block: int, offset: int) -> int:
    """Largest block size <= ``block`` that divides evenly into ``offset``
    (halving search, floor 1).  Used when a window base offset must land on
    a block boundary so the BlockSpec index_map stays exact."""
    b = max(block, 1)
    while offset % b != 0:
        b //= 2
    return max(b, 1)


def interpreted() -> bool:
    """True when a Pallas kernel runs in the interpreter: the platform is
    not a TPU.  The one answer to "interpreted or compiled" — every
    kernel's ``interpret=None`` default and ``ops._interpret`` read it."""
    return jax.devices()[0].platform != "tpu"


# ---------------------------------------------------------------------------
# candidate enumeration (the autotuner's search space, DESIGN.md §11)
#
# Every planner's heuristic tile is one point in a small neighborhood of
# legal configurations; the tuner (core/tune.py) measures or cost-scores
# that neighborhood instead of trusting the one-shot formula.  Enumeration
# lives here so the legality rules (alignment, VMEM budget) stay next to
# the heuristics they relax.
# ---------------------------------------------------------------------------


def neighborhood(value: int, mult: int, dim: int) -> tuple[int, ...]:
    """The ±1 multiplier-step neighborhood of a block size over an axis of
    extent ``dim``: the heuristic ``value`` first (always kept verbatim, so
    the tuner's tie-break recovers the untuned plan exactly), then its
    halving and doubling, each aligned to ``mult`` and clamped to
    ``[mult, round_up(dim, mult)]``.  Axes at or below one ``mult`` tile
    have no neighbors (the heuristic already takes the whole axis)."""
    out = [value]
    if dim > mult:
        hi = round_up(dim, mult)
        for v in (value // 2, value * 2):
            v = max(mult, min(round_up(v, mult), hi))
            if v not in out:
                out.append(v)
    return tuple(out)


def transpose_tile_candidates(
    rows: int, cols: int, dtype, seed: TilePlan | None = None
) -> tuple[TilePlan, ...]:
    """Tile candidates for the transpose plane: the ``seed`` tile first
    (the analytic derivation when the planner recognized the request as
    affine, else the :func:`plan_transpose_tiles` heuristic), then its
    (block_r, block_c) ±1 neighborhood, keeping only VMEM-legal
    combinations (both the load and store blocks double-buffered)."""
    itemsize = jnp.dtype(dtype).itemsize
    base = seed if seed is not None else plan_transpose_tiles(rows, cols, dtype)
    mr = LANES if rows >= LANES else sublanes(dtype)
    mc = LANES if cols >= LANES else sublanes(dtype)
    out = []
    for br in neighborhood(base.block_r, mr, rows):
        for bc in neighborhood(base.block_c, mc, cols):
            if 4 * br * bc * itemsize > VMEM_BUDGET * 2:
                continue
            tp = TilePlan(br, bc, cdiv(rows, br), cdiv(cols, bc))
            if tp not in out:
                out.append(tp)
    return tuple(out) or (base,)


def vec_tile_candidates(
    rows: int, cols: int, vec: int, dtype, seed: VecTilePlan | None = None
) -> tuple[VecTilePlan, ...]:
    """Tile candidates for the V-deep transpose plane: the ``seed`` tile
    first (analytic derivation or the :func:`plan_transpose_vec_tiles`
    heuristic), then the (block_r, block_c) neighborhood at the seed's
    ``block_v`` (the lane-axis depth is fixed by payload contiguity, so
    only the plane tile is searched)."""
    itemsize = jnp.dtype(dtype).itemsize
    sl = sublanes(dtype)
    base = seed if seed is not None else plan_transpose_vec_tiles(rows, cols, vec, dtype)
    budget_elems = max(VMEM_BUDGET // (2 * itemsize), 1)
    plane_budget = max(budget_elems // max(base.block_v, 1), 1)
    out = []
    for br in neighborhood(base.block_r, sl, rows):
        for bc in neighborhood(base.block_c, sl, cols):
            if br * bc > plane_budget:
                continue
            vp = VecTilePlan(
                br, bc, base.block_v, cdiv(rows, br), cdiv(cols, bc),
                cdiv(vec, base.block_v),
            )
            if vp not in out:
                out.append(vp)
    return tuple(out) or (base,)


def copy_tile_candidates(
    rows: int, cols: int, dtype, seed: TilePlan | None = None
) -> tuple[TilePlan, ...]:
    """Tile candidates for the streaming-copy plane: columns stay full
    width (the long contiguous DMAs are the point of the route), only the
    row-block height is searched around the ``seed`` tile (analytic
    derivation or the :func:`plan_copy_tiles` heuristic)."""
    itemsize = jnp.dtype(dtype).itemsize
    sl = sublanes(dtype)
    base = seed if seed is not None else plan_copy_tiles(rows, cols, dtype)
    max_elems = VMEM_BUDGET // (2 * itemsize)
    out = []
    for br in neighborhood(base.block_r, sl, rows):
        br = min(br, rows)
        if br * base.block_c > max_elems:
            continue
        tp = TilePlan(br, base.block_c, cdiv(rows, br), cdiv(cols, base.block_c))
        if tp not in out:
            out.append(tp)
    return tuple(out) or (base,)


def row_block_candidates(
    base: int, n_out: int, row_bytes: int, dtype, top_k: int = 1
) -> tuple[int, ...]:
    """Row-block candidates for the index-set kernels: the IndexPlan
    heuristic height (``base``) first, then its ±1 step neighborhood, all
    sublane aligned and inside the double-buffered VMEM budget (divided by
    the combine fan-in ``top_k``, which keeps k source rows resident)."""
    sl = sublanes(dtype)
    br_budget = max(VMEM_BUDGET // (2 * max(row_bytes, 1) * top_k), 1)
    hi = min(max(br_budget // sl * sl, sl), max(n_out, 1))
    seen, out = set(), []
    for b in neighborhood(base, sl, hi):
        b = min(b, n_out)
        if b > 0 and b not in seen:
            seen.add(b)
            out.append(b)
    return tuple(out)
