"""Generic N-D reorder kernel (paper §III-B "Reorder Kernel"), TPU-native.

The paper's canonicalization — *every valid reorder reduces to batched 2-D
data movement in the plane of the fastest-changing input dim and the
fastest-changing output dim* — is kept intact.  What changes on TPU:

* CUDA stores the stride tables in **constant memory**; every thread reads
  them to compute its source address.  On TPU we go one better: block
  indices are computed *arithmetically in the scalar core* inside the
  BlockSpec ``index_map`` (mixed-radix decomposition of the linearized
  batch grid index, with radices baked in as compile-time constants).
  Zero memory traffic for metadata, and no 5-dim performance cliff — the
  paper's Table 2 shows 43 GB/s at 5-D because of metadata-lookup overhead;
  our index arithmetic is free relative to the DMAs it schedules.
* Exactly **two axes are blocked**: the input-fastest axis (lane dim of the
  load tile) and the axis that becomes output-fastest (lane dim of the
  store tile).  All other axes are batch.  Both DMAs therefore move full
  lane-aligned tiles — coalesced-on-both-sides, per the paper.  The TPU
  tiling rule also needs the second-minor axis of each block sublane-deep,
  so a batch axis in that position gets a sublane block too (see
  :func:`_tile_blocks`).
* If the permutation *preserves* the fastest axis ("copy mode"), the kernel
  degenerates to a blocked gather of contiguous rows — the paper's N-to-M
  case with preserved dim-0.

``permute_nd`` is the full-array form; ``reorder_window`` is the windowed
N->M form (paper §III-B), sharing the same grid builder with the (static)
window base folded into the input index map (DESIGN.md §6).

``perm`` uses numpy convention: ``out axis j  <-  in axis perm[j]``.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    LANES,
    VMEM_BYTES,
    align_block,
    cdiv,
    interpreted,
    plan_copy_tiles,
    plan_transpose_tiles,
    sublanes,
)


def _permute_kernel(perm, extra, x_ref, o_ref):
    """Move one block: ``o = transpose(x, perm)`` over the kernel's view.

    ``extra`` lists the view axes that carry sublane-deep batch blocks.  A
    whole-block N-D transpose of such a block makes Mosaic stage ~25x its
    size in VMEM, so those axes are unrolled instead: each step moves one
    2-D (rows, lanes) plane, transposed when the lane axis changes."""
    if not extra:
        o_ref[...] = jnp.transpose(x_ref[...], perm)
        return
    swap = perm[-1] != len(perm) - 1
    for pos in itertools.product(*(range(x_ref.shape[a]) for a in extra)):
        at = dict(zip(extra, pos))
        plane = x_ref[tuple(at.get(a, slice(None)) for a in range(len(perm)))]
        o_ref[tuple(at.get(a, slice(None)) for a in perm)] = plane.T if swap else plane


def _compiler_params(n: int, block_bytes: int) -> pltpu.CompilerParams:
    """Sequential grid semantics, and a scoped-VMEM limit that holds the
    double-buffered in + out blocks (raised above the compiler default only
    when the blocks need it)."""
    need = 4 * block_bytes + (2 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.ARBITRARY,) * n,
        vmem_limit_bytes=max(need, VMEM_BYTES),
    )


def _movement_axes(perm: tuple[int, ...]) -> tuple[int | None, int, bool]:
    """The two blocked axes of the movement plane: (r_in, c_in, transpose?).

    r_in is None at rank 1 (no second axis to block — a pure lane copy)."""
    N = len(perm)
    c_in = N - 1
    transpose_mode = perm[-1] != c_in
    if N < 2:
        return None, c_in, False
    r_in = perm[-1] if transpose_mode else perm[-2]
    return r_in, c_in, transpose_mode


#: per-buffer byte cap for a reorder block: the plane tile shrinks toward
#: it when batch axes take sublane blocks (see :func:`_tile_blocks`)
_BLOCK_BYTES = 2 << 20


def _tile_blocks(
    perm: tuple[int, ...],
    shape: tuple[int, ...],
    sizes: tuple[int, ...],
    base: tuple[int, ...],
    dtype,
    br: int,
    bc: int,
    *,
    tpu_rule: bool = True,
) -> list[int] | None:
    """Per-input-axis block sizes of ``transpose(x[base:base+sizes], perm)``
    or None when every window base is not a whole number of blocks, or
    (``tpu_rule``) the blocks break the TPU tiling rule on either side.

    The two plane axes carry the (br, bc) tile.  The rule also binds the
    second-minor axis of the input block (axis N-2) and of the output block
    (input axis ``perm[-2]``): when either is a batch axis it takes a
    sublane-deep block instead of a unit one, and the plane tile halves
    (keeping its own alignment) until the block fits ``_BLOCK_BYTES``."""
    N = len(perm)
    r_in, c_in, transpose_mode = _movement_axes(perm)
    blocks = [1] * N
    blocks[c_in] = bc
    if r_in is not None:
        blocks[r_in] = br
    if N >= 2:
        sl = sublanes(dtype)
        for k in {N - 2, perm[-2]} - {r_in, c_in}:
            blocks[k] = align_block(min(sl, sizes[k]), base[k])
        # halve the plane tile toward the byte cap, keeping each side legal
        itemsize = jnp.dtype(dtype).itemsize
        need = {c_in: LANES, r_in: LANES if transpose_mode else sl}
        while math.prod(blocks) * itemsize > _BLOCK_BYTES:
            can = [a for a in need if blocks[a] > 1 and (blocks[a] // 2) % need[a] == 0]
            if not can:
                break
            k = max(can, key=lambda a: blocks[a])
            blocks[k] //= 2
    out_sizes = [sizes[p] for p in perm]

    def legal(b: int, dim: int, mult: int) -> bool:
        return b == dim or b % mult == 0

    ok = all(base[k] % blocks[k] == 0 for k in range(N))
    if not tpu_rule:
        return blocks if ok else None
    ok = ok and legal(blocks[N - 1], shape[N - 1], LANES)
    ok = ok and legal(blocks[perm[-1]], out_sizes[-1], LANES)
    if N >= 2:
        ok = ok and legal(blocks[N - 2], shape[N - 2], 8)
        ok = ok and legal(blocks[perm[-2]], out_sizes[-2], 8)
    return blocks if ok else None


def _reorder_call(
    x: jax.Array,
    perm: tuple[int, ...],
    base: tuple[int, ...],
    sizes: tuple[int, ...],
    blocks: list[int],
    grid_order: str,
    interpret: bool,
) -> jax.Array:
    """Shared grid builder: ``transpose(x[base : base+sizes], perm)`` as one
    pallas_call over the :func:`_tile_blocks` blocks.  The two plane axes
    ride the (i, j) grid axes; every other axis walks the flattened batch
    grid (unit-block batch axes are squeezed out of the kernel's view)."""
    N = x.ndim
    W = sizes
    out_shape = tuple(W[p] for p in perm)
    r_in, c_in, _ = _movement_axes(perm)
    nblocks = [cdiv(W[k], blocks[k]) for k in range(N)]
    offs = [base[k] // blocks[k] for k in range(N)]  # exact: blocks aligned

    plane = {c_in} if r_in is None else {r_in, c_in}
    if grid_order == "out":
        batch_in_axes = [p for p in perm if p not in plane]
    elif grid_order == "in":
        batch_in_axes = [k for k in range(N) if k not in plane]
    else:
        raise ValueError(f"grid_order must be 'in' or 'out', got {grid_order!r}")
    batch_radix = [nblocks[a] for a in batch_in_axes]
    G = math.prod(batch_radix) if batch_radix else 1

    # mixed-radix weights: coordinate of batch axis a = (g // w[a]) % radix[a]
    weights: dict[int, int] = {}
    w = 1
    for a, r in zip(reversed(batch_in_axes), reversed(batch_radix)):
        weights[a] = w
        w *= r

    def win_coords(g, i, j):
        coords = []
        for k in range(N):
            if k == r_in:
                coords.append(i)
            elif k == c_in:
                coords.append(j)
            else:
                coords.append(lax.rem(g // weights[k], nblocks[k]))
        return coords

    def in_map(g, i, j):
        return tuple(c + offs[k] for k, c in enumerate(win_coords(g, i, j)))

    def out_map(g, i, j):
        c = win_coords(g, i, j)
        return tuple(c[p] for p in perm)

    # unit blocks are squeezed, so the kernel transposes only the kept axes
    kept = [k for k in range(N) if blocks[k] > 1 or k in plane]
    in_block = tuple(blocks[k] if k in kept else None for k in range(N))
    out_block = tuple(in_block[p] for p in perm)
    kernel_perm = tuple(kept.index(p) for p in perm if p in kept)
    extra = tuple(i for i, k in enumerate(kept) if k not in plane)
    grid_r = nblocks[r_in] if r_in is not None else 1
    block_bytes = math.prod(blocks) * jnp.dtype(x.dtype).itemsize

    return pl.pallas_call(
        functools.partial(_permute_kernel, kernel_perm, extra),
        grid=(G, grid_r, nblocks[c_in]),
        in_specs=[pl.BlockSpec(in_block, in_map)],
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(3, block_bytes),
    )(x)


def _plan_blocks(
    perm: tuple[int, ...], sizes: tuple[int, ...], dtype
) -> tuple[int, int]:
    """Heuristic (br, bc) tile of the movement plane of ``perm`` over
    (window) ``sizes``."""
    r_in, c_in, transpose_mode = _movement_axes(perm)
    R = sizes[r_in] if r_in is not None else 1
    C = sizes[c_in]
    if transpose_mode:
        plan = plan_transpose_tiles(R, C, dtype)
    else:
        plan = plan_copy_tiles(R, C, dtype)
    return plan.block_r, plan.block_c


@functools.partial(
    jax.jit,
    static_argnames=("perm", "block_r", "block_c", "grid_order", "interpret"),
)
def permute_nd(
    x: jax.Array,
    perm: tuple[int, ...],
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    grid_order: str = "out",
    interpret: bool | None = None,
) -> jax.Array:
    """General N-D permute: ``out = jnp.transpose(x, perm)`` as a tiled
    Pallas data-movement kernel.

    grid_order: 'out' walks batch blocks in output-linear order (stores are
    sequential in HBM), 'in' walks in input-linear order (loads sequential).
    This is the TPU analogue of the paper's block-scheduling policies.
    """
    N = x.ndim
    perm = tuple(int(p) for p in perm)
    if sorted(perm) != list(range(N)):
        raise ValueError(f"bad perm {perm} for rank {N}")
    if N == 0 or perm == tuple(range(N)):
        # identity: fall through to a plain copy (still a kernel-shaped op)
        return x + jnp.zeros((), x.dtype)

    r_in, c_in, _ = _movement_axes(perm)
    pr, pc = _plan_blocks(perm, x.shape, x.dtype)
    br = min(block_r or pr, x.shape[r_in]) if r_in is not None else 1
    bc = min(block_c or pc, x.shape[c_in])
    interpret = interpreted() if interpret is None else interpret
    base = (0,) * N
    blocks = _tile_blocks(
        perm, x.shape, x.shape, base, x.dtype, br, bc, tpu_rule=not interpret
    )
    if blocks is None:
        raise ValueError(f"tile ({br}, {bc}) breaks the TPU tiling rule for {x.shape}")
    return _reorder_call(x, perm, base, x.shape, blocks, grid_order, interpret)


def _affine_body(perm_axes, out_block, rshift, x_ref, o_ref):
    """Kernel body for the affine route: reorder the loaded block into the
    output digit order, then (diagonal maps) apply the per-row modular lane
    shift while the lane digit is fully resident."""
    blk = jnp.transpose(x_ref[...], perm_axes).reshape(out_block)
    if rshift is not None:
        C, rot, sign, kind, weight, radix, br = rshift
        rows = max(blk.size // C, 1)
        # out[col] = plane[(col + rot + sign * coord) % C]: a lane rotation
        # by -(rot + sign * coord), strided by -sign per row on the row kind
        if kind == "row":
            coord0, stride = pl.program_id(1) * br, (-sign) % C
        else:  # batch digit: one coordinate per grid step
            coord0, stride = lax.rem(pl.program_id(0) // weight, radix), 0
        shift = lax.rem(lax.rem(-(rot + sign * coord0), C) + C, C)
        plane = blk.reshape(rows, C)
        wide = jnp.float32 if jnp.issubdtype(plane.dtype, jnp.floating) else jnp.int32
        kw = {"stride": stride, "stride_axis": 0} if stride else {}
        # Mosaic rotates only 32-bit data: narrower types widen exactly
        plane = pltpu.roll(plane.astype(wide), shift, 1, **kw).astype(blk.dtype)
        blk = plane.reshape(out_block)
    o_ref[...] = blk


def _affine_blocks(ex, block_r: int | None, block_c: int | None):
    """(br, bc, in_block, out_block) of the affine kernel for the derived
    execution ``ex`` (tile overrides ``block_r`` / ``block_c``)."""
    m = ex.amap
    outd = m.out_digits
    jr, jc = ex.jr, ex.jc
    R = outd[jr] if jr is not None else 1
    C = outd[jc]
    br = align_block(min(block_r or ex.block_r, R),
                     m.base[m.src[jr]]) if jr is not None else 1
    if ex.resident_skew:
        bc = C  # lane digit fully resident (shifted in-kernel)
    else:
        bc = align_block(min(block_c or ex.block_c, C), m.base[m.src[jc]])
    in_block = [1] * len(m.in_digits)
    out_block = [1] * len(outd)
    if jr is not None:
        in_block[m.src[jr]] = out_block[jr] = br
    in_block[m.src[jc]] = out_block[jc] = bc
    return br, bc, tuple(in_block), tuple(out_block)


def affine_tiling_ok(ex) -> bool:
    """True when the kernel for the derived execution ``ex``
    (``affine.derive``) satisfies the TPU tiling rule: the last two dims
    of its input and output blocks are (8, 128)-divisible or whole.  The
    planner records it on every affine plan (``RearrangePlan.tpu_kernel``);
    dispatch sends a map without it to the oracle on the chip, where the
    compiler would refuse the kernel."""
    if ex.mode != "affine":
        return True  # permutation class: permute_nd tiles it legally
    _, _, in_block, out_block = _affine_blocks(ex, None, None)

    def legal(block, dims):
        ok = block[-1] == dims[-1] or block[-1] % LANES == 0
        if len(dims) >= 2:
            ok = ok and (block[-2] == dims[-2] or block[-2] % 8 == 0)
        return ok

    return legal(in_block, ex.amap.in_digits) and legal(out_block, ex.amap.out_digits)


@functools.partial(
    jax.jit,
    static_argnames=("amap", "block_r", "block_c", "grid_order", "interpret"),
)
def reorder_affine(
    x: jax.Array,
    amap,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    grid_order: str = "out",
    interpret: bool | None = None,
) -> jax.Array:
    """Generalized reorder driven by an :class:`repro.core.affine.AffineMap`:
    ONE pallas_call computing ``out[o] = in[A·o + b]`` over mixed-radix
    digit spaces (window bases, per-digit rotations, and the diagonal skew).

    The map's closed-form derivation (``affine.derive``) picks the two
    blocked output digits; every other digit walks the batch grid with the
    per-digit mod-affine arithmetic evaluated *in the scalar core* inside
    the BlockSpec index_map — the affine generalization of ``permute_nd``'s
    mixed-radix decomposition, still zero memory traffic for metadata.  A
    skewed lane digit stays fully resident and is shifted in-kernel (a
    strided lane rotation).  Raises ValueError when the map has no
    single-pass lowering; the planner routes such maps to the oracle."""
    from repro.core import affine as af  # lazy: affine imports tiling only

    ex = af.derive(amap, x.dtype, grid_order)
    m = ex.amap
    if m.n_out == 0 or m.n_in == 0:
        return jnp.zeros(m.out_digits, x.dtype)
    if ex.mode != "affine":
        # permutation class: the merged map is a plain (shape, perm) pair
        return permute_nd(
            x.reshape(m.in_digits), m.src,
            block_r=block_r or ex.block_r, block_c=block_c or ex.block_c,
            grid_order=grid_order, interpret=interpret,
        ).reshape(amap.out_digits)
    x = x.reshape(m.in_digits)
    outd, ind = m.out_digits, m.in_digits
    mo, ni = len(outd), len(ind)
    jr, jc = ex.jr, ex.jc
    R = outd[jr] if jr is not None else 1
    C = outd[jc]
    br, bc, in_block, out_block = _affine_blocks(ex, block_r, block_c)

    batch = [j for j in range(mo) if j != jr and j != jc]
    if grid_order == "in":
        batch.sort(key=lambda j: m.src[j])
    elif grid_order != "out":
        raise ValueError(f"grid_order must be 'in' or 'out', got {grid_order!r}")
    # the skew source of every *batch* digit must itself be decodable from
    # the grid step: another batch digit, or a blocked digit at unit block
    for j in batch:
        k = m.skew[j]
        if k == jr and br != 1 or k == jc and bc != 1:
            raise ValueError("batch digit skewed off a blocked digit")
    gweights: dict[int, int] = {}
    w = 1
    for j in reversed(batch):
        gweights[j] = w
        w *= outd[j]
    G = w

    def coord(jdig, g, i, j):
        if jdig == jr:
            return i  # exact: br == 1 when used as a skew source
        if jdig == jc:
            return j
        return lax.rem(g // gweights[jdig], outd[jdig])

    def in_map(g, i, j):
        c = [m.base[d] for d in range(ni)]  # unmapped digits: pinned, block 1
        for jd in range(mo):
            d = m.src[jd]
            if jd == jr:
                c[d] = i + m.base[d] // br
            elif jd == jc:
                c[d] = 0 if ex.resident_skew else j + m.base[d] // bc
            else:
                o = coord(jd, g, i, j) + m.rot[jd]
                if m.skew[jd] >= 0:
                    o = o + m.skew_sign[jd] * coord(m.skew[jd], g, i, j)
                r = outd[jd]
                c[d] = m.base[d] + lax.rem(lax.rem(o, r) + r, r)
        return tuple(c)

    def out_map(g, i, j):
        return tuple(
            i if jd == jr else j if jd == jc else coord(jd, g, i, j)
            for jd in range(mo)
        )

    # in-block axes -> output digit order (trailing axes are unit window /
    # pinned digits, absorbed by the reshape)
    perm_axes = [m.src[jd] for jd in range(mo)]
    perm_axes += [d for d in range(ni) if d not in perm_axes]

    rshift = None
    if ex.resident_skew:
        k0 = m.skew[jc]
        if k0 == -1:  # rotation only: constant lane shift
            rshift = (C, m.rot[jc], 0, "batch", 1, 1, br)
        elif k0 == jr or k0 in gweights:
            kind = "row" if k0 == jr else "batch"
            rshift = (
                C, m.rot[jc], m.skew_sign[jc], kind,
                gweights.get(k0, 1), outd[k0], br,
            )
        else:
            raise ValueError("lane digit skewed off an undecodable digit")

    interpret = interpreted() if interpret is None else interpret
    block_bytes = math.prod(out_block) * jnp.dtype(x.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(
            _affine_body, tuple(perm_axes), out_block, rshift
        ),
        grid=(G, cdiv(R, br) if jr is not None else 1, cdiv(C, bc)),
        in_specs=[pl.BlockSpec(in_block, in_map)],
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=jax.ShapeDtypeStruct(outd, x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(3, block_bytes),
    )(x)
    return out.reshape(amap.out_digits)


@functools.partial(
    jax.jit, static_argnames=("perm", "base", "sizes", "grid_order", "interpret")
)
def reorder_window(
    x: jax.Array,
    perm: tuple[int, ...],
    base: tuple[int, ...],
    sizes: tuple[int, ...],
    *,
    grid_order: str = "out",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused windowed N->M reorder (paper §III-B): one pallas_call computing
    ``transpose(x[base : base + sizes], perm)``.

    The window slice is *not* materialized — the static base offsets are
    folded into the input BlockSpec ``index_map`` (the TPU analogue of the
    paper's constant-memory metadata), so the windowed reorder is a single
    pass over HBM instead of slice-then-permute.  Blocked plane axes shrink
    their block (by halving) until the base offset is block-aligned; batch
    axes use unit blocks so any offset is exact.  A base so misaligned that
    the plane blocks would degrade below the sublane floor raises
    ValueError — dispatch then falls back to the two-pass form rather than
    issuing element-granular DMAs.
    """
    N = x.ndim
    perm = tuple(int(p) for p in perm)
    if sorted(perm) != list(range(N)):
        raise ValueError(f"bad perm {perm} for rank {N}")
    if len(base) != N or len(sizes) != N:
        raise ValueError(f"base/sizes must have rank {N}")
    for k in range(N):
        if not (0 <= base[k] and base[k] + sizes[k] <= x.shape[k]):
            raise ValueError(
                f"window [{base[k]}, {base[k]}+{sizes[k]}) exceeds axis {k} "
                f"of shape {x.shape}"
            )
    interpret = interpreted() if interpret is None else interpret
    base = tuple(int(b) for b in base)
    W = tuple(int(s) for s in sizes)
    blocks = window_blocks(x.shape, x.dtype, perm, base, W, tpu_rule=not interpret)
    if blocks is None:
        raise ValueError(f"window base {base} too misaligned for fused blocks")
    return _reorder_call(x, perm, base, W, blocks, grid_order, interpret)


def window_blocks(
    shape: tuple[int, ...],
    dtype,
    perm: tuple[int, ...],
    base: tuple[int, ...],
    sizes: tuple[int, ...],
    *,
    tpu_rule: bool,
) -> list[int] | None:
    """The fused windowed reorder's blocks, or None when the window cannot
    run as one pass — the precondition dispatch checks before choosing the
    fused form over slice-then-permute.

    Blocked plane axes shrink (by halving) until the base offset is
    block-aligned.  None when that drives a plane block below the dtype's
    sublane floor (the fused pass would issue element-granular DMAs), or,
    with ``tpu_rule``, when the blocks break the TPU compiler's tiling
    rule (the interpreter has no such rule)."""
    r_in, c_in, _ = _movement_axes(perm)
    pr, pc = _plan_blocks(perm, sizes, dtype)
    br = align_block(min(pr, sizes[r_in]), base[r_in]) if r_in is not None else 1
    bc = align_block(min(pc, sizes[c_in]), base[c_in])
    sl = sublanes(dtype)
    if r_in is not None and br < min(sl, sizes[r_in]) or bc < min(sl, sizes[c_in]):
        return None
    return _tile_blocks(perm, shape, sizes, base, dtype, br, bc, tpu_rule=tpu_rule)
