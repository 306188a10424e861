"""Generic 2-D stencil kernels (paper §III-D), TPU-native — single-sweep
functor stencils and fused multi-stage pipelines (DESIGN.md §9).

The CUDA kernel loads a 34x34 halo'd tile for a 32x32 block (overlapping,
partially uncoalesced apron loads; texture-memory variants to soften the
misalignment) and takes a *functor* for the per-point computation so any
stencil compiles to a specialized kernel.

TPU version:
* row-panel decomposition: each grid step owns a (block_rows, W) panel with
  the full row width resident in VMEM, so no column apron is ever loaded —
  the paper's misaligned-apron problem is deleted instead of patched with
  texture fetches.
* the row halo is expressed by passing the input again with small
  halo-block specs above and below the owned panel (clamped index maps).
  The Pallas pipeline DMAs each as a lane-aligned tile — the overlap costs
  ``2*halo_rows/block_rows`` extra reads per panel, the same apron
  redundancy the paper reports, but every load stays aligned.
* **temporal blocking** (`stencil2d_pipeline`): a program of k stages is
  applied entirely in VMEM.  The panel is loaded once with a halo of
  ``sum(radius_i)`` rows, every stage runs on it, and the final stage's
  owned rows are the only store.  One HBM round trip replaces k.
* **how a stage takes its shifted views** (:func:`shift_route`, fixed by
  the shapes alone):

  - *roll* — a row of whole lanes (``W % 128 == 0``), a 32-bit dtype and a
    band of whole sublane tiles: the band keeps ONE fixed, tile-aligned
    shape (the owned rows plus the halo blocks) through every stage, and
    ``shift(dy, dx)`` is a sublane rotation then a lane rotation of it.
    Rows the rotation wraps in are garbage but lie in the row apron, which
    is never stored; lanes it wraps in are the periodic boundary, and for
    zero / nearest / reflect a select on the lane index fixes them in the
    first or last lane tile only.  Linear stages scale the band once per
    distinct weight and rotate the scaled band.
  - *slice* — everything else: each stage consumes its radius from the
    band (shrink), re-pads its columns by concatenation and takes each
    view as a static slice.  On the chip those slices are misaligned: a
    one-lane pad shifts every vreg, and the shrinking band has an
    unaligned row count at every stage.
* the boundary-condition family ``zero | nearest | reflect | periodic`` is
  resolved per stage against *global* row indices (which also kills OOB
  garbage in the final partial panel) plus the boundary's column
  extension.  On the roll path the row masking and the one-hot regather
  run only on panels whose band leaves the domain: one branch per panel
  picks between those stages (a loop) and the interior's (unrolled).
* functors run at **trace time** — the exact analogue of the paper's
  compile-time C++ functor: any jnp expression over ``shift(dy, dx)`` views
  specializes the kernel with no interpretive overhead.
"""

from __future__ import annotations

import functools
import itertools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import BOUNDARY_PAD_MODES
from repro.kernels.tiling import (
    LANES,
    VMEM_BYTES,
    cdiv,
    interpreted,
    round_up,
    sublanes,
)

# the supported boundary-condition family, derived from the oracle's pad
# table so the two can never drift ('clamp' is a legacy 'nearest' alias).
BOUNDARIES = tuple(BOUNDARY_PAD_MODES)

Stage = tuple[Callable[..., jax.Array], int]


@functools.lru_cache(maxsize=512)
def _linear_functor(offsets: tuple, weights: tuple) -> Callable:
    """Build (and memoize) the weighted-sum functor for a linear stencil.

    Memoizing on the (offsets, weights) table keeps the functor's identity
    stable across calls, so jit tracing caches hit instead of respecializing
    the kernel for every invocation of the same stencil.  The table rides
    along as ``functor.taps``: the rotation path scales the band once per
    distinct weight and rotates the scaled band (``w * roll(x) ==
    roll(w * x)``, so the products and their order are unchanged).
    """

    def functor(shift, *_unused):
        acc = None
        for (dy, dx), w in zip(offsets, weights):
            term = w * shift(dy, dx)
            acc = term if acc is None else acc + term
        return acc

    functor.taps = tuple(zip(offsets, weights))
    return functor


def _smallest_divisor_at_least(n: int, lo: int) -> int:
    """Smallest divisor of ``n`` that is >= ``lo`` (``n`` itself worst case)."""
    for d in range(max(lo, 1), n):
        if n % d == 0:
            return d
    return n


def pick_panel(
    H: int,
    W: int,
    dtype,
    total_radius: int,
    boundary: str,
    block_rows: int | None = None,
) -> tuple[int, int, bool]:
    """Choose the fused kernel's row-panel configuration.

    Returns ``(block_rows, halo_block_rows, wrap_local)``:

    * ``block_rows`` — rows owned per grid step;
    * ``halo_block_rows`` — row count of the above/below halo blocks (a
      divisor of ``block_rows`` so halo offsets stay block-aligned); 0 when
      the program needs no halo;
    * ``wrap_local`` — periodic-only single-panel mode: the whole grid is
      VMEM-resident and the wrap halo is built from resident rows.

    Raises ``ValueError`` when no fused configuration exists for the shape
    (:func:`fused_panel` turns that into the precondition dispatch checks:
    the library never fails on an awkward shape, it runs per-sweep oracle
    sweeps instead).
    """
    sl = sublanes(dtype)
    itemsize = jnp.dtype(dtype).itemsize
    R = int(total_radius)
    if H <= 0 or W <= 0:
        raise ValueError("empty grid has no fused path")

    if boundary == "periodic":
        # periodic halos wrap across panels, which is only exact when the
        # panel size divides H (no partial panel to misalign the wrap).
        if block_rows is not None:
            br = int(block_rows)
            if br >= H:
                br = H
            elif H % br or br < max(R, 1):
                raise ValueError(
                    f"periodic needs block_rows dividing H and >= radius; "
                    f"got {block_rows} for H={H}, radius={R}"
                )
        else:
            divs = [d for d in range(max(R, 1), H + 1) if H % d == 0]
            br = min(divs, key=lambda d: (d % sl != 0, abs(d - 64))) if divs else H
        wrap_local = br >= H
        rp = 0 if wrap_local else _smallest_divisor_at_least(br, R)
    else:
        wrap_local = False
        if R == 0:
            rp = 0
            br = int(block_rows) if block_rows is not None else max(sl, min(64, H))
        else:
            if block_rows is not None:
                br = int(block_rows)
                if br < R:
                    raise ValueError(f"block_rows {br} < total radius {R}")
                rp = _smallest_divisor_at_least(br, R)
            else:
                rp = round_up(R, sl)
                br = round_up(max(min(64, H), sl, R), rp)

    # conservative VMEM sanity: halo'd working panel plus pipeline buffers,
    # plus the (T, T) one-hot boundary-gather matrix and f32 panel cast the
    # nearest/reflect paths build per stage
    T = br + 2 * R
    need = T * (W + 2 * R) * itemsize * 6
    if boundary in ("nearest", "clamp", "reflect"):
        need += T * T * 4 + T * (W + 2 * R) * 4
    if need > VMEM_BYTES:
        raise ValueError(
            f"fused stencil panel ({br}+2*{R}, {W}) exceeds the VMEM budget"
        )
    return br, rp, wrap_local


def fused_panel(
    H: int,
    W: int,
    dtype,
    radii: tuple[int, ...],
    boundary: str,
    *,
    block_rows: int | None = None,
    halo_resident: bool = False,
) -> tuple[int, int, bool] | None:
    """The fused pipeline's panel ``(block_rows, halo_block_rows,
    wrap_local)`` for a program with stage ``radii``, or None when no fused
    configuration exists — the precondition dispatch and the stencil
    planner check before choosing the kernel over per-sweep oracle sweeps.

    None when a stage's column halo does not fit the row width (reflect
    needs ``W >= r + 1``, periodic ``W >= r``) or :func:`pick_panel` finds
    no panel.  ``halo_resident`` marks periodic wrap rows as physically
    present (the §10 ring exchange delivered them), so the panel geometry
    uses the clamped (non-wrapping) family."""
    for r in radii:
        if r and boundary == "reflect" and W < r + 1:
            return None
        if r and boundary == "periodic" and W < r:
            return None
    geo_boundary = "zero" if (halo_resident and boundary == "periodic") else boundary
    try:
        return pick_panel(H, W, dtype, sum(radii), geo_boundary, block_rows)
    except ValueError:
        return None


def shift_route(
    W: int, dtype, panel: tuple[int, int, bool], radii: tuple[int, ...]
) -> str:
    """How the fused kernel takes a stage's ``shift(dy, dx)`` views for a
    ``panel`` of :func:`fused_panel` — fixed by the shapes alone.

    * ``"roll"`` — the band keeps one fixed shape through every stage and
      each view is a sublane and a lane rotation of it.  Taken when the row
      is whole lanes (``W % 128 == 0``), the dtype is 32-bit, the band and
      its row apron are whole sublane tiles, and every stage radius is under
      one lane tile (the column fix reads the first and last lane tiles).
    * ``"slice"`` — everything else: each stage re-pads its columns and
      slices the views out of a band that shrinks by the stage's radius.
    """
    br, rp, wrap_local = panel
    apron = 0 if (wrap_local or not sum(radii)) else rp
    sl = sublanes(dtype)
    ok = (
        W % LANES == 0
        and jnp.dtype(dtype).itemsize == 4
        and br % sl == 0
        and apron % sl == 0
        and max(radii, default=0) < LANES
    )
    return "roll" if ok else "slice"


def _row_source(g, boundary, h_glob):
    """The in-domain row that global row ``g`` re-extends from
    (nearest / reflect; in-domain rows map to themselves)."""
    if boundary == "reflect" and h_glob > 1:
        p = 2 * h_glob - 2
        m = g % p
        return jnp.where(m < h_glob, m, p - m)
    return jnp.clip(g, 0, h_glob - 1)  # nearest / clamp (and a 1-row reflect)


def _regather(c, pos):
    """Rows ``c[pos]`` of a band, 0 where ``pos`` falls outside it, as a
    one-hot row-gather on the MXU at full f32 precision (a bf16 pass would
    round the copied rows)."""
    T = c.shape[0]
    sel = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 1) == pos).astype(jnp.float32)
    return jax.lax.dot_general(
        sel,
        c.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(c.dtype)


def _col_view(a, dx, boundary, fill):
    """``a[:, c + dx]`` with the boundary's column extension, as one lane
    rotation of ``a``.  Lanes the rotation wraps in are right only for
    periodic; otherwise they are fixed by a select on the lane index,
    confined to the first (``dx < 0``) or last (``dx > 0``) lane tile."""
    if dx == 0:
        return a
    W = a.shape[1]
    out = pltpu.roll(a, (-dx) % W, 1)
    if boundary == "periodic":
        return out
    k, left = abs(dx), dx < 0
    edge = slice(0, LANES) if left else slice(W - LANES, W)
    piece, fixed = a[:, edge], out[:, edge]
    lane = jax.lax.broadcasted_iota(jnp.int32, piece.shape, 1)
    outside = lane < k if left else lane >= LANES - k
    if boundary == "zero":
        fixed = jnp.where(outside, fill, fixed)
    elif boundary in ("nearest", "clamp"):
        end = piece[:, :1] if left else piece[:, LANES - 1:]
        fixed = jnp.where(outside, jnp.broadcast_to(end, piece.shape), fixed)
    else:  # reflect: lane c reads column k - c (left) / 2W - 2 - c - k (right)
        for j in range(k):
            c = j if left else LANES - 1 - j
            s = (2 * c - k if left else 2 * c + k + 2) % LANES
            fixed = jnp.where(lane == c, pltpu.roll(piece, s, 1) if s else piece, fixed)
    if W == LANES:
        return fixed
    if left:
        return jnp.concatenate([fixed, out[:, LANES:]], axis=1)
    return jnp.concatenate([out[:, : W - LANES], fixed], axis=1)


def _rolled_views(band, r, boundary):
    """``view(dy, dx, w)`` = ``w * shift(dy, dx)`` of a fixed (T, W) band
    (``w=None``: unscaled): a sublane rotation for the row shift, then
    :func:`_col_view` for the column shift, each computed once.  Rows the
    sublane rotation wraps in are garbage; they lie in the row apron, which
    is never stored (every stage radius is paid for by the apron)."""
    T = band.shape[0]
    scaled, rows, views = {}, {}, {}

    def view(dy, dx, w=None):
        if max(abs(dy), abs(dx)) > r:
            raise ValueError(f"shift ({dy},{dx}) exceeds stage radius {r}")
        if (dy, dx, w) not in views:
            if w not in scaled:
                scaled[w] = band if w is None else w * band
            b = scaled[w]
            if (dy, w) not in rows:
                rows[dy, w] = pltpu.roll(b, (-dy) % T, 0) if dy else b
            # zero columns read as w * 0, as w times the reference's pad
            dt = b.dtype
            fill = np.zeros((), dt) * np.asarray(1 if w is None else w, dt)
            views[dy, dx, w] = _col_view(rows[dy, w], dx, boundary, fill)
        return views[dy, dx, w]

    return view


def _pipeline_kernel(
    stages, boundary, br, rp, H, W, R, has_aux, wrap_local, h_glob, has_row0,
    roll, *refs,
):
    i = pl.program_id(0)
    o_ref = refs[-1]
    n_per = 1 if (R == 0 or wrap_local) else 3
    x_refs = refs[:n_per]
    pos_ref = n_per + (n_per if has_aux else 0)
    a_refs = refs[n_per:pos_ref] if has_aux else ()
    # global-row window (§10 halo exchange): row 0 of this array sits at
    # global row `row0v` of a `h_glob`-row grid, so boundary masks fire at
    # the TRUE grid edges, not the shard edges.  Single-device calls pass
    # no row0 operand and h_glob == H — identical arithmetic to before.
    row0v = refs[pos_ref][0, 0] if has_row0 else 0
    if roll:
        _roll_stages(
            stages, boundary, br, rp, H, R, has_aux, wrap_local, h_glob,
            has_row0, i, row0v, x_refs, a_refs, o_ref,
        )
    else:
        _slice_stages(
            stages, boundary, br, rp, H, W, R, has_aux, wrap_local, h_glob,
            has_row0, i, row0v, x_refs, a_refs, o_ref,
        )


def _roll_stages(
    stages, boundary, br, rp, H, R, has_aux, wrap_local, h_glob, has_row0,
    i, row0v, x_refs, a_refs, o_ref,
):
    """Rotation path: one fixed, tile-aligned band of global rows
    ``[i*br - P, (i+1)*br + P)`` (P = the ``rp``-row halo block, 0 for a
    single wrapping panel) serves every stage; each stage's views are
    rotations of it (:func:`_rolled_views`) and the owned rows
    ``[P, P + br)`` are the only store.  Row masking and the one-hot
    regather run only on panels whose band leaves the domain, in a branch
    of their own."""
    if wrap_local or R == 0:
        # a single wrapping panel holds the whole grid, so a sublane
        # rotation IS the periodic row extension (R may exceed H)
        P, band = 0, (lambda rs: rs[0][...])
    else:
        P, band = rp, (lambda rs: jnp.concatenate([r[...] for r in rs], axis=0))
    tile = band(x_refs)
    atile = band(a_refs) if has_aux else None
    T = tile.shape[0]
    e0 = i * br - P  # local row of band row 0
    g0 = e0 + row0v

    # rows past a window's local array (has_row0) only feed the cropped
    # apron, so only the global edges decide the branch
    touches = (g0 < 0) | (g0 + T > h_glob)

    def in_domain():
        j = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        ok = (j + g0 >= 0) & (j + g0 < h_glob)
        if has_row0:
            # window mode: rows past the local array (final-partial-panel
            # padding) can sit inside the global domain
            ok = ok & (j + e0 >= 0) & (j + e0 < H)
        return j, ok

    def edge_rows(c, h):
        # zero rows outside the domain or this stage's dependency cone
        # [P - h, P + br + h), so no garbage reaches the MXU; nearest /
        # reflect then rebuild the outside rows from in-domain rows
        j, ok = in_domain()
        ok = ok & (j >= P - h) & (j < P + br + h)
        c = jnp.where(ok, c, jnp.zeros((), c.dtype))
        if boundary == "zero":
            return c
        return _regather(c, _row_source(j + g0, boundary, h_glob) - g0)

    def stage(functor, r, edge, a, t, h):
        cur = edge_rows(t, h) if edge else t
        view = _rolled_views(cur, r, boundary)
        taps = getattr(functor, "taps", None)
        if taps is None:
            return functor(view, lambda: a) if has_aux else functor(view)
        out = None
        for (dy, dx), w in taps:
            term = view(dy, dx, w)
            out = term if out is None else out + term
        return out

    def run(edge):
        # Interior stages are unrolled, so the scheduler overlaps them.  On
        # the few edge panels a run of equal stages (repeat(k)) is one
        # loop: unrolled, its regathers make the program several times
        # larger, and the interior step then slows (PERF.md §6)
        a = atile
        if edge and has_aux:
            # zero out-of-domain aux rows (partial-panel garbage may be NaN)
            a = jnp.where(in_domain()[1], a, jnp.zeros((), a.dtype))
        t, h = tile, R
        for (functor, r), group in itertools.groupby(stages):
            n = len(list(group))
            step = functools.partial(stage, functor, r, edge, a)
            loop = edge and n > 1
            if loop:  # a loop carries the band, so a stage must keep its type
                out = jax.eval_shape(lambda c, _s=step, _h=h: _s(c, _h), t)
                loop = (out.shape, out.dtype) == (t.shape, t.dtype)
            if loop:
                t = jax.lax.fori_loop(
                    0, n, lambda k, c, _s=step, _h=h, _r=r: _s(c, _h - k * _r), t
                )
            else:
                for k in range(n):
                    t = step(t, h - k * r)
            h -= n * r
        o_ref[...] = t[P:P + br].astype(o_ref.dtype)

    if boundary == "periodic":
        # the band holds the wrapped extension (mod index maps / resident
        # rows / a single wrapping panel): no row is ever outside
        run(False)
        return
    pl.when(touches)(lambda: run(True))
    pl.when(jnp.logical_not(touches))(lambda: run(False))


def _slice_stages(
    stages, boundary, br, rp, H, W, R, has_aux, wrap_local, h_glob, has_row0,
    i, row0v, x_refs, a_refs, o_ref,
):
    """Slice path: each stage shrinks the band by its radius, re-pads its
    columns by concatenation, and takes each view as a static slice."""

    def band(rs):
        # assemble the halo'd panel: nominal global rows [i*br - R, (i+1)*br + R)
        if wrap_local:
            # single panel owns the whole grid (br == H): the periodic halo
            # is built from resident rows, m wraps deep when R > H
            c = rs[0][...]
            m = cdiv(R, H) if R else 0
            big = jnp.concatenate([c] * (2 * m + 1), axis=0) if m else c
            return jax.lax.slice_in_dim(big, m * H - R, m * H + H + R, axis=0)
        if R == 0:
            return rs[0][...]
        t = jnp.concatenate([rs[0][...], rs[1][...], rs[2][...]], axis=0)
        return jax.lax.slice_in_dim(t, rp - R, rp + br + R, axis=0)

    tile = band(x_refs)
    atile = band(a_refs) if has_aux else None
    if has_aux and boundary != "periodic":
        # zero OOB aux rows so final-partial-panel garbage (possibly NaN)
        # cannot poison rows that survive the shrink
        ea = jax.lax.broadcasted_iota(jnp.int32, (br + 2 * R, 1), 0) + i * br - R
        ga = ea + row0v
        a_ok = (ga >= 0) & (ga < h_glob)
        if has_row0:
            # window mode: padding rows past the local array can sit inside
            # the global domain (see the x-path mask below) — zero them too
            a_ok = a_ok & (ea >= 0) & (ea < H)
        atile = jnp.where(a_ok, atile, jnp.zeros((), atile.dtype))

    h = R
    for functor, r in stages:
        T = br + 2 * h
        g0 = i * br - h + row0v
        # global row ids of the current band (2-D iota — Mosaic wants >=2-D)
        g = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) + g0
        if boundary == "periodic":
            # periodic content is already the wrapped extension (mod index
            # maps / wrap_local assembly / resident halo rows) and stays so
            # under each stage
            cur = tile
        else:
            inside = (g >= 0) & (g < h_glob)
            if has_row0:
                # window mode: rows past the local array (final-partial-panel
                # padding) can sit INSIDE the global domain, so the global
                # mask alone would keep their garbage (possibly NaN, which
                # the regather dot then spreads).  Zero them — everything
                # depending on them is in the cropped apron.
                eg = g - row0v
                inside = inside & (eg >= 0) & (eg < H)
            cur = jnp.where(inside, tile, jnp.zeros((), tile.dtype))
            if boundary != "zero":
                # re-extend the boundary from in-domain rows (pos may fall
                # outside the band for rows deeper than this stage needs;
                # those resolve to 0 and are shrunk away before they can
                # matter).  Panels whose band lies fully in-domain skip it —
                # the gather would be identity.
                pos = _row_source(g, boundary, h_glob) - g0
                touches_edge = (g0 < 0) | (g0 + T > h_glob)
                cur = jax.lax.cond(
                    touches_edge, lambda c, _p=pos: _regather(c, _p), lambda c: c, cur
                )
        # column halo: boundary-correct pad of r lanes per side
        if r == 0:
            curp = cur
        elif boundary == "zero":
            curp = jnp.pad(cur, ((0, 0), (r, r)))
        elif boundary in ("nearest", "clamp"):
            left = jnp.broadcast_to(jax.lax.slice(cur, (0, 0), (T, 1)), (T, r))
            right = jnp.broadcast_to(jax.lax.slice(cur, (0, W - 1), (T, W)), (T, r))
            curp = jnp.concatenate([left, cur, right], axis=1)
        elif boundary == "reflect":
            # mirrored columns r..1 and W-2..W-r-1, one lane slice each
            # (Mosaic has no lowering for a lane-axis reverse)
            def col(c, _cur=cur, _T=T):
                return jax.lax.slice(_cur, (0, c), (_T, c + 1))

            left = [col(c) for c in range(r, 0, -1)]
            right = [col(c) for c in range(W - 2, W - r - 2, -1)]
            curp = jnp.concatenate([*left, cur, *right], axis=1)
        else:  # periodic
            left = jax.lax.slice(cur, (0, W - r), (T, W))
            right = jax.lax.slice(cur, (0, 0), (T, r))
            curp = jnp.concatenate([left, cur, right], axis=1)

        h2 = h - r
        rows_out = br + 2 * h2

        def shift(dy: int, dx: int, _curp=curp, _r=r, _rows=rows_out):
            if max(abs(dy), abs(dx)) > _r:
                raise ValueError(f"shift ({dy},{dx}) exceeds stage radius {_r}")
            return jax.lax.slice(
                _curp, (_r + dy, _r + dx), (_r + dy + _rows, _r + dx + W)
            )

        if has_aux:
            def src_view(_a=atile, _h2=h2, _rows=rows_out):
                return jax.lax.slice(_a, (R - _h2, 0), (R - _h2 + _rows, W))

            tile = functor(shift, src_view)
        else:
            tile = functor(shift)
        h = h2
    o_ref[...] = tile.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "stages", "boundary", "block_rows", "global_rows", "halo_resident",
        "interpret",
    ),
)
def stencil2d_pipeline(
    x: jax.Array,
    stages: Sequence[Stage],
    *,
    boundary: str = "zero",
    aux: jax.Array | None = None,
    block_rows: int | None = None,
    row0: jax.Array | None = None,
    global_rows: int | None = None,
    halo_resident: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Run a multi-stage stencil program in ONE fused `pallas_call`.

    ``stages`` is a tuple of ``(functor, radius)`` pairs; each functor is
    called as ``functor(shift)`` (or ``functor(shift, src)`` when ``aux``
    is given, where ``src()`` yields the aux band, e.g. a Poisson source
    term).  Stages apply in sequence with the boundary condition re-applied
    between them — semantically identical to ``len(stages)`` full-grid
    sweeps (`ref.stencil_pipeline`) but with a single HBM round trip via
    temporal blocking: each grid panel loads a ``sum(radius_i)``-row halo
    once, runs every stage in VMEM, and stores once.

    Global-row window (the §10 halo-exchange hook): when ``x`` is a
    halo-extended shard of a larger grid, ``row0`` (a traced int32 scalar,
    fed to the kernel as a (1, 1) operand) gives the global row of ``x``'s
    row 0 and ``global_rows`` the full grid height, so every boundary mask
    fires at the true grid edges.  ``halo_resident=True`` marks periodic
    wrap rows as physically present in ``x`` (the ring exchange delivered
    them), switching periodic to the clamped halo BlockSpecs.  Rows whose
    dependency cone leaves ``x`` come out contaminated and must be cropped
    by the caller (the ``sum(radius_i)`` apron — `core/dist_plan.py` does).
    """
    if x.ndim != 2:
        raise ValueError(f"stencil pipeline wants 2-D input, got {x.shape}")
    if boundary not in BOUNDARIES:
        raise ValueError(f"unknown boundary {boundary!r}; want one of {BOUNDARIES}")
    stages = tuple((f, int(r)) for f, r in stages)
    if not stages:
        raise ValueError("empty stencil program")
    if any(r < 0 for _, r in stages):
        raise ValueError("negative stage radius")
    H, W = x.shape
    R = sum(r for _, r in stages)
    panel = fused_panel(
        H, W, x.dtype, tuple(r for _, r in stages), boundary,
        block_rows=block_rows, halo_resident=halo_resident,
    )
    if panel is None:
        raise ValueError(
            f"no fused stencil panel for {x.shape} (radius {R}, boundary "
            f"{boundary!r}, block_rows={block_rows})"
        )
    br, rp, wrap_local = panel
    has_aux = aux is not None
    if has_aux and aux.shape != x.shape:
        raise ValueError(f"aux shape {aux.shape} != grid shape {x.shape}")
    has_row0 = row0 is not None
    h_glob = H if global_rows is None else int(global_rows)

    geo_boundary = "zero" if (halo_resident and boundary == "periodic") else boundary
    nb = cdiv(H, br)
    roll = shift_route(W, x.dtype, panel, tuple(r for _, r in stages)) == "roll"
    interpret = interpreted() if interpret is None else interpret

    def im_cur(i):
        return (i, 0)

    if wrap_local or R == 0:
        per_input = [pl.BlockSpec((br, W), im_cur)]
    else:
        q = br // rp
        nq = cdiv(H, rp)
        if geo_boundary == "periodic":
            below = lambda i: ((i * q - 1) % nq, 0)  # noqa: E731
            above = lambda i: (((i + 1) * q) % nq, 0)  # noqa: E731
        else:
            below = lambda i: (jnp.maximum(i * q - 1, 0), 0)  # noqa: E731
            above = lambda i: (jnp.minimum((i + 1) * q, nq - 1), 0)  # noqa: E731
        per_input = [
            pl.BlockSpec((rp, W), below),
            pl.BlockSpec((br, W), im_cur),
            pl.BlockSpec((rp, W), above),
        ]

    operands = [x] * len(per_input)
    in_specs = list(per_input)
    if has_aux:
        operands += [aux] * len(per_input)
        in_specs += list(per_input)
    if has_row0:
        # (1, 1) int32 scalar operand, broadcast to every panel
        operands.append(jnp.asarray(row0, jnp.int32).reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))

    return pl.pallas_call(
        functools.partial(
            _pipeline_kernel,
            stages,
            boundary,
            br,
            rp,
            H,
            W,
            R,
            has_aux,
            wrap_local,
            h_glob,
            has_row0,
            roll,
        ),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, W), im_cur),
        out_shape=jax.ShapeDtypeStruct((H, W), x.dtype),
        # the roll path's two branches (edge, interior) each spill band-sized
        # values to scoped VMEM of their own: about 16.8 MB with the
        # benchmark's panel, over the compiler's 16 MiB default (v5e has
        # 128 MiB of VMEM)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * VMEM_BYTES if roll else None
        ),
        interpret=interpret,
    )(*operands)


def stencil2d_functor(
    x: jax.Array,
    functor: Callable,
    radius: int,
    *,
    boundary: str = "zero",
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Apply a generic stencil functor over a 2-D grid (single sweep).

    ``functor(shift)`` -> Array, where ``shift(dy, dx)`` yields the panel
    shifted by (dy, dx).  See ``repro.kernels.ref.stencil2d_functor`` for
    the oracle semantics.  A one-stage special case of
    :func:`stencil2d_pipeline`.
    """
    return stencil2d_pipeline(
        x,
        ((functor, int(radius)),),
        boundary=boundary,
        block_rows=block_rows,
        interpret=interpret,
    )


def stencil2d(
    x: jax.Array,
    offsets,
    weights,
    *,
    boundary: str = "zero",
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Weighted-sum stencil via the functor kernel (single sweep)."""
    offs = tuple((int(dy), int(dx)) for dy, dx in offsets)
    wts = tuple(float(w) for w in weights)
    radius = max(max(abs(dy), abs(dx)) for dy, dx in offs)
    return stencil2d_functor(
        x,
        _linear_functor(offs, wts),
        radius,
        boundary=boundary,
        block_rows=block_rows,
        interpret=interpret,
    )
