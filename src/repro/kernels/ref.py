"""Pure-jnp oracles for every kernel in ``repro.kernels``.

These are the ground-truth semantics the Pallas kernels are validated
against (interpret mode on CPU, real lowering on TPU).  They are also the
*dispatch target* on non-TPU platforms: XLA fuses these into respectable
code on CPU/GPU, while the Pallas implementations own the TPU fast path.

Conventions
-----------
* numpy axis order: axis 0 slowest, axis -1 fastest (row-major), matching
  the paper's "row major linearized storage".
* The paper's ``order`` vectors (fastest-dim-first) are converted to numpy
  transpose permutations by :func:`repro.core.layout.paper_order_to_perm`.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array

# ---------------------------------------------------------------------------
# §III-A  basic read/write
# ---------------------------------------------------------------------------


def copy(x: Array) -> Array:
    """Contiguous device-to-device copy (the paper's read/write kernel)."""
    return x + jnp.zeros((), x.dtype)  # force a materialized copy under jit


def copy_range(x: Array, start: int, size: int) -> Array:
    """Ranged access: copy ``x[start:start+size]`` along axis 0."""
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=0)


def gather_rows(x: Array, idx: Array) -> Array:
    """Index-set access: rows of ``x`` (axis 0) selected by ``idx``."""
    return jnp.take(x, idx, axis=0)


def gather_rows_masked(x: Array, idx: Array) -> Array:
    """Sentinel-aware index-set access: ``out[i] = x[idx[i]]`` with
    ``idx[i] < 0`` producing a zero row (the in-kernel masking semantics
    of the blocked gather, DESIGN.md §4)."""
    if x.shape[0] == 0:
        return jnp.zeros((idx.shape[0],) + x.shape[1:], x.dtype)
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    rows = jnp.take(x, safe, axis=0)
    mask = (idx >= 0).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(mask, rows, jnp.zeros((), x.dtype))


def scatter_rows(x: Array, idx: Array, num_out: int | None = None) -> Array:
    """Permutation scatter: ``out[idx[i]] = x[i]``.  ``idx`` must be a
    permutation (or injective into ``num_out`` rows)."""
    n = x.shape[0] if num_out is None else num_out
    out = jnp.zeros((n,) + x.shape[1:], x.dtype)
    return out.at[idx].set(x, mode="drop")


def gather_combine(src: Array, back: Array, gates: Array) -> Array:
    """Fused gather + weighted combine oracle:
    ``out[t] = sum_k gates[t, k] * src[back[t, k]]``; ``back[t, k] < 0``
    contributes zero.  Ground truth for
    `gather_scatter.gather_combine_blocked` (products and the k-sum run in
    ``src.dtype``, matching the unfused gather->multiply->sum chain)."""
    t, k = back.shape
    rows = gather_rows_masked(src, back.reshape(-1)).reshape(t, k, src.shape[1])
    return (rows * gates.astype(rows.dtype)[..., None]).sum(axis=1)


def grouped_swiglu(
    x: Array, w_gate: Array, w_up: Array, w_down: Array, tile_group: Array,
    n_used: Array, tm: int,
) -> Array:
    """Grouped SwiGLU oracle: each ``tm``-row tile of ``x`` through the
    weights of its group ``tile_group[tile]`` only, one tile at a time, in
    the kernel's precision (f32 products, the hidden rounded once to
    ``x.dtype``): ``rows x 6·d·f`` FLOPs, as the kernel's grid, with one
    expert's weights live at a time.  Rows of tiles past ``n_used`` are
    zero.  Ground truth for `grouped_matmul.grouped_swiglu`."""
    d = x.shape[1]

    def tile(args):
        xt, g, live = args
        gate = jnp.dot(xt, w_gate[g], preferred_element_type=jnp.float32)
        up = jnp.dot(xt, w_up[g], preferred_element_type=jnp.float32)
        h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = jnp.dot(h, w_down[g], preferred_element_type=jnp.float32)
        return jnp.where(live, y, 0.0).astype(x.dtype)

    n_tiles = tile_group.shape[0]
    live = jnp.arange(n_tiles) < n_used
    out = jax.lax.map(tile, (x.reshape(n_tiles, tm, d), tile_group, live))
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# §III-B  permute / reorder
# ---------------------------------------------------------------------------


def transpose2d(x: Array) -> Array:
    """2-D transpose — the building block of every reorder."""
    return x.T


def transpose2d_batched(x: Array) -> Array:
    """(B, R, C) -> (B, C, R): batched 2-D transpose."""
    return jnp.swapaxes(x, -1, -2)


def permute(x: Array, perm: Sequence[int]) -> Array:
    """N-D permute with a numpy-convention permutation."""
    return jnp.transpose(x, tuple(perm))


def reorder_nm(
    x: Array,
    perm: Sequence[int],
    base: Sequence[int] | None = None,
    sizes: Sequence[int] | None = None,
) -> Array:
    """The paper's generic N->M reorder: slice a window ``[base, base+sizes)``
    out of ``x``, transpose the kept axes into ``perm`` order, and squeeze
    axes not present in ``perm`` (their window size must be 1).

    ``perm`` lists the *input* axes (numpy convention) that appear in the
    output, slowest-first.  Axes of ``x`` not in ``perm`` are reduced to a
    single element selected by ``base``.
    """
    nd = x.ndim
    base = [0] * nd if base is None else list(base)
    sizes = list(x.shape) if sizes is None else list(sizes)
    kept = set(int(p) for p in perm)
    for ax in range(nd):
        if ax not in kept and sizes[ax] != 1:
            raise ValueError(
                f"axis {ax} dropped by perm {perm} must have window size 1, "
                f"got {sizes[ax]}"
            )
    window = jax.lax.dynamic_slice(x, base, sizes)
    full_perm = list(perm) + [ax for ax in range(nd) if ax not in kept]
    moved = jnp.transpose(window, full_perm)
    return moved.reshape(tuple(sizes[ax] for ax in perm))


def bit_reversal(x: Array, *, axis: int = 0) -> Array:
    """Bit-reversal reorder along ``axis`` (FFT layouts): element ``i`` moves
    to the index whose base-2 digits are ``i``'s reversed.  The axis length
    must be a power of two."""
    n = x.shape[axis]
    if n & (n - 1):
        raise ValueError(f"bit_reversal axis length {n} is not a power of 2")
    bits = max(n.bit_length() - 1, 0)
    i = jnp.arange(n)
    rev = jnp.zeros_like(i)
    for b in range(bits):
        rev = rev | (((i >> b) & 1) << (bits - 1 - b))
    return jnp.take(x, rev, axis=axis)


def strided_gather(x: Array, stride: int, *, phase: int = 0, axis: int = 0) -> Array:
    """Strided slice ``x[..., phase::stride, ...]`` along ``axis`` (the
    affine window/stride class)."""
    if stride <= 0:
        raise ValueError(f"stride must be positive, got {stride}")
    idx = jnp.arange(phase, x.shape[axis], stride)
    return jnp.take(x, idx, axis=axis)


def diagonal_reorder(x: Array) -> Array:
    """Skewed-diagonal reorder of the trailing plane:
    ``out[..., i, j] = x[..., i, (i + j) % C]`` (the paper's diagonal block
    walk applied to the data itself — cyclically shift row ``i`` left by
    ``i``)."""
    if x.ndim < 2:
        raise ValueError("diagonal_reorder wants rank >= 2")
    rows, cols = x.shape[-2], x.shape[-1]
    i = jnp.arange(rows)[:, None]
    j = jnp.arange(cols)[None, :]
    idx = jnp.broadcast_to((i + j) % max(cols, 1), x.shape[-2:])
    return jnp.take_along_axis(x, jnp.broadcast_to(idx, x.shape), axis=-1)


def shuffle(x: Array, seed: int = 0) -> Array:
    """Seeded bijective row shuffle along axis 0: the same mixed-radix
    digit-permute + per-digit-rotation bijection the table-free Pallas
    route lowers (``affine.shuffle_map``), materialized here as one gather
    through the map's index table."""
    from repro.core import affine  # lazy: keep ref importable standalone

    n = x.shape[0]
    if n <= 1:
        return x + jnp.zeros((), x.dtype)
    amap = affine.shuffle_map(n, seed=seed)
    return jnp.take(x, jnp.asarray(amap.index_vector()), axis=0)


# ---------------------------------------------------------------------------
# §III-C  interlace / de-interlace
# ---------------------------------------------------------------------------


def interlace(arrays: Sequence[Array]) -> Array:
    """n arrays of shape (..., L) -> one array (..., L*n) with
    ``out[..., j*n + k] = arrays[k][..., j]`` (AoS from SoA)."""
    stacked = jnp.stack(arrays, axis=-1)  # (..., L, n)
    return stacked.reshape(*stacked.shape[:-2], -1)


def deinterlace(x: Array, n: int) -> list[Array]:
    """Inverse of :func:`interlace`: (..., L*n) -> n arrays (..., L)."""
    if x.shape[-1] % n:
        raise ValueError(f"last dim {x.shape[-1]} not divisible by n={n}")
    split = x.reshape(*x.shape[:-1], x.shape[-1] // n, n)
    return [split[..., k] for k in range(n)]


# ---------------------------------------------------------------------------
# §III-D  generic 2-D stencil
# ---------------------------------------------------------------------------

# boundary-condition family (DESIGN.md §9): name -> jnp.pad mode.  'clamp'
# is a back-compat alias for 'nearest'.
BOUNDARY_PAD_MODES = {
    "zero": "constant",
    "nearest": "edge",
    "clamp": "edge",
    "reflect": "reflect",
    "periodic": "wrap",
}


def pad_boundary(x: Array, radius: int, boundary: str) -> Array:
    """Extend ``x`` by ``radius`` cells on every side per the boundary
    condition: ``zero`` (constant 0), ``nearest`` (edge replicate),
    ``reflect`` (mirror about the edge cell), ``periodic`` (wrap)."""
    if boundary not in BOUNDARY_PAD_MODES:
        raise ValueError(
            f"unknown boundary {boundary!r}; want one of {sorted(BOUNDARY_PAD_MODES)}"
        )
    return jnp.pad(x, radius, mode=BOUNDARY_PAD_MODES[boundary])


def stencil2d(
    x: Array,
    offsets: Sequence[tuple[int, int]],
    weights: Array,
    *,
    boundary: str = "zero",
) -> Array:
    """Weighted-sum stencil: ``out[y,x] = sum_k w[k] * in[y+dy_k, x+dx_k]``.

    boundary: one of ``zero | nearest | reflect | periodic`` (see
    :func:`pad_boundary`; 'clamp' is accepted as an alias for 'nearest').
    """
    r = max(max(abs(dy), abs(dx)) for dy, dx in offsets)
    xp = pad_boundary(x, r, boundary)
    h, w = x.shape
    out = jnp.zeros_like(x)
    for (dy, dx), wk in zip(offsets, weights):
        out = out + wk * jax.lax.dynamic_slice(xp, (r + dy, r + dx), (h, w))
    return out


def stencil2d_functor(
    x: Array,
    functor: Callable[..., Array],
    radius: int,
    *,
    boundary: str = "zero",
    aux: Array | None = None,
) -> Array:
    """Generic functor stencil (the paper's template/functor mechanism).

    ``functor(shift)`` receives a function ``shift(dy, dx) -> Array`` that
    returns the input shifted by (dy, dx) (same shape as ``x``), and returns
    the output grid.  Arbitrary point-wise combinations are allowed, e.g.::

        def laplace(shift):
            return shift(-1, 0) + shift(1, 0) + shift(0, -1) + shift(0, 1) \
                   - 4.0 * shift(0, 0)

    With ``aux`` (an extra same-shape array, e.g. a Poisson source term) the
    functor is called as ``functor(shift, src)`` where ``src()`` returns the
    aux grid.
    """
    xp = pad_boundary(x, radius, boundary)
    h, w = x.shape

    def shift(dy: int, dx: int) -> Array:
        if max(abs(dy), abs(dx)) > radius:
            raise ValueError(f"shift ({dy},{dx}) exceeds radius {radius}")
        return jax.lax.dynamic_slice(xp, (radius + dy, radius + dx), (h, w))

    if aux is None:
        return functor(shift)
    return functor(shift, lambda: aux)


def stencil_pipeline(
    x: Array,
    stages: Sequence[tuple[Callable[..., Array], int]],
    *,
    boundary: str = "zero",
    aux: Array | None = None,
) -> Array:
    """Oracle for a multi-stage stencil program: apply each ``(functor,
    radius)`` stage as one full-grid sweep, re-extending the boundary
    between sweeps.  This is the k-HBM-round-trip semantics the fused
    temporal-blocking kernel (``stencil2d.stencil2d_pipeline``) must match.
    """
    for functor, radius in stages:
        x = stencil2d_functor(x, functor, radius, boundary=boundary, aux=aux)
    return x


def stencil_pipeline_window(
    x: Array,
    stages: Sequence[tuple[Callable[..., Array], int]],
    *,
    boundary: str = "zero",
    row0: Array | int = 0,
    global_rows: int | None = None,
) -> Array:
    """Oracle for a stencil program on a *window* of a larger global grid
    (the §10 halo-exchange semantics, mirrored from the fused kernel's
    ``row0``/``global_rows`` mode).

    ``x`` holds rows ``[row0, row0 + x.shape[0])`` of a ``global_rows``-row
    grid (``row0`` may be traced — it is ``axis_index * rows_per_shard``
    under `shard_map`); columns are complete.  Each stage re-extends the
    row boundary *in global coordinates*: rows outside the global domain
    are rebuilt from in-domain rows per the boundary mode (periodic rows
    are already resident — the ring exchange delivered them), then the
    stage sweeps with the true column boundary.  Rows whose dependency cone
    leaves the window come out contaminated and must be cropped by the
    caller (``sum(radius_i)`` rows per side — `core/dist_plan.py` does).
    """
    if boundary not in BOUNDARY_PAD_MODES:
        raise ValueError(
            f"unknown boundary {boundary!r}; want one of {sorted(BOUNDARY_PAD_MODES)}"
        )
    h_ext, w = x.shape
    hg = h_ext if global_rows is None else int(global_rows)
    g = jnp.asarray(row0, jnp.int32) + jnp.arange(h_ext, dtype=jnp.int32)
    for functor, r in stages:
        if boundary == "periodic" or hg <= 0:
            cur = x
        elif boundary == "zero":
            inside = (g >= 0) & (g < hg)
            cur = jnp.where(inside[:, None], x, jnp.zeros((), x.dtype))
        else:
            if boundary == "reflect" and hg > 1:
                p = 2 * hg - 2
                m = g % p
                src = jnp.where(m < hg, m, p - m)
            else:  # nearest / clamp (and reflect on a 1-row grid)
                src = jnp.clip(g, 0, hg - 1)
            pos = jnp.clip(src - jnp.asarray(row0, jnp.int32), 0, h_ext - 1)
            cur = jnp.take(x, pos, axis=0)
        # rows beyond the window only feed contaminated (cropped) outputs:
        # a zero row pad is enough.  Columns are complete, so the column
        # boundary is the true one.
        xp = jnp.pad(cur, ((r, r), (0, 0)))
        if r:
            xp = jnp.pad(xp, ((0, 0), (r, r)), mode=BOUNDARY_PAD_MODES[boundary])

        def shift(dy: int, dx: int, _xp=xp, _r=r) -> Array:
            if max(abs(dy), abs(dx)) > _r:
                raise ValueError(f"shift ({dy},{dx}) exceeds radius {_r}")
            return jax.lax.dynamic_slice(_xp, (_r + dy, _r + dx), (h_ext, w))

        x = functor(shift)
    return x


def fd_stencil_offsets(order: int) -> tuple[list[tuple[int, int]], list[float]]:
    """Central finite-difference Laplacian stencil of a given order
    (paper Fig. 2 runs orders I..IV — half-widths 1..4 along each axis).

    Returns cross-shaped (offsets, weights) for the 2-D Laplacian using
    standard central-difference coefficients of accuracy 2*order.
    """
    coeffs = {
        1: [-2.0, 1.0],
        2: [-5.0 / 2.0, 4.0 / 3.0, -1.0 / 12.0],
        3: [-49.0 / 18.0, 3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0],
        4: [-205.0 / 72.0, 8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0],
    }[order]
    offsets: list[tuple[int, int]] = [(0, 0)]
    weights: list[float] = [2.0 * coeffs[0]]  # d2/dy2 + d2/dx2 share center
    for k in range(1, order + 1):
        for off in ((k, 0), (-k, 0), (0, k), (0, -k)):
            offsets.append(off)
            weights.append(coeffs[k])
    return offsets, weights


# ---------------------------------------------------------------------------
# attention oracle (flash forward/backward ground truth, DESIGN.md §13)
# ---------------------------------------------------------------------------


def attention(
    q: Array,  # (B, Hq, Sq, D)
    k: Array,  # (B, Hkv, Skv, D)
    v: Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
) -> Array:
    """Naive GQA attention: materializes the full (Sq, Skv) matrix in fp32.

    Exact semantics of ``kernels.flash.flash_attention`` — unscaled
    ``softmax(q k^T) v`` (callers pre-scale q by 1/sqrt(d)), causal mask
    at absolute query position ``q_offset + i``, kv head ``h // g`` serving
    query head ``h``.  Ground truth for the gradient-correctness tier
    (tests/test_train_engine.py) and the second-order fallback of the flash
    backward custom VJP; it is fully differentiable to any order.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1) if g > 1 else k
    vv = jnp.repeat(v, g, axis=1) if g > 1 else v
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    )
    if causal:
        q_pos = q_offset + jnp.arange(sq)[:, None]
        k_pos = jnp.arange(skv)[None, :]
        s = jnp.where(q_pos >= k_pos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return o.astype(q.dtype)
