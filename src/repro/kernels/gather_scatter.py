"""Index-set read/write kernels (paper §III-A "specified set of indices").

The paper's basic access kernels support gathering/scattering rows by an
index table; in CUDA the table lives in constant memory.  On TPU the table
is **scalar-prefetched** (`pltpu.PrefetchScalarGridSpec`): it lands in SMEM
before the grid runs, and the kernel reads it to choose which rows each
grid step DMAs.  This is the exact functional analogue of constant memory:
small, uniformly read metadata off the datapath.

Two generations of kernels live here (DESIGN.md §4):

* **row-wise** (`gather_rows` / `scatter_rows`) — the seed kernels: one
  grid step per row, the row choice riding in the BlockSpec ``index_map``.
  Kept as the benchmark baseline and the fallback for exotic shapes.
* **blocked** (`gather_rows_blocked` / `gather_combine_blocked`) — the
  IndexPlan-engine kernels (`core/index_plan.py`): the index table is
  reshaped to ``(nB, br)`` row blocks so each grid step moves ``br`` rows
  off an HBM-resident source via explicit async copies, with

  - **run detection**: a block whose indices form a contiguous run
    (``idx[base + r] == idx[base] + r``) collapses to ONE strided block
    copy — the index-table analogue of the rearrangement planner's axis
    collapsing, resolved at run time because the table is data;
  - **in-kernel sentinel masking**: a negative index zero-fills its row
    (``pl.when``), so callers never concatenate sentinel rows onto the
    source array;
  - a **fused gather+weighted-combine** form: ``out[t] = sum_k
    gates[t, k] * src[back[t, k]]`` in one kernel — the whole MoE combine
    (gather -> reshape -> multiply -> sum) as a single `pallas_call`.

These kernels are the framework's MoE dispatch/combine primitives: token
permutation by expert id is precisely an index-set gather (DESIGN.md §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    cdiv,
    hbm_tile_rows,
    interpreted,
    plan_copy_tiles,
    sublanes,
)

# ---------------------------------------------------------------------------
# row-wise kernels (seed generation; benchmark baseline)
# ---------------------------------------------------------------------------


def _copy_row_kernel(idx_ref, x_ref, o_ref):
    del idx_ref  # consumed by the index maps
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def gather_rows(
    x: jax.Array,
    idx: jax.Array,
    *,
    block_c: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """out[i, :] = x[idx[i], :].  idx: int32 (num_out,).

    Row-wise seed kernel: one grid step (one DMA) per output row, the
    source row riding in the input BlockSpec ``index_map``.  The blocked
    generation (:func:`gather_rows_blocked`) moves ``br`` rows per step.
    """
    if x.ndim != 2 or idx.ndim != 1:
        raise ValueError(f"gather_rows wants 2-D x and 1-D idx, got {x.shape}, {idx.shape}")
    n_out = idx.shape[0]
    C = x.shape[1]
    bc = min(block_c or plan_copy_tiles(1, C, x.dtype).block_c, C)
    nC = cdiv(C, bc)

    interpret = interpreted() if interpret is None else interpret
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out, nC),
        in_specs=[pl.BlockSpec((1, bc), lambda i, j, idx_ref: (idx_ref[i], j))],
        out_specs=pl.BlockSpec((1, bc), lambda i, j, idx_ref: (i, j)),
    )
    return pl.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, C), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def scatter_rows(
    x: jax.Array,
    idx: jax.Array,
    *,
    block_c: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """out[idx[i], :] = x[i, :].  ``idx`` must be a permutation of
    range(x.shape[0]) — every output row is written exactly once.

    Row-wise seed kernel; the IndexPlan engine executes general (capacity)
    scatters as a masked blocked gather through the inverted table
    (`kernels.ops.scatter_rows`).
    """
    if x.ndim != 2 or idx.ndim != 1 or idx.shape[0] != x.shape[0]:
        raise ValueError(f"scatter_rows wants idx over rows, got {x.shape}, {idx.shape}")
    n = x.shape[0]
    C = x.shape[1]
    bc = min(block_c or plan_copy_tiles(1, C, x.dtype).block_c, C)
    nC = cdiv(C, bc)

    interpret = interpreted() if interpret is None else interpret
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, nC),
        in_specs=[pl.BlockSpec((1, bc), lambda i, j, idx_ref: (i, j))],
        out_specs=pl.BlockSpec((1, bc), lambda i, j, idx_ref: (idx_ref[i], j)),
    )
    return pl.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, C), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x)


# ---------------------------------------------------------------------------
# blocked kernels (IndexPlan engine generation)
# ---------------------------------------------------------------------------


def _pad_table(idx: jax.Array, rows: int) -> jax.Array:
    """Pad the int32 index table to ``rows`` entries with the sentinel -1
    (no concatenate: a full-sized fill + static-slice update)."""
    idx = idx.astype(jnp.int32)
    n = idx.shape[0]
    if n == rows:
        return idx
    return jnp.full((rows,), -1, jnp.int32).at[:n].set(idx)


def _wide(dtype):
    """The 32-bit type rows are rotated in (Mosaic rotates only 32-bit
    data; narrower types widen exactly and narrow back)."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _fetch_rows(x_hbm, tiles, sem, srcs):
    """Rows ``x[srcs[r]]`` at rows ``r`` of a (T, C) 32-bit value, zero
    where ``srcs[r] < 0`` (``len(srcs) <= T``; T = the dtype's sublane
    count, so the group is one aligned store).

    A DMA addresses only whole (H, C) HBM tiles of the source (H =
    :func:`hbm_tile_rows`: 8 rows for 32-bit and 16-bit dtypes, not the
    16-row VMEM tile of bf16), so each row is fetched as the H-row tile
    that holds it — all copies in flight at once — and rotated within its
    H sublanes so that its row lands on row ``r % H`` (rotations take
    32-bit data; narrower dtypes widen exactly).  It is selected into slab
    ``r // H`` of the group: two 8-row slabs for bf16, one for f32."""
    T, H, C = tiles.shape
    wide = _wide(tiles.dtype)
    copies = []
    for r, s in enumerate(srcs):
        t0 = pl.multiple_of(jnp.maximum(s, 0) // H * H, H)
        cp = pltpu.make_async_copy(x_hbm.at[pl.ds(t0, H), :], tiles.at[r], sem.at[r])
        pl.when(s >= 0)(cp.start)
        copies.append(cp)
    # output row of each element: the T-row group as T / H slabs of H rows
    slab = (T // H, H, C)
    row = (jax.lax.broadcasted_iota(jnp.int32, slab, 0) * H
           + jax.lax.broadcasted_iota(jnp.int32, slab, 1))
    acc = jnp.zeros(slab, wide)
    for r, (s, cp) in enumerate(zip(srcs, copies)):
        pl.when(s >= 0)(cp.wait)
        rolled = pltpu.roll(tiles[r].astype(wide), (r - s % H + H) % H, 0)
        acc = jnp.where((row == r) & (s >= 0), rolled, acc)
    return acc.reshape(T, C)


def _gather_block_kernel(use_run: bool, idx_ref, x_hbm, o_ref, tiles, window, sem):
    """One grid step = one (br, C) output block.

    ``idx_ref`` is this block's (br,) slice of the index table in SMEM (a
    whole table can outgrow SMEM).  A DMA can only address whole HBM row
    tiles of the source, so source rows are fetched as aligned tiles and
    rotated into place in VMEM; stores go in groups of T rows (T = the
    dtype's sublane count), so each is one aligned store:

    * run path — when the block's br indices are a contiguous run, ONE
      copy fetches the T-aligned (br + T)-row window around it and one
      sublane rotation aligns the run (``use_run`` is static: False when
      br is not whole tiles or the window does not fit the source);
    * row path — otherwise, per group of T output rows, the T source rows
      are fetched concurrently, each as the 8-row HBM tile that holds it,
      and rotated so that it lands on its output row (:func:`_fetch_rows`).
      Negative (sentinel) rows fetch nothing and stay zero.
    """
    br, C = o_ref.shape
    T = tiles.shape[0]
    start = idx_ref[0]
    wide = _wide(o_ref.dtype)

    def _row_path():
        def store(off, size):
            rows = _fetch_rows(x_hbm, tiles, sem, [idx_ref[off + r] for r in range(size)])
            o_ref[pl.ds(off, size), :] = rows[:size].astype(o_ref.dtype)

        g = min(T, br)  # whole groups at tile-aligned offsets, then the rest

        def body(i, carry):
            store(pl.multiple_of(i * g, g), g)
            return carry

        jax.lax.fori_loop(0, br // g, body, 0)
        if br % g:
            store(br - br % g, br % g)

    if not use_run:
        _row_path()
        return

    def _consecutive(r, ok):
        return jnp.logical_and(ok, idx_ref[r] == start + r)

    is_run = jax.lax.fori_loop(1, br, _consecutive, start >= 0)

    @pl.when(is_run)
    def _run_path():
        span = window.shape[0]
        w0 = pl.multiple_of(jnp.minimum(start // T * T, x_hbm.shape[0] - span), T)
        cp = pltpu.make_async_copy(x_hbm.at[pl.ds(w0, span), :], window, sem.at[0])
        cp.start()
        cp.wait()
        shift = (span - (start - w0)) % span
        o_ref[...] = pltpu.roll(window[...].astype(wide), shift, 0)[:br].astype(o_ref.dtype)

    pl.when(jnp.logical_not(is_run))(_row_path)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def gather_rows_blocked(
    x: jax.Array,
    idx: jax.Array,
    *,
    block_r: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked masked gather: ``out[i, :] = x[idx[i], :]``, ``idx[i] < 0``
    -> zero row.

    The index table is reshaped to ``(nB, block_r)`` row blocks; each grid
    step moves ``block_r`` full-width rows off the HBM-resident source.
    Contiguous index runs collapse to one block copy (run detection), and
    sentinel rows are zero-filled in-kernel — no caller-side sentinel-row
    concatenates.  Rows move as whole HBM tiles (see
    :func:`_gather_block_kernel`); a source whose row count is not whole
    sublane tiles (the run path's window alignment) is zero-padded to
    whole sublane tiles first.  Planned by
    :func:`repro.core.index_plan.plan_index_op`.

    This one kernel carries three plan semantics: masked ``gather``,
    ``scatter`` (via the inverted table), and the serving engine's
    ``ragged_rows`` unpack (DESIGN.md §12), where per-sequence packed
    rows are contiguous runs — the run-detected block-copy fast path —
    and the ``-1`` tail sentinels zero-fill each KV ring beyond its
    prompt length.
    """
    if x.ndim != 2 or idx.ndim != 1:
        raise ValueError(
            f"gather_rows_blocked wants 2-D x and 1-D idx, got {x.shape}, {idx.shape}"
        )
    n_out = idx.shape[0]
    n_src, C = x.shape
    if n_out == 0 or C == 0 or n_src == 0:
        return jnp.zeros((n_out, C), x.dtype)
    br = max(1, min(block_r, n_out))
    nB = cdiv(n_out, br)
    idxp = _pad_table(idx, nB * br)
    T = sublanes(x.dtype)
    if n_src % T:
        x = jnp.pad(x, ((0, T - n_src % T), (0, 0)))
    span = br + T
    use_run = br % T == 0 and span <= x.shape[0]

    interpret = interpreted() if interpret is None else interpret
    return pl.pallas_call(
        functools.partial(_gather_block_kernel, use_run),
        grid=(nB,),
        in_specs=[
            pl.BlockSpec((None, None, br), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, hbm_tile_rows(x.dtype), C), x.dtype),
            pltpu.VMEM((span if use_run else T, C), x.dtype),
            pltpu.SemaphoreType.DMA((T,)),
        ],
        out_shape=jax.ShapeDtypeStruct((n_out, C), x.dtype),
        interpret=interpret,
    )(idxp.reshape(nB, 1, br), x)


def _gather_combine_kernel(back_ref, src_hbm, gates_ref, o_ref, rows, tiles, sem):
    """One grid step = one (bt, C) combined-output block.

    ``back_ref`` is this block's (bt * k,) slice of the flattened table in
    SMEM.  The block's bt * k source rows land in the VMEM block ``rows``
    one group of T table entries at a time (T = the dtype's sublane count),
    each row fetched as the 8-row HBM tile that holds it and rotated into
    place (:func:`_fetch_rows`; sentinels stay zero), so every store is
    tile-aligned.  Then the weighted combine runs on-chip, as the same
    expression as the oracle: ``out[t] = sum_k gates[t, k] * rows[t, k]``
    — the gathered (T*k, C) intermediate never exists in HBM."""
    bt, C = o_ref.shape
    k = gates_ref.shape[1]
    T = tiles.shape[0]
    n = bt * k

    def store(off, size):
        srcs = [back_ref[off + r] for r in range(size)]
        got = _fetch_rows(src_hbm, tiles, sem, srcs)[:size]
        rows[pl.ds(off, size), :] = got.astype(rows.dtype)

    gs = min(T, n)  # whole groups at tile-aligned offsets, then the rest

    def body(i, carry):
        store(pl.multiple_of(i * gs, gs), gs)
        return carry

    jax.lax.fori_loop(0, n // gs, body, 0)
    if n % gs:
        store(n - n % gs, n % gs)
    # products and the k-sum in f32, rounded once: how XLA evaluates the
    # oracle's narrow-dtype multiply + sum fusion (a product of two values
    # of a narrower float type is exact in f32)
    v = rows[...].reshape(bt, k, C).astype(jnp.float32)
    g = gates_ref[...].astype(o_ref.dtype).astype(jnp.float32)[..., None]
    o_ref[...] = (v * g).sum(axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def gather_combine_blocked(
    src: jax.Array,
    back: jax.Array,
    gates: jax.Array,
    *,
    block_t: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather + weighted combine (the MoE combine primitive):

        out[t, :] = sum_k gates[t, k] * src[back[t, k], :]

    with ``back[t, k] < 0`` contributing zero.  ``src``: (n_src, C);
    ``back``: int (T, k); ``gates``: (T, k) float.  ONE `pallas_call`
    replaces the seed's gather -> reshape -> multiply -> sum chain, and
    the (T*k, C) gathered intermediate never round-trips HBM.  Products
    and the per-``k`` sum run in f32 and round once to ``src.dtype``, as
    XLA evaluates the unfused chain, so results are bit-identical to the
    seed path.  Source rows move as whole 8-row HBM tiles (see
    :func:`_gather_combine_kernel`); a source whose row count is not whole
    HBM tiles is zero-padded to whole tiles first.  Planned by
    :func:`repro.core.index_plan.plan_index_op` with
    ``semantics="gather_combine"``.
    """
    if src.ndim != 2 or back.ndim != 2 or gates.shape != back.shape:
        raise ValueError(
            f"gather_combine_blocked wants 2-D src and matching (T, k) "
            f"back/gates, got {src.shape}, {back.shape}, {gates.shape}"
        )
    T, k = back.shape
    n_src, C = src.shape
    if T == 0 or C == 0 or k == 0 or n_src == 0:
        return jnp.zeros((T, C), src.dtype)
    bt = max(1, min(block_t, T))
    nT = cdiv(T, bt)
    backp = _pad_table(back.reshape(-1), nT * bt * k)
    sl, h = sublanes(src.dtype), hbm_tile_rows(src.dtype)
    if n_src % h:  # whole fetch tiles: the only alignment the row fetch needs
        src = jnp.pad(src, ((0, h - n_src % h), (0, 0)))

    interpret = interpreted() if interpret is None else interpret
    return pl.pallas_call(
        _gather_combine_kernel,
        grid=(nT,),
        in_specs=[
            pl.BlockSpec((None, None, bt * k), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, C), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bt * k, C), src.dtype),
            pltpu.VMEM((sl, h, C), src.dtype),
            pltpu.SemaphoreType.DMA((sl,)),
        ],
        out_shape=jax.ShapeDtypeStruct((T, C), src.dtype),
        interpret=interpret,
    )(backp.reshape(nT, 1, bt * k), src, gates)
