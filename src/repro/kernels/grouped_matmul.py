"""Grouped SwiGLU matmul over expert-sorted rows: the MoE layer's expert FFN.

The dispatch gather lays the rows routed to each expert out contiguously,
each expert's run padded to whole ``tm``-row tiles (:func:`tile_groups`),
so every tile belongs to one expert.  One grid step computes one tile:

    out[tile] = (silu(x[tile] @ w_gate[g]) * (x[tile] @ w_up[g])) @ w_down[g]

with ``g`` the tile's expert, read from a scalar-prefetched tile -> expert
table.  An expert's three weight matrices are one block each, so the
pipeline fetches them once per run of that expert's tiles and prefetches
the next expert's while the last tile of this one computes.  Group sizes
are known only on the device: the grid covers the static worst case and
steps past the used tiles compute nothing and fetch nothing new (their
block indices repeat the last used tile's).  Rows of those steps are left
unwritten; the combine reads only rows it routed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import force_interpret

#: scoped VMEM the kernel may ask for (v5e has 128 MiB per core)
VMEM_LIMIT = 100 * 1024 * 1024


def tile_groups(sizes: jax.Array, tm: int, n_tiles: int):
    """The tile-padded layout of groups of ``sizes`` rows (in group order).

    Returns ``(starts, tile_group, n_used)``: the first row of each group
    (its run padded to whole ``tm``-row tiles), the group of each of the
    ``n_tiles`` tiles (tiles past the used ones take the last group), and
    the number of tiles used."""
    tiles = (sizes + tm - 1) // tm
    ends = jnp.cumsum(tiles)
    starts = ((ends - tiles) * tm).astype(jnp.int32)
    tile_group = jnp.searchsorted(ends, jnp.arange(n_tiles), side="right")
    tile_group = jnp.minimum(tile_group, sizes.shape[0] - 1).astype(jnp.int32)
    return starts, tile_group, ends[-1].astype(jnp.int32)


def fits(d: int, f: int, tm: int, dtype) -> bool:
    """True when the kernel's VMEM — one expert's three weight blocks, row
    tiles and output tiles, all double-buffered, and a tile's f32
    intermediates — fits :data:`VMEM_LIMIT`."""
    item = jnp.dtype(dtype).itemsize
    need = 2 * 3 * d * f * item + 4 * tm * d * item + tm * (3 * f + d) * 4
    return need <= VMEM_LIMIT


def _used_tile(i, n_used_ref):
    return jnp.minimum(i, jnp.maximum(n_used_ref[0] - 1, 0))


def _kernel(tile_group_ref, n_used_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del tile_group_ref  # consumed by the index maps

    @pl.when(pl.program_id(0) < n_used_ref[0])
    def _tile():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_swiglu(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    tile_group: jax.Array,
    n_used: jax.Array,
    *,
    tm: int,
    interpret: bool | None = None,
) -> jax.Array:
    """SwiGLU of each ``tm``-row tile of ``x`` (M, D) through the weights of
    its group: ``w_gate``/``w_up`` (G, D, F), ``w_down`` (G, F, D);
    ``tile_group`` (M // tm,) int32 and ``n_used`` () int32 as
    :func:`tile_groups` gives them.  Rows of tiles past ``n_used`` are
    unspecified."""
    m, d = x.shape
    f = w_gate.shape[2]
    if m % tm or tile_group.shape != (m // tm,):
        raise ValueError(f"grouped_swiglu wants {m} rows in whole {tm}-row tiles, "
                         f"one group per tile; got a table of {tile_group.shape}")
    interpret = force_interpret() if interpret is None else interpret
    w_in = pl.BlockSpec((None, d, f), lambda i, tg, nu: (tg[_used_tile(i, nu)], 0, 0))
    rows = pl.BlockSpec((tm, d), lambda i, tg, nu: (_used_tile(i, nu), 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tm,),
            in_specs=[
                rows, w_in, w_in,
                pl.BlockSpec((None, f, d), lambda i, tg, nu: (tg[_used_tile(i, nu)], 0, 0)),
            ],
            out_specs=rows,
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(tile_group.astype(jnp.int32), jnp.reshape(n_used, (1,)).astype(jnp.int32),
      x, w_gate, w_up, w_down)
