"""Index-set planner: block -> route -> cache (DESIGN.md §4).

The paper's §III-A index-set kernels read/write "a specified set of
indices" off a constant-memory table.  PR 1 gave permutations a plan
engine (`core/plan.py`) and PR 2 gave stencils one (`core/stencil.py`);
this module is the third leg — **data-dependent** movement — and follows
the same three-step contract:

1. **block** — the index table is reshaped to ``(nB, block_rows)`` row
   blocks so each grid step moves ``block_rows`` rows instead of one (the
   batching the paper gets from multi-row thread blocks).  In-kernel run
   detection collapses blocks whose indices form a contiguous run into a
   single strided block copy — the index-table analogue of PR 1's axis
   collapsing, but resolved at run time because the table is data.
2. **route** — pick the kernel for ``(semantics, shape)``:
   ``gather`` / ``scatter`` -> the blocked masked gather
   (`kernels.gather_scatter.gather_rows_blocked`; a scatter is executed
   as a gather through the inverted table), ``gather_combine`` -> the
   fused gather+weighted-combine kernel (ONE `pallas_call` for the whole
   MoE combine).  Degenerate sizes route to ``noop``/``oracle``.
3. **cache** — plans are memoized on ``(src_shape, dtype, n_out,
   semantics, masked, top_k)`` so steady-state serving steps pay zero
   planning overhead (repeated calls return the *identical* plan object).

Sentinel semantics: a negative index means "no source row" and the kernel
zero-fills (gather) or contributes zero (combine) — in-kernel masking via
``pl.when``, which is what lets `models.moe.moe_sort` drop its
sentinel-row concatenates.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import tune
from repro.kernels.tiling import (
    VMEM_BUDGET,
    cdiv,
    round_up,
    row_block_candidates,
    sublanes,
)
from repro.utils.roofline import HBM_BW, movement_cost_s

#: semantics accepted by :func:`plan_index_op`.  ``ragged_rows`` is the
#: serving engine's pack/unpack route (DESIGN.md §12): a masked gather
#: whose table maps packed-prefill rows into per-slot ring rows — each
#: sequence's rows form a contiguous run, so the blocked kernel's run
#: detection collapses them into strided block copies.
SEMANTICS = ("gather", "scatter", "gather_combine", "ragged_rows")

#: row-block target: enough rows per grid step to amortize per-step
#: overhead without starving the double-buffered VMEM budget.
BLOCK_ROWS_TARGET = 64


@dataclass(frozen=True)
class IndexPlan:
    """Cached lowering decision for one index-set movement.

    Mirrors :class:`repro.core.plan.RearrangePlan`: the kernel route, the
    row-block geometry, and the predicted HBM traffic (data rows plus the
    int32 index-table stream) so callers and benchmarks can compare
    achieved vs predicted movement.

    Example::

        plan = plan_index_op((4096, 512), jnp.bfloat16, 4096, "gather")
        print(plan.describe())
    """

    semantics: str  # gather | scatter | gather_combine
    mode: str  # blocked | rowwise | oracle | noop
    kernel: str  # gather_rows_blocked | gather_combine_blocked | gather_rows | ref | noop
    n_src: int  # rows in the source array
    n_out: int  # rows produced
    row_elems: int  # elements per row (C)
    block_rows: int  # rows moved per grid step (br)
    grid: int  # number of row blocks (nB)
    table_rows: int  # padded index-table length (grid * block_rows [* top_k])
    masked: bool  # negative indices zero-fill
    top_k: int  # combine fan-in (1 for gather/scatter)
    bytes_moved: int  # data read + write + index-table traffic
    roofline_s: float  # bytes / HBM bandwidth (one chip)

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        return (
            f"{self.semantics}: {self.mode} kernel={self.kernel} "
            f"src={self.n_src}x{self.row_elems} out={self.n_out} "
            f"blocks={self.grid}x{self.block_rows} rows"
            f"{f' k={self.top_k}' if self.top_k > 1 else ''} "
            f"{self.bytes_moved/1e6:.2f} MB moved, "
            f"roofline {self.roofline_s*1e6:.1f} us @ {HBM_BW / 1e9:g} GB/s"
        )


def _build_plan(
    n_src: int,
    row_elems: int,
    dtype_name: str,
    n_out: int,
    semantics: str,
    masked: bool,
    top_k: int,
    block_rows: int | None = None,
    engine: str | None = None,
) -> IndexPlan:
    """Route one index-set movement and materialize the plan.

    ``block_rows`` overrides the heuristic row-block height and
    ``engine="rowwise"`` forces the seed one-row-per-grid-step kernel
    (the tuner's hooks); with both defaults this is exactly the pre-tuner
    planner.
    """
    itemsize = jnp.dtype(dtype_name).itemsize

    def _mk(mode, kernel, br, grid, table_rows, bytes_moved):
        return IndexPlan(
            semantics=semantics,
            mode=mode,
            kernel=kernel,
            n_src=n_src,
            n_out=n_out,
            row_elems=row_elems,
            block_rows=br,
            grid=grid,
            table_rows=table_rows,
            masked=masked,
            top_k=top_k,
            bytes_moved=bytes_moved,
            roofline_s=bytes_moved / HBM_BW,
        )

    if n_out == 0 or row_elems == 0:
        return _mk("noop", "noop", 1, 0, 0, 0)
    if n_src == 0:
        # nothing to read: every index is a sentinel; output is zeros
        return _mk("noop", "noop", 1, 0, 0, n_out * row_elems * itemsize)

    # row-block geometry: full-width rows (long contiguous DMAs), the row
    # count bounded by the double-buffered VMEM budget.  Combine keeps
    # top_k source rows per output row resident, so its budget divides by k.
    sl = sublanes(dtype_name)
    row_bytes = max(row_elems * itemsize, 1)
    br_budget = max(VMEM_BUDGET // (2 * row_bytes * top_k), 1)
    br = min(round_up(BLOCK_ROWS_TARGET, sl), max(br_budget // sl * sl, sl), n_out)
    if block_rows is not None:
        br = min(int(block_rows), n_out)
    grid = cdiv(n_out, br)

    if engine == "rowwise":
        # the seed per-row kernel: one grid step per output row, no
        # sentinel masking, gather semantics only (the tuner offers this
        # engine only where those preconditions hold)
        if semantics != "gather" or masked or top_k != 1:
            raise ValueError("rowwise engine is unmasked gather-only")
        return _mk(
            "rowwise", "gather_rows", 1, n_out, n_out,
            2 * n_out * row_bytes + n_out * 4,
        )

    # traffic: each output row is one read + one write of row_bytes (upper
    # bound under masking), plus the int32 index-table stream; combine
    # reads top_k source rows and a float32 gate per (row, k).
    if semantics == "gather_combine":
        bytes_moved = (
            n_out * top_k * row_bytes  # source rows in
            + n_out * row_bytes  # combined rows out
            + n_out * top_k * 4  # back table
            + n_out * top_k * 4  # gates
        )
        return _mk(
            "blocked", "gather_combine_blocked", br, grid, grid * br * top_k, bytes_moved
        )
    bytes_moved = 2 * n_out * row_bytes + n_out * 4
    if semantics == "scatter":
        # executed as a masked gather through the inverted table; the
        # inversion itself is an int32 table op (n_src * 4 extra bytes)
        bytes_moved += n_src * 4
    return _mk("blocked", "gather_rows_blocked", br, grid, grid * br, bytes_moved)


@functools.lru_cache(maxsize=4096)
def _plan_cached(
    n_src: int,
    row_elems: int,
    dtype_name: str,
    n_out: int,
    semantics: str,
    masked: bool,
    top_k: int,
) -> IndexPlan:
    return _build_plan(n_src, row_elems, dtype_name, n_out, semantics, masked, top_k)


def _candidates(base: IndexPlan, dtype_name: str) -> list[tune.Candidate]:
    """The index engine's search space: the row-block neighborhood of the
    blocked kernel (heuristic first) plus — for unmasked single-fan-in
    gathers, where the two kernels are bit-identical — the seed rowwise
    engine as an engine-choice candidate."""
    itemsize = jnp.dtype(dtype_name).itemsize
    row_bytes = max(base.row_elems * itemsize, 1)
    cands = []
    for br in row_block_candidates(
        base.block_rows, base.n_out, row_bytes, dtype_name, base.top_k
    ):
        grid = cdiv(base.n_out, br)
        # padded table rows round the data traffic up to whole blocks
        padded = 2 * grid * br * row_bytes * max(base.top_k, 1)
        cands.append(
            tune.Candidate(
                label=f"br{br}",
                params=(("block_rows", br), ("engine", "blocked")),
                cost_s=movement_cost_s(padded, grid),
            )
        )
    if base.semantics == "gather" and not base.masked and base.top_k == 1:
        cands.append(
            tune.Candidate(
                label="rowwise",
                params=(("block_rows", 1), ("engine", "rowwise")),
                cost_s=movement_cost_s(2 * base.n_out * row_bytes, base.n_out),
            )
        )
    return cands


def _runner_factory(
    n_src: int, row_elems: int, dtype_name: str, n_out: int,
    semantics: str, masked: bool, top_k: int,
):
    """Measured-mode runner: execute one candidate plan on deterministic
    sample data through the dispatch layer's plan executor."""

    def factory(cand: tune.Candidate):
        import jax

        from repro.kernels import ops  # lazy: ops imports this module

        d = cand.param_dict()
        plan = _build_plan(
            n_src, row_elems, dtype_name, n_out, semantics, masked, top_k,
            block_rows=d["block_rows"], engine=d["engine"],
        )
        x = tune.sample_array((n_src, row_elems), dtype_name)
        rows = n_out * top_k if semantics == "gather_combine" else n_out
        idx = (jnp.arange(rows, dtype=jnp.int32) * 7919) % max(n_src, 1)
        if semantics == "gather_combine":
            idx = idx.reshape(n_out, top_k)
            gates = jnp.full((n_out, top_k), 1.0 / top_k, jnp.float32)
            fn = jax.jit(lambda a, i, g: ops.apply_index_plan(a, i, plan, gates=g))
            return lambda: fn(x, idx, gates)
        if semantics == "scatter":
            idx = (jnp.arange(n_src, dtype=jnp.int32) * 7919) % max(n_out, 1)
        fn = jax.jit(lambda a, i: ops.apply_index_plan(a, i, plan))
        return lambda: fn(x, idx)

    return factory


@functools.lru_cache(maxsize=4096)
def _plan_tuned_cached(
    n_src: int,
    row_elems: int,
    dtype_name: str,
    n_out: int,
    semantics: str,
    masked: bool,
    top_k: int,
    mode: str,
) -> IndexPlan:
    base = _plan_cached(n_src, row_elems, dtype_name, n_out, semantics, masked, top_k)
    if base.mode == "noop":
        return base  # nothing to tune: no kernel runs
    choice = tune.select(
        "index",
        f"src=({n_src},{row_elems})|dtype={dtype_name}|n_out={n_out}"
        f"|{semantics}|masked={masked}|k={top_k}",
        _candidates(base, dtype_name),
        _runner_factory(n_src, row_elems, dtype_name, n_out, semantics, masked, top_k),
        mode=mode,
    )
    d = choice.param_dict()
    if d["engine"] == "blocked" and d["block_rows"] == base.block_rows:
        return base  # heuristic won: tuned and untuned plans are the SAME object
    return _build_plan(
        n_src, row_elems, dtype_name, n_out, semantics, masked, top_k,
        block_rows=d["block_rows"], engine=d["engine"],
    )


def plan_index_op(
    src_shape: Sequence[int],
    dtype,
    n_out: int,
    semantics: str,
    *,
    masked: bool = False,
    top_k: int = 1,
    tuned: bool | None = None,
) -> IndexPlan:
    """Plan (and cache) an index-set movement.

    ``src_shape`` is the 2-D source array shape ``(n_src, C)``; ``n_out``
    the number of output rows (for ``scatter`` that is the *destination*
    row count); ``semantics`` one of ``gather | scatter | gather_combine |
    ragged_rows`` (the last is the serving engine's masked unpack gather
    over a :func:`ragged_layout`, DESIGN.md §12).
    ``masked`` enables sentinel handling (negative index -> zero row) and
    ``top_k`` is the combine fan-in.

    Example::

        plan = plan_index_op((1024, 256), jnp.float32, 2048, "gather",
                             masked=True)
        assert plan is plan_index_op((1024, 256), jnp.float32, 2048,
                                     "gather", masked=True)  # cached

    ``tuned=None`` (default) resolves from ``REPRO_TUNE``; ``tuned=True``
    routes through the autotuner (DESIGN.md §11): the row-block
    neighborhood — plus the rowwise engine where it is bit-identical — is
    measured (TPU) or cost-scored (elsewhere), same lru identity
    guarantees as untuned plans.
    """
    if semantics not in SEMANTICS:
        raise ValueError(f"unknown semantics {semantics!r}; want one of {SEMANTICS}")
    if semantics == "ragged_rows" and not masked:
        raise ValueError(
            "ragged_rows plans are always masked: rows past each sequence's "
            "length are sentinels (-1) that zero-fill the ring tail"
        )
    if len(src_shape) != 2:
        raise ValueError(f"index plans want 2-D sources, got {tuple(src_shape)}")
    if n_out < 0:
        raise ValueError(f"n_out must be >= 0, got {n_out}")
    if top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    n_src, row_elems = (int(s) for s in src_shape)
    if tuned is None:
        tuned = tune.tune_default()
    key = (
        n_src,
        row_elems,
        jnp.dtype(dtype).name,
        int(n_out),
        semantics,
        bool(masked),
        int(top_k),
    )
    if not tuned:
        return _plan_cached(*key)
    return _plan_tuned_cached(*key, tune.resolve_mode())


def index_plan_cache_info():
    """Expose the plan-memo stats (tests / benchmarks)."""
    return _plan_cached.cache_info()


# ---------------------------------------------------------------------------
# ragged packed layout (qo_indptr) — the serving engine's prefill route
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RaggedLayout:
    """``qo_indptr``-style packed layout for one ragged prefill batch
    (DESIGN.md §12): n variable-length prompts concatenated along one
    packed token axis, bucket-padded to ``t_pad``.

    The layout carries the masking tables the packed forward needs
    (``seg_ids``/``positions`` drive the block-diagonal causal mask and
    per-sequence RoPE) plus the pack/unpack geometry (``indptr``,
    :meth:`unpack_index`) the engine's ``ragged_rows`` IndexPlan gather
    uses to move packed KV rows into decode slots.  Zero-length sequences
    are legal at the layout level (an empty segment, all-sentinel unpack
    rows); admitting one is the *engine's* error.

    Example::

        lay = ragged_layout((3, 5), bucket=8)
        assert lay.indptr == (0, 3, 8) and lay.t_pad == 8
    """

    lengths: tuple[int, ...]  #: per-sequence prompt lengths
    bucket: int  #: packed-width rounding (compile-shape stability)
    total: int  #: sum of lengths
    t_pad: int  #: bucket-rounded packed width
    indptr: tuple[int, ...]  #: (n+1,) prefix sums — sequence j owns rows [indptr[j], indptr[j+1])
    seg_ids: np.ndarray = field(compare=False)  #: (t_pad,) int32 sequence id, -1 pad
    positions: np.ndarray = field(compare=False)  #: (t_pad,) int32 within-sequence position
    last_ix: np.ndarray = field(compare=False)  #: (n,) packed index of each sequence's last token

    def unpack_index(self, n_rows: int) -> np.ndarray:
        """The unpack gather table: (n_seq, n_rows) int32 mapping slot row
        s of sequence j to its packed row, ``-1`` (zero-fill sentinel)
        past the sequence's length — the operand for a ``ragged_rows``
        :func:`plan_index_op` gather."""
        n = len(self.lengths)
        out = np.full((n, n_rows), -1, np.int32)
        for j, ln in enumerate(self.lengths):
            take = min(ln, n_rows)
            out[j, :take] = np.arange(self.indptr[j], self.indptr[j] + take)
        return out


@functools.lru_cache(maxsize=1024)
def ragged_layout(lengths: tuple[int, ...], bucket: int = 64) -> RaggedLayout:
    """Plan (and cache) the packed layout for prompts of ``lengths``.

    Cached on the exact length tuple — steady-state admission waves with
    repeating shapes pay zero planning overhead, mirroring the other plan
    engines' memo contract."""
    lengths = tuple(int(x) for x in lengths)
    if not lengths:
        raise ValueError("ragged_layout needs at least one sequence")
    if any(x < 0 for x in lengths):
        raise ValueError(f"negative sequence length in {lengths}")
    if bucket < 1:
        raise ValueError(f"bucket must be >= 1, got {bucket}")
    total = sum(lengths)
    t_pad = max(round_up(max(total, 1), bucket), bucket)
    indptr = [0]
    for ln in lengths:
        indptr.append(indptr[-1] + ln)
    seg = np.full((t_pad,), -1, np.int32)
    pos = np.zeros((t_pad,), np.int32)
    last = np.zeros((len(lengths),), np.int32)
    for j, ln in enumerate(lengths):
        seg[indptr[j] : indptr[j + 1]] = j
        pos[indptr[j] : indptr[j + 1]] = np.arange(ln)
        last[j] = max(indptr[j + 1] - 1, indptr[j])  # undefined for ln == 0
    return RaggedLayout(
        lengths=lengths,
        bucket=int(bucket),
        total=total,
        t_pad=t_pad,
        indptr=tuple(indptr),
        seg_ids=seg,
        positions=pos,
        last_ix=last,
    )
