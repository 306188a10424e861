"""Generic stencil API (paper §III-D): stencils and stencil *programs* as
first-class objects.

The paper ships the stencil as a C++ functor compiled into the kernel; we
ship it as a trace-time Python functor (or an (offsets, weights) table)
compiled into the Pallas kernel.  ``Stencil`` objects compose: scale, add,
``then`` (sequential stages) and ``repeat`` (k sweeps) build a
:class:`StencilProgram` that the plan engine lowers to ONE fused
`pallas_call` via temporal blocking (DESIGN.md §9) — the iterative-workload
analogue of the rearrangement planner in `core/plan.py`:

1. **describe** — a program is a tuple of stage descriptors (linear
   (offsets, weights) tables and/or trace-time functors with a radius);
2. **plan** — :func:`plan_stencil` picks the row-panel configuration and
   predicts HBM traffic for the fused pipeline vs per-sweep execution;
3. **cache** — plans are memoized on (shape, dtype, stages, boundary,
   has_aux), so steady-state solvers (e.g. the CFD cavity example) pay
   zero planning or retracing overhead after the first step.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import tune
from repro.kernels import ops, ref
from repro.kernels import stencil2d as st_k
from repro.kernels.tiling import cdiv, neighborhood, round_up, sublanes
from repro.utils.roofline import HBM_BW, movement_cost_s

Array = jax.Array

#: boundary-condition family accepted by every stencil entry point, derived
#: from the oracle's pad table (kernels/ref.py) so the copies cannot drift;
#: the legacy alias ``'clamp'`` (= nearest) is accepted but not advertised.
BOUNDARIES = tuple(b for b in ref.BOUNDARY_PAD_MODES if b != "clamp")


@dataclass(frozen=True)
class Stencil:
    """A linear stencil: ``out[p] = sum_k weights[k] * in[p + offsets[k]]``.

    Example::

        lap = Stencil(((0, 0), (1, 0), (-1, 0), (0, 1), (0, -1)),
                      (-4.0, 1.0, 1.0, 1.0, 1.0))
        y = lap(x)                       # one sweep, zero boundary
        y = lap(x, boundary="reflect")   # any of the four boundary modes
        prog = lap.repeat(8)             # 8 fused sweeps, ONE kernel
    """

    offsets: tuple[tuple[int, int], ...]
    weights: tuple[float, ...]

    @property
    def radius(self) -> int:
        """Chebyshev radius of the stencil's footprint."""
        return max(max(abs(dy), abs(dx)) for dy, dx in self.offsets)

    def __call__(self, x: Array, *, boundary: str = "zero") -> Array:
        """Apply one sweep of the stencil to a 2-D grid ``x``."""
        return ops.stencil2d(x, self.offsets, self.weights, boundary=boundary)

    def scale(self, a: float) -> "Stencil":
        """New stencil with every weight multiplied by ``a``."""
        return Stencil(self.offsets, tuple(a * w for w in self.weights))

    def __add__(self, other: "Stencil") -> "Stencil":
        table: dict[tuple[int, int], float] = {}
        for off, w in zip(self.offsets, self.weights):
            table[off] = table.get(off, 0.0) + w
        for off, w in zip(other.offsets, other.weights):
            table[off] = table.get(off, 0.0) + w
        offs = tuple(sorted(table))
        return Stencil(offs, tuple(table[o] for o in offs))

    def as_program(self) -> "StencilProgram":
        """Lift this stencil into a one-stage :class:`StencilProgram`."""
        return StencilProgram((("linear", self.offsets, self.weights),))

    def then(self, other: "Stencil | StencilProgram") -> "StencilProgram":
        """Sequential composition: ``self`` then ``other`` (one fused kernel).

        Example::

            prog = box_blur(1).then(fd_laplacian(1))  # blur, then laplacian
            y = prog(x)                               # ONE pallas_call
        """
        return self.as_program().then(other)

    def repeat(self, k: int) -> "StencilProgram":
        """``k`` fused sweeps of this stencil (temporal blocking).

        Example::

            jacobi = Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)
            y = jacobi.repeat(8)(x)   # == 8 sequential sweeps, ONE kernel
        """
        return self.as_program().repeat(k)


@dataclass(frozen=True)
class StencilPlan:
    """Compiled lowering decision for a stencil program on a given grid.

    Mirrors :class:`repro.core.plan.RearrangePlan`: routing (`mode`), the
    chosen panel geometry, and the predicted HBM traffic of the fused
    pipeline vs per-sweep execution so callers and benchmarks can compare
    achieved vs predicted movement.
    """

    mode: str  # fused | reference
    kernel: str  # stencil2d_pipeline | ref.stencil_pipeline
    shape: tuple[int, int]
    boundary: str
    n_stages: int
    total_radius: int
    block_rows: int  # rows owned per grid panel (0 on the reference path)
    halo_block_rows: int  # halo block height loaded above/below each panel
    grid: int  # number of row panels
    bytes_moved: int  # fused-path HBM traffic (reads incl. halo + 1 write)
    bytes_per_sweep_path: int  # traffic of n_stages separate sweeps
    roofline_s: float  # fused bytes / HBM bandwidth (one chip)
    shift: str  # roll | slice: how the fused kernel takes its views ("" on the reference path)
    stages_exec: tuple = field(repr=False, hash=False, compare=False)

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        saving = self.bytes_per_sweep_path / max(self.bytes_moved, 1)
        return (
            f"{self.mode}: shape={self.shape} stages={self.n_stages} "
            f"radius={self.total_radius} boundary={self.boundary} "
            f"panel=({self.block_rows}+2*{self.halo_block_rows} halo rows)x{self.grid} "
            + (f"shift={self.shift} " if self.shift else "")
            + f"{self.bytes_moved/1e6:.2f} MB moved vs "
            f"{self.bytes_per_sweep_path/1e6:.2f} MB per-sweep ({saving:.1f}x), "
            f"roofline {self.roofline_s*1e6:.1f} us @ {HBM_BW / 1e9:g} GB/s"
        )


def _stage_exec(desc) -> tuple[Callable, int]:
    """Materialize a stage descriptor into the kernel's (functor, radius)."""
    if desc[0] == "linear":
        _, offsets, weights = desc
        radius = max(max(abs(dy), abs(dx)) for dy, dx in offsets)
        return st_k._linear_functor(offsets, weights), radius
    _, functor, radius = desc
    return functor, int(radius)


def _build_plan(
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
    has_aux: bool,
    block_rows: int | None = None,
) -> StencilPlan:
    """Route one stencil program and materialize the plan.

    ``block_rows`` overrides the heuristic row-panel height (the tuner's
    hook; an illegal override raises so the tuner can skip the candidate);
    with ``None`` this is exactly the pre-tuner planner.
    """
    H, W = shape
    itemsize = jnp.dtype(dtype_name).itemsize
    stages_exec = tuple(_stage_exec(d) for d in stages)
    radii = tuple(r for _, r in stages_exec)
    R = sum(radii)
    n = H * W

    br = rp = 0
    mode, shift = "reference", ""
    if n > 0:
        panel = st_k.fused_panel(H, W, dtype_name, radii, boundary,
                                 block_rows=block_rows)
        if panel is not None:
            br, rp, _ = panel
            mode = "fused"
            # the kernel is called with block_rows=br, and its route is
            # fixed by the panel it derives from that
            kernel_panel = st_k.fused_panel(H, W, dtype_name, radii, boundary,
                                            block_rows=br)
            shift = st_k.shift_route(W, dtype_name, kernel_panel, radii)
    if block_rows is not None and mode != "fused":
        raise ValueError("no fused panel for this block_rows override")
    grid = cdiv(H, br) if br else 0

    # cost model: useful traffic is one read + one write of the grid; the
    # fused path adds the apron redundancy (2*rp halo rows per panel, plus
    # a second operand stream when an aux/source grid rides along), while
    # the per-sweep path pays the full round trip once per stage.
    n_streams = 2 if has_aux else 1
    fused_reads = (n + 2 * rp * W * grid) * n_streams
    bytes_fused = (fused_reads + n) * itemsize
    sl = sublanes(dtype_name)
    per_sweep = 0
    for r in radii:
        rp_s = round_up(r, sl) if (r and br) else 0
        per_sweep += ((n + 2 * rp_s * W * (cdiv(H, br) if br else 0)) * n_streams + n)
    bytes_per_sweep = per_sweep * itemsize

    return StencilPlan(
        mode=mode,
        kernel="stencil2d_pipeline" if mode == "fused" else "ref.stencil_pipeline",
        shape=shape,
        boundary=boundary,
        n_stages=len(stages_exec),
        total_radius=R,
        block_rows=br,
        halo_block_rows=rp,
        grid=grid,
        bytes_moved=bytes_fused if mode == "fused" else bytes_per_sweep,
        bytes_per_sweep_path=bytes_per_sweep,
        roofline_s=(bytes_fused if mode == "fused" else bytes_per_sweep)
        / HBM_BW,
        shift=shift,
        stages_exec=stages_exec,
    )


@functools.lru_cache(maxsize=1024)
def _plan_cached(
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
    has_aux: bool,
) -> StencilPlan:
    return _build_plan(shape, dtype_name, stages, boundary, has_aux)


def _stage_key(stages: tuple) -> tuple[str, bool]:
    """A stable string for the stage descriptors plus whether it is stable
    across processes (opaque Python functors are not — their plans tune
    in-memory but are never persisted to the disk cache)."""
    parts, stable = [], True
    for d in stages:
        if d[0] == "linear":
            parts.append(f"lin{d[1]}{d[2]}")
        else:
            parts.append(f"functor@r{d[2]}")
            stable = False
    return ";".join(parts), stable


def _candidates(
    base: StencilPlan, shape: tuple, dtype_name: str, stages: tuple, has_aux: bool
) -> list[tune.Candidate]:
    """The stencil engine's search space: the row-panel neighborhood of
    the fused kernel, heuristic panel first.  The fused/per-sweep *mode*
    is deliberately not a candidate — per-sweep execution matches fused to
    tolerance, not bit-exactly, and tuning must never change results."""
    H, W = shape
    sl = sublanes(dtype_name)
    cands, seen = [], set()
    for br in neighborhood(base.block_rows, sl, H):
        if br in seen:
            continue
        seen.add(br)
        try:
            cp = _build_plan(shape, dtype_name, stages, base.boundary, has_aux, br)
        except ValueError:
            continue
        cands.append(
            tune.Candidate(
                label=f"panel{br}",
                params=(("block_rows", br),),
                cost_s=movement_cost_s(cp.bytes_moved, cp.grid),
            )
        )
    return cands


def _runner_factory(
    shape: tuple, dtype_name: str, stages: tuple, boundary: str, has_aux: bool
):
    """Measured-mode runner: run the fused pipeline at one candidate panel
    height on a deterministic sample grid."""

    def factory(cand: tune.Candidate):
        plan = _build_plan(
            shape, dtype_name, stages, boundary, has_aux,
            cand.param_dict()["block_rows"],
        )
        x = tune.sample_array(shape, dtype_name)
        aux = jnp.ones_like(x) if has_aux else None
        fn = jax.jit(
            lambda a: ops.stencil_program(
                a, plan.stages_exec, boundary=boundary,
                block_rows=plan.block_rows or None, aux=aux, fused=True,
            )
        )
        return lambda: fn(x)

    return factory


@functools.lru_cache(maxsize=1024)
def _plan_tuned_cached(
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
    has_aux: bool,
    mode: str,
) -> StencilPlan:
    base = _plan_cached(shape, dtype_name, stages, boundary, has_aux)
    if base.mode != "fused":
        return base  # reference route / empty grid: nothing to tune
    stage_key, stable = _stage_key(stages)
    choice = tune.select(
        "stencil",
        f"shape={shape}|dtype={dtype_name}|stages={stage_key}"
        f"|b={boundary}|aux={has_aux}",
        _candidates(base, shape, dtype_name, stages, has_aux),
        _runner_factory(shape, dtype_name, stages, boundary, has_aux),
        mode=mode,
        persist=stable,
    )
    br = choice.param_dict()["block_rows"]
    if br == base.block_rows:
        return base  # heuristic won: tuned and untuned plans are the SAME object
    return _build_plan(shape, dtype_name, stages, boundary, has_aux, br)


@dataclass(frozen=True)
class StencilProgram:
    """A compiled-together sequence of stencil stages (DESIGN.md §9).

    Built via :meth:`Stencil.then` / :meth:`Stencil.repeat` /
    :func:`functor_stage`; applying the program lowers every stage into ONE
    fused `pallas_call` with a ``sum(radius_i)``-row halo (temporal
    blocking), matching ``len(stages)`` sequential sweeps to fp32 tolerance.

    Example::

        jacobi = Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)
        prog = jacobi.repeat(8)
        y = prog(x, boundary="reflect")         # one kernel, 8 sweeps
        plan = prog.compile(x.shape, x.dtype)   # inspect the lowering
        print(plan.describe())
    """

    stages: tuple[tuple, ...]

    @property
    def n_stages(self) -> int:
        """Number of stages (sweeps) in the program."""
        return len(self.stages)

    @property
    def total_radius(self) -> int:
        """Halo rows each panel loads: the sum of all stage radii."""
        return sum(_stage_exec(d)[1] for d in self.stages)

    def then(self, other: "Stencil | StencilProgram") -> "StencilProgram":
        """Append ``other`` (a stencil or a whole program) as later stage(s)."""
        if isinstance(other, Stencil):
            other = other.as_program()
        return StencilProgram(self.stages + other.stages)

    def repeat(self, k: int) -> "StencilProgram":
        """Repeat the whole program ``k`` times (``k >= 1``)."""
        if k < 1:
            raise ValueError(f"repeat wants k >= 1, got {k}")
        return StencilProgram(self.stages * k)

    def compile(
        self, shape: Sequence[int], dtype, *, boundary: str = "zero",
        has_aux: bool = False, tuned: bool | None = None,
    ) -> StencilPlan:
        """Plan (and cache) the lowering of this program for a grid.

        Repeated calls with equal arguments return the *identical*
        :class:`StencilPlan` object (lru cache keyed on shape, dtype, the
        stage descriptors, boundary, and aux-presence).  ``tuned=None``
        resolves from ``REPRO_TUNE``; ``tuned=True`` searches the row-panel
        neighborhood through the autotuner (DESIGN.md §11).
        """
        return plan_stencil(shape, dtype, self.stages, boundary, has_aux,
                            tuned=tuned)

    def shard(self, x: Array, *, mesh, axis: str, boundary: str = "zero") -> Array:
        """Run the program on a row-sharded grid with halo exchange.

        ``x`` is sharded ``P(axis, None)`` on ``mesh``; the distributed
        planner (`core/dist_plan.py`, DESIGN.md §10) partitions the program
        into k-blocks, swaps ``sum(radius_i)`` edge rows with the two mesh
        neighbors per block (one ``ppermute`` pair), and runs each block as
        ONE fused §9 kernel per shard.  Bit-identical to
        ``self(x, boundary=...)`` on a single device.

        Example::

            y = jacobi.repeat(8).shard(x, mesh=mesh, axis="data")
        """
        from repro.core import dist_plan

        return dist_plan.shard_stencil(
            self, x, mesh=mesh, axis=axis, boundary=boundary
        )

    def __call__(
        self, x: Array, *, boundary: str = "zero", aux: Array | None = None
    ) -> Array:
        """Run the program on a 2-D grid.

        ``aux`` optionally supplies a same-shape source grid; functor stages
        then receive it as ``functor(shift, src)`` where ``src()`` yields
        the aux band (e.g. the right-hand side of a Jacobi iteration).
        """
        if x.ndim != 2:
            raise ValueError(f"stencil programs want 2-D grids, got {x.shape}")
        if x.size == 0:
            return x
        plan = self.compile(
            x.shape, x.dtype, boundary=boundary, has_aux=aux is not None
        )
        return ops.stencil_program(
            x,
            plan.stages_exec,
            boundary=boundary,
            block_rows=plan.block_rows or None,
            aux=aux,
            fused=plan.mode == "fused",
        )


def functor_stage(functor: Callable, radius: int) -> StencilProgram:
    """One-stage program from an arbitrary trace-time functor.

    ``functor(shift)`` (or ``functor(shift, src)`` in aux programs) may be
    any jnp expression over ``shift(dy, dx)`` views — the paper's
    compile-time C++ functor, as a Python closure.

    Example::

        damp = functor_stage(lambda s: 0.5 * s(0, 0) + 0.5 * s(0, 1), 1)
        prog = damp.then(fd_laplacian(1)).repeat(2)
    """
    return StencilProgram((("functor", functor, int(radius)),))


def plan_stencil(
    shape: Sequence[int],
    dtype,
    stages: tuple,
    boundary: str = "zero",
    has_aux: bool = False,
    *,
    tuned: bool | None = None,
) -> StencilPlan:
    """Plan (and cache) the lowering of stage descriptors for a grid.

    The program-facing wrapper is :meth:`StencilProgram.compile`; this
    entry point exists for benchmarks and tests that build descriptor
    tuples directly.  ``tuned=None`` resolves from ``REPRO_TUNE``;
    ``tuned=True`` searches the fused kernel's row-panel neighborhood
    through the autotuner (DESIGN.md §11) — panel geometry only, so a
    tuned program's output stays bit-identical to the untuned one.
    """
    if boundary not in ref.BOUNDARY_PAD_MODES:
        raise ValueError(f"unknown boundary {boundary!r}; want one of {BOUNDARIES}")
    shape_t = tuple(int(s) for s in shape)
    if len(shape_t) != 2:
        raise ValueError(f"stencil plans want 2-D shapes, got {shape_t}")
    if tuned is None:
        tuned = tune.tune_default()
    key = (shape_t, jnp.dtype(dtype).name, tuple(stages), boundary, bool(has_aux))
    if not tuned:
        return _plan_cached(*key)
    return _plan_tuned_cached(*key, tune.resolve_mode())


def stencil_plan_cache_info():
    """Expose the plan-memo stats (tests / benchmarks)."""
    return _plan_cached.cache_info()


def fd_laplacian(order: int) -> Stencil:
    """2-D Laplacian, central differences of accuracy 2*order (paper Fig. 2
    orders I..IV).

    Example::

        y = fd_laplacian(2)(x)           # 9-point, 4th-order accurate
        y = fd_laplacian(1).repeat(4)(x) # 4 fused diffusion sweeps
    """
    offs, wts = ref.fd_stencil_offsets(order)
    return Stencil(tuple(offs), tuple(wts))


def box_blur(radius: int = 1) -> Stencil:
    """(2r+1)^2 box smoothing filter (the paper's image-filter example).

    Example::

        smooth = box_blur(1)             # 3x3 mean filter
        y = smooth(img, boundary="nearest")
    """
    offs = tuple(
        (dy, dx)
        for dy in range(-radius, radius + 1)
        for dx in range(-radius, radius + 1)
    )
    w = 1.0 / len(offs)
    return Stencil(offs, (w,) * len(offs))


def apply_functor(
    x: Array, functor: Callable, radius: int, *, boundary: str = "zero"
) -> Array:
    """Single sweep of an arbitrary (possibly non-linear) stencil functor.

    Example::

        def sharpen(shift):
            return 2.0 * shift(0, 0) - 0.25 * (
                shift(-1, 0) + shift(1, 0) + shift(0, -1) + shift(0, 1))
        y = apply_functor(img, sharpen, radius=1)

    For multi-sweep functor pipelines use :func:`functor_stage` and
    ``repeat`` — see ``repro.kernels.stencil2d.stencil2d_functor`` for the
    kernel underneath.
    """
    return ops.stencil2d_functor(x, functor, radius, boundary=boundary)


def conv1d_depthwise(x: Array, kernel: Array) -> Array:
    """Causal depthwise temporal conv over (B, S, D) with kernel (K, D) —
    the RG-LRU / recurrentgemma temporal-conv building block, expressed as
    a 1-D stencil (a degenerate §III-D stencil: all offsets (dy, 0)).

    out[b, s, d] = sum_k kernel[k, d] * x[b, s - (K-1) + k, d]
    """
    k = kernel.shape[0]
    pads = [(0, 0)] * x.ndim
    pads[-2] = (k - 1, 0)
    xp = jnp.pad(x, pads)
    out = jnp.zeros_like(x)
    s = x.shape[-2]
    for i in range(k):
        out = out + kernel[i] * jax.lax.dynamic_slice_in_dim(xp, i, s, axis=-2)
    return out
