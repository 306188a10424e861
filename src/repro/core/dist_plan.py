"""The distributed plan engine: decompose -> reuse local plan -> cache
(DESIGN.md §10).

On a production mesh the scarce bandwidth is the interconnect, not HBM, so
sharded rearrangement is planned exactly like tiled rearrangement: a
:class:`DistPlan` decomposes any mesh-level movement into

    (optional collective) -> local cached plan -> (optional collective)

and memoizes the decision on ``(mesh_shape, in_spec, out_spec,
local_plan_key)``.  The *local* stage of every strategy is one of the three
existing per-device engines — ``core/plan.py`` (§3), ``core/stencil.py``
(§9), ``core/index_plan.py`` (§4) — run unchanged on each shard, so a
sharded op still lowers to the same single-``pallas_call`` kernels per
device; the planner's only new job is choosing what (if anything) crosses
the wire:

* ``local``       — the requested output sharding is the permuted input
                    sharding (or nothing is sharded): zero bytes on wire.
* ``all_to_all``  — axis-aligned redistribution: ONE tiled ``all_to_all``
                    moves ``(P-1)/P`` of the array, then the local plan
                    runs on the re-sharded shard.
* ``halo``        — stencil programs exchange ``sum(radius_i)`` edge rows
                    with mesh neighbors (one ``ppermute`` pair per k-block)
                    and run the fused temporal-blocking kernel per shard.
* ``ep``          — expert-parallel MoE: the blocked dispatch/combine
                    kernels sandwich a capacity-bucketed ``all_to_all``
                    pair (one per direction), keeping the gathered
                    intermediate out of HBM *and* off the wire.
* ``replicate``   — fallback for specs with no aligned collective:
                    ``all_gather``, run the full local plan, slice.  The
                    library never fails on an awkward spec; it loses the
                    wire-optimal path (same contract as the kernels).

Every plan carries the predicted bytes-on-wire of its strategy so callers
can compare strategies the same way the per-device planners expose
predicted HBM traffic.

``tuned=`` (DESIGN.md §11) ranks every *feasible* strategy decomposition
through the autotuner's cost model instead of taking the first feasible
one; all strategies are movement-only and bit-identical, so the swap
never changes results.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import tune
from repro.core.plan import plan_rearrange
from repro.kernels import ops
from repro.utils.roofline import ICI_BW, movement_cost_s

# NOTE: the shard_map/ppermute shims live in repro.launch.mesh and are
# imported lazily inside the executors — the planner half of this module
# (everything above the executors) stays importable with no coupling
# beyond core/kernels, and no import cycle can form through launch.

Array = jax.Array

#: strategies a DistPlan can route to (DESIGN.md §10 cost table).
STRATEGIES = ("local", "all_to_all", "halo", "ep", "replicate", "noop")


# ---------------------------------------------------------------------------
# keys: meshes and PartitionSpecs as plain hashable data
# ---------------------------------------------------------------------------


def mesh_key(mesh) -> tuple[tuple[str, int], ...]:
    """Reduce a ``jax.sharding.Mesh`` to the hashable ``((name, size), ...)``
    tuple every planner caches on (plans are pure metadata — they never
    hold device objects)."""
    return tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)


def spec_key(spec, ndim: int) -> tuple:
    """Normalize a PartitionSpec (or None) to a rank-``ndim`` tuple whose
    entries are ``None``, a mesh-axis name, or a tuple of names."""
    entries = tuple(spec) if spec is not None else ()
    if len(entries) > ndim:
        raise ValueError(f"spec {spec} longer than rank {ndim}")
    entries = entries + (None,) * (ndim - len(entries))
    out = []
    for e in entries:
        if e is None or isinstance(e, str):
            out.append(e)
        else:
            t = tuple(e)
            out.append(t[0] if len(t) == 1 else t)
    return tuple(out)


def sharded_axes(spec_t: tuple) -> dict[int, str]:
    """Map logical axis -> mesh-axis name for single-name entries.  Entries
    sharding one logical axis over multiple mesh axes raise (the distributed
    planner routes those to the ``replicate`` fallback before calling this).
    """
    out: dict[int, str] = {}
    for ax, e in enumerate(spec_t):
        if e is None:
            continue
        if not isinstance(e, str):
            raise ValueError(f"multi-axis sharding {e} has no aligned collective")
        out[ax] = e
    return out


def _axis_sizes(mesh_shape: tuple) -> dict[str, int]:
    return dict(mesh_shape)


def _replicas(mesh_shape: tuple, involved: int) -> int:
    """Replica groups a collective runs in: the mesh axes NOT carrying the
    op replicate it, so total wire traffic is the per-group cost times
    ``total_devices / involved`` (``involved`` = devices per comm group)."""
    total = 1
    for _, s in mesh_shape:
        total *= int(s)
    return max(total // max(involved, 1), 1)


# ---------------------------------------------------------------------------
# the plan object
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DistPlan:
    """Cached decomposition of one mesh-level movement.

    Mirrors :class:`repro.core.plan.RearrangePlan` one layer up the
    transport hierarchy: the strategy (collective choice), the mesh axis
    that carries the communication, the in/out shardings, the cache key of
    the *local* plan each shard reuses, and the predicted bytes-on-wire so
    callers and benchmarks can compare strategies.

    Example::

        plan = plan_dist_rearrange(mesh_key(mesh), spec_key(P("x"), 3),
                                   None, (8, 6, 128), jnp.float32, (1, 0, 2))
        print(plan.describe())
    """

    workload: str  # rearrange | interlace | stencil | moe
    strategy: str  # one of STRATEGIES
    mesh_shape: tuple[tuple[str, int], ...]
    axis: str | None  # mesh axis carrying the communication (None = no comm)
    in_spec: tuple
    out_spec: tuple
    local_key: tuple  # cache key of the per-shard local plan being reused
    detail: tuple  # strategy-specific geometry (see each planner)
    collectives: tuple[str, ...]  # primitive names, in execution order
    bytes_on_wire: int  # total interconnect traffic across the mesh
    bytes_local: int  # per-device HBM traffic of the local plan(s)
    wire_roofline_s: float  # bytes_on_wire / one ICI link

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        mesh = "x".join(f"{n}={s}" for n, s in self.mesh_shape)
        comm = ",".join(self.collectives) if self.collectives else "none"
        return (
            f"{self.workload}/{self.strategy}: mesh({mesh}) axis={self.axis} "
            f"{self.in_spec}->{self.out_spec} collectives=[{comm}] "
            f"{self.bytes_on_wire/1e6:.2f} MB on wire "
            f"(+{self.bytes_local/1e6:.2f} MB local HBM), "
            f"wire roofline {self.wire_roofline_s*1e6:.1f} us "
            f"@ {ICI_BW / 1e9:g} GB/s/link"
        )


def _mk(workload, strategy, mesh_shape, axis, in_spec, out_spec, local_key,
        detail, collectives, wire, local) -> DistPlan:
    return DistPlan(
        workload=workload,
        strategy=strategy,
        mesh_shape=mesh_shape,
        axis=axis,
        in_spec=in_spec,
        out_spec=out_spec,
        local_key=local_key,
        detail=detail,
        collectives=tuple(collectives),
        bytes_on_wire=int(wire),
        bytes_local=int(local),
        wire_roofline_s=wire / ICI_BW,
    )


# ---------------------------------------------------------------------------
# workload 1: sharded rearrangement (permute / interlace)
# ---------------------------------------------------------------------------


def permuted_spec(in_spec: tuple, perm: Sequence[int]) -> tuple:
    """The output sharding a comm-free local permute produces: the input
    sharding carried along by the permutation (``out[j] = in[perm[j]]``)."""
    return tuple(in_spec[p] for p in perm)


def _build_rearrange(
    mesh_shape: tuple,
    in_spec: tuple,
    out_spec: tuple | None,
    shape: tuple[int, ...],
    dtype_name: str,
    perm: tuple[int, ...],
    strategy: str | None = None,
) -> DistPlan:
    """Decompose one sharded permute into collective + local plan.

    ``strategy`` forces one route (the tuner's hook — an infeasible
    forced strategy raises ``ValueError``); with ``None`` the planner
    keeps its preference order local > all_to_all > replicate, exactly
    the pre-tuner behavior.
    """
    sizes = _axis_sizes(mesh_shape)
    itemsize = jnp.dtype(dtype_name).itemsize
    n_elems = 1
    for s in shape:
        n_elems *= int(s)
    gbytes = n_elems * itemsize
    derived = permuted_spec(in_spec, perm)
    if out_spec is None:
        out_spec = derived
    if n_elems == 0 and strategy is None:
        # nothing to move or exchange: the output is an empty array
        return _mk("rearrange", "noop", mesh_shape, None, in_spec, out_spec,
                   (shape, dtype_name, perm), (), (), 0, 0)

    def shard_div(spec_t):
        """Local shape under spec_t; None when some sharded dim is ragged.
        Multi-axis entries divide by the product of their axis sizes (they
        have no aligned all_to_all, but local execution is still local)."""
        local = list(shape)
        for ax, e in enumerate(spec_t):
            p = 1
            for name in (e,) if isinstance(e, str) else (e or ()):
                p *= sizes.get(name, 1)
            if local[ax] % p:
                return None
            local[ax] //= p
        return tuple(local)

    def local_plan_of(local_shape):
        lp = plan_rearrange(local_shape, dtype_name, perm)
        return (local_shape, dtype_name, perm), lp.bytes_moved

    in_local = shard_div(in_spec)

    def sig(spec_t):
        """Spec signature modulo size-1 mesh axes (which shard nothing)."""
        out = []
        for e in spec_t:
            if e is None:
                out.append(None)
            elif isinstance(e, str):
                out.append(e if sizes.get(e, 1) > 1 else None)
            else:
                t = tuple(n for n in e if sizes.get(n, 1) > 1)
                out.append(t[0] if len(t) == 1 else (t if t else None))
        return tuple(out)

    # --- sharding carried by the permutation: comm-free local execution ---
    # (covers fully-replicated arrays and size-1 mesh axes, where any
    # requested output sharding is a no-op and the permute is local)
    if in_local is not None and sig(out_spec) == sig(derived):
        if strategy in (None, "local"):
            key, lb = local_plan_of(in_local)
            return _mk("rearrange", "local", mesh_shape, None, in_spec, out_spec,
                       key, (), (), 0, lb)
    elif strategy == "local":
        raise ValueError("local strategy infeasible: output sharding differs")

    # --- axis-aligned redistribution: one tiled all_to_all, then local ---
    in_sh = None
    try:
        in_sh = sharded_axes(sig(in_spec))
        out_sh = sharded_axes(sig(out_spec))
    except ValueError:
        in_sh = None
    if (
        strategy in (None, "all_to_all")
        and in_sh is not None
        and len(in_sh) == 1
        and len(out_sh) == 1
    ):
        (a, m_in), = in_sh.items()
        (j, m_out), = out_sh.items()
        b = perm[j]  # logical input axis the output wants sharded
        p = sizes.get(m_in, 1)
        if (
            m_in == m_out
            and p > 1
            and b != a
            and shape[a] % p == 0
            and shape[b] % p == 0
        ):
            # after the exchange each shard holds (full a, b/P): split the
            # local block along b, concat received chunks along a
            resharded = list(shape)
            resharded[b] //= p
            key, lb = local_plan_of(tuple(resharded))
            wire = gbytes * (p - 1) // p * _replicas(mesh_shape, p)
            return _mk("rearrange", "all_to_all", mesh_shape, m_in, in_spec,
                       out_spec, key, (a, b, p), ("all_to_all",), wire, lb)
    if strategy == "all_to_all":
        raise ValueError("all_to_all strategy infeasible for these specs")

    # --- fallback: gather everything, run the full local plan, slice ---
    # within one dim the gathers must run minor-axis-first: the minor
    # all_gather makes each device's chunk contiguous before the major
    # all_gather concatenates chunks (major-first would interleave blocks)
    gather_axes = []
    for ax, e in enumerate(in_spec):
        names = (e,) if isinstance(e, str) else tuple(reversed(e or ()))
        prod = 1
        for name in names:
            prod *= sizes.get(name, 1)
        if shape[ax] % prod:
            raise ValueError(
                f"dim {ax} of {shape} not divisible by mesh axes "
                f"{names} (x{prod}) — cannot shard"
            )
        gather_axes.extend(
            (ax, name) for name in names if sizes.get(name, 1) > 1
        )
    slice_axes = []
    for j, e in enumerate(out_spec):
        for name in ((e,) if isinstance(e, str) else (e or ())):
            if sizes.get(name, 1) > 1:
                if shape[perm[j]] % sizes[name]:
                    raise ValueError(
                        f"out dim {j} ({shape[perm[j]]}) not divisible by mesh "
                        f"axis {name!r} ({sizes[name]}) — cannot shard"
                    )
                slice_axes.append((j, name))
    key, lb = local_plan_of(shape)
    # all_gather delivers (shards-1) remote shards to each group device,
    # repeated in every replica group over the uninvolved mesh axes
    wire = 0
    shards = 1
    for _, name in gather_axes:
        shards *= sizes[name]
    if shards > 1:
        wire = gbytes * (shards - 1) * _replicas(mesh_shape, shards)
    comm_axis = gather_axes[0][1] if gather_axes else (
        slice_axes[0][1] if slice_axes else None
    )
    return _mk("rearrange", "replicate", mesh_shape, comm_axis, in_spec,
               out_spec, key, (tuple(gather_axes), tuple(slice_axes)),
               ("all_gather",) * len(gather_axes), wire, lb)


@functools.lru_cache(maxsize=4096)
def _plan_rearrange_cached(
    mesh_shape: tuple,
    in_spec: tuple,
    out_spec: tuple | None,
    shape: tuple[int, ...],
    dtype_name: str,
    perm: tuple[int, ...],
) -> DistPlan:
    return _build_rearrange(mesh_shape, in_spec, out_spec, shape, dtype_name, perm)


def _dist_cost_s(plan: DistPlan) -> float:
    """Strategy score: local HBM traffic plus the wire term (bytes at one
    ICI link, one launch latency per collective)."""
    return movement_cost_s(
        plan.bytes_local,
        1,
        wire_bytes=plan.bytes_on_wire,
        collectives=len(plan.collectives),
    )


def _select_strategy(
    engine: str, key: str, plans: list[DistPlan], mode: str
) -> DistPlan:
    """Pick among feasible strategy decompositions by cost model.

    Strategies are proven bit-identical (the §10 test suite), so choice
    only moves bytes between wire and HBM.  There is no measured runner —
    a cached planner cannot re-materialize the caller's mesh — so the
    tuner's cost fallback does the ranking in every mode; the point of
    routing through :func:`repro.core.tune.select` is the shared tie-break
    contract (the planner's preferred strategy is first) and the recorded
    search space.
    """
    cands = [
        tune.Candidate(label=p.strategy, params=(("i", i),), cost_s=_dist_cost_s(p))
        for i, p in enumerate(plans)
    ]
    choice = tune.select(engine, key, cands, None, mode=mode)
    return plans[choice.param_dict()["i"]]


@functools.lru_cache(maxsize=4096)
def _plan_rearrange_tuned(
    mesh_shape: tuple,
    in_spec: tuple,
    out_spec: tuple | None,
    shape: tuple[int, ...],
    dtype_name: str,
    perm: tuple[int, ...],
    mode: str,
) -> DistPlan:
    base = _plan_rearrange_cached(
        mesh_shape, in_spec, out_spec, shape, dtype_name, perm
    )
    if base.strategy in ("local", "noop"):
        return base  # zero bytes on wire: nothing can beat it
    plans = [base]
    for strat in STRATEGIES:
        if strat in (base.strategy, "local", "halo", "ep", "noop"):
            continue
        try:
            plans.append(
                _build_rearrange(
                    mesh_shape, in_spec, out_spec, shape, dtype_name, perm, strat
                )
            )
        except ValueError:
            continue
    return _select_strategy(
        "dist-rearrange",
        f"mesh={mesh_shape}|{in_spec}->{out_spec}|shape={shape}"
        f"|dtype={dtype_name}|perm={perm}",
        plans,
        mode,
    )


def plan_dist_rearrange(
    mesh_shape: tuple,
    in_spec: tuple,
    out_spec: tuple | None,
    shape: Sequence[int],
    dtype,
    perm: Sequence[int],
    *,
    tuned: bool | None = None,
) -> DistPlan:
    """Plan (and cache) a sharded ``permute(x, perm)``.

    ``mesh_shape`` is :func:`mesh_key` data; ``in_spec``/``out_spec`` are
    :func:`spec_key` tuples (``out_spec=None`` requests the comm-free
    sharding, i.e. the input sharding carried along by the permutation).
    Repeated calls with equal arguments return the *identical* plan object.

    ``tuned=None`` resolves from ``REPRO_TUNE``; ``tuned=True`` ranks every
    feasible strategy decomposition through the autotuner's cost model
    (DESIGN.md §11) instead of taking the first feasible one.
    """
    perm_t = tuple(int(p) for p in perm)
    shape_t = tuple(int(s) for s in shape)
    if sorted(perm_t) != list(range(len(shape_t))):
        raise ValueError(f"bad perm {perm_t} for rank {len(shape_t)}")
    if tuned is None:
        tuned = tune.tune_default()
    key = (
        tuple(mesh_shape),
        spec_key(in_spec, len(shape_t)),
        None if out_spec is None else spec_key(out_spec, len(shape_t)),
        shape_t,
        jnp.dtype(dtype).name,
        perm_t,
    )
    if not tuned:
        return _plan_rearrange_cached(*key)
    return _plan_rearrange_tuned(*key, tune.resolve_mode())


@functools.lru_cache(maxsize=1024)
def _plan_interlace_cached(
    mesh_shape: tuple, spec: tuple, shape: tuple, dtype_name: str, n: int
) -> DistPlan:
    sizes = _axis_sizes(mesh_shape)
    itemsize = jnp.dtype(dtype_name).itemsize
    local = list(shape)
    for ax, e in enumerate(spec):
        names = (e,) if isinstance(e, str) else (e or ())
        p = 1
        for name in names:
            p *= sizes.get(name, 1)
        if local[ax] % p:
            raise ValueError(
                f"dim {ax} of {shape} not divisible by mesh axes {names} (x{p})"
            )
        local[ax] //= p
    n_local = 1
    for s in local:
        n_local *= int(s)
    # interlace is a position-wise expansion along the last axis, so ANY
    # sharding (even of the interlaced axis) commutes with it: shard s of
    # the output is exactly the interlace of shard s of each input.  Zero
    # bytes cross the wire, always.
    return _mk("interlace", "local", mesh_shape, None, spec, spec,
               (tuple(local), dtype_name, n), (n,), (), 0,
               2 * n * n_local * itemsize)


def plan_dist_interlace(
    mesh_shape: tuple, spec: tuple, shape: Sequence[int], dtype, n: int
) -> DistPlan:
    """Plan (and cache) a sharded ``interlace`` of ``n`` same-shape arrays.

    Interlace commutes with every sharding (it is position-wise along the
    last axis), so the plan is always comm-free — the point of routing it
    through the planner is the cache + the explicit 0-bytes-on-wire record.
    """
    if n < 1:
        raise ValueError(f"interlace wants n >= 1 arrays, got {n}")
    shape_t = tuple(int(s) for s in shape)
    return _plan_interlace_cached(
        tuple(mesh_shape), spec_key(spec, len(shape_t)), shape_t,
        jnp.dtype(dtype).name, int(n),
    )


# ---------------------------------------------------------------------------
# workload 2: halo-exchanged stencil programs
# ---------------------------------------------------------------------------


def _build_stencil(
    mesh_shape: tuple,
    axis: str,
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
    strategy: str | None = None,
) -> DistPlan:
    """Decompose one row-sharded stencil program into halo k-blocks (or a
    fallback strategy).

    ``strategy`` forces ``halo`` / ``replicate`` (the tuner's hook; an
    infeasible forced strategy raises ``ValueError``); ``None`` keeps the
    pre-tuner preference: halo whenever every stage radius fits one shard.
    """
    from repro.core import stencil as st

    sizes = _axis_sizes(mesh_shape)
    p = sizes.get(axis, 1)
    H, W = shape
    itemsize = jnp.dtype(dtype_name).itemsize
    in_spec = (axis, None)
    radii = tuple(st._stage_exec(d)[1] for d in stages)

    if H * W == 0:
        return _mk("stencil", "noop", mesh_shape, None, in_spec, in_spec,
                   (shape, dtype_name, stages, boundary), (), (), 0, 0)
    if p <= 1:
        lp = st.plan_stencil(shape, dtype_name, stages, boundary)
        return _mk("stencil", "local", mesh_shape, None, in_spec, in_spec,
                   (shape, dtype_name, stages, boundary), (), (), 0,
                   lp.bytes_moved)
    if H % p:
        raise ValueError(f"grid rows {H} not divisible by mesh axis {axis!r} ({p})")
    hl = H // p

    if max(radii, default=0) > hl or strategy == "replicate":
        if strategy == "halo":
            raise ValueError("halo strategy infeasible: a stage radius "
                             "reaches past the nearest neighbor")
        # gather the full grid, run the whole single-device plan, keep the
        # owned rows
        lp = st.plan_stencil(shape, dtype_name, stages, boundary)
        wire = H * W * itemsize * (p - 1) * _replicas(mesh_shape, p)
        return _mk("stencil", "replicate", mesh_shape, axis, in_spec, in_spec,
                   (shape, dtype_name, stages, boundary), (),
                   ("all_gather",), wire, lp.bytes_moved)

    # k-block partition: pack consecutive stages while the block's summed
    # radius stays within one shard (the ppermute pair only reaches the
    # nearest neighbor).  Each block costs ONE exchange; within a block the
    # whole stage run is the existing fused temporal-blocking kernel.
    blocks: list[tuple[int, int]] = []  # (n_stages, block_radius)
    cur_n = cur_r = 0
    for r in radii:
        if cur_n and cur_r + r > hl:
            blocks.append((cur_n, cur_r))
            cur_n = cur_r = 0
        cur_n += 1
        cur_r += r
    blocks.append((cur_n, cur_r))

    # local-plan reuse: each block lowers through the §9 stencil planner on
    # the halo-extended shard (periodic geometry resolves through the
    # clamped specs because the wrap rows are physically resident)
    geo_boundary = "zero" if boundary == "periodic" else boundary
    bytes_local = 0
    off = 0
    for n_b, r_b in blocks:
        block_stages = stages[off : off + n_b]
        off += n_b
        lp = st.plan_stencil((hl + 2 * r_b, W), dtype_name, block_stages,
                             geo_boundary)
        bytes_local += lp.bytes_moved
    wire = sum(
        2 * r_b * W * itemsize * p for _, r_b in blocks
    ) * _replicas(mesh_shape, p)
    collectives = tuple(
        c for _, r_b in blocks for c in (("ppermute", "ppermute") if r_b else ())
    )
    return _mk("stencil", "halo", mesh_shape, axis, in_spec, in_spec,
               ((hl, W), dtype_name, stages, boundary), tuple(blocks),
               collectives, wire, bytes_local)


@functools.lru_cache(maxsize=1024)
def _plan_stencil_cached(
    mesh_shape: tuple,
    axis: str,
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
) -> DistPlan:
    return _build_stencil(mesh_shape, axis, shape, dtype_name, stages, boundary)


@functools.lru_cache(maxsize=1024)
def _plan_stencil_tuned(
    mesh_shape: tuple,
    axis: str,
    shape: tuple[int, int],
    dtype_name: str,
    stages: tuple,
    boundary: str,
    mode: str,
) -> DistPlan:
    base = _plan_stencil_cached(mesh_shape, axis, shape, dtype_name, stages, boundary)
    if base.strategy != "halo":
        return base  # local/noop have no wire; replicate means halo is infeasible
    plans = [base]
    try:
        plans.append(
            _build_stencil(
                mesh_shape, axis, shape, dtype_name, stages, boundary, "replicate"
            )
        )
    except ValueError:
        pass
    return _select_strategy(
        "dist-stencil",
        f"mesh={mesh_shape}|axis={axis}|shape={shape}|dtype={dtype_name}"
        f"|b={boundary}|n_stages={len(stages)}",
        plans,
        mode,
    )


def plan_dist_stencil(
    mesh_shape: tuple,
    axis: str,
    shape: Sequence[int],
    dtype,
    stages: tuple,
    boundary: str = "zero",
    *,
    tuned: bool | None = None,
) -> DistPlan:
    """Plan (and cache) a stencil *program* on a row-sharded grid.

    ``stages`` are the :class:`repro.core.stencil.StencilProgram` stage
    descriptors; ``axis`` the mesh axis the rows are sharded over.  The plan
    partitions the program into k-blocks of consecutive stages whose summed
    radius fits one shard; each block costs one ``ppermute`` pair (send the
    top/bottom edge rows to the two neighbors) and runs as ONE fused local
    kernel per shard (§9 temporal blocking on the halo-extended shard).

    ``tuned=None`` resolves from ``REPRO_TUNE``; ``tuned=True`` ranks the
    halo decomposition against the replicate fallback through the
    autotuner's cost model (DESIGN.md §11).
    """
    shape_t = tuple(int(s) for s in shape)
    if len(shape_t) != 2:
        raise ValueError(f"stencil plans want 2-D shapes, got {shape_t}")
    if tuned is None:
        tuned = tune.tune_default()
    key = (
        tuple(mesh_shape), str(axis), shape_t, jnp.dtype(dtype).name,
        tuple(stages), str(boundary),
    )
    if not tuned:
        return _plan_stencil_cached(*key)
    return _plan_stencil_tuned(*key, tune.resolve_mode())


# ---------------------------------------------------------------------------
# workload 3: expert-parallel MoE dispatch
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1024)
def _plan_moe_cached(
    mesh_shape: tuple,
    axis: str,
    t_global: int,
    d_model: int,
    n_experts: int,
    capacity: int,
    top_k: int,
    dtype_name: str,
) -> DistPlan:
    from repro.core.index_plan import plan_index_op

    sizes = _axis_sizes(mesh_shape)
    p = sizes.get(axis, 1)
    itemsize = jnp.dtype(dtype_name).itemsize
    in_spec = (axis, None)
    if t_global % p:
        raise ValueError(f"tokens {t_global} not divisible by mesh axis {axis!r} ({p})")
    if n_experts % p:
        raise ValueError(
            f"experts {n_experts} not divisible by mesh axis {axis!r} ({p})"
        )
    tl = t_global // p
    el = n_experts // p
    slots = n_experts * capacity  # per source device
    # local plans being reused: the §4 blocked dispatch gather and the fused
    # gather+combine — identical kernels to the single-device moe_sort
    disp = plan_index_op((tl, d_model), dtype_name, slots, "gather", masked=True)
    comb = plan_index_op((slots, d_model), dtype_name, tl, "gather_combine",
                         masked=True, top_k=top_k)
    if p <= 1:
        return _mk("moe", "local", mesh_shape, None, in_spec, in_spec,
                   (disp.kernel, comb.kernel, slots, tl), (), (), 0,
                   disp.bytes_moved + comb.bytes_moved)
    # each direction moves the (P-1)/P remote fraction of every device's
    # (E*cap, D) slot block — in every replica group over uninvolved mesh
    # axes; the gathered intermediate itself never round-trips HBM (it is
    # produced by / consumed into the fused kernels)
    wire_dir = (
        p * slots * d_model * itemsize * (p - 1) // p
        * _replicas(mesh_shape, p)
    )
    return _mk("moe", "ep", mesh_shape, axis, in_spec, in_spec,
               (disp.kernel, comb.kernel, slots, tl),
               (p, el, capacity, top_k),
               ("all_to_all", "all_to_all"), 2 * wire_dir,
               disp.bytes_moved + comb.bytes_moved)


def plan_dist_moe(
    mesh_shape: tuple,
    axis: str,
    t_global: int,
    d_model: int,
    n_experts: int,
    capacity: int,
    top_k: int,
    dtype,
) -> DistPlan:
    """Plan (and cache) expert-parallel MoE dispatch+combine.

    ``capacity`` is per (source shard, expert) — the capacity bucketing that
    makes the exchanged slot blocks fixed-size so ONE tiled ``all_to_all``
    per direction suffices.  The local stages reuse the §4 IndexPlan
    kernels unchanged (blocked masked gather out, fused combine back).
    """
    return _plan_moe_cached(
        tuple(mesh_shape), str(axis), int(t_global), int(d_model),
        int(n_experts), int(capacity), int(top_k), jnp.dtype(dtype).name,
    )


# ---------------------------------------------------------------------------
# executors (the shard_map wrappers around the local engines)
# ---------------------------------------------------------------------------


def _pspec(spec_t: tuple) -> P:
    return P(*spec_t)


def shard_permute(
    x: Array,
    perm: Sequence[int],
    *,
    mesh,
    in_spec,
    out_spec=None,
    tuned: bool | None = None,
) -> Array:
    """Sharded N-D permute through the distributed plan engine.

    ``x`` is (or will be treated as) sharded per ``in_spec`` on ``mesh``.
    With ``out_spec=None`` the output keeps the input sharding carried along
    by the permutation — zero communication.  Requesting a different
    ``out_spec`` makes the planner insert the minimal axis-aligned
    ``all_to_all`` (or the ``replicate`` fallback) before the local plan.

    Example::

        y = shard_permute(x, (1, 0, 2), mesh=mesh, in_spec=P("b"))
        z = shard_permute(x, (1, 0, 2), mesh=mesh, in_spec=P("b"),
                          out_spec=P(None, None, "b"))   # one all_to_all
    """
    perm = tuple(int(p) for p in perm)
    plan = plan_dist_rearrange(
        mesh_key(mesh), spec_key(in_spec, x.ndim),
        None if out_spec is None else spec_key(out_spec, x.ndim),
        x.shape, x.dtype, perm, tuned=tuned,
    )
    if plan.strategy == "noop":
        # an empty array has no shards to move (and XLA reports empty
        # outputs as replicated, whatever sharding is requested)
        return jnp.zeros(tuple(x.shape[p] for p in perm), x.dtype)
    if plan.strategy == "local":
        f = lambda xl: ops.permute(xl, perm)  # noqa: E731
    elif plan.strategy == "all_to_all":
        a, b, _p = plan.detail

        def f(xl):
            xl = jax.lax.all_to_all(
                xl, plan.axis, split_axis=b, concat_axis=a, tiled=True
            )
            return ops.permute(xl, perm)
    else:  # replicate
        gather_axes, slice_axes = plan.detail

        def f(xl):
            for ax, name in gather_axes:
                xl = jax.lax.all_gather(xl, name, axis=ax, tiled=True)
            y = ops.permute(xl, perm)
            for j, name in slice_axes:
                n_loc = y.shape[j] // dict(plan.mesh_shape)[name]
                start = jax.lax.axis_index(name) * n_loc
                y = jax.lax.dynamic_slice_in_dim(y, start, n_loc, axis=j)
            return y

    return jax.shard_map(
        f, mesh=mesh, in_specs=(_pspec(plan.in_spec),),
        out_specs=_pspec(plan.out_spec), check_vma=False,
    )(x)


def shard_interlace(arrays: Sequence[Array], *, mesh, spec) -> Array:
    """Sharded interlace: ``n`` same-shape arrays interleaved along the last
    axis.  Always comm-free (see :func:`plan_dist_interlace`); each shard
    runs the existing single-kernel interlace and the output keeps ``spec``.
    """
    arrays = list(arrays)
    if not arrays:
        raise ValueError("interlace wants at least one array")
    plan = plan_dist_interlace(
        mesh_key(mesh), spec_key(spec, arrays[0].ndim), arrays[0].shape,
        arrays[0].dtype, len(arrays),
    )
    f = lambda *ls: ops.interlace(list(ls))  # noqa: E731
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=tuple(_pspec(plan.in_spec) for _ in arrays),
        out_specs=_pspec(plan.out_spec), check_vma=False,
    )(*arrays)


def shard_stencil(
    program,
    x: Array,
    *,
    mesh,
    axis: str,
    boundary: str = "zero",
    tuned: bool | None = None,
) -> Array:
    """Run a :class:`repro.core.stencil.StencilProgram` on a row-sharded
    2-D grid with halo exchange (DESIGN.md §10).

    Per k-block of the plan: one ``ppermute`` pair swaps ``block_radius``
    edge rows with the two mesh neighbors, the halo-extended shard runs the
    existing fused §9 kernel (global-row window semantics keep the four
    boundary modes exact at the true grid edges), and the owned rows are
    kept.  Bit-identical to ``program(x, boundary=...)`` on one device.
    """
    from repro.core import stencil as st
    from repro.launch.mesh import ring_perm

    if x.ndim != 2:
        raise ValueError(f"stencil programs want 2-D grids, got {x.shape}")
    plan = plan_dist_stencil(
        mesh_key(mesh), axis, x.shape, x.dtype, program.stages, boundary,
        tuned=tuned,
    )
    if plan.strategy == "noop":
        return x
    if plan.strategy == "local":
        return program(x, boundary=boundary)
    H, W = x.shape
    p = dict(plan.mesh_shape)[axis]
    hl = H // p
    stages_exec = tuple(st._stage_exec(d) for d in program.stages)

    if plan.strategy == "replicate":
        def f(xl):
            xg = jax.lax.all_gather(xl, axis, axis=0, tiled=True)
            y = ops.stencil_program(xg, stages_exec, boundary=boundary)
            start = jax.lax.axis_index(axis) * hl
            return jax.lax.dynamic_slice_in_dim(y, start, hl, axis=0)
    else:  # halo
        blocks = plan.detail
        perm_dn = ring_perm(p)  # i -> i+1: my bottom rows become their top halo
        perm_up = ring_perm(p, reverse=True)  # i -> i-1: top rows go up

        def f(xl):
            row0 = jax.lax.axis_index(axis).astype(jnp.int32) * hl
            off = 0
            for n_b, r_b in blocks:
                block = stages_exec[off : off + n_b]
                off += n_b
                if r_b:
                    top_halo = jax.lax.ppermute(xl[-r_b:], axis, perm_dn)
                    bot_halo = jax.lax.ppermute(xl[:r_b], axis, perm_up)
                    ext = jnp.concatenate([top_halo, xl, bot_halo], axis=0)
                else:
                    ext = xl
                y = ops.stencil_program(
                    ext, block, boundary=boundary,
                    window=(row0 - r_b, H),
                )
                xl = jax.lax.slice_in_dim(y, r_b, r_b + hl, axis=0) if r_b else y
            return xl

    return jax.shard_map(
        f, mesh=mesh, in_specs=(_pspec(plan.in_spec),),
        out_specs=_pspec(plan.out_spec), check_vma=False,
    )(x)


def dist_plan_cache_info() -> dict:
    """Expose the per-workload plan-memo stats (tests / benchmarks)."""
    return {
        "rearrange": _plan_rearrange_cached.cache_info(),
        "rearrange_tuned": _plan_rearrange_tuned.cache_info(),
        "interlace": _plan_interlace_cached.cache_info(),
        "stencil": _plan_stencil_cached.cache_info(),
        "stencil_tuned": _plan_stencil_tuned.cache_info(),
        "moe": _plan_moe_cached.cache_info(),
    }
