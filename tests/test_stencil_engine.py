"""Stencil plan engine: fused multi-stage pipelines via temporal blocking.

Covers the acceptance surface of the stencil-engine PR (DESIGN.md §9):
* oracle equivalence of a fused ``repeat(k)`` program vs k sequential
  reference sweeps for every boundary mode, radii 1-2, fp32/bf16,
  non-multiple-of-panel heights, and zero-size inputs;
* a fused program (k >= 4) lowers to exactly ONE pallas_call;
* the plan cache returns the identical plan object on repeated calls;
* ``then`` composition, trace-time functor stages, and aux (source-term)
  programs match their sequential references;
* kernel-level panel/boundary corner cases (forced small panels, periodic
  mod-index-map wrap, halo deeper than the grid).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stencil as st
from repro.kernels import ops, ref
from repro.kernels import stencil2d as st_k

RNG = np.random.default_rng(11)

BOUNDARIES = ["zero", "nearest", "reflect", "periodic"]


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def sweeps(x, stencil: st.Stencil, k: int, boundary: str):
    """k sequential full-grid reference sweeps — the fused oracle."""
    for _ in range(k):
        x = ref.stencil2d(x, stencil.offsets, stencil.weights, boundary=boundary)
    return x


def n_pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# fused repeat(k) vs k sequential sweeps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("radius", [1, 2])
@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_repeat_matches_sequential_sweeps(boundary, radius, dtype, pallas_interpret):
    """H=67 is a non-multiple of the default 64-row panel (partial final
    panel); radius 2 uses the 9-point fd_laplacian(2)."""
    s = st.fd_laplacian(radius).scale(0.1)
    x = rand((67, 33), dtype)
    got = s.repeat(4)(x, boundary=boundary)
    want = sweeps(x, s, 4, boundary)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("shape", [(8, 128), (64, 64), (70, 17), (3, 9)])
def test_repeat_shapes_zero_boundary(shape, pallas_interpret):
    """Sub-panel, exact, ragged, and halo-deeper-than-grid heights."""
    s = st.fd_laplacian(1).scale(0.2)
    x = rand(shape)
    got = s.repeat(5)(x)
    want = sweeps(x, s, 5, "zero")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(0, 16), (16, 0), (0, 0)])
def test_zero_size_inputs(shape, pallas_interpret):
    prog = st.fd_laplacian(1).repeat(4)
    out = prog(jnp.zeros(shape, jnp.float32))
    assert out.shape == shape


# ---------------------------------------------------------------------------
# single fused pallas_call + plan cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_repeat4_single_pallas_call(boundary, pallas_interpret):
    prog = st.fd_laplacian(1).scale(0.1).repeat(4)
    x = rand((64, 40))
    assert n_pallas_calls(lambda t: prog(t, boundary=boundary), x) == 1
    plan = prog.compile(x.shape, x.dtype, boundary=boundary)
    assert plan.mode == "fused" and plan.kernel == "stencil2d_pipeline"


def test_deep_repeat_single_pallas_call(pallas_interpret):
    """k=8 with radius 1: a 8-row halo, still one kernel."""
    prog = st.fd_laplacian(1).scale(0.1).repeat(8)
    x = rand((128, 32))
    assert n_pallas_calls(prog, x) == 1


def test_plan_cache_returns_identical_object():
    a = st.fd_laplacian(1).repeat(6).compile((256, 128), jnp.float32)
    b = st.fd_laplacian(1).repeat(6).compile((256, 128), jnp.float32)
    assert a is b  # distinct program objects, same descriptors -> same plan
    c = st.fd_laplacian(1).repeat(6).compile((256, 128), jnp.float32, boundary="reflect")
    assert c is not a and c.boundary == "reflect"
    d = st.fd_laplacian(1).repeat(6).compile((256, 128), jnp.bfloat16)
    assert d is not a


def test_plan_cost_model_prefers_fusion():
    plan = st.fd_laplacian(1).repeat(8).compile((4096, 4096), jnp.float32)
    assert plan.mode == "fused"
    assert plan.bytes_per_sweep_path > 4 * plan.bytes_moved  # ~8x ideal
    assert plan.grid == 4096 // plan.block_rows
    assert "fused" in plan.describe()


def test_plan_reference_fallback_on_tiny_columns():
    """reflect columns need W >= radius+1; the planner must route the
    program to the reference path instead of failing."""
    plan = st.fd_laplacian(2).repeat(2).compile((64, 2), jnp.float32, boundary="reflect")
    assert plan.mode == "reference"
    x = rand((64, 2))
    prog = st.fd_laplacian(2).repeat(2)
    got = prog(x, boundary="reflect")
    want = sweeps(x, st.fd_laplacian(2), 2, "reflect")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# composition: then / functor stages / aux programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_then_composition_mixed_radii(boundary, pallas_interpret):
    blur, lap = st.box_blur(2), st.fd_laplacian(1)
    prog = blur.then(lap).repeat(2)  # radii 2,1,2,1 -> halo 6
    assert prog.n_stages == 4 and prog.total_radius == 6
    x = rand((48, 24))
    got = prog(x, boundary=boundary)
    want = x
    for s in [blur, lap, blur, lap]:
        want = ref.stencil2d(want, s.offsets, s.weights, boundary=boundary)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def _shift_max(shift):
    return jnp.maximum(jnp.maximum(shift(0, -1), shift(0, 1)), shift(0, 0))


def test_functor_stage_nonlinear_pipeline(pallas_interpret):
    """Non-linear trace-time functor stages compose with linear ones."""
    prog = st.functor_stage(_shift_max, 1).then(st.box_blur(1)).repeat(2)
    x = rand((40, 30))
    got = prog(x)
    want = x
    for _ in range(2):
        want = ref.stencil2d_functor(want, _shift_max, 1)
        want = ref.stencil2d(want, st.box_blur(1).offsets, st.box_blur(1).weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert n_pallas_calls(prog, x) == 1


def _jacobi_src(shift, src):
    return 0.25 * (shift(1, 0) + shift(-1, 0) + shift(0, 1) + shift(0, -1)) + src()


def test_aux_source_term_program(pallas_interpret):
    """Jacobi iteration with a right-hand side rides as the aux operand
    (the CFD cavity Poisson solve, examples/cfd_cavity.py)."""
    prog = st.functor_stage(_jacobi_src, 1).repeat(6)
    x, b = rand((67, 31)), rand((67, 31))
    got = prog(x, aux=b)
    want = x
    for _ in range(6):
        want = ref.stencil2d_functor(want, _jacobi_src, 1, aux=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert n_pallas_calls(lambda t, a: prog(t, aux=a), x, b) == 1


# ---------------------------------------------------------------------------
# kernel-level panel / boundary corner cases
# ---------------------------------------------------------------------------


def _lap(shift, *_):
    return shift(-1, 0) + shift(1, 0) + shift(0, -1) + shift(0, 1) - 4.0 * shift(0, 0)


@pytest.mark.parametrize("boundary", ["zero", "nearest", "reflect"])
def test_forced_small_panels_partial_final(boundary):
    """block_rows=16 over H=50: four panels, ragged final panel."""
    x = rand((50, 21))
    stages = ((_lap, 1),) * 4
    got = st_k.stencil2d_pipeline(
        x, stages, boundary=boundary, block_rows=16, interpret=True
    )
    want = ref.stencil_pipeline(x, stages, boundary=boundary)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_periodic_multi_panel_mod_index_maps():
    """H=48 with block_rows=16 exercises the wrap-around halo blocks."""
    x = rand((48, 19))
    stages = ((_lap, 1),) * 4
    got = st_k.stencil2d_pipeline(
        x, stages, boundary="periodic", block_rows=16, interpret=True
    )
    want = ref.stencil_pipeline(x, stages, boundary="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_periodic_halo_deeper_than_grid():
    """R=5 > H=3: the wrap halo must tile the grid multiple times."""
    x = rand((3, 9))
    stages = ((_lap, 1),) * 5
    got = st_k.stencil2d_pipeline(x, stages, boundary="periodic", interpret=True)
    want = ref.stencil_pipeline(x, stages, boundary="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# shift routes: rotations of a fixed band vs slices of a shrinking band
# ---------------------------------------------------------------------------

_JACOBI = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)
# radius 2 with power-of-two weights: every product is exact, so a fused
# multiply-add in the CPU backend cannot change a bit of the reference
_CROSS2 = st.Stencil(
    ((0, 0), (1, 0), (-1, 0), (0, 1), (0, -1), (2, 0), (-2, 0), (0, 2), (0, -2)),
    (-0.5, 0.25, 0.25, 0.25, 0.25, -0.0625, -0.0625, -0.0625, -0.0625),
)
_PROGRAMS = {
    "r1x8": _JACOBI.repeat(8),  # the benchmark's Jacobi: halo 8 = one tile
    "r2x4": _CROSS2.repeat(4),
    "r1x2": _JACOBI.repeat(2),  # halo 2 inside an 8-row halo block
}


def _route_cases():
    cases = [
        (b, prog, w, "plain")
        for b in BOUNDARIES
        for prog in _PROGRAMS
        for w in (256, 200)  # lane multiple -> roll, else slice
    ]
    cases += [(b, "r1x8", 256, "aux") for b in BOUNDARIES]
    cases += [(b, "r1x8", 256, "row0") for b in BOUNDARIES]
    # negative weights on a zero grid: the zero boundary's columns must
    # read w * 0 = -0.0, as the reference's padded zeros do
    cases += [("zero", "neg", 256, "zeros")]
    return cases


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("boundary,prog,width,extra", _route_cases())
def test_fused_kernel_bit_exact_on_both_routes(boundary, prog, width, extra):
    """The fused kernel equals ``ref.stencil_pipeline`` bit for bit on the
    rotation path (lane-multiple width) and the slice path, over several
    panels: H=150 leaves a partial final panel (periodic needs panels that
    divide H, so it runs three whole ones), with an aux source term, and
    in the global-row window mode of the halo exchange."""
    program = _JACOBI.scale(-1.0).as_program() if prog == "neg" else _PROGRAMS[prog]
    stages = program.compile((64, width), jnp.float32).stages_exec
    H = 192 if boundary == "periodic" else 150
    x = jnp.zeros((H, width)) if extra == "zeros" else rand((H, width))
    radii = tuple(r for _, r in stages)
    panel = st_k.fused_panel(H, width, jnp.float32, radii, boundary,
                             halo_resident=extra == "row0")
    assert panel is not None and panel[0] < H  # several panels
    # periodic's halo block is the panel's smallest divisor >= R: 2 rows
    # for r1x2, not a whole sublane tile, so that case stays on slices
    roll = width % 128 == 0 and (boundary, prog) != ("periodic", "r1x2")
    route = st_k.shift_route(width, jnp.float32, panel, radii)
    assert route == ("roll" if roll else "slice")
    if extra in ("plain", "zeros"):
        got = st_k.stencil2d_pipeline(x, stages, boundary=boundary, interpret=True)
        want = ref.stencil_pipeline(x, stages, boundary=boundary)
    elif extra == "aux":
        a = rand((H, width))
        stages = ((_jacobi_src, 1),) * 8
        got = st_k.stencil2d_pipeline(x, stages, boundary=boundary, aux=a,
                                      interpret=True)
        want = ref.stencil_pipeline(x, stages, boundary=boundary, aux=a)
    else:
        # a window of rows [-8, H - 8) of a grid of H - 20 rows: both grid
        # edges fall inside it; rows whose cone leaves the window are cropped
        row0, rows = -8, H - 20
        got = st_k.stencil2d_pipeline(
            x, stages, boundary=boundary, row0=jnp.int32(row0),
            global_rows=rows, halo_resident=True, interpret=True,
        )
        want = ref.stencil_pipeline_window(
            x, stages, boundary=boundary, row0=row0, global_rows=rows
        )
        R = sum(radii)
        keep = slice(max(R, -row0), min(H - R, rows - row0))
        got, want = got[keep], want[keep]
    assert np.array_equal(_bits(got), _bits(want))


def test_plan_reports_shift_route():
    """The benchmark's program plans fused with a (64, 8) panel on the
    rotation path; a width that is not whole lanes plans the slice path."""
    prog = _JACOBI.repeat(8)
    plan = prog.compile((65536, 7168), jnp.float32, boundary="reflect")
    assert plan.mode == "fused"
    assert (plan.block_rows, plan.halo_block_rows) == (64, 8)
    assert plan.shift == "roll" and "shift=roll" in plan.describe()
    plan = prog.compile((65536, 7000), jnp.float32, boundary="reflect")
    assert plan.mode == "fused" and plan.shift == "slice"
    assert "shift=slice" in plan.describe()
    plan = _CROSS2.repeat(2).compile((64, 2), jnp.float32, boundary="reflect")
    assert plan.mode == "reference" and "shift=" not in plan.describe()


def test_single_sweep_boundary_family_dispatch(pallas_interpret):
    """ops.stencil2d now routes every boundary mode through the kernel."""
    s = st.fd_laplacian(1)
    x = rand((33, 20))
    for boundary in BOUNDARIES:
        got = ops.stencil2d(x, s.offsets, s.weights, boundary=boundary)
        want = ref.stencil2d(x, s.offsets, s.weights, boundary=boundary)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_program_rejects_bad_inputs():
    prog = st.fd_laplacian(1).repeat(2)
    with pytest.raises(ValueError, match="2-D"):
        prog(jnp.zeros((4, 4, 4), jnp.float32))
    with pytest.raises(ValueError, match="k >= 1"):
        prog.repeat(0)
    with pytest.raises(ValueError, match="boundary"):
        prog.compile((32, 32), jnp.float32, boundary="sideways")


def test_kernel_rejects_bad_block_rows():
    x = rand((64, 32))
    with pytest.raises(ValueError, match="block_rows"):
        st_k.stencil2d_pipeline(
            x, ((_lap, 1),) * 4, block_rows=2, interpret=True
        )


def test_shift_beyond_stage_radius_raises():
    x = rand((32, 32))

    def too_far(shift):
        return shift(2, 0)

    with pytest.raises(ValueError, match="exceeds stage radius"):
        st_k.stencil2d_pipeline(x, ((too_far, 1),), interpret=True)
