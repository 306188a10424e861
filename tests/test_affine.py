"""Affine analytic planner: IR algebra, recognizer, closed-form tiles, ops.

Covers the acceptance surface of the affine refactor (DESIGN.md §14):
* AffineMap algebra: lift == jnp.transpose, compose . invert == identity,
  digit_split / from_window semantics, validation;
* the index-vector recognizer round-trips seeded shuffles (including
  rotated composite radixes) and refuses non-affine vectors;
* derive() reproduces the heuristic planner's tiles exactly for the
  permutation class — plans stamp `analytic` and stay the SAME object;
* the tuner's search space for affine-recognized requests is the analytic
  seed's ±1 neighborhood only (candidate count asserted), enumerated from
  the seed even when the heuristic formulas are unavailable;
* the plan_copy_tiles VMEM-shrink clamp stays sublane aligned (regression);
* the new ops (bit_reversal / strided_gather / diagonal_reorder / shuffle)
  match their jnp oracles for fp32 + bf16, ragged and zero-size shapes,
  and each compiles to exactly ONE pallas_call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affine, layout
from repro.core import rearrange as rr
from repro.core.plan import (
    _affine_tile_candidates,
    _tile_candidates,
    plan_affine,
    plan_rearrange,
)
from repro.kernels import ops, ref, reorder_nd, tiling

RNG = np.random.default_rng(11)


def rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def n_pallas_calls(fn, *args) -> int:
    """Count pallas_call eqns anywhere in the traced jaxpr (incl. nested)."""
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


# ---------------------------------------------------------------------------
# IR algebra
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape,perm",
    [
        ((3, 4), (1, 0)),
        ((2, 3, 4), (2, 0, 1)),
        ((2, 3, 4, 5), (0, 2, 1, 3)),
        ((1, 5, 1), (2, 1, 0)),
    ],
)
def test_lift_matches_transpose(shape, perm):
    amap = layout.to_affine(shape, perm)
    x = np.arange(int(np.prod(shape))).reshape(shape)
    np.testing.assert_array_equal(
        x.ravel()[amap.index_vector()], np.transpose(x, perm).ravel()
    )


@pytest.mark.parametrize(
    "make",
    [
        lambda: layout.to_affine((2, 3, 4), (2, 0, 1)),
        lambda: affine.bit_reversal_map((16, 5)),
        lambda: affine.diagonal_map((6, 8)),
        lambda: affine.shuffle_map(360, seed=3),
    ],
)
def test_compose_invert_is_identity(make):
    amap = make()
    ident = amap.compose(amap.invert())
    np.testing.assert_array_equal(ident.index_vector(), np.arange(amap.n_in))


def test_digit_split_preserves_semantics():
    amap = layout.to_affine((4, 6), (1, 0)).digit_split(0, (2, 3))
    assert amap.out_digits == (2, 3, 4)
    x = np.arange(24).reshape(4, 6)
    np.testing.assert_array_equal(
        x.ravel()[amap.index_vector()], x.T.ravel()
    )


def test_from_window_matches_sliced_transpose():
    amap = affine.AffineMap.from_window((8, 10), (2, 4), (3, 5), (1, 0))
    x = np.arange(80).reshape(8, 10)
    want = x[2:5, 4:9].T.ravel()
    np.testing.assert_array_equal(x.ravel()[amap.index_vector()], want)


def test_validation_rejects_bad_maps():
    with pytest.raises(ValueError):  # src not injective
        affine.AffineMap((2, 2), (2, 2), (0, 0), (0, 0), (0, 0), (-1, -1), (1, 1))
    with pytest.raises(ValueError):  # window exceeds radix
        affine.AffineMap.from_window((4, 4), (2, 0), (3, 4), (0, 1))
    with pytest.raises(ValueError):  # rot out of range
        affine.AffineMap((4,), (4,), (0,), (0,), (4,), (-1,), (1,))
    with pytest.raises(ValueError):  # only plain digits split
        affine.diagonal_map((4, 4)).digit_split(1, (2, 2))


# ---------------------------------------------------------------------------
# recognizer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,seed",
    [
        (12, 0),
        (360, 5),
        (1 << 10, 7),
        (3584, 1473368956),  # regression: radix-4 digit with odd rotation
        (97, 1),  # prime row count: rotation-only digit space
    ],
)
def test_recognizer_roundtrips_shuffles(n, seed):
    amap = affine.shuffle_map(n, seed=seed)
    iv = amap.index_vector()
    assert sorted(iv.tolist()) == list(range(n))
    rec = affine.recognize_index_vector(iv)
    assert rec is not None
    np.testing.assert_array_equal(rec.index_vector(), iv)


def test_recognizer_roundtrips_bit_reversal():
    iv = affine.bit_reversal_map((32,)).index_vector()
    rec = affine.recognize_index_vector(iv)
    assert rec is not None
    np.testing.assert_array_equal(rec.index_vector(), iv)


def test_recognizer_refuses_non_affine():
    idx = np.arange(64)
    idx[3], idx[17] = idx[17], idx[3]  # a lone transposition is not separable
    assert affine.recognize_index_vector(idx) is None
    bad = np.arange(16)
    bad[0] = bad[1]  # not a permutation
    assert affine.recognize_index_vector(bad) is None


# ---------------------------------------------------------------------------
# closed-form derivation == heuristic route (the SAME-object contract)
# ---------------------------------------------------------------------------

PERM_CASES = [
    ((5, 9), (1, 0)),
    ((3, 40, 50), (0, 2, 1)),
    ((8, 512, 16, 64), (0, 2, 1, 3)),
    ((4, 5, 6, 128), (2, 1, 0, 3)),
    ((7, 11, 13), (2, 1, 0)),
    ((1, 5, 1), (2, 1, 0)),
    ((0, 4, 8), (1, 0, 2)),
]


@pytest.mark.parametrize("shape,perm", PERM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plans_are_same_object_and_stamped(shape, perm, dtype):
    p1 = plan_rearrange(shape, dtype, perm)
    p2 = plan_rearrange(shape, dtype, perm)
    assert p1 is p2  # lru identity: bit-identical is free
    assert p1.plan_source in ("heuristic", "analytic")
    if 0 not in shape and 1 not in shape:
        # every clean shape must derive analytically (closed-form == routed)
        assert p1.plan_source == "analytic"


@pytest.mark.parametrize("shape,perm", [c for c in PERM_CASES if 0 not in c[0]])
def test_derive_reproduces_heuristic_tiles(shape, perm):
    plan = plan_rearrange(shape, jnp.float32, perm)
    ex = affine.derive(layout.to_affine(shape, perm), "float32", "out")
    if plan.plan_source == "analytic":
        assert (ex.mode, ex.block_r, ex.block_c, ex.block_v, ex.exec_shape) == (
            plan.mode, plan.block_r, plan.block_c, plan.block_v, plan.exec_shape
        )


def test_describe_includes_tiles_exec_and_source():
    plan = plan_rearrange((8, 512, 16, 64), jnp.float32, (0, 2, 1, 3))
    s = plan.describe()
    assert f",{plan.block_v})" in s  # vec route: block_v rides in tiles=(..)
    assert f"exec={plan.exec_shape}" in s
    assert f"source={plan.plan_source}" in s


# ---------------------------------------------------------------------------
# tuner search space: analytic seed ± 1 neighborhood only
# ---------------------------------------------------------------------------


def test_candidates_enumerate_from_seed(monkeypatch):
    """The enumerators must expand the *seed* tile, not re-run the
    heuristic formulas: with the formulas disabled the seeded calls still
    enumerate, and the seed itself is candidate 0."""

    def boom(*a, **k):
        raise AssertionError("enumerator re-ran the heuristic formula")

    monkeypatch.setattr(tiling, "plan_transpose_tiles", boom)
    monkeypatch.setattr(tiling, "plan_transpose_vec_tiles", boom)
    monkeypatch.setattr(tiling, "plan_copy_tiles", boom)
    seed = tiling.TilePlan(256, 256, 2, 2)
    cands = tiling.transpose_tile_candidates(512, 512, jnp.float32, seed)
    assert cands[0] == seed
    cands = tiling.copy_tile_candidates(512, 512, jnp.float32, seed)
    assert cands[0].block_r == 256
    vseed = tiling.VecTilePlan(64, 64, 128, 8, 8, 1)
    vcands = tiling.vec_tile_candidates(512, 512, 128, jnp.float32, vseed)
    assert vcands[0] == vseed


@pytest.mark.parametrize("shape,perm", [c for c in PERM_CASES if 0 not in c[0]])
def test_search_space_is_seed_neighborhood(shape, perm):
    """Affine-recognized requests search only the analytic seed's ±1
    neighborhood: <= 3x3 tile pairs per grid-walk order."""
    plan = plan_rearrange(shape, jnp.float32, perm)
    if plan.mode == "identity":
        return
    cands = _tile_candidates(plan, shape, "float32", "out")
    orders = {dict(c.params)["grid_order"] for c in cands}
    assert len(cands) <= 9 * len(orders)
    assert dict(cands[0].params)["block_r"] == plan.block_r
    assert dict(cands[0].params)["block_c"] == plan.block_c


@pytest.mark.parametrize(
    "make",
    [
        lambda: affine.diagonal_map((256, 384)),
        lambda: affine.shuffle_map(4096, payload=(256,), seed=9),
        lambda: affine.strided_map((64, 256), axis=0, stride=4),
    ],
)
def test_affine_search_space_is_seed_neighborhood(make):
    plan = plan_affine(make(), jnp.float32)
    cands = _affine_tile_candidates(plan, "float32")
    assert 1 <= len(cands) <= 9
    assert dict(cands[0].params)["block_r"] == plan.block_r
    assert dict(cands[0].params)["block_c"] == plan.block_c


def test_affine_tuned_seed_win_keeps_object_identity():
    amap = affine.diagonal_map((256, 384))
    base = plan_affine(amap, jnp.float32, tuned=False)
    tuned = plan_affine(amap, jnp.float32, tuned=True)
    if tuned.plan_source == "analytic":
        assert tuned is base  # seed verified: SAME object as the untuned plan
    else:
        assert tuned.plan_source == "tuned"


def test_zero_radix_is_rejected_by_the_ir():
    # zero-size arrays never reach the IR: the ops guard on x.size and
    # dispatch to the oracle, and the map constructor rejects radix 0
    with pytest.raises(ValueError):
        layout.to_affine((0, 4), (1, 0))


# ---------------------------------------------------------------------------
# plan_copy_tiles clamp regression (the VMEM-shrink must stay aligned)
# ---------------------------------------------------------------------------


def test_copy_tiles_shrink_stays_sublane_aligned():
    # bf16: sl=16; br=24 over budget halves once.  Plain //2 gave 12
    # (unaligned); the clamp floors at the sublane count.
    assert tiling.shrink_rows(24, 43691, 1_048_576, 16) == 16
    assert tiling.shrink_rows(512, 43691, 1_048_576, 16) == 16
    assert tiling.shrink_rows(512, 1024, 1_048_576, 16) == 512  # fits: no-op
    # end to end: every copy-route row block is the whole axis or aligned
    # to (at least) the sublane floor
    sl = tiling.sublanes(jnp.bfloat16)
    for rows, cols in [(100, 4096), (4096, 512), (8, 100000), (1000, 131072)]:
        tp = tiling.plan_copy_tiles(rows, cols, jnp.bfloat16)
        assert tp.block_r == rows or tp.block_r >= sl


def test_hbm_tile_rows_is_eight_for_32_and_16_bit():
    # the row gather's fetch height: XLA's 8-row HBM tile, not bf16's
    # 16-row VMEM tile
    assert tiling.hbm_tile_rows(jnp.float32) == 8
    assert tiling.hbm_tile_rows(jnp.bfloat16) == 8
    assert tiling.hbm_tile_rows(jnp.int32) == 8
    assert tiling.sublanes(jnp.bfloat16) == 16


# ---------------------------------------------------------------------------
# the ops the planner unlocks (kernels in interpret mode)
# ---------------------------------------------------------------------------

OP_DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("dtype", OP_DTYPES)
@pytest.mark.parametrize("shape,axis", [((64, 128), 0), ((8, 32, 128), 1), ((16,), 0)])
def test_bit_reversal_matches_oracle(pallas_interpret, shape, axis, dtype):
    x = rand(shape, dtype)
    got = ops.bit_reversal(x, axis=axis)
    n = shape[axis]
    bits = n.bit_length() - 1
    rev = np.array(
        [int(format(i, f"0{bits}b")[::-1], 2) for i in range(n)]
    ) if bits else np.array([0])
    want = np.take(np.asarray(x), rev, axis=axis)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_bit_reversal_rejects_non_power_of_two(pallas_interpret):
    with pytest.raises(ValueError):
        ops.bit_reversal(rand((12, 8), jnp.float32))


@pytest.mark.parametrize("dtype", OP_DTYPES)
@pytest.mark.parametrize(
    "shape,axis,stride,phase",
    [((64, 128), 0, 4, 0), ((64, 128), 0, 4, 3), ((8, 30, 128), 1, 5, 2), ((63, 130), 1, 13, 7)],
)
def test_strided_gather_matches_oracle(pallas_interpret, shape, axis, stride, phase, dtype):
    x = rand(shape, dtype)
    got = ops.strided_gather(x, stride, phase=phase, axis=axis)
    idx = [slice(None)] * len(shape)
    idx[axis] = slice(phase, None, stride)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[tuple(idx)])


@pytest.mark.parametrize("dtype", OP_DTYPES)
@pytest.mark.parametrize("shape", [(64, 128), (4, 33, 130), (5, 7)])
def test_diagonal_reorder_matches_oracle(pallas_interpret, shape, dtype):
    x = rand(shape, dtype)
    got = np.asarray(ops.diagonal_reorder(x))
    xn = np.asarray(x)
    rows, cols = shape[-2], shape[-1]
    want = np.empty_like(xn)
    for i in range(rows):
        want[..., i, :] = xn[..., i, (i + np.arange(cols)) % cols]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", OP_DTYPES)
@pytest.mark.parametrize("shape", [(4096, 256), (360, 33), (97, 8), (1000,)])
def test_shuffle_matches_oracle_and_is_seeded(pallas_interpret, shape, dtype):
    x = rand(shape, dtype)
    got = ops.shuffle(x, seed=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.shuffle(x, seed=5)))
    # bijective: sorting rows back recovers the multiset; same seed repeats
    np.testing.assert_array_equal(
        np.sort(np.asarray(got), axis=0), np.sort(np.asarray(x), axis=0)
    )
    again = ops.shuffle(x, seed=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    other = ops.shuffle(x, seed=6)
    assert not np.array_equal(np.asarray(got), np.asarray(other))


@pytest.mark.parametrize(
    "fn",
    [
        lambda x: ops.bit_reversal(x, axis=1),
        lambda x: ops.strided_gather(x, 2, axis=1),
        lambda x: ops.diagonal_reorder(x),
        lambda x: ops.shuffle(x, seed=3),
    ],
)
def test_zero_size_inputs(pallas_interpret, fn):
    x = jnp.zeros((0, 8), jnp.float32)
    out = fn(x)
    assert out.shape[0] == 0


@pytest.mark.parametrize(
    "fn,shape",
    [
        (lambda x: ops.bit_reversal(x, axis=0), (64, 128)),
        (lambda x: ops.strided_gather(x, 4, phase=1, axis=0), (64, 128)),
        (lambda x: ops.diagonal_reorder(x), (64, 128)),
        (lambda x: ops.shuffle(x, seed=2), (360, 128)),
    ],
)
def test_new_ops_are_one_pallas_call(pallas_interpret, fn, shape):
    x = rand(shape, jnp.float32)
    assert n_pallas_calls(fn, x) == 1


def test_rearrange_wrappers_delegate(pallas_interpret):
    x = rand((32, 64), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(rr.bit_reversal(x)), np.asarray(ops.bit_reversal(x))
    )
    np.testing.assert_array_equal(
        np.asarray(rr.strided_gather(x, 2)), np.asarray(ops.strided_gather(x, 2))
    )
    np.testing.assert_array_equal(
        np.asarray(rr.diagonal_reorder(x)), np.asarray(ops.diagonal_reorder(x))
    )
    np.testing.assert_array_equal(
        np.asarray(rr.shuffle(x, seed=1)), np.asarray(ops.shuffle(x, seed=1))
    )


# ---------------------------------------------------------------------------
# reorder_affine kernel vs the index-vector oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", OP_DTYPES)
@pytest.mark.parametrize(
    "make",
    [
        lambda: affine.bit_reversal_map((64, 128)),
        lambda: affine.strided_map((64, 128), axis=0, stride=4, phase=2),
        lambda: affine.diagonal_map((48, 96)),
        lambda: affine.shuffle_map(720, payload=(32,), seed=4),
        lambda: affine.AffineMap.from_window((40, 64), (8, 0), (16, 64), (0, 1)),
    ],
)
def test_reorder_affine_matches_index_vector(pallas_interpret, make, dtype):
    amap = make()
    x = rand(amap.in_digits, dtype)
    got = reorder_nd.reorder_affine(x, amap, interpret=True)
    want = np.asarray(x).ravel()[amap.index_vector()].reshape(amap.out_digits)
    np.testing.assert_array_equal(np.asarray(got), want)
