"""IndexPlan engine: block -> route -> cache (DESIGN.md §4).

Covers the acceptance surface of the index-set engine:
* oracle equivalence for the blocked masked gather, the capacity scatter,
  and the fused gather+weighted-combine — sentinel indices, contiguous-run
  inputs (the run-detection fast path), ragged/odd row counts and C,
  zero-size tables, fp32 + bf16;
* the MoE sort path lowers to exactly TWO `pallas_call`s (blocked
  dispatch gather + fused combine) with no sentinel-row concatenate in
  the jaxpr, and the plan engine is bit-identical to the seed row-wise
  path under jit;
* eager validation of the scatter contract;
* the plan cache returns the identical plan object on repeated calls
  (mirroring test_plan_engine.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index_plan import index_plan_cache_info, plan_index_op
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)

DTYPES = [jnp.float32, jnp.bfloat16]


def rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def n_pallas_calls(fn, *args) -> int:
    """Count pallas_call eqns anywhere in the traced jaxpr (incl. nested)."""
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


# ---------------------------------------------------------------------------
# routing / planning
# ---------------------------------------------------------------------------


def test_plan_routes_and_geometry():
    p = plan_index_op((1024, 512), jnp.bfloat16, 4096, "gather", masked=True)
    assert p.mode == "blocked" and p.kernel == "gather_rows_blocked"
    assert p.grid * p.block_rows >= p.n_out == 4096
    assert p.table_rows == p.grid * p.block_rows
    c = plan_index_op((4096, 512), jnp.bfloat16, 1024, "gather_combine", top_k=2)
    assert c.kernel == "gather_combine_blocked" and c.top_k == 2
    assert "MB moved" in p.describe() and "gather" in p.describe()


def test_plan_zero_size_routes_noop():
    assert plan_index_op((16, 128), jnp.float32, 0, "gather").mode == "noop"
    assert plan_index_op((16, 0), jnp.float32, 8, "gather").mode == "noop"
    assert plan_index_op((0, 128), jnp.float32, 8, "gather", masked=True).mode == "noop"


def test_plan_validates_inputs():
    with pytest.raises(ValueError, match="semantics"):
        plan_index_op((16, 128), jnp.float32, 8, "sideways")
    with pytest.raises(ValueError, match="2-D"):
        plan_index_op((16, 128, 2), jnp.float32, 8, "gather")
    with pytest.raises(ValueError, match="top_k"):
        plan_index_op((16, 128), jnp.float32, 8, "gather", top_k=0)


def test_plan_cache_returns_identical_object():
    a = plan_index_op((256, 128), jnp.bfloat16, 512, "gather", masked=True)
    b = plan_index_op((256, 128), jnp.bfloat16, 512, "gather", masked=True)
    assert a is b
    # dtype spellings normalize to the same key
    c = plan_index_op((256, 128), np.dtype("bfloat16"), 512, "gather", masked=True)
    assert c is a
    # semantics/top_k are part of the key
    d = plan_index_op((256, 128), jnp.bfloat16, 512, "scatter", masked=True)
    assert d is not a
    before = index_plan_cache_info().hits
    plan_index_op((256, 128), jnp.bfloat16, 512, "gather", masked=True)
    assert index_plan_cache_info().hits == before + 1


# ---------------------------------------------------------------------------
# blocked gather: oracle equivalence
# ---------------------------------------------------------------------------

# bf16 rows travel as 8-row HBM tiles into 16-row output groups: the
# table sends rows from both 8-row halves of 16-row source spans to both
# halves of each 16-row output group, with duplicates and sentinels, and
# ends in a partial group and the source's last rows.
TILE_HALVES_IDX = [
    0, 15, 8, 7, -1, 16, 23, 9, 9, 1, -1, 22, 17, 6, 14, 2,
    23, 8, 0, 0, 12, -1, 5, 19, 10, 3, 21, 11, 4, -1, 13, 18,
    7, 16, 20,
]


def tile_halves_table(n_src):
    return np.asarray(TILE_HALVES_IDX + [n_src - 1, n_src - 8, n_src - 9], np.int32)


GATHER_CASES = [
    # (n_src, C, idx builder) — sentinels, duplicates, runs, ragged sizes
    (64, 128, lambda n: RNG.integers(0, n, 64)),
    (37, 130, lambda n: RNG.integers(0, n, 101)),  # odd C, ragged n_out
    (64, 128, lambda n: np.concatenate([np.arange(n), [-1, 0, 0, n - 1]])),
    (16, 256, lambda n: np.full(40, -1)),  # all sentinels
    (200, 64, lambda n: np.arange(n)),  # pure contiguous run (fast path)
    (200, 64, lambda n: np.arange(5, 133)),  # misaligned run
    (8, 128, lambda n: RNG.integers(-1, n, 300)),  # n_out >> n_src
    (24, 256, tile_halves_table),  # whole 8-row, not 16-row, tiles
    (37, 256, tile_halves_table),  # neither
    (64, 256, tile_halves_table),
]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", range(len(GATHER_CASES)))
def test_masked_gather_matches_oracle(case, dtype, pallas_interpret):
    n_src, c, mk = GATHER_CASES[case]
    x = rand((n_src, c), dtype)
    idx = jnp.asarray(mk(n_src), jnp.int32)
    got = ops.gather_rows(x, idx, masked=True)
    want = ref.gather_rows_masked(x, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unmasked_gather_matches_take(pallas_interpret):
    x = rand((50, 160), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 50, 77), jnp.int32)
    got = ops.gather_rows(x, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[np.asarray(idx)])


def test_gather_zero_size_idx(pallas_interpret):
    x = rand((16, 128), jnp.float32)
    out = ops.gather_rows(x, jnp.zeros((0,), jnp.int32), masked=True)
    assert out.shape == (0, 128)


def test_gather_single_pallas_call(pallas_interpret):
    x = rand((64, 128), jnp.float32)
    idx = jnp.asarray(RNG.integers(-1, 64, 96), jnp.int32)
    assert n_pallas_calls(lambda a, i: ops.gather_rows(a, i, masked=True), x, idx) == 1


def test_rowwise_engine_still_available(pallas_interpret):
    x = rand((32, 128), jnp.float32)
    idx = jnp.asarray(RNG.permutation(32), jnp.int32)
    got = ops.gather_rows(x, idx, engine="rowwise")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[np.asarray(idx)])


# ---------------------------------------------------------------------------
# scatter: permutation + capacity forms, eager contract validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n,c", [(16, 128), (37, 200)])
def test_scatter_permutation_matches_oracle(n, c, dtype, pallas_interpret):
    x = rand((n, c), dtype)
    idx = jnp.asarray(RNG.permutation(n), jnp.int32)
    got = ops.scatter_rows(x, idx)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.scatter_rows(x, idx))
    )


@pytest.mark.parametrize("n,num_out", [(16, 40), (37, 64), (8, 9)])
def test_capacity_scatter_zero_fills_dropped_slots(n, num_out, pallas_interpret):
    """num_out > n (capacity scatter): unmapped rows must be zero."""
    x = rand((n, 128), jnp.float32)
    targets = np.asarray(RNG.permutation(num_out)[:n], np.int32)
    got = ops.scatter_rows(x, jnp.asarray(targets), num_out=num_out)
    want = np.zeros((num_out, 128), np.float32)
    want[targets] = np.asarray(x)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (
        n_pallas_calls(
            lambda a, i: ops.scatter_rows(a, i, num_out=num_out),
            x,
            jnp.asarray(targets),
        )
        == 1
    )


def test_scatter_contract_validated_eagerly(pallas_interpret):
    x = rand((16, 128), jnp.float32)
    with pytest.raises(ValueError, match="1-D idx"):
        ops.scatter_rows(x, jnp.zeros((16, 2), jnp.int32))
    with pytest.raises(ValueError, match="1-D idx"):
        ops.scatter_rows(x, jnp.zeros((8,), jnp.int32))  # wrong length
    with pytest.raises(ValueError, match="injective"):
        ops.scatter_rows(x, jnp.asarray(RNG.permutation(16), jnp.int32), num_out=8)


# ---------------------------------------------------------------------------
# fused gather + weighted combine
# ---------------------------------------------------------------------------

COMBINE_CASES = [
    (64, 128, 33, 2),  # ragged T
    (37, 130, 20, 3),  # odd C, odd k
    (16, 256, 50, 1),  # k = 1
    (128, 64, 8, 6),   # wide fan-in
]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n_src,c,t,k", COMBINE_CASES)
def test_gather_combine_matches_oracle(n_src, c, t, k, dtype, pallas_interpret):
    src = rand((n_src, c), dtype)
    back = jnp.asarray(RNG.integers(-1, n_src, (t, k)), jnp.int32)
    gates = jnp.asarray(RNG.standard_normal((t, k)), jnp.float32)
    got = jax.jit(ops.gather_combine)(src, back, gates)
    want = jax.jit(ref.gather_combine)(src, back, gates)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_src", [24, 37, 64])
def test_bf16_gather_combine_across_tile_halves(n_src, pallas_interpret):
    src = rand((n_src, 256), jnp.bfloat16)
    back = jnp.asarray(tile_halves_table(n_src).reshape(19, 2))
    gates = jnp.asarray(RNG.standard_normal((19, 2)), jnp.float32)
    got = jax.jit(ops.gather_combine)(src, back, gates)
    want = jax.jit(ref.gather_combine)(src, back, gates)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gather_combine_all_sentinels_is_zero(pallas_interpret):
    src = rand((16, 128), jnp.float32)
    back = jnp.full((9, 2), -1, jnp.int32)
    gates = jnp.ones((9, 2), jnp.float32)
    out = ops.gather_combine(src, back, gates)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((9, 128), np.float32))


def test_gather_combine_zero_tokens(pallas_interpret):
    src = rand((16, 128), jnp.float32)
    out = ops.gather_combine(
        src, jnp.zeros((0, 2), jnp.int32), jnp.zeros((0, 2), jnp.float32)
    )
    assert out.shape == (0, 128)


def test_gather_combine_single_pallas_call(pallas_interpret):
    src = rand((64, 128), jnp.float32)
    back = jnp.asarray(RNG.integers(-1, 64, (24, 2)), jnp.int32)
    gates = jnp.asarray(RNG.standard_normal((24, 2)), jnp.float32)
    assert n_pallas_calls(ops.gather_combine, src, back, gates) == 1


def test_gather_combine_validates_shapes(pallas_interpret):
    src = rand((16, 128), jnp.float32)
    with pytest.raises(ValueError, match="back/gates"):
        ops.gather_combine(
            src, jnp.zeros((4, 2), jnp.int32), jnp.zeros((4, 3), jnp.float32)
        )


# ---------------------------------------------------------------------------
# the MoE sort path through the engine
# ---------------------------------------------------------------------------


def _moe_setup():
    from repro import configs
    from repro.models import moe

    cfg = configs.get_config("deepseek-moe-16b-smoke")
    p = moe.moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(
        jax.random.PRNGKey(4), (2, 16, cfg.d_model), jnp.float32
    ).astype(cfg.np_dtype)
    cap = 2 * 16 * cfg.moe.top_k  # dropless
    return moe, cfg, p, x, cap


def test_moe_sort_two_pallas_calls_no_sentinel_concat(pallas_interpret):
    """Dispatch + combine must be exactly 2 kernels (blocked gather, fused
    combine) and the jaxpr must not concatenate sentinel rows."""
    moe, cfg, p, x, cap = _moe_setup()
    jaxpr = str(
        jax.make_jaxpr(lambda a: moe.moe_sort(p, cfg, a, capacity=cap)[0])(x)
    )
    assert jaxpr.count("pallas_call[") == 2
    assert jaxpr.count("concatenate") == 0


def test_moe_sort_plan_bit_identical_to_rowwise(pallas_interpret):
    moe, cfg, p, x, cap = _moe_setup()
    y_plan = jax.jit(
        lambda a: moe.moe_sort(p, cfg, a, capacity=cap, engine="plan")[0]
    )(x)
    y_row = jax.jit(
        lambda a: moe.moe_sort(p, cfg, a, capacity=cap, engine="rowwise")[0]
    )(x)
    np.testing.assert_array_equal(np.asarray(y_plan), np.asarray(y_row))


def test_moe_sort_rejects_unknown_engine():
    moe, cfg, p, x, cap = _moe_setup()
    with pytest.raises(ValueError, match="engine"):
        moe.moe_sort(p, cfg, x, engine="warp")


def test_moe_decode_capacity_is_lossless_and_tight():
    """top_k expert ids are distinct per token, so capacity == batch is
    lossless for a single decode step (the seed oversized it k-fold)."""
    from repro import configs
    from repro.models import moe

    cfg = configs.get_config("deepseek-moe-16b-smoke")
    assert moe.decode_capacity(cfg, 8) == 8
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, cfg.d_model)).astype(
        cfg.np_dtype
    )
    tight, _ = moe.moe_sort(p, cfg, x, capacity=moe.decode_capacity(cfg, 8))
    loose, _ = moe.moe_sort(p, cfg, x, capacity=8 * cfg.moe.top_k)
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(loose))
