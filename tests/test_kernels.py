"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes (deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import copy as copy_k
from repro.kernels import gather_scatter as gs_k
from repro.kernels import interlace as il_k
from repro.kernels import permute3d as p3_k
from repro.kernels import ref
from repro.kernels import reorder_nd as rnd_k
from repro.kernels import stencil2d as st_k

RNG = np.random.default_rng(42)

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8]


def rand(shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(RNG.integers(-100, 100, shape), dtype)
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# §III-A copy / ranged / index-set
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(8, 128), (64, 256), (33, 130), (3, 17, 256)])
def test_copy(shape, dtype):
    x = rand(shape, dtype)
    out = copy_k.copy(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("start,size", [(0, 8), (7, 20), (40, 24)])
def test_copy_range(start, size):
    x = rand((64, 256), jnp.float32)
    out = copy_k.copy_range(x, jnp.int32(start), size, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x)[start : start + size])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,c", [(16, 128), (37, 200), (64, 384)])
def test_gather_scatter_rows(n, c, dtype):
    x = rand((n, c), dtype)
    idx = jnp.asarray(RNG.permutation(n), jnp.int32)
    g = gs_k.gather_rows(x, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(x)[np.asarray(idx)])
    s = gs_k.scatter_rows(x, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(s)[np.asarray(idx)], np.asarray(x))


def test_gather_rows_with_duplicates():
    x = rand((16, 128), jnp.float32)
    idx = jnp.asarray([0, 0, 3, 15, 3, 1, 1, 1], jnp.int32)
    g = gs_k.gather_rows(x, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(x)[np.asarray(idx)])


def _masked_take(x, idx):
    safe = np.clip(idx, 0, x.shape[0] - 1)
    return np.where((idx >= 0)[:, None], np.asarray(x)[safe], 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_r", [1, 4, 8, 64])
def test_gather_rows_blocked_vs_oracle(block_r, dtype):
    """Blocked masked gather across block sizes: sentinels, duplicates,
    and a contiguous run that exercises the run-detection fast path."""
    x = rand((40, 130), dtype)
    idx = jnp.asarray(
        list(range(8, 24)) + [-1, 0, 0, 39, -7, 5] + list(range(10)), jnp.int32
    )
    got = gs_k.gather_rows_blocked(x, idx, block_r=block_r, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), _masked_take(x, np.asarray(idx))
    )


def test_gather_rows_blocked_pure_run_fast_path():
    """A fully contiguous table must hit the single-block-copy path and
    stay exact (same result as the row-by-row path)."""
    x = rand((64, 128), jnp.float32)
    idx = jnp.arange(64, dtype=jnp.int32)
    got = gs_k.gather_rows_blocked(x, idx, block_r=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    # misaligned run start
    idx2 = jnp.arange(5, 37, dtype=jnp.int32)
    got2 = gs_k.gather_rows_blocked(x, idx2, block_r=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(x)[5:37])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,k,block_t", [(23, 3, 8), (16, 2, 16), (7, 1, 4)])
def test_gather_combine_blocked_vs_oracle(t, k, block_t, dtype):
    src = rand((37, 130), dtype)
    back = jnp.asarray(RNG.integers(-1, 37, (t, k)), jnp.int32)
    gates = jnp.asarray(RNG.standard_normal((t, k)), jnp.float32)
    got = gs_k.gather_combine_blocked(
        src, back, gates, block_t=block_t, interpret=True
    )
    want = jax.jit(ref.gather_combine)(src, back, gates)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6, atol=1e-2,
    )


# ---------------------------------------------------------------------------
# §III-B permute / reorder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,r,c", [(3, 40, 257), (1, 128, 128), (5, 7, 9)])
def test_transpose2d_batched(b, r, c, dtype):
    x = rand((b, r, c), dtype)
    out = p3_k.transpose2d_batched(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.swapaxes(np.asarray(x), 1, 2))


def test_transpose_diagonal_walk():
    x = rand((2, 300, 400), jnp.float32)
    out = p3_k.transpose2d_batched(x, diagonal=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.swapaxes(np.asarray(x), 1, 2))


ALL_3D_PERMS = [(0, 1, 2), (0, 2, 1), (1, 0, 2), (1, 2, 0), (2, 0, 1), (2, 1, 0)]


@pytest.mark.parametrize("perm", ALL_3D_PERMS)
def test_permute3d_all_orders(perm):
    x = rand((6, 24, 136), jnp.float32)
    out = rnd_k.permute_nd(x, perm, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.transpose(np.asarray(x), perm))


@pytest.mark.parametrize(
    "shape,perm",
    [
        ((4, 6, 8, 130), (2, 0, 3, 1)),
        ((4, 6, 8, 130), (1, 0, 2, 3)),
        ((3, 4, 5, 6, 7), (4, 2, 0, 3, 1)),
        ((2, 3, 4, 5, 6, 7), (5, 0, 4, 1, 3, 2)),
        ((8, 16, 131), (0, 1, 2)),
        ((6, 256), (1, 0)),
    ],
)
def test_permute_nd(shape, perm):
    x = rand(shape, jnp.float32)
    out = rnd_k.permute_nd(x, perm, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.transpose(np.asarray(x), perm))


@pytest.mark.parametrize("grid_order", ["in", "out"])
def test_permute_grid_order_policies(grid_order):
    x = rand((4, 5, 6, 64), jnp.float32)
    out = rnd_k.permute_nd(x, (2, 0, 3, 1), grid_order=grid_order, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.transpose(np.asarray(x), (2, 0, 3, 1))
    )


# ---------------------------------------------------------------------------
# §III-C interlace / de-interlace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 3, 4, 5, 8, 9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interlace_roundtrip(n, dtype):
    arrays = tuple(rand((512,), dtype) for _ in range(n))
    il = il_k.interlace(arrays, interpret=True)
    expect = np.stack([np.asarray(a) for a in arrays], -1).reshape(-1)
    np.testing.assert_array_equal(np.asarray(il), expect)
    back = il_k.deinterlace(il, n, interpret=True)
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# §III-D stencil
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", [1, 2, 3, 4])
@pytest.mark.parametrize("shape", [(64, 128), (50, 130), (33, 257)])
def test_fd_stencil_orders(order, shape):
    x = rand(shape, jnp.float32)
    offs, wts = ref.fd_stencil_offsets(order)
    got = st_k.stencil2d(x, offs, wts, interpret=True)
    want = ref.stencil2d(x, offs, wts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_stencil_functor_nonlinear():
    x = rand((48, 128), jnp.float32)

    def maxpool_like(shift):
        return jnp.maximum(
            jnp.maximum(shift(0, 0), shift(0, 1)), jnp.maximum(shift(1, 0), shift(1, 1))
        )

    got = st_k.stencil2d_functor(x, maxpool_like, 1, interpret=True)
    want = ref.stencil2d_functor(x, maxpool_like, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_stencil_block_rows_sweep():
    x = rand((64, 128), jnp.float32)
    offs, wts = ref.fd_stencil_offsets(2)
    want = ref.stencil2d(x, offs, wts)
    for br in (8, 16, 32, 64):
        got = st_k.stencil2d(x, offs, wts, block_rows=br, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused flash attention kernel (hillclimb #1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,causal",
    [(2, 4, 2, 128, 128, True), (2, 4, 1, 64, 160, False), (1, 2, 2, 100, 100, True)],
)
def test_flash_kernel_vs_exact(b, hq, hkv, sq, skv, causal):
    from repro.kernels import flash

    d = 32
    q = rand((b, hq, sq, d), jnp.float32)
    k = rand((b, hkv, skv, d), jnp.float32)
    v = rand((b, hkv, skv, d), jnp.float32)
    out = flash.flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=64, interpret=True
    )
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k)
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        logits = jnp.where(mask, logits, -1e30)
    want = jnp.einsum(
        "bhgqk,bhkd->bhgqd", jax.nn.softmax(logits, -1), v
    ).reshape(b, hq, sq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_flash_kernel_model_path(monkeypatch):
    """Model attention routed through the fused kernel == jnp flash path."""
    from repro.models import attention as attn

    q = rand((1, 4, 64, 32), jnp.float32)
    k = rand((1, 2, 64, 32), jnp.float32)
    v = rand((1, 2, 64, 32), jnp.float32)
    base = attn.flash_attention(q, k, v, causal=True, chunk=32)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    fused = attn.flash_attention(q, k, v, causal=True, chunk=32)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base), rtol=2e-4, atol=2e-4)


def test_flash_dma_accounting():
    from repro.kernels import flash

    got = flash.dma_bytes(1, 8, 2, 1024, 1024, 128, 2, block_q=512, block_k=512)
    # nq=nk=2: q 8*2*2*512*128*2, kv 2x, o 8*2*512*128*2
    assert got == (8 * 4 * 512 * 128 * 2) + 2 * (8 * 4 * 512 * 128 * 2) + 8 * 2 * 512 * 128 * 2


@pytest.mark.parametrize("s,bq", [(128, 32), (96, 32), (160, 64)])
def test_flash_triangular_matches_rectangular(s, bq):
    """Triangular-grid causal flash (half the K/V DMA) is bit-exact vs the
    rectangular grid."""
    from repro.kernels import flash

    q = rand((2, 4, s, 32), jnp.float32)
    k = rand((2, 2, s, 32), jnp.float32)
    v = rand((2, 2, s, 32), jnp.float32)
    tri = flash.flash_attention_triangular(q, k, v, block_q=bq, block_k=bq, interpret=True)
    rect = flash.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bq, interpret=True)
    np.testing.assert_array_equal(np.asarray(tri), np.asarray(rect))
