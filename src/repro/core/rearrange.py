"""Public rearrangement API (the paper's library surface, §III).

Every entry point accepts either numpy-convention permutations or the
paper's fastest-first ``order`` vectors, and dispatches through
``repro.kernels.ops`` (Pallas on TPU, fused-XLA oracle elsewhere).  Each
permute-shaped call routes through the plan engine (`core/plan.py`):
collapse adjacent axes -> route to the cheapest kernel -> cached plan.

Model-facing fused helpers (`split_qkv`, `split_heads`, `space_to_depth`,
`rope_halves`, ...) make the kernels first-class citizens of the training
framework — see DESIGN.md §4 for the mapping.  The reshape halves of each
helper fold into the plan's canonical shape (metadata-only merges of a
contiguous array), so every helper lowers to a SINGLE kernel invocation —
never a materialized reshape intermediate.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import layout
from repro.core.plan import plan_rearrange
from repro.kernels import ops

Array = jax.Array

# ---------------------------------------------------------------------------
# §III-B permute / reorder
# ---------------------------------------------------------------------------


def permute(x: Array, perm: Sequence[int]) -> Array:
    """out = transpose(x, perm), numpy convention."""
    return ops.permute(x, tuple(perm))


def permute_order(x: Array, order: Sequence[int]) -> Array:
    """Paper convention: ``order`` lists input dims fastest-first for the
    output (row-major linearized storage, paper §III-B)."""
    return ops.permute(x, layout.paper_order_to_perm(order))


def reorder(
    x: Array,
    out_order: Sequence[int],
    *,
    base: Sequence[int] | None = None,
    sizes: Sequence[int] | None = None,
) -> Array:
    """Generic N->M reorder, paper convention.  ``out_order`` lists the
    input dims (paper numbering, fastest-first) appearing in the output;
    dims not listed are fixed at ``base`` with window size 1."""
    nd = x.ndim
    # paper dim k <-> numpy axis nd-1-k
    kept_np = [nd - 1 - k for k in out_order]
    perm = tuple(reversed(kept_np))  # slowest-first for numpy
    return ops.reorder_nm(x, perm, base=base, sizes=sizes)


def transpose(x: Array) -> Array:
    """2-D transpose (paper's [1 0] permute)."""
    if x.ndim != 2:
        raise ValueError(f"transpose wants 2-D, got {x.shape}")
    return ops.permute(x, (1, 0))


# ---------------------------------------------------------------------------
# affine ops (DESIGN.md §14): requests the analytic planner recognizes
# beyond plain permutations — each ONE kernel pass, no index tables
# ---------------------------------------------------------------------------


def bit_reversal(x: Array, *, axis: int = 0) -> Array:
    """Bit-reversal reorder along ``axis`` (FFT layouts); the axis length
    must be a power of two."""
    return ops.bit_reversal(x, axis=axis)


def strided_gather(x: Array, stride: int, *, phase: int = 0, axis: int = 0) -> Array:
    """Strided window gather ``x[..., phase::stride, ...]`` along ``axis``."""
    return ops.strided_gather(x, stride, phase=phase, axis=axis)


def diagonal_reorder(x: Array) -> Array:
    """Skewed-diagonal reorder of the trailing plane:
    ``out[..., i, j] = x[..., i, (i + j) % C]``."""
    return ops.diagonal_reorder(x)


def shuffle(x: Array, seed: int = 0) -> Array:
    """Table-free seeded bijective row shuffle (epoch shuffling,
    ROADMAP item 3): same seed, same permutation, no index table in HBM."""
    return ops.shuffle(x, seed)


# ---------------------------------------------------------------------------
# §III-C interlace / de-interlace (axis-generalized)
# ---------------------------------------------------------------------------


def interlace(arrays: Sequence[Array]) -> Array:
    """n same-shape arrays -> one array with the last axis interleaved:
    out[..., j*n + k] = arrays[k][..., j].  N-D flattening happens inside
    the op (metadata-only), so this is a single kernel pass."""
    return ops.interlace(list(arrays))


def deinterlace(x: Array, n: int) -> list[Array]:
    """Inverse of :func:`interlace` along the last axis (single kernel)."""
    return ops.deinterlace(x, n)


# ---------------------------------------------------------------------------
# framework-facing fused helpers (DESIGN.md §4)
# ---------------------------------------------------------------------------


def split_qkv(
    qkv: Array, n_q_heads: int, n_kv_heads: int, head_dim: int
) -> tuple[Array, Array, Array]:
    """De-interlace a fused QKV projection (..., (Hq+2*Hkv)*D) into
    q (..., Hq*D), k (..., Hkv*D), v (..., Hkv*D).  The fused layout is
    block-concatenated (the common convention), so this is a ranged read."""
    dq = n_q_heads * head_dim
    dkv = n_kv_heads * head_dim
    q = qkv[..., :dq]
    k = qkv[..., dq : dq + dkv]
    v = qkv[..., dq + dkv :]
    return q, k, v


def split_heads(x: Array, n_heads: int) -> Array:
    """(B, S, H*D) -> (B, H, S, D): the attention head permute.

    The leading reshape is metadata-only; the (0, 2, 1, 3) permute is the
    adjacent-swap family, so the planner routes it to ONE batched 2-D
    transpose kernel with D-deep vector elements (plan mode 'transpose')."""
    b, s, hd = x.shape
    d = hd // n_heads
    return ops.permute(x.reshape(b, s, n_heads, d), (0, 2, 1, 3))


def merge_heads(x: Array) -> Array:
    """(B, H, S, D) -> (B, S, H*D): inverse of :func:`split_heads`, the same
    single batched-transpose kernel with the trailing reshape folded away."""
    b, h, s, d = x.shape
    return ops.permute(x, (0, 2, 1, 3)).reshape(b, s, h * d)


def rope_halves(x: Array) -> tuple[Array, Array]:
    """Split the head dim into (first, second) halves for rotary embedding
    (the planar convention; the interleaved convention would be
    ``deinterlace(x, 2)`` — both are §III-C patterns)."""
    d = x.shape[-1]
    return x[..., : d // 2], x[..., d // 2 :]


def space_to_depth(img: Array, patch: int) -> Array:
    """(B, H, W, C) -> (B, H/p, W/p, p*p*C): the ViT patchify reorder —
    an N->M reorder in the paper's taxonomy (§III-B).

    The rank-6 permute collapses to canonical (B*H/p, p, W/p, p*C) with
    perm (0, 2, 1, 3) — again the swap family, so the whole patchify is a
    single batched-transpose kernel despite the two framing reshapes."""
    b, h, w, c = img.shape
    x = img.reshape(b, h // patch, patch, w // patch, patch, c)
    x = ops.permute(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(b, h // patch, w // patch, patch * patch * c)


def kv_cache_to_decode_layout(k: Array) -> Array:
    """(B, S, H, D) prefill layout -> (B, H, S, D) decode layout.
    Decode reads one (H, D) slab per new token but attends over S; keeping
    S minor-adjacent to D makes the attention matmul layout-native."""
    return ops.permute(k, (0, 2, 1, 3))


def plan(x: Array, perm: Sequence[int], *, grid_order: str = "out"):
    """Expose the (cached) planner for inspection."""
    return plan_rearrange(x.shape, x.dtype, tuple(perm), grid_order=grid_order)
