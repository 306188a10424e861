"""Shared model building blocks: norms, embeddings, RoPE, initializers.

Parameters are plain nested dicts of jax.Arrays; every init function has a
matching ``*_pspec`` producing the logical PartitionSpec tree (resolved
against the mesh by ``repro.sharding.partition``).  RoPE uses the planar
half-split from ``repro.core.rearrange`` — a §III-C de-interlace pattern.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import rearrange as rr

Array = jax.Array


def feinsum(eq: str, a: Array, b: Array) -> Array:
    """einsum with fp32 accumulation.  On TPU this is the MXU-native
    bf16-in/f32-out dot (preferred_element_type); the CPU backend cannot
    execute some of those thunks, so inputs are upcast there instead.
    ``REPRO_BF16_DOT=1`` forces the TPU form regardless of backend — the
    dry-run sets it so the lowered HLO is TPU-faithful."""
    if os.environ.get("REPRO_BF16_DOT") == "1" or jax.default_backend() == "tpu":
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


@jax.custom_vjp
def bf16_grads(x: Array) -> Array:
    """Identity forward; casts the cotangent to bf16.

    Measured in the dry run (a refuted hypothesis): inserting
    this after the TP projections does NOT shrink the fp32 all-reduces in
    the qwen2 lowering — those reductions are *forward-side* dot outputs
    that XLA reduces in accumulator precision before the bf16 convert.
    Kept as a utility (useful where genuinely fp32 cotangents arise).
    """
    return x


def _bf16_grads_fwd(x):
    return x, None


def _bf16_grads_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


bf16_grads.defvjp(_bf16_grads_fwd, _bf16_grads_bwd)


#: names accepted by :func:`remat_policy` (SNIPPETS Snippet 2 convention).
REMAT_POLICIES = (
    "nothing_saveable",
    "dots_saveable",
    "dots_with_no_batch_dims_saveable",
    "everything_saveable",
)


def remat_policy(name: str | None):
    """Resolve a remat-policy name to a ``jax.checkpoint`` policy callable.

    ``None`` / ``"none"`` / ``"nothing_saveable"`` map to ``None`` — the
    ``jax.checkpoint`` default, which saves nothing and recomputes the whole
    block on the backward pass (the blockwise-parallel training default:
    peak activation memory is one chunk).  The ``dots*`` policies save
    matmul outputs (recompute only the cheap elementwise tail), and
    ``everything_saveable`` disables rematerialization while keeping the
    chunked structure.  Unknown names raise ``ValueError``.
    """
    if name in (None, "none", "nothing_saveable"):
        return None
    pols = jax.checkpoint_policies
    table = {
        "dots_saveable": pols.dots_saveable,
        "dots_with_no_batch_dims_saveable": pols.dots_with_no_batch_dims_saveable,
        "everything_saveable": pols.everything_saveable,
    }
    if name not in table:
        raise ValueError(
            f"unknown remat policy {name!r}; expected one of {REMAT_POLICIES}"
        )
    return table[name]


def truncated_normal_init(key, shape, scale: float, dtype) -> Array:
    stddev = scale / max(1.0, (shape[-2] if len(shape) > 1 else shape[-1])) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def layernorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: dict, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def norm_init(kind: str, d: int) -> dict:
    return rmsnorm_init(d) if kind == "rmsnorm" else layernorm_init(d)


def apply_norm(kind: str, params: dict, x: Array) -> Array:
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ---------------------------------------------------------------------------
# rotary position embedding (planar convention)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, D) with positions (..., S) or (S,).  Planar half-split
    rotation — the de-interlace pattern of paper §III-C."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = rr.rope_halves(x)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    y1 = x1f * cos - x2f * sin
    y2 = x2f * cos + x1f * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoidal_pos(positions: Array, d: int) -> Array:
    """Classic sinusoidal absolute position embedding, (..., S, D)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10000.0) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype) -> dict:
    return {"tok": truncated_normal_init(key, (vocab, d), 1.0, dtype)}


def embed(params: dict, tokens: Array) -> Array:
    return jnp.take(params["tok"], tokens, axis=0)


def unembed(params: dict, x: Array, head: Array | None = None) -> Array:
    """Logits: tied (embed.T) or separate lm_head (D, V)."""
    w = params["tok"].T if head is None else head
    return jnp.einsum("...d,dv->...v", x, w, preferred_element_type=jnp.float32)
