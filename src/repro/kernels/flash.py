"""Fused flash attention (Pallas TPU).

Why this kernel exists: the pure-JAX chunked attention in
``models.attention`` is *algorithmically* flash (online softmax, O(S)
memory), but XLA materializes each (Sq, chunk) logits tile to HBM between
the two dots.  At qwen2 train_4k scale that is ~30 GB of HBM traffic per
layer per device — the memory roofline term is 5x the compute term.  The
fused kernel keeps the logits tile in VMEM: HBM traffic drops to the
Q/K/V/O streams, which is what the (8,128)-tiled DMA schedule below moves
and *nothing else*.

Layout: grid (BH, nQ, nK), K innermost with VMEM scratch (m, l, acc)
carried across K steps; out written on the last K step.  GQA is handled
by the q-index -> kv-index map (bh // group).  Causal masking is applied
per-tile from program ids; fully-masked tiles short-circuit via pl.when.

``dma_bytes()`` reports the kernel's exact HBM traffic from its grid x
BlockSpec schedule — the roofline accounting used for the §Perf 'after'
numbers (deterministic, not estimated).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import cdiv, interpreted, round_up
from repro.utils.roofline import HBM_BW, movement_cost_s

NEG_INF = -1e30


def _flash_kernel(
    nk: int, bq: int, bk: int, causal: bool, q_offset: int, skv: int,
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    needed = (not causal) or (ik * bk <= q_offset + iq * bq + bq - 1)

    @pl.when(needed)
    def compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        valid = k_pos < skv
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        # zero OOB value rows: the final partial K tile reads padded HBM
        # rows whose contents are unspecified (0 * NaN would poison acc)
        v_rows = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v_clean = jnp.where(v_rows < skv, v_ref[0], jnp.zeros((), v_ref.dtype))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_clean.dtype), v_clean, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def finalize():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _flash_call(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool, q_offset: int, block_q: int, block_k: int, interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Raw rectangular-grid forward: (out, lse) with lse = m + log(l), the
    per-row softmax normalizer the recompute backward needs (fp32,
    (B, Hq, Sq))."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if 0 in (b, hq, sq, skv, d):
        return jnp.zeros_like(q), jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    nq, nk = cdiv(sq, bq), cdiv(skv, bk)

    q3 = q.reshape(b * hq, sq, d)
    k3 = k.reshape(b * hkv, skv, d)
    v3 = v.reshape(b * hkv, skv, d)

    def kv_index(bh, iq, ik):
        return (bh // g, ik, 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, nk, bq, bk, causal, q_offset, skv),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, hq, sq, d), lse.reshape(b, hq, sq)


def _ref_o_lse(q, k, v, causal, q_offset):
    """jnp (o, lse) reference — the jvp fallback for higher-order AD
    through the forward residuals.  Materializes s x s; only reachable
    when the *forward pallas call itself* is being differentiated (e.g.
    ``check_grads(order=2)`` rev-over-rev), never on the training path."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if 0 in (b, hq, sq, skv, d):
        return jnp.zeros_like(q), jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1) if g > 1 else k
    vv = jnp.repeat(v, g, axis=1) if g > 1 else v
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    )
    if causal:
        q_pos = q_offset + jnp.arange(sq)[:, None]
        k_pos = jnp.arange(skv)[None, :]
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                   vv.astype(jnp.float32))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_jvp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_call_d(q, k, v, tri, causal, q_offset, block_q, block_k, interpret):
    """(o, lse) through the Pallas forward, jvp-able: tangents fall back
    to :func:`_ref_o_lse` so rev-over-rev AD never needs a pallas jvp."""
    if tri:
        return _flash_tri_call(q, k, v, block_q, block_k, interpret)
    return _flash_call(q, k, v, causal, q_offset, block_q, block_k, interpret)


@_flash_call_d.defjvp
def _flash_call_d_jvp(tri, causal, q_offset, block_q, block_k, interpret,
                      primals, tangents):
    q, k, v = primals
    out = _flash_call_d(q, k, v, tri, causal, q_offset, block_q, block_k,
                        interpret)
    _, t = jax.jvp(
        lambda a, b2, c: _ref_o_lse(a, b2, c, causal, q_offset),
        primals, tangents,
    )
    return out, t


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, q_offset, block_q, block_k, interpret):
    return _flash_call_d(q, k, v, False, causal, q_offset, block_q, block_k,
                         interpret)[0]


def _flash_vjp_fwd(q, k, v, causal, q_offset, block_q, block_k, interpret):
    o, lse = _flash_call_d(q, k, v, False, causal, q_offset, block_q, block_k,
                           interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, q_offset, block_q, block_k, interpret, res, do):
    # backward tile is planned independently of the forward tile
    # (plan_flash_bwd, DESIGN.md §11/§13) — pass None through.
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, causal, q_offset, None, None, interpret)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked online-softmax attention over decode-layout (B, H, S, D)
    tensors, GQA-aware (Hq a multiple of Hkv); out = softmax(qk^T)v with
    optional causal masking (callers pre-scale q by 1/sqrt(d)).

    ``q_offset`` is the absolute position of q row 0 relative to k for the
    causal mask — the blockwise training path (DESIGN.md §13) runs each
    query chunk at its own static offset.  Differentiable: a custom VJP
    recomputes the probability tiles from (q, k, lse) in the Pallas
    backward kernels (:func:`flash_attention_bwd`), so no (Sq, Skv)
    attention matrix is ever materialized in either direction.
    """
    interpret = interpreted() if interpret is None else interpret
    return _flash_vjp(q, k, v, causal, q_offset, block_q, block_k, interpret)


def _flash_tri_call(
    q: jax.Array, k: jax.Array, v: jax.Array,
    block_q: int, block_k: int, interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Raw triangular-grid forward returning (out, lse)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if sq != skv:
        raise ValueError("triangular grid needs Sq == Skv")
    if 0 in (b, hq, sq, d):
        return jnp.zeros_like(q), jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if bq != bk:
        bq = bk = min(bq, bk)
    nq = cdiv(sq, bq)
    ntiles = nq * (nq + 1) // 2

    # lower-triangle walk, row-major: (0,0),(1,0),(1,1),(2,0)...
    iq_tab, ik_tab = [], []
    for i in range(nq):
        for j in range(i + 1):
            iq_tab.append(i)
            ik_tab.append(j)
    tables = jnp.array([iq_tab, ik_tab], jnp.int32)  # (2, ntiles)

    q3 = q.reshape(b * hq, sq, d)
    k3 = k.reshape(b * hkv, skv, d)
    v3 = v.reshape(b * hkv, skv, d)

    def kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
        t = pl.program_id(1)
        iq = tab_ref[0, t]
        ik = tab_ref[1, t]

        @pl.when(ik == 0)
        def init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qv = q_ref[0]
        kv = k_ref[0]
        s = jax.lax.dot_general(
            qv, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = (q_pos >= k_pos) & (k_pos < skv)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v_rows = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v_clean = jnp.where(v_rows < skv, v_ref[0], jnp.zeros((), v_ref.dtype))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_clean.dtype), v_clean, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(ik == iq)  # last tile of this q row
        def finalize():
            o_ref[0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            ).astype(o_ref.dtype)
            lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, ntiles),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, t, tab: (bh, tab[0, t], 0)),
            pl.BlockSpec((1, bk, d), lambda bh, t, tab: (bh // g, tab[1, t], 0)),
            pl.BlockSpec((1, bk, d), lambda bh, t, tab: (bh // g, tab[1, t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, t, tab: (bh, tab[0, t], 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, t, tab: (bh, tab[0, t], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * hq, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tables, q3, k3, v3)
    return out.reshape(b, hq, sq, d), lse.reshape(b, hq, sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_tri_vjp(q, k, v, block_q, block_k, interpret):
    return _flash_call_d(q, k, v, True, True, 0, block_q, block_k, interpret)[0]


def _flash_tri_vjp_fwd(q, k, v, block_q, block_k, interpret):
    o, lse = _flash_call_d(q, k, v, True, True, 0, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_tri_vjp_bwd(block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, True, 0, None, None, interpret)


_flash_tri_vjp.defvjp(_flash_tri_vjp_fwd, _flash_tri_vjp_bwd)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def flash_attention_triangular(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash with a *triangular* grid: only the nq(nq+1)/2
    lower-triangle (iq, ik) tiles are visited, so K/V DMA traffic halves
    vs the rectangular grid.  The (iq, ik) coordinates per grid step come
    from scalar-prefetched index tables — the same constant-memory
    analogue the paper uses for reorder strides (§III-B).  Requires
    Sq == Skv (self-attention).  Differentiable via the same recompute
    backward kernels as :func:`flash_attention` (the backward grid is
    rectangular with causal short-circuit — its upper-triangle tiles cost
    one predicated-off grid step each)."""
    interpret = interpreted() if interpret is None else interpret
    return _flash_tri_vjp(q, k, v, block_q, block_k, interpret)


def dma_bytes(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int, itemsize: int,
    *, block_q: int = 512, block_k: int = 512, causal: bool = True,
) -> int:
    """Exact HBM traffic of the kernel from its grid x BlockSpec schedule:
    Q loaded once per (iq, ik) visit, K/V once per visit, O once per iq.
    With causal skipping, ~half the (iq, ik) tiles load K/V only to be
    skipped — the Pallas pipeline still DMAs mapped blocks, so we count
    them (upper bound; a triangle-remapped index map would halve this)."""
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    nq, nk = cdiv(sq, bq), cdiv(skv, bk)
    q_bytes = b * hq * nq * nk * bq * d * itemsize
    kv_bytes = 2 * b * hq * nq * nk * bk * d * itemsize  # via the bh//g map
    o_bytes = b * hq * nq * bq * d * itemsize
    return q_bytes + kv_bytes + o_bytes


# ---------------------------------------------------------------------------
# flash backward pass (training hot path, DESIGN.md §13)
#
# Recompute-based: the forward saves only (o, lse); each backward tile
# rebuilds its probability block p = exp(s - lse) from (q, k) in VMEM, so
# the (Sq, Skv) matrix never exists in HBM in either direction.  Two
# kernels with transposed grids share the recompute:
#
#   dq  grid (BH, nQ, nK), K innermost: dq_iq = sum_ik ds.k     (row carry)
#   dkv grid (BH, nK, nQ), Q innermost: dk_ik = sum_iq ds^T.q,
#                                       dv_ik = sum_iq p^T.do   (col carry)
#
# with ds = p * (do.v^T - delta), delta = rowsum(do * o) (precomputed in
# fp32 outside the kernels — O(S.D) elementwise, no s x s).  GQA: dk/dv
# are produced per *query* head and group-summed outside — an output block
# indexed bh//g would be revisited across non-adjacent grid steps, which
# the Pallas output-accumulation contract forbids.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    nk: int, bq: int, bk: int, causal: bool, q_offset: int, sq: int, skv: int,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = (not causal) or (ik * bk <= q_offset + iq * bq + bq - 1)

    @pl.when(needed)
    def compute():
        # zero every OOB row before the dots: partial-tile HBM padding is
        # unspecified and 0 * NaN would poison the accumulators
        q_rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_rows = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        q = jnp.where(q_rows < sq, q_ref[0], jnp.zeros((), q_ref.dtype))
        do = jnp.where(q_rows < sq, do_ref[0], jnp.zeros((), do_ref.dtype))
        k = jnp.where(k_rows < skv, k_ref[0], jnp.zeros((), k_ref.dtype))
        v = jnp.where(k_rows < skv, v_ref[0], jnp.zeros((), v_ref.dtype))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        q_idx = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = (q_idx < sq) & (k_pos < skv)
        if causal:
            valid = valid & (q_offset + q_idx >= k_pos)
        lse = lse_ref[0]  # (bq, 1) fp32
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        delta = jnp.where(q_rows < sq, delta_ref[0], 0.0)  # (bq, 1)
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    nq: int, bq: int, bk: int, causal: bool, q_offset: int, sq: int, skv: int,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (not causal) or (q_offset + iq * bq + bq - 1 >= ik * bk)

    @pl.when(needed)
    def compute():
        q_rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_rows = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        q = jnp.where(q_rows < sq, q_ref[0], jnp.zeros((), q_ref.dtype))
        do = jnp.where(q_rows < sq, do_ref[0], jnp.zeros((), do_ref.dtype))
        k = jnp.where(k_rows < skv, k_ref[0], jnp.zeros((), k_ref.dtype))
        v = jnp.where(k_rows < skv, v_ref[0], jnp.zeros((), v_ref.dtype))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        q_idx = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = (q_idx < sq) & (k_pos < skv)
        if causal:
            valid = valid & (q_offset + q_idx >= k_pos)
        lse = lse_ref[0]
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.where(q_rows < sq, delta_ref[0], 0.0)
        ds = p * (dp - delta)
        # contract over the q rows (axis 0 of both operands) -> (bk, d)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def finalize():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention_bwd(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,
    o: jax.Array,  # forward output (B, Hq, Sq, D)
    lse: jax.Array,  # forward log-sum-exp (B, Hq, Sq) fp32
    do: jax.Array,  # output cotangent (B, Hq, Sq, D)
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Recompute-based flash backward: (dq, dk, dv) from the forward
    residuals (o, lse) in two Pallas kernels with transposed grids.

    Tile geometry (``block_q`` x ``block_k``) defaults to the
    :func:`plan_flash_bwd` plan — heuristic or autotuned per ``REPRO_TUNE``
    exactly like the split-KV decode tile (DESIGN.md §11/§13).  GQA dk/dv
    are accumulated per query head in fp32 and group-summed outside the
    kernels (a ``bh // g`` output block would be revisited across
    non-adjacent grid steps).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if 0 in (b, hq, sq, skv, d):
        return jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
    g = hq // hkv
    if block_q is None or block_k is None:
        plan = plan_flash_bwd(b, hq, hkv, sq, skv, d, q.dtype, causal=causal)
        block_q = plan.block_q if block_q is None else block_q
        block_k = plan.block_k if block_k is None else block_k
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    nq, nk = cdiv(sq, bq), cdiv(skv, bk)
    interpret = interpreted() if interpret is None else interpret

    q3 = q.reshape(b * hq, sq, d)
    k3 = k.reshape(b * hkv, skv, d)
    v3 = v.reshape(b * hkv, skv, d)
    do3 = do.reshape(b * hq, sq, d)
    lse2 = lse.reshape(b * hq, sq, 1)
    # delta = rowsum(do * o): O(S.D) elementwise in fp32, never s x s
    delta2 = (
        (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    ).reshape(b * hq, sq, 1)

    dq3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, nk, bq, bk, causal, q_offset, sq, skv
        ),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q3, k3, v3, do3, lse2, delta2)

    dkh, dvh = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, nq, bq, bk, causal, q_offset, sq, skv
        ),
        grid=(b * hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ik, iq: (bh, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hq, skv, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3, do3, lse2, delta2)

    dq = dq3.reshape(b, hq, sq, d)
    dk = dkh.reshape(b, hkv, g, skv, d).sum(axis=2).astype(k.dtype)
    dv = dvh.reshape(b, hkv, g, skv, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_bwd(q, k, v, o, lse, do, causal, q_offset, block_q, block_k, interpret):
    """The backward map as a differentiable primitive: first-order grads
    come from the Pallas kernels; differentiating *this* function (rev-
    over-rev, e.g. ``check_grads(order=2)``) falls back to the jnp
    reference VJP below, which recomputes everything from (q, k, v, do) —
    test-scale only, it materializes s x s."""
    return flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_bwd_fwd(q, k, v, o, lse, do, causal, q_offset, block_q, block_k, interpret):
    out = _flash_bwd(q, k, v, o, lse, do, causal, q_offset, block_q, block_k, interpret)
    return out, (q, k, v, o, lse, do)


def _flash_bwd_bwd(causal, q_offset, block_q, block_k, interpret, res, cts):
    # Second-order cotangents via the naive ref.attention VJP-of-VJP: the
    # reference recomputes o and lse from (q, k, v) internally, so its AD
    # carries the TOTAL derivative — the o/lse residual inputs get zero
    # cotangents to avoid double counting.
    q, k, v, o, lse, do = res
    from repro.kernels import ref as _ref

    def grads(qq, kk, vv, dd):
        _, vjp = jax.vjp(
            lambda a, b2, c: _ref.attention(
                a, b2, c, causal=causal, q_offset=q_offset
            ),
            qq, kk, vv,
        )
        return vjp(dd)

    _, vjp2 = jax.vjp(grads, q, k, v, do)
    gq, gk, gv, gdo = vjp2(tuple(cts))
    return gq, gk, gv, jnp.zeros_like(o), jnp.zeros_like(lse), gdo


_flash_bwd.defvjp(_flash_bwd_fwd, _flash_bwd_bwd)


def bwd_dma_bytes(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int, itemsize: int,
    *, block_q: int = 512, block_k: int = 512, causal: bool = True,
) -> int:
    """Exact HBM traffic of the backward sweep from its grid x BlockSpec
    schedules: both kernels stream (q, do) blocks + (lse, delta) fp32 rows
    + (k, v) blocks once per (iq, ik) visit; dq is written once per
    (bh, iq) block, dk/dv once per (bh, ik) block in fp32 (group-summed
    outside); plus the delta precompute (do, o read once, delta written).
    Causal predication skips the compute of upper-triangle tiles but the
    pipeline still DMAs mapped blocks — counted, same contract as
    :func:`dma_bytes`."""
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    nq, nk = cdiv(sq, bq), cdiv(skv, bk)
    visits = b * hq * nq * nk
    per_visit = (
        2 * bq * d * itemsize  # q + do blocks
        + 2 * bq * 4  # lse + delta fp32 rows
        + 2 * bk * d * itemsize  # k + v blocks (via the bh//g map)
    )
    dq_out = b * hq * nq * bq * d * itemsize
    dkv_out = 2 * b * hq * nk * bk * d * 4  # per-query-head fp32 partials
    delta_pre = 2 * b * hq * sq * d * itemsize + b * hq * sq * 4
    return 2 * visits * per_visit + dq_out + dkv_out + delta_pre


@dataclass(frozen=True)
class FlashBwdPlan:
    """Cached backward tile decision for one flash-attention shape.

    Mirrors :class:`DecodePlan` (DESIGN.md §11): frozen, memoized on the
    static shape key, carrying the deterministic traffic accounting so
    benchmarks compare achieved vs predicted movement for the backward
    sweep too."""

    block_q: int  # query rows per backward tile
    block_k: int  # key rows per backward tile
    grid_dq: tuple  # (B*Hq, nQ, nK) — dq kernel, K innermost
    grid_dkv: tuple  # (B*Hq, nK, nQ) — dk/dv kernel, Q innermost
    bytes_moved: int  # both kernels + delta precompute
    roofline_s: float  # bytes / HBM bandwidth (one chip)

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        return (
            f"flash_bwd: block_q={self.block_q} block_k={self.block_k} "
            f"grid_dq={self.grid_dq} grid_dkv={self.grid_dkv} "
            f"{self.bytes_moved/1e6:.2f} MB moved, "
            f"roofline {self.roofline_s*1e6:.1f} us"
        )


def _bwd_heuristic(sq: int, skv: int) -> tuple[int, int]:
    """Default backward tile: the forward's 512-row blocks clamped to the
    sequence — big enough to amortize the per-tile recompute dot, small
    enough that (q, k, v, do) tiles + two fp32 accumulators fit VMEM."""
    return min(512, round_up(sq, 8)), min(512, round_up(skv, 8))


def _bwd_candidates(b, hq, hkv, sq, skv, d, itemsize, causal):
    """The backward search space: the heuristic (block_q, block_k) tile
    first (tie-break contract), then the half/double neighbors."""
    from repro.core import tune

    base_bq, base_bk = _bwd_heuristic(sq, skv)
    pairs = [(base_bq, base_bk)]
    for bq in (base_bq // 2, base_bq, base_bq * 2):
        for bk in (base_bk // 2, base_bk, base_bk * 2):
            bq_c = max(8, min(round_up(bq, 8), round_up(sq, 8)))
            bk_c = max(8, min(round_up(bk, 8), round_up(skv, 8)))
            if (bq_c, bk_c) not in pairs:
                pairs.append((bq_c, bk_c))
    cands = []
    for bq, bk in pairs:
        steps = 2 * b * hq * cdiv(sq, min(bq, sq)) * cdiv(skv, min(bk, skv))
        cands.append(
            tune.Candidate(
                label=f"bq{bq}_bk{bk}",
                params=(("block_q", bq), ("block_k", bk)),
                cost_s=movement_cost_s(
                    bwd_dma_bytes(
                        b, hq, hkv, sq, skv, d, itemsize,
                        block_q=bq, block_k=bk, causal=causal,
                    ),
                    steps,
                ),
            )
        )
    return cands


def _bwd_runner_factory(b, hq, hkv, sq, skv, d, dtype_name, causal):
    """Measured-mode runner: execute one candidate backward tile on
    deterministic sample tensors (forward residuals computed once)."""

    def factory(cand):
        from repro.core import tune

        p = cand.param_dict()
        q = tune.sample_array((b, hq, sq, d), dtype_name)
        k = tune.sample_array((b, hkv, skv, d), dtype_name)
        v = tune.sample_array((b, hkv, skv, d), dtype_name)
        do = tune.sample_array((b, hq, sq, d), dtype_name)
        o, lse = _flash_call(q, k, v, causal, 0, 512, 512, interpreted())
        fn = jax.jit(
            lambda q, k, v, o, lse, do: flash_attention_bwd(
                q, k, v, o, lse, do, causal=causal,
                block_q=p["block_q"], block_k=p["block_k"],
            )
        )
        return lambda: fn(q, k, v, o, lse, do)

    return factory


def _bwd_mk(b, hq, hkv, sq, skv, d, dtype_name, causal, bq, bk) -> FlashBwdPlan:
    itemsize = jnp.dtype(dtype_name).itemsize
    bq = min(bq, round_up(sq, 8))
    bk = min(bk, round_up(skv, 8))
    nq, nk = cdiv(sq, min(bq, sq)), cdiv(skv, min(bk, skv))
    bytes_moved = bwd_dma_bytes(
        b, hq, hkv, sq, skv, d, itemsize, block_q=bq, block_k=bk, causal=causal
    )
    return FlashBwdPlan(
        block_q=bq,
        block_k=bk,
        grid_dq=(b * hq, nq, nk),
        grid_dkv=(b * hq, nk, nq),
        bytes_moved=bytes_moved,
        roofline_s=bytes_moved / HBM_BW,
    )


@functools.lru_cache(maxsize=1024)
def _bwd_plan_cached(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int,
    dtype_name: str, causal: bool,
) -> FlashBwdPlan:
    bq, bk = _bwd_heuristic(sq, skv)
    return _bwd_mk(b, hq, hkv, sq, skv, d, dtype_name, causal, bq, bk)


@functools.lru_cache(maxsize=1024)
def _bwd_plan_tuned_cached(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int,
    dtype_name: str, causal: bool, mode: str,
) -> FlashBwdPlan:
    from repro.core import tune

    base = _bwd_plan_cached(b, hq, hkv, sq, skv, d, dtype_name, causal)
    itemsize = jnp.dtype(dtype_name).itemsize
    choice = tune.select(
        "flash_bwd",
        f"b={b}|hq={hq}|hkv={hkv}|sq={sq}|skv={skv}|d={d}"
        f"|dtype={dtype_name}|causal={int(causal)}",
        _bwd_candidates(b, hq, hkv, sq, skv, d, itemsize, causal),
        _bwd_runner_factory(b, hq, hkv, sq, skv, d, dtype_name, causal),
        mode=mode,
    )
    p = choice.param_dict()
    if (p["block_q"], p["block_k"]) == (base.block_q, base.block_k):
        return base  # heuristic won: tuned plan IS the untuned plan object
    return _bwd_mk(
        b, hq, hkv, sq, skv, d, dtype_name, causal, p["block_q"], p["block_k"]
    )


def plan_flash_bwd(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int, dtype,
    *, causal: bool = True, tuned: bool | None = None,
) -> FlashBwdPlan:
    """Plan (and cache) the flash backward tile for one attention shape.

    ``tuned=None`` resolves from ``REPRO_TUNE`` like every other plan
    engine: off -> the deterministic heuristic; on -> the (block_q,
    block_k) neighborhood is measured on TPU or cost-scored elsewhere via
    ``core.tune.select`` with the same lru identity guarantees (repeated
    calls return the *identical* plan object).

    Example::

        plan = plan_flash_bwd(8, 32, 8, 4096, 4096, 128, jnp.bfloat16)
        print(plan.describe())
    """
    from repro.core import tune

    if tuned is None:
        tuned = tune.tune_default()
    key = (
        int(b), int(hq), int(hkv), int(sq), int(skv), int(d),
        jnp.dtype(dtype).name, bool(causal),
    )
    if not tuned:
        return _bwd_plan_cached(*key)
    return _bwd_plan_tuned_cached(*key, tune.resolve_mode())


# ---------------------------------------------------------------------------
# split-KV decode attention (serving hot path, DESIGN.md §12)
#
# Decode reads the whole KV ring for ONE query row per head — pure memory
# bound.  The one-shot grid serializes the S axis behind a single (m, l,
# acc) carry; the split-KV grid partitions each slot's ring into splits
# computed in parallel, each keeping its own running statistics, and a
# second single-pallas_call stage folds the per-split partials with a
# mid-softmax rescale (the `_fwd_kernel_stage2_asm` shape).  GQA packs the
# G = Hq//Hkv query heads of one KV head into the sublane axis so K/V rows
# stream from HBM once per KV head instead of once per query head.
# ---------------------------------------------------------------------------


def _decode_split_kernel(
    nks: int, bk: int, s_max: int, hkv: int,
    len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
    m_ref, l_ref, acc_ref,
):
    """Stage 1: one (KV-head, split, k-block) grid step of the partial
    online softmax; per-split (m, l, acc) land in the mid arrays."""
    bh = pl.program_id(0)
    isp = pl.program_id(1)
    ik = pl.program_id(2)
    g = q_ref.shape[1]

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = jnp.minimum(len_ref[bh // hkv], s_max)
    start = (isp * nks + ik) * bk

    @pl.when(start < length)
    def compute():
        q = q_ref[0]  # (G, d), pre-scaled
        k = k_ref[0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bk)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        # zero rows past the valid length: their logits are NEG_INF so the
        # probabilities underflow to 0, but 0 * garbage must stay 0
        v_rows = start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v_clean = jnp.where(v_rows < length, v_ref[0], jnp.zeros((), v_ref.dtype))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_clean.dtype), v_clean, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nks - 1)
    def finalize():
        o_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _decode_combine_kernel(ns: int, mid_o_ref, mid_m_ref, mid_l_ref, o_ref):
    """Stage 2: fold the per-split (m, l, acc) partials with a running
    mid-softmax rescale — the `_fwd_kernel_stage2_asm` recurrence."""
    g, d = o_ref.shape[1], o_ref.shape[2]
    e_max = jnp.full((g, 1), NEG_INF, jnp.float32)
    e_sum = jnp.zeros((g, 1), jnp.float32)
    acc = jnp.zeros((g, d), jnp.float32)
    for i in range(ns):
        tv = mid_o_ref[0, i]  # (G, d) unnormalized partial
        tm = mid_m_ref[0, i]  # (G, 1) split max
        tl = mid_l_ref[0, i]  # (G, 1) split exp-sum
        n_e_max = jnp.maximum(tm, e_max)
        old_scale = jnp.exp(e_max - n_e_max)
        p = jnp.exp(tm - n_e_max)
        acc = acc * old_scale + p * tv
        e_sum = e_sum * old_scale + p * tl
        e_max = n_e_max
    o_ref[0] = (acc / jnp.maximum(e_sum, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_splits", "interpret"))
def decode_combine(
    mid_o: jax.Array,  # (BH, ns, G, d) float32
    mid_m: jax.Array,  # (BH, ns, G, 1) float32
    mid_l: jax.Array,  # (BH, ns, G, 1) float32
    *,
    num_splits: int,
    interpret: bool | None = None,
) -> jax.Array:
    """The stage-2 combine as ONE ``pallas_call`` over the (BH,) grid —
    jaxpr-assertable (tests/test_serve_engine.py) and reused verbatim by
    :func:`flash_decode`.  Returns the normalized output (BH, G, d)."""
    bh, ns, g, d = mid_o.shape
    interpret = interpreted() if interpret is None else interpret
    return pl.pallas_call(
        functools.partial(_decode_combine_kernel, num_splits),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, ns, g, d), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, ns, g, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, ns, g, 1), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, d), mid_o.dtype),
        interpret=interpret,
        name="decode_combine",
    )(mid_o, mid_m, mid_l)


@functools.partial(
    jax.jit, static_argnames=("num_splits", "block_k", "interpret")
)
def flash_decode(
    q: jax.Array,  # (B, Hq, 1, D)
    k: jax.Array,  # (B, Hkv, S_max, D) ring buffer
    v: jax.Array,
    *,
    lengths: jax.Array,  # (B,) int32 valid rows per slot
    num_splits: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Split-KV decode attention over per-slot ring buffers.

    Each slot's KV ring is partitioned into ``num_splits`` splits computed
    in parallel (grid axis 1), each carrying its own running (m, l, acc)
    statistics; :func:`decode_combine` then folds the partials with a
    mid-softmax rescale.  ``lengths`` holds the TRUE per-slot valid-row
    counts, so a slot admitted late never attends over another slot's ring
    tail (the Engine.step position bug this kernel replaces).  Tile
    geometry (``num_splits`` x ``block_k``) defaults to the
    :func:`plan_flash_decode` plan — heuristic or autotuned per
    ``REPRO_TUNE`` (DESIGN.md §11).
    """
    b, hq, sq, d = q.shape
    _, hkv, s_max, _ = k.shape
    if sq != 1:
        raise ValueError(f"flash_decode is single-token only, got Sq={sq}")
    g = hq // hkv
    if num_splits is None or block_k is None:
        plan = plan_flash_decode(b, hq, hkv, s_max, d, q.dtype)
        num_splits = plan.num_splits if num_splits is None else num_splits
        block_k = plan.block_k if block_k is None else block_k
    bk = min(block_k, s_max)
    nkb = cdiv(s_max, bk)
    ns = max(1, min(num_splits, nkb))
    nks = cdiv(nkb, ns)  # k blocks per split
    ns = cdiv(nkb, nks)  # splits actually visited
    s_pad = ns * nks * bk
    if s_pad != s_max:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad - s_max), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad - s_max), (0, 0)))

    q3 = (q * (d ** -0.5)).reshape(b * hkv, g, d)
    k3 = k.reshape(b * hkv, s_pad, d)
    v3 = v.reshape(b * hkv, s_pad, d)
    lens = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (b,)), s_max
    )

    interpret = interpreted() if interpret is None else interpret
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, ns, nks),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, isp, ik, lens: (bh, 0, 0)),
            pl.BlockSpec(
                (1, bk, d), lambda bh, isp, ik, lens: (bh, isp * nks + ik, 0)
            ),
            pl.BlockSpec(
                (1, bk, d), lambda bh, isp, ik, lens: (bh, isp * nks + ik, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bh, isp, ik, lens: (bh, isp, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bh, isp, ik, lens: (bh, isp, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bh, isp, ik, lens: (bh, isp, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    mid_o, mid_m, mid_l = pl.pallas_call(
        functools.partial(_decode_split_kernel, nks, bk, s_max, hkv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, ns, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, ns, g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(lens, q3, k3, v3)
    out = decode_combine(mid_o, mid_m, mid_l, num_splits=ns, interpret=interpret)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


@dataclass(frozen=True)
class DecodePlan:
    """Cached split-KV tile decision for one decode-attention shape.

    Mirrors the other plan engines (DESIGN.md §3/§4/§11): frozen, memoized
    on the static shape key, and carrying the deterministic traffic
    accounting so benchmarks compare achieved vs predicted movement.
    """

    num_splits: int  # parallel KV splits per slot (stage-1 grid axis)
    block_k: int  # KV rows per grid step inside a split
    grid: tuple  # (B*Hkv, num_splits, k-blocks-per-split)
    bytes_moved: int  # stage-1 + stage-2 HBM traffic
    roofline_s: float  # bytes / HBM bandwidth (one chip)

    def describe(self) -> str:
        """One-line human-readable summary (benchmarks / debugging)."""
        return (
            f"flash_decode: splits={self.num_splits} block_k={self.block_k} "
            f"grid={self.grid} {self.bytes_moved/1e6:.2f} MB moved, "
            f"roofline {self.roofline_s*1e6:.1f} us"
        )


def decode_dma_bytes(
    b: int, hq: int, hkv: int, s_max: int, d: int, itemsize: int,
    *, num_splits: int, block_k: int,
) -> int:
    """Exact HBM traffic of the two-stage split-KV schedule: K/V rows once
    per (split, k-block) visit, the G query rows re-read per grid step,
    the fp32 mid partials written by stage 1 and re-read by stage 2, and
    the final output rows."""
    g = hq // hkv
    bk = min(block_k, s_max)
    nkb = cdiv(s_max, bk)
    ns = max(1, min(num_splits, nkb))
    nks = cdiv(nkb, ns)
    ns = cdiv(nkb, nks)
    steps = b * hkv * ns * nks
    kv_bytes = 2 * steps * bk * d * itemsize
    q_bytes = steps * g * d * itemsize
    mid_bytes = 2 * b * hkv * ns * g * (d + 2) * 4  # written then re-read
    o_bytes = b * hq * d * itemsize
    return kv_bytes + q_bytes + mid_bytes + o_bytes


def _decode_candidates(b, hq, hkv, s_max, d, itemsize):
    """The split-KV search space: the heuristic (num_splits, block_k) tile
    first (tie-break contract), then the split-count and block neighbors."""
    from repro.core import tune

    base_ns, base_bk = _decode_heuristic(s_max)
    pairs = [(base_ns, base_bk)]
    for ns in (base_ns // 2, base_ns * 2, 1):
        for bk in (base_bk // 2, base_bk, base_bk * 2):
            ns_c = max(1, min(ns, cdiv(s_max, 8)))
            bk_c = max(8, min(round_up(bk, 8), round_up(s_max, 8)))
            if (ns_c, bk_c) not in pairs:
                pairs.append((ns_c, bk_c))
    cands = []
    for ns, bk in pairs:
        nkb = cdiv(s_max, bk)
        nks = cdiv(nkb, min(ns, nkb))
        ns_eff = cdiv(nkb, nks)
        steps = b * hkv * ns_eff * nks + b * hkv  # stage 1 + stage 2
        cands.append(
            tune.Candidate(
                label=f"ns{ns}_bk{bk}",
                params=(("num_splits", ns), ("block_k", bk)),
                cost_s=movement_cost_s(
                    decode_dma_bytes(
                        b, hq, hkv, s_max, d, itemsize,
                        num_splits=ns, block_k=bk,
                    ),
                    steps,
                ),
            )
        )
    return cands


def _decode_heuristic(s_max: int) -> tuple[int, int]:
    """Default tile: ~512-row splits (enough rows to amortize the per-step
    overhead) in 256-row k-blocks, clamped to the ring size."""
    bk = min(256, round_up(s_max, 8))
    ns = max(1, min(cdiv(s_max, 512), 8, cdiv(s_max, bk)))
    return ns, bk


def _decode_runner_factory(b, hq, hkv, s_max, d, dtype_name):
    """Measured-mode runner: execute one candidate tile on deterministic
    sample tensors (full-length slots — the steady-state decode shape)."""

    def factory(cand):
        from repro.core import tune

        p = cand.param_dict()
        q = tune.sample_array((b, hq, 1, d), dtype_name)
        k = tune.sample_array((b, hkv, s_max, d), dtype_name)
        v = tune.sample_array((b, hkv, s_max, d), dtype_name)
        lens = jnp.full((b,), s_max, jnp.int32)
        fn = jax.jit(
            lambda q, k, v, lens: flash_decode(
                q, k, v, lengths=lens,
                num_splits=p["num_splits"], block_k=p["block_k"],
            )
        )
        return lambda: fn(q, k, v, lens)

    return factory


@functools.lru_cache(maxsize=1024)
def _decode_plan_cached(
    b: int, hq: int, hkv: int, s_max: int, d: int, dtype_name: str
) -> DecodePlan:
    ns, bk = _decode_heuristic(s_max)
    return _decode_mk(b, hq, hkv, s_max, d, dtype_name, ns, bk)


def _decode_mk(b, hq, hkv, s_max, d, dtype_name, ns, bk) -> DecodePlan:
    itemsize = jnp.dtype(dtype_name).itemsize
    bk = min(bk, round_up(s_max, 8))
    nkb = cdiv(s_max, bk)
    ns = max(1, min(ns, nkb))
    nks = cdiv(nkb, ns)
    ns = cdiv(nkb, nks)
    bytes_moved = decode_dma_bytes(
        b, hq, hkv, s_max, d, itemsize, num_splits=ns, block_k=bk
    )
    return DecodePlan(
        num_splits=ns,
        block_k=bk,
        grid=(b * hkv, ns, nks),
        bytes_moved=bytes_moved,
        roofline_s=bytes_moved / HBM_BW,
    )


@functools.lru_cache(maxsize=1024)
def _decode_plan_tuned_cached(
    b: int, hq: int, hkv: int, s_max: int, d: int, dtype_name: str, mode: str
) -> DecodePlan:
    from repro.core import tune

    base = _decode_plan_cached(b, hq, hkv, s_max, d, dtype_name)
    itemsize = jnp.dtype(dtype_name).itemsize
    choice = tune.select(
        "flash_decode",
        f"b={b}|hq={hq}|hkv={hkv}|s={s_max}|d={d}|dtype={dtype_name}",
        _decode_candidates(b, hq, hkv, s_max, d, itemsize),
        _decode_runner_factory(b, hq, hkv, s_max, d, dtype_name),
        mode=mode,
    )
    p = choice.param_dict()
    if (p["num_splits"], p["block_k"]) == (base.num_splits, base.block_k):
        return base  # heuristic won: tuned plan IS the untuned plan object
    return _decode_mk(
        b, hq, hkv, s_max, d, dtype_name, p["num_splits"], p["block_k"]
    )


def plan_flash_decode(
    b: int, hq: int, hkv: int, s_max: int, d: int, dtype,
    *, tuned: bool | None = None,
) -> DecodePlan:
    """Plan (and cache) the split-KV decode tile for one attention shape.

    ``tuned=None`` resolves from ``REPRO_TUNE`` like every other plan
    engine: off -> the deterministic heuristic; on -> the (num_splits,
    block_k) neighborhood is measured on TPU or cost-scored elsewhere via
    ``core.tune.select`` with the same lru identity guarantees (repeated
    calls return the *identical* plan object).

    Example::

        plan = plan_flash_decode(8, 32, 8, 4096, 128, jnp.bfloat16)
        print(plan.describe())
    """
    from repro.core import tune

    if tuned is None:
        tuned = tune.tune_default()
    key = (int(b), int(hq), int(hkv), int(s_max), int(d), jnp.dtype(dtype).name)
    if not tuned:
        return _decode_plan_cached(*key)
    return _decode_plan_tuned_cached(*key, tune.resolve_mode())
