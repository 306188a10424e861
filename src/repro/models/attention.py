"""Attention: GQA flash (chunked online-softmax), block-local/SWA, cross,
and single-token decode — all pure JAX, GSPMD-shardable.

Layout engineering is where the paper's library plugs in (DESIGN.md §4):
head split/merge are §III-B permutes, the KV-cache prefill->decode layout
swap is `rearrange.kv_cache_to_decode_layout`, fused-QKV splitting is a
§III-C de-interlace.

Every head split/merge below goes through the plan engine (core/plan.py):
the (B, S, H, D)-swap family collapses to ONE batched 2-D transpose kernel
with D-deep vector elements per call — the projection reshape is folded
into the plan's canonical shape, so the hot per-layer permutes never
materialize a reshape intermediate (DESIGN.md §3-§4).

Shapes: q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D); GQA groups G = Hq // Hkv.
Softmax statistics are fp32 regardless of io dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import rearrange as rr
from repro.kernels import flash as flash_k, ops
from repro.models import common
from repro.utils.scanutil import maybe_scan

Array = jax.Array

NEG_INF = -1e30
#: query / key tile of the fused flash kernel path
KERNEL_BLOCK = 512


def _group_q(q: Array, n_kv: int) -> Array:
    b, hq, s, d = q.shape
    return q.reshape(b, n_kv, hq // n_kv, s, d)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    chunk: int = 512,
    q_offset: int = 0,
) -> Array:
    """Chunked online-softmax attention (never materializes Sq x Skv).

    ``q_offset``: absolute position of q[.., 0, :] relative to k (for
    prefill continuation / decode with cache).
    """
    b, hkv, skv, d = k.shape
    if ops.use_pallas():
        # the fused Pallas kernel (kernels/flash.py) keeps the logits tile
        # in VMEM
        return flash_k.flash_attention(
            q * (d ** -0.5), k, v, causal=causal, q_offset=q_offset,
            block_q=min(KERNEL_BLOCK, q.shape[2]), block_k=min(KERNEL_BLOCK, skv),
            interpret=ops._interpret(),
        )
    qg = _group_q(q, hkv)  # (B, Hkv, G, Sq, D)
    sq = qg.shape[3]
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    # pad KV to a chunk multiple so dynamic_slice never clamps (clamped
    # slices would double-count trailing keys); padded keys are masked.
    if n_chunks * chunk != skv:
        pad = n_chunks * chunk - skv
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = d ** -0.5

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, i):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=2)
        s_log = common.feinsum("bhgqd,bhkd->bhgqk", qg, kc) * scale
        k_pos = i * chunk + jnp.arange(chunk)
        valid = k_pos < skv
        if causal:
            valid = (q_pos[:, None] >= k_pos[None, :]) & valid[None, :]
            s_log = jnp.where(valid, s_log, NEG_INF)
        else:
            s_log = jnp.where(valid[None, :], s_log, NEG_INF)
        m_new = jnp.maximum(m, s_log.max(axis=-1))
        p = jnp.exp(s_log - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + common.feinsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vc
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full(qg.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(qg.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = maybe_scan(body, (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(q.shape).astype(q.dtype)


def flash_attention_blockwise(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    chunk: int = 512,
    q_chunk: int = 1024,
    q_offset: int = 0,
    policy=None,
) -> Array:
    """Blockwise-parallel attention (DESIGN.md §13): the query axis is cut
    into ``q_chunk`` blocks, each computed under its own ``jax.checkpoint``
    so peak activation memory is one block, not the full sequence.

    Bit-identical to :func:`flash_attention` on the same inputs: every
    block calls the same chunked online-softmax (or Pallas kernel) with a
    static per-block ``q_offset``, and — when causal — the KV stream is
    truncated to the block's last needed ``chunk`` boundary.  Truncation is
    exact, not approximate: a fully-masked KV chunk contributes
    ``p = exp(NEG_INF - m) == 0.0`` (f32 underflow) and ``alpha == 1``, so
    the online-softmax state (m, l, acc) passes through such chunks
    unchanged.  ``policy`` is a resolved ``jax.checkpoint`` policy
    (``models.common.remat_policy``); ``None`` saves nothing (full
    recompute per block).
    """
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    cq = min(q_chunk, sq)
    # the KV tile the monolithic call would use: the kernel's key block on
    # the kernel path, the scan chunk otherwise
    ck = min(KERNEL_BLOCK if ops.use_pallas() else chunk, skv)

    def block(qc, kc, vc, off):
        return flash_attention(
            qc, kc, vc, causal=causal, chunk=chunk, q_offset=off
        )

    outs = []
    for lo in range(0, sq, cq):
        hi = min(sq, lo + cq)
        if causal:
            # KV rows past the block's last query are fully masked; keep
            # KV tile boundaries (and the tile itself) aligned with the
            # monolithic path so the accumulation order is identical.
            kv_hi = min(skv, -(-(q_offset + hi) // ck) * ck)
        else:
            kv_hi = skv
        fn = jax.checkpoint(
            functools.partial(block, off=q_offset + lo), policy=policy
        )
        outs.append(fn(q[:, :, lo:hi], k[:, :, :kv_hi], v[:, :, :kv_hi]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


def local_attention(
    q: Array, k: Array, v: Array, *, window: int
) -> Array:
    """Block-local sliding-window attention, O(S * 2w): queries in block i
    attend to kv blocks {i-1, i} with a causal + window mask.  Sequences
    are padded up to a window multiple (padded keys sit at future
    positions, so causality masks them for every real query)."""
    b, hkv, s, d = k.shape
    w = window
    s_orig = s
    if s % w:
        pad = w - s % w
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s = s + pad
    qg = _group_q(q, hkv)
    g = qg.shape[2]
    nb = s // w
    scale = d ** -0.5

    qb = qg.reshape(b, hkv, g, nb, w, d)
    kb = k.reshape(b, hkv, nb, w, d)
    vb = v.reshape(b, hkv, nb, w, d)
    # previous kv block (zeros for block 0)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :, :1]), kb[:, :, :-1]], axis=2)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], axis=2)
    k2 = jnp.concatenate([kprev, kb], axis=3)  # (B, Hkv, nb, 2w, D)
    v2 = jnp.concatenate([vprev, vb], axis=3)

    logits = common.feinsum("bhgnqd,bhnkd->bhgnqk", qb, k2) * scale
    q_pos = jnp.arange(w)[:, None] + w  # position within the 2w strip
    k_pos = jnp.arange(2 * w)[None, :]
    mask = (q_pos >= k_pos) & (k_pos > q_pos - w)  # causal, within window
    first_block = jnp.arange(nb)[:, None, None] == 0
    valid = jnp.where(first_block, mask & (k_pos >= w), mask)
    logits = jnp.where(valid[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = common.feinsum("bhgnqk,bhnkd->bhgnqd", p.astype(v.dtype), v2)
    return out.reshape(q.shape)[:, :, :s_orig].astype(q.dtype)


def decode_attention(
    q1: Array, k: Array, v: Array, *,
    length: Array | None = None, engine: str | None = None,
) -> Array:
    """One-token decode: q1 (B, Hq, 1, D) vs cache (B, Hkv, S, D).

    ``length`` masks the cache tail — a scalar, or a (B,) per-slot vector
    so every slot of a continuous-batching engine attends over exactly its
    own valid rows (DESIGN.md §12).  ``engine`` picks the implementation:
    ``"splitkv"`` is the two-stage split-KV Pallas kernel
    (`kernels.flash.flash_decode`), ``"oneshot"`` the plain-reduction jnp
    path (GSPMD turns a sequence-sharded cache into partial-softmax +
    all-reduce automatically); ``None`` picks ``"splitkv"`` where a Pallas
    kernel runs (``ops.use_pallas``).
    """
    b, hkv, s, d = k.shape
    if engine is None:
        engine = "splitkv" if ops.use_pallas() else "oneshot"
    if engine == "splitkv":
        lens = s if length is None else length
        lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32).reshape(-1), (b,))
        return flash_k.flash_decode(
            q1, k, v, lengths=lens, interpret=ops._interpret(),
        )
    qg = _group_q(q1, hkv)  # (B, Hkv, G, 1, D)
    logits = common.feinsum("bhgqd,bhkd->bhgqk", qg, k) * (d ** -0.5)
    if length is not None:
        pos = jnp.arange(s)
        lb = jnp.asarray(length)
        if lb.ndim == 0:
            mask = pos[None, None, None, None, :] < lb
        else:  # per-slot (B,) lengths
            mask = pos[None, None, None, None, :] < lb[:, None, None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = common.feinsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return out.reshape(q1.shape).astype(q1.dtype)


def segment_attention(
    q: Array, k: Array, v: Array, *,
    seg_ids: Array, positions: Array, chunk: int = 512,
) -> Array:
    """Block-diagonal causal attention over a ``qo_indptr``-packed ragged
    batch (DESIGN.md §12): token i attends to token j iff they belong to
    the same segment and ``positions[i] >= positions[j]``.  ``seg_ids``
    (T,) carries the per-token sequence id with ``-1`` for padding rows
    (masked as keys everywhere); ``positions`` (T,) the within-sequence
    position.  Chunked online softmax like :func:`flash_attention` — the
    (T, T) mask is never materialized."""
    b, hkv, t, d = k.shape
    qg = _group_q(q, hkv)  # (B, Hkv, G, T, D)
    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    if n_chunks * chunk != t:
        pad = n_chunks * chunk - t
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        seg_k = jnp.pad(seg_ids, (0, pad), constant_values=-1)
        pos_k = jnp.pad(positions, (0, pad))
    else:
        seg_k, pos_k = seg_ids, positions
    scale = d ** -0.5

    def body(carry, i):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=2)
        sc = jax.lax.dynamic_slice_in_dim(seg_k, i * chunk, chunk, axis=0)
        pc = jax.lax.dynamic_slice_in_dim(pos_k, i * chunk, chunk, axis=0)
        s_log = common.feinsum("bhgqd,bhkd->bhgqk", qg, kc) * scale
        valid = (
            (seg_ids[:, None] == sc[None, :])
            & (sc[None, :] >= 0)
            & (positions[:, None] >= pc[None, :])
        )  # (T, chunk)
        s_log = jnp.where(valid, s_log, NEG_INF)
        m_new = jnp.maximum(m, s_log.max(axis=-1))
        p = jnp.exp(s_log - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + common.feinsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vc
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full(qg.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(qg.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = maybe_scan(body, (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(q.shape).astype(q.dtype)


def prefix_attention(
    q: Array, k: Array, v: Array, *, lengths: Array, chunk: int = 512,
) -> Array:
    """Chunked-prefill continuation attention (DESIGN.md §12): q (B, Hq, C,
    D) is a chunk of C new tokens per slot whose KV rows were just written
    into the ring at ``[lengths[b], lengths[b]+C)``; query row i of slot b
    attends to ring rows ``[0, lengths[b] + i + 1)`` — the already-valid
    prefix plus its own causal triangle.  Chunked online softmax over the
    ring axis."""
    b, hkv, s, d = k.shape
    qg = _group_q(q, hkv)  # (B, Hkv, G, C, D)
    c = qg.shape[3]
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    if n_chunks * chunk != s:
        pad = n_chunks * chunk - s
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = d ** -0.5
    limit = lengths[:, None] + jnp.arange(c)[None, :] + 1  # (B, C)

    def body(carry, i):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=2)
        s_log = common.feinsum("bhgqd,bhkd->bhgqk", qg, kc) * scale
        k_pos = i * chunk + jnp.arange(chunk)
        valid = (k_pos[None, None, :] < limit[:, :, None]) & (
            k_pos[None, None, :] < s
        )  # (B, C, chunk)
        s_log = jnp.where(valid[:, None, None], s_log, NEG_INF)
        m_new = jnp.maximum(m, s_log.max(axis=-1))
        p = jnp.exp(s_log - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + common.feinsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vc
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full(qg.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(qg.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = maybe_scan(body, (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(q.shape).astype(q.dtype)


def cross_attention(q: Array, k: Array, v: Array) -> Array:
    """Full (non-causal) cross attention; encoder/image keys are short, so
    no chunking needed."""
    b, hkv, skv, d = k.shape
    qg = _group_q(q, hkv)
    logits = common.feinsum("bhgqd,bhkd->bhgqk", qg, k) * (d ** -0.5)
    p = jax.nn.softmax(logits, axis=-1)
    out = common.feinsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return out.reshape(q.shape).astype(q.dtype)


# ---------------------------------------------------------------------------
# parameterized attention layer (init + apply + decode)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, *, cross: bool = False) -> dict:
    d = cfg.d_model
    hd = cfg.head_dim_resolved
    kq, kk, ko = jax.random.split(key, 3)
    dt = cfg.np_dtype
    p = {
        "norm": common.norm_init(cfg.norm, d),
        "w_o": common.truncated_normal_init(ko, (cfg.n_heads * hd, d), 1.0, dt),
    }
    if cross:
        p["w_q"] = common.truncated_normal_init(kq, (d, cfg.n_heads * hd), 1.0, dt)
        p["w_kv"] = common.truncated_normal_init(kk, (d, 2 * cfg.n_kv_heads * hd), 1.0, dt)
    else:
        fused = (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
        p["w_qkv"] = common.truncated_normal_init(kq, (d, fused), 1.0, dt)
        if cfg.qkv_bias:
            p["b_qkv"] = jnp.zeros((fused,), dt)
    return p


def _shard_qkv(cfg, q: Array, k: Array, v: Array):
    """Attention sharding policy (set by the launcher via cfg.attn_shard):

    head  — Q heads on 'model' (Megatron); K/V heads too when divisible,
            replicated otherwise (GQA with few KV heads).
    seq   — Q sequence-sharded on 'model', K/V replicated: the layout
            fallback when head counts don't divide the model axis (e.g.
            28 heads on a 16-way axis).  Without this GSPMD contraction-
            shards head_dim and all-reduces the S^2 logits — catastrophic
            in the dry run.
    """
    from repro.sharding.partition import BATCH, constrain
    from jax.sharding import PartitionSpec as P

    if cfg.attn_shard == "head":
        q = constrain(q, P(BATCH, "model", None, None))
        kv_ax = "model" if cfg.n_kv_heads == cfg.n_heads else None
        k = constrain(k, P(BATCH, kv_ax, None, None))
        v = constrain(v, P(BATCH, kv_ax, None, None))
    elif cfg.attn_shard == "seq":
        q = constrain(q, P(BATCH, None, "model", None))
        k = constrain(k, P(BATCH, None, None, None))
        v = constrain(v, P(BATCH, None, None, None))
    return q, k, v


def _project_qkv(p: dict, cfg, x: Array) -> tuple[Array, Array, Array]:
    hd = cfg.head_dim_resolved
    qkv = x @ p["w_qkv"]
    if "b_qkv" in p:
        qkv = qkv + p["b_qkv"]
    q, k, v = rr.split_qkv(qkv, cfg.n_heads, cfg.n_kv_heads, hd)
    b, s, _ = x.shape
    # each split is one fused batched-transpose kernel (plan mode
    # 'transpose'), directly producing the (B, H, S, D) attention layout
    q = rr.split_heads(q, cfg.n_heads)        # (B, Hq, S, D)
    k = rr.split_heads(k, cfg.n_kv_heads)
    v = rr.split_heads(v, cfg.n_kv_heads)
    return _shard_qkv(cfg, q, k, v)


def attn_apply(
    p: dict,
    cfg,
    x: Array,
    *,
    kind: str = "full",  # full | swa | local | bidir
    positions: Array | None = None,
) -> Array:
    from repro.sharding.partition import constrain, replicated_spec, residual_spec

    h = common.apply_norm(cfg.norm, p["norm"], x)
    if getattr(cfg, "sp", False):
        h = constrain(h, replicated_spec(3))
    q, k, v = _project_qkv(p, cfg, x=h)
    s = x.shape[1]
    pos = jnp.arange(s) if positions is None else positions
    if cfg.use_rope:
        q = common.apply_rope(q, pos, cfg.rope_theta)
        k = common.apply_rope(k, pos, cfg.rope_theta)
    if kind in ("swa", "local") and s > cfg.window:
        o = local_attention(q, k, v, window=cfg.window)
    elif kind == "bidir":
        o = cross_attention(q, k, v)  # full bidirectional self-attn
    elif getattr(cfg, "blockwise", False):
        o = flash_attention_blockwise(
            q, k, v, causal=True, chunk=cfg.attn_chunk,
            q_chunk=cfg.blockwise_chunk,
            policy=common.remat_policy(cfg.remat_policy),
        )
    else:
        o = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    out = rr.merge_heads(o) @ p["w_o"]
    if getattr(cfg, "sp", False):
        out = constrain(out, residual_spec(cfg, 3))
    return x + out


def attn_prefill(
    p: dict, cfg, x: Array, *, kind: str = "full",
    positions: Array | None = None, seg_ids: Array | None = None,
) -> tuple[Array, dict]:
    """Like apply, but also returns the decode-layout KV cache.

    ``positions``/``seg_ids`` (both (T,)) switch the batch to the packed
    ragged layout: RoPE uses the within-sequence positions and attention is
    the block-diagonal :func:`segment_attention` (DESIGN.md §12)."""
    from repro.sharding.partition import constrain, replicated_spec, residual_spec

    h = common.apply_norm(cfg.norm, p["norm"], x)
    if getattr(cfg, "sp", False):
        h = constrain(h, replicated_spec(3))
    q, k, v = _project_qkv(p, cfg, x=h)
    s = x.shape[1]
    pos = jnp.arange(s) if positions is None else positions
    if cfg.use_rope:
        q = common.apply_rope(q, pos, cfg.rope_theta)
        k = common.apply_rope(k, pos, cfg.rope_theta)
    if seg_ids is not None:
        o = segment_attention(
            q, k, v, seg_ids=seg_ids, positions=pos, chunk=cfg.attn_chunk
        )
    elif kind in ("swa", "local") and s > cfg.window:
        o = local_attention(q, k, v, window=cfg.window)
    else:
        o = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    proj = rr.merge_heads(o) @ p["w_o"]
    if getattr(cfg, "sp", False):
        proj = constrain(proj, residual_spec(cfg, 3))
    out = x + proj
    cache = {"k": k, "v": v}  # already (B, Hkv, S, D) decode layout
    return out, cache


def attn_decode(
    p: dict, cfg, x1: Array, cache: dict, pos: Array, *, kind: str = "full"
) -> tuple[Array, dict]:
    """One-token decode. cache: k/v (B, Hkv, S_max, D) ring buffer; ``pos``
    is the absolute position — an int32 scalar (every slot at the same
    position, the seed path) or a (B,) per-slot vector (continuous
    batching, DESIGN.md §12): each slot writes its KV row at its OWN ring
    position and attends over exactly its own valid length.  For swa/local
    kinds S_max is the window and the slot is pos % window."""
    h = common.apply_norm(cfg.norm, p["norm"], x1)
    q, k, v = _project_qkv(p, cfg, x=h)
    pos = jnp.asarray(pos)
    if cfg.use_rope:
        # scalar -> (1,) broadcast; per-slot -> (B, 1, 1) so the rotation
        # angles broadcast against (B, H, 1, D/2)
        posv = pos[None] if pos.ndim == 0 else pos[:, None, None]
        q = common.apply_rope(q, posv, cfg.rope_theta)
        k = common.apply_rope(k, posv, cfg.rope_theta)
    s_max = cache["k"].shape[2]
    if pos.ndim == 0:
        slot = (pos % s_max) if kind in ("swa", "local") else pos
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=2)
    else:
        slotv = (
            (pos % s_max) if kind in ("swa", "local")
            else jnp.minimum(pos, s_max - 1)
        )
        bi = jnp.arange(x1.shape[0])
        kc = cache["k"].at[bi, :, slotv].set(k[:, :, 0])
        vc = cache["v"].at[bi, :, slotv].set(v[:, :, 0])
    length = jnp.minimum(pos + 1, s_max)  # scalar or (B,)
    o = decode_attention(q, kc, vc, length=length)
    out = x1 + rr.merge_heads(o) @ p["w_o"]
    return out, {"k": kc, "v": vc}


def attn_prefill_chunk(
    p: dict, cfg, x: Array, cache: dict, pos: Array, active: Array,
) -> tuple[Array, dict]:
    """Prefill one chunk of C prompt tokens per slot directly into the
    engine's ring caches (chunked prefill, DESIGN.md §12).

    ``x`` (B, C, D) hidden chunk; ``cache`` k/v (B, Hkv, S_max, D) rings;
    ``pos`` (B,) valid rows already in each slot's ring (the chunk's rows
    land at ``[pos, pos+C)``); ``active`` (B,) bool — inactive slots leave
    their cache untouched and their outputs are ignored.  Full-attention
    kinds only (the engine's scheduler gates this path)."""
    b, c, _ = x.shape
    h = common.apply_norm(cfg.norm, p["norm"], x)
    q, k, v = _project_qkv(p, cfg, x=h)  # (B, H, C, D)
    positions = pos[:, None] + jnp.arange(c)[None, :]  # (B, C)
    if cfg.use_rope:
        q = common.apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = common.apply_rope(k, positions[:, None, :], cfg.rope_theta)
    s_max = cache["k"].shape[2]
    rows = jnp.minimum(positions, s_max - 1)  # (B, C)
    bi = jnp.arange(b)[:, None]
    # scatter the chunk rows; advanced indexing puts (B, C) in front
    kc = cache["k"].at[bi, :, rows].set(jnp.swapaxes(k, 1, 2))
    vc = cache["v"].at[bi, :, rows].set(jnp.swapaxes(v, 1, 2))
    sel = active[:, None, None, None]
    kc = jnp.where(sel, kc, cache["k"])
    vc = jnp.where(sel, vc, cache["v"])
    o = prefix_attention(q, kc, vc, lengths=pos, chunk=cfg.attn_chunk)
    out = x + rr.merge_heads(o) @ p["w_o"]
    return out, {"k": kc, "v": vc}


def xattn_init(key, cfg) -> dict:
    return attn_init(key, cfg, cross=True)


def xattn_apply(p: dict, cfg, x: Array, kv_src: Array) -> Array:
    """Cross-attention block (decoder x: (B,S,D), kv_src: (B,Skv,D))."""
    hd = cfg.head_dim_resolved
    h = common.apply_norm(cfg.norm, p["norm"], x)
    q = rr.split_heads(h @ p["w_q"], cfg.n_heads)
    kv = kv_src @ p["w_kv"]
    k, v = jnp.split(kv, 2, axis=-1)
    k = rr.split_heads(k, cfg.n_kv_heads)
    v = rr.split_heads(v, cfg.n_kv_heads)
    o = cross_attention(q, k, v)
    return x + rr.merge_heads(o) @ p["w_o"]


def xattn_cache(p: dict, cfg, kv_src: Array) -> dict:
    """Precompute cross-attention K/V once (prefill) for decode reuse."""
    kv = kv_src @ p["w_kv"]
    k, v = jnp.split(kv, 2, axis=-1)
    return {
        "k": rr.split_heads(k, cfg.n_kv_heads),
        "v": rr.split_heads(v, cfg.n_kv_heads),
    }


def xattn_decode(p: dict, cfg, x1: Array, cache: dict) -> Array:
    h = common.apply_norm(cfg.norm, p["norm"], x1)
    q = rr.split_heads(h @ p["w_q"], cfg.n_heads)
    o = cross_attention(q, cache["k"], cache["v"])
    return x1 + rr.merge_heads(o) @ p["w_o"]
