"""Model assembler: stacks block units into scanned stages, provides
init / forward / loss / prefill / decode for every assigned architecture
(decoder-only, enc-dec, VLM cross-attn, MoE, recurrent families).

HLO hygiene: layers are stacked and scanned (one block body per distinct
unit in the plan), loss is computed in sequence chunks (never a full
(B, S, V) logits tensor), and each scan body is rematerialized.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import common, mlp, moe, rglru, xlstm
from repro.sharding import partition
from repro.utils.scanutil import maybe_scan

Array = jax.Array

# ---------------------------------------------------------------------------
# block dispatch tables
# ---------------------------------------------------------------------------


def block_init(key, cfg, kind: str) -> dict:
    k1, k2 = jax.random.split(key)
    if kind in ("attn", "attn_dense", "local", "enc"):
        dff = cfg.d_ff_dense if (kind == "attn_dense" and cfg.d_ff_dense) else cfg.d_ff
        return {"attn": attn.attn_init(k1, cfg), "mlp": mlp.mlp_init(k2, cfg, d_ff=dff)}
    if kind == "attn_moe":
        return {"attn": attn.attn_init(k1, cfg), "moe": moe.moe_init(k2, cfg)}
    if kind == "xattn":
        return {"xattn": attn.xattn_init(k1, cfg), "mlp": mlp.mlp_init(k2, cfg)}
    if kind == "dec":
        k3, k4 = jax.random.split(k2)
        return {
            "attn": attn.attn_init(k1, cfg),
            "xattn": attn.xattn_init(k3, cfg),
            "mlp": mlp.mlp_init(k4, cfg),
        }
    if kind == "mlstm":
        return {"cell": xlstm.mlstm_init(k1, cfg)}
    if kind == "slstm":
        return {"cell": xlstm.slstm_init(k1, cfg)}
    if kind == "rglru":
        return {"cell": rglru.rglru_init(k1, cfg), "mlp": mlp.mlp_init(k2, cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


def _mlp(p: dict, cfg, x: Array) -> Array:
    """Dense-FFN dispatch for the training forward: the blockwise-parallel
    seq-chunked FFN (DESIGN.md §13) when ``cfg.blockwise``, else the
    monolithic :func:`repro.models.mlp.mlp_apply` (bit-identical)."""
    if getattr(cfg, "blockwise", False):
        return mlp.mlp_apply_blockwise(
            p, cfg, x, chunk=cfg.blockwise_chunk,
            policy=common.remat_policy(cfg.remat_policy),
        )
    return mlp.mlp_apply(p, cfg, x)


def block_apply(kind: str, p: dict, cfg, x: Array, src: Array | None) -> tuple[Array, Array]:
    """Training/eval forward for one block. Returns (x, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_dense"):
        x = attn.attn_apply(p["attn"], cfg, x, kind=cfg.attn_kind)
        return _mlp(p["mlp"], cfg, x), zero
    if kind == "local":
        x = attn.attn_apply(p["attn"], cfg, x, kind="local")
        return _mlp(p["mlp"], cfg, x), zero
    if kind == "enc":
        x = attn.attn_apply(p["attn"], cfg, x, kind="bidir")
        return _mlp(p["mlp"], cfg, x), zero
    if kind == "attn_moe":
        x = attn.attn_apply(p["attn"], cfg, x, kind=cfg.attn_kind)
        x, aux = moe.moe_apply(p["moe"], cfg, x)
        return x, aux
    if kind == "xattn":
        x = attn.xattn_apply(p["xattn"], cfg, x, src)
        return _mlp(p["mlp"], cfg, x), zero
    if kind == "dec":
        x = attn.attn_apply(p["attn"], cfg, x, kind="full")
        x = attn.xattn_apply(p["xattn"], cfg, x, src)
        return _mlp(p["mlp"], cfg, x), zero
    if kind == "mlstm":
        return xlstm.mlstm_apply(p["cell"], cfg, x), zero
    if kind == "slstm":
        return xlstm.slstm_apply(p["cell"], cfg, x), zero
    if kind == "rglru":
        x = rglru.rglru_apply(p["cell"], cfg, x)
        return _mlp(p["mlp"], cfg, x), zero
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# parameter init (stacked stages)
# ---------------------------------------------------------------------------


def _stage_init(key, cfg, unit: tuple[str, ...], count: int) -> dict:
    def unit_init(k):
        ks = jax.random.split(k, len(unit))
        return {f"b{i}": block_init(ks[i], cfg, kind) for i, kind in enumerate(unit)}

    keys = jax.random.split(key, count)
    return jax.vmap(unit_init)(keys)


def init_params(key, cfg) -> dict:
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": common.embed_init(keys[0], cfg.vocab, cfg.d_model, cfg.np_dtype),
        "final_norm": common.norm_init(cfg.norm, cfg.d_model),
    }
    params["stages"] = [
        _stage_init(k, cfg, unit, count)
        for k, (unit, count) in zip(
            jax.random.split(keys[1], len(cfg.decoder_plan())), cfg.decoder_plan()
        )
    ]
    if not cfg.tie_embeddings:
        params["lm_head"] = common.truncated_normal_init(
            keys[2], (cfg.d_model, cfg.vocab), 1.0, cfg.np_dtype
        )
    if cfg.encoder_layers:
        params["encoder"] = {
            "stages": [
                _stage_init(k, cfg, unit, count)
                for k, (unit, count) in zip(
                    jax.random.split(keys[3], len(cfg.encoder_plan())),
                    cfg.encoder_plan(),
                )
            ],
            "final_norm": common.norm_init(cfg.norm, cfg.d_model),
        }
    return params


def abstract_params(cfg) -> Any:
    """ShapeDtypeStruct tree (no allocation) — used by the dry-run."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# forward (training / full-sequence eval)
# ---------------------------------------------------------------------------


def _maybe_remat(body, cfg):
    """Wrap a scan body in ``jax.checkpoint`` under ``cfg.remat``, resolving
    the named ``cfg.remat_policy`` (``nothing_saveable`` — the default,
    matching plain ``jax.checkpoint`` — ``dots_saveable``, ...) through
    :func:`repro.models.common.remat_policy`."""
    if not cfg.remat:
        return body
    policy = common.remat_policy(getattr(cfg, "remat_policy", None))
    return jax.checkpoint(body, policy=policy)


def _run_stages(
    stages: list, plans, cfg, x: Array, src: Array | None, batch_spec: P | None
) -> tuple[Array, Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for stage_params, (unit, count) in zip(stages, plans):

        def body(carry, unit_params):
            h, aux = carry
            if batch_spec is not None:
                h = partition.constrain(h, batch_spec)
            for i, kind in enumerate(unit):
                h, a = block_apply(kind, unit_params[f"b{i}"], cfg, h, src)
                aux = aux + a
            return (h, aux), None

        body = _maybe_remat(body, cfg)
        (x, aux_total), _ = maybe_scan(body, (x, aux_total), stage_params)
    return x, aux_total


def forward(
    params: dict,
    cfg,
    tokens: Array,
    *,
    frontend: Array | None = None,
    batch_spec: P | None = None,
) -> tuple[Array, Array]:
    """tokens (B, S) [+ frontend (B, N, D) stub embeddings] -> hidden (B,S,D)."""
    x = common.embed(params["embed"], tokens).astype(cfg.np_dtype)
    if cfg.pos_embed == "sinusoidal":
        pos = common.sinusoidal_pos(jnp.arange(tokens.shape[1]), cfg.d_model)
        x = x + pos.astype(cfg.np_dtype)
    src = None
    if cfg.encoder_layers:
        if frontend is None:
            raise ValueError(f"{cfg.name} needs frontend embeddings (audio frames)")
        enc = frontend.astype(cfg.np_dtype)
        enc, _ = _run_stages(
            params["encoder"]["stages"], cfg.encoder_plan(), cfg, enc, None, batch_spec
        )
        src = common.apply_norm(cfg.norm, params["encoder"]["final_norm"], enc)
    elif cfg.n_frontend_tokens:
        if frontend is None:
            raise ValueError(f"{cfg.name} needs frontend embeddings (image patches)")
        src = frontend.astype(cfg.np_dtype)

    x, aux = _run_stages(params["stages"], cfg.decoder_plan(), cfg, x, src, batch_spec)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    return x, aux


def _logits_chunk(params: dict, cfg, h: Array) -> Array:
    head = params.get("lm_head")
    return common.unembed(params["embed"], h, head)


def loss_fn(
    params: dict,
    cfg,
    tokens: Array,
    labels: Array,
    *,
    frontend: Array | None = None,
    batch_spec: P | None = None,
    aux_weight: float = 0.01,
) -> Array:
    """Chunked softmax cross-entropy (never materializes (B, S, V))."""
    h, aux = forward(
        params, cfg, tokens, frontend=frontend, batch_spec=batch_spec
    )
    # SP residual is sequence-sharded; gather once before loss chunking
    h = partition.constrain(h, partition.replicated_spec(3))
    b, s, d = h.shape
    chunk = min(cfg.loss_chunk, s)
    n_chunks = s // chunk
    hc = h[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, d)
    lc = labels[:, : n_chunks * chunk].reshape(b, n_chunks, chunk)
    hc = jnp.moveaxis(hc, 1, 0)  # (n_chunks, B, chunk, D)
    lc = jnp.moveaxis(lc, 1, 0)

    def body(tot, xs):
        hj, lj = xs
        logits = _logits_chunk(params, cfg, hj)  # (B, chunk, V) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lj[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    body = _maybe_remat(body, cfg)
    total, _ = maybe_scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    loss = total / (b * n_chunks * chunk)
    if cfg.moe is not None:
        loss = loss + aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _cache_shape_for(kind: str, cfg, batch: int, s_max: int) -> dict:
    hd = cfg.head_dim_resolved
    hkv = cfg.n_kv_heads
    dt = cfg.np_dtype
    if kind in ("attn", "attn_dense", "attn_moe", "dec"):
        s_eff = min(s_max, cfg.window) if cfg.attn_kind == "swa" else s_max
        c = {
            "k": jnp.zeros((batch, hkv, s_eff, hd), dt),
            "v": jnp.zeros((batch, hkv, s_eff, hd), dt),
        }
        if kind == "dec":
            n_src = cfg.n_frontend_tokens or 1
            c["cross"] = {
                "k": jnp.zeros((batch, hkv, n_src, hd), dt),
                "v": jnp.zeros((batch, hkv, n_src, hd), dt),
            }
        return c
    if kind == "local":
        w = min(cfg.window, s_max)
        return {
            "k": jnp.zeros((batch, hkv, w, hd), dt),
            "v": jnp.zeros((batch, hkv, w, hd), dt),
        }
    if kind == "xattn":
        n_src = cfg.n_frontend_tokens or 1
        return {
            "k": jnp.zeros((batch, hkv, n_src, hd), dt),
            "v": jnp.zeros((batch, hkv, n_src, hd), dt),
        }
    if kind == "mlstm":
        return xlstm.mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_init_state(cfg, batch)
    if kind == "rglru":
        return rglru.rglru_init_state(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch: int, s_max: int) -> list:
    """Stacked (per stage) decode caches."""
    caches = []
    for unit, count in cfg.decoder_plan():
        unit_cache = {
            f"b{i}": _cache_shape_for(kind, cfg, batch, s_max)
            for i, kind in enumerate(unit)
        }
        caches.append(
            jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (count,) + l.shape), unit_cache
            )
        )
    return caches


def _serve_ffn(kind: str, p: dict, cfg, x: Array, valid: Array | None) -> tuple[Array, Array]:
    """The feed-forward half of a serving block: the MoE layer over this
    chip's held experts, through which only ``valid`` rows route
    (`models.moe.moe_held`), or the dense MLP.  Returns ``(x, counts of
    moe.STATS)``."""
    if kind == "attn_moe":
        return moe.moe_held(p["moe"], cfg, x, valid)
    return mlp.mlp_apply(p["mlp"], cfg, x), moe.no_stats()


def block_decode(
    kind: str, p: dict, cfg, x1: Array, cache: dict, pos: Array, src: Array | None,
    valid: Array | None = None, moe_layer: str = "held",
) -> tuple[Array, dict, Array]:
    """One block of a decode step: ``(x1, new cache, counts of moe.STATS)``;
    ``valid`` (B,) marks the slots whose rows may route to experts, and
    ``moe_layer`` is as `decode_step` takes it."""
    if kind in ("attn", "attn_dense", "attn_moe", "dec"):
        akind = "swa" if cfg.attn_kind == "swa" else "full"
        sub = {k: cache[k] for k in ("k", "v")}
        x1, sub = attn.attn_decode(p["attn"], cfg, x1, sub, pos, kind=akind)
        new = dict(cache)
        new.update(sub)
        if kind == "dec":
            x1 = attn.xattn_decode(p["xattn"], cfg, x1, cache["cross"])
        if kind == "attn_moe" and moe_layer == "mesh":
            x1, _ = moe.moe_apply(
                p["moe"], cfg, x1, capacity=moe.decode_capacity(cfg, x1.shape[0])
            )
            return x1, new, moe.no_stats()
        x1, counts = _serve_ffn(kind, p, cfg, x1, valid)
        return x1, new, counts
    if kind == "local":
        x1, new = attn.attn_decode(p["attn"], cfg, x1, cache, pos, kind="local")
        x1 = mlp.mlp_apply(p["mlp"], cfg, x1)
    elif kind == "xattn":
        x1 = attn.xattn_decode(p["xattn"], cfg, x1, cache)
        x1, new = mlp.mlp_apply(p["mlp"], cfg, x1), cache
    elif kind == "mlstm":
        x1, new = xlstm.mlstm_decode(p["cell"], cfg, x1, cache)
    elif kind == "slstm":
        x1, new = xlstm.slstm_decode(p["cell"], cfg, x1, cache)
    elif kind == "rglru":
        x1, new = rglru.rglru_decode(p["cell"], cfg, x1, cache)
        x1 = mlp.mlp_apply(p["mlp"], cfg, x1)
    else:
        raise ValueError(kind)
    return x1, new, moe.no_stats()


def decode_step(
    params: dict,
    cfg,
    token: Array,  # (B,) int32
    cache: list,
    pos: Array,  # int32 absolute position: scalar, or (B,) per slot
    *,
    frontend_src: Array | None = None,
    batch_spec: P | None = None,
    valid: Array | None = None,
    moe_layer: str = "held",
) -> tuple[Array, list, Array]:
    """One serving step: next-token logits, the updated cache, and the
    counts of ``moe.STATS`` summed over the layers.

    ``pos`` may be a scalar (all slots at the same position, the seed
    path) or a (B,) per-slot vector — the continuous-batching engine's
    layout, threaded through to the attention ring writes and per-slot
    length masks (DESIGN.md §12).  ``valid`` (B,) marks the live slots
    (default all): an idle slot's row routes to no expert.  ``moe_layer``
    picks the MoE blocks' layer: ``"held"``, this chip's held experts,
    dropless (`models.moe.moe_held`, what the engine serves); or
    ``"mesh"``, the configured capacity dispatch at a lossless decode
    capacity (`models.moe.moe_apply`), whose expert-axis sharding a mesh
    lowering partitions (`launch.specs.make_decode_step`); it counts
    nothing."""
    if moe_layer not in ("held", "mesh"):
        raise ValueError(f"moe_layer must be 'held' or 'mesh', got {moe_layer!r}")
    pos = jnp.asarray(pos)
    x = common.embed(params["embed"], token[:, None]).astype(cfg.np_dtype)
    if cfg.pos_embed == "sinusoidal":
        pv = pos[None] if pos.ndim == 0 else pos[:, None]
        x = x + common.sinusoidal_pos(pv, cfg.d_model).astype(cfg.np_dtype)
    src = frontend_src
    new_caches, total = [], moe.no_stats()
    for stage_params, stage_cache, (unit, count) in zip(
        params["stages"], cache, cfg.decoder_plan()
    ):

        def body(h, xs):
            unit_params, unit_cache = xs
            if batch_spec is not None:
                h = partition.constrain(h, batch_spec)
            new_unit, counts = {}, moe.no_stats()
            for i, kind in enumerate(unit):
                h, new_unit[f"b{i}"], c = block_decode(
                    kind, unit_params[f"b{i}"], cfg, h, unit_cache[f"b{i}"], pos, src,
                    valid, moe_layer,
                )
                counts = counts + c
            return h, (new_unit, counts)

        x, (new_stage, counts) = maybe_scan(body, x, (stage_params, stage_cache))
        new_caches.append(new_stage)
        total = total + counts.sum(axis=0)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits_chunk(params, cfg, x)[:, 0]  # (B, V)
    return logits, new_caches, total


def prefill(
    params: dict,
    cfg,
    tokens: Array,
    *,
    frontend: Array | None = None,
    batch_spec: P | None = None,
) -> tuple[Array, list]:
    """Process a prompt, return (last-token logits, decode cache)."""
    x = common.embed(params["embed"], tokens).astype(cfg.np_dtype)
    b, s = tokens.shape
    if cfg.pos_embed == "sinusoidal":
        x = x + common.sinusoidal_pos(jnp.arange(s), cfg.d_model).astype(cfg.np_dtype)
    src = None
    if cfg.encoder_layers:
        enc = frontend.astype(cfg.np_dtype)
        enc, _ = _run_stages(
            params["encoder"]["stages"], cfg.encoder_plan(), cfg, enc, None, batch_spec
        )
        src = common.apply_norm(cfg.norm, params["encoder"]["final_norm"], enc)
    elif cfg.n_frontend_tokens:
        src = frontend.astype(cfg.np_dtype) if frontend is not None else None

    caches = []
    for stage_params, (unit, count) in zip(params["stages"], cfg.decoder_plan()):

        def body(h, unit_params):
            if batch_spec is not None:
                h = partition.constrain(h, batch_spec)
            unit_cache = {}
            for i, kind in enumerate(unit):
                h, unit_cache[f"b{i}"] = _block_prefill(
                    kind, unit_params[f"b{i}"], cfg, h, src
                )
            return h, unit_cache

        body = _maybe_remat(body, cfg)
        x, stage_cache = maybe_scan(body, x, stage_params)
        caches.append(stage_cache)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits_chunk(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def _block_prefill(kind: str, p: dict, cfg, x: Array, src) -> tuple[Array, dict]:
    if kind in ("attn", "attn_dense", "attn_moe", "dec", "local"):
        akind = (
            "local"
            if kind == "local"
            else ("swa" if cfg.attn_kind == "swa" else "full")
        )
        x, kv = attn.attn_prefill(p["attn"], cfg, x, kind=akind)
        if akind in ("swa", "local"):
            # keep only the window, laid out as the decode ring buffer:
            # token t lives at slot t % w
            w = cfg.window
            s = kv["k"].shape[2]
            if s > w:
                shift = (s - w) % w
                kv = {
                    k: jnp.roll(v[:, :, -w:], shift, axis=2) for k, v in kv.items()
                }
        if kind == "dec":
            x = attn.xattn_apply(p["xattn"], cfg, x, src)
            kv["cross"] = attn.xattn_cache(p["xattn"], cfg, src)
        if kind == "attn_moe":
            x, _ = moe.moe_apply(p["moe"], cfg, x)
        else:
            x = mlp.mlp_apply(p["mlp"], cfg, x)
        return x, kv
    if kind == "xattn":
        cache = attn.xattn_cache(p["xattn"], cfg, src)
        x = attn.xattn_apply(p["xattn"], cfg, x, src)
        return mlp.mlp_apply(p["mlp"], cfg, x), cache
    if kind == "mlstm":
        y, state = xlstm.mlstm_apply(p["cell"], cfg, x, return_state=True)
        return y, state
    if kind == "slstm":
        y, state = xlstm.slstm_apply(p["cell"], cfg, x, return_state=True)
        return y, state
    if kind == "rglru":
        y, state = rglru.rglru_apply(p["cell"], cfg, x, return_state=True)
        return mlp.mlp_apply(p["mlp"], cfg, y), state
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# serving: ragged packed prefill + chunked prefill (DESIGN.md §12)
# ---------------------------------------------------------------------------

#: block kinds the packed/chunked serving prefills support: plain causal
#: attention blocks only — recurrent state and window rings carry context
#: across the packed axis and cannot be segment-masked.
ATTN_ONLY_KINDS = ("attn", "attn_dense", "attn_moe")


def supports_ragged(cfg) -> bool:
    """True when ``cfg`` can take the packed ragged / chunked prefill
    routes: every decoder block is a full-attention kind and there is no
    encoder/frontend stream (segment masks don't reach those paths)."""
    kinds = {k for unit, _ in cfg.decoder_plan() for k in unit}
    return (
        kinds <= set(ATTN_ONLY_KINDS)
        and cfg.attn_kind != "swa"
        and not cfg.encoder_layers
        and not cfg.n_frontend_tokens
    )


def _block_prefill_ragged(
    kind: str, p: dict, cfg, x: Array, positions: Array, seg_ids: Array
) -> tuple[Array, dict, Array]:
    if kind not in ATTN_ONLY_KINDS:
        raise ValueError(f"ragged prefill supports attention blocks only, got {kind!r}")
    x, kv = attn.attn_prefill(
        p["attn"], cfg, x, kind="full", positions=positions, seg_ids=seg_ids
    )
    x, counts = _serve_ffn(kind, p, cfg, x, (seg_ids >= 0)[None])
    return x, kv, counts


def prefill_ragged(
    params: dict,
    cfg,
    tokens: Array,  # (1, T) packed prompts
    seg_ids: Array,  # (T,) int32 sequence id per token, -1 for padding
    positions: Array,  # (T,) int32 within-sequence positions
    last_ix: Array,  # (n_seq,) packed index of each sequence's last token
    *,
    batch_spec: P | None = None,
) -> tuple[Array, list, Array]:
    """Packed ragged prefill: several prompts share ONE prefill batch in a
    ``qo_indptr``-style layout (`core.index_plan.ragged_layout`); attention
    is segment-masked block-diagonal causal and padding rows route to no
    expert.  Returns (per-sequence last-token logits (n_seq, V), packed
    caches whose KV rows sit in packed order — the engine's ragged_rows
    IndexPlan gather unpacks them into the decode slots), and the counts
    of ``moe.STATS`` summed over the layers."""
    x = common.embed(params["embed"], tokens).astype(cfg.np_dtype)
    if cfg.pos_embed == "sinusoidal":
        x = x + common.sinusoidal_pos(positions, cfg.d_model).astype(cfg.np_dtype)
    caches, total = [], moe.no_stats()
    for stage_params, (unit, count) in zip(params["stages"], cfg.decoder_plan()):

        def body(h, unit_params):
            if batch_spec is not None:
                h = partition.constrain(h, batch_spec)
            unit_cache, counts = {}, moe.no_stats()
            for i, kind in enumerate(unit):
                h, unit_cache[f"b{i}"], c = _block_prefill_ragged(
                    kind, unit_params[f"b{i}"], cfg, h, positions, seg_ids
                )
                counts = counts + c
            return h, (unit_cache, counts)

        body = _maybe_remat(body, cfg)
        x, (stage_cache, counts) = maybe_scan(body, x, stage_params)
        caches.append(stage_cache)
        total = total + counts.sum(axis=0)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    h_last = x[0, last_ix]  # (n_seq, D)
    logits = _logits_chunk(params, cfg, h_last[:, None])[:, 0]
    return logits, caches, total


def _block_prefill_chunk(
    kind: str, p: dict, cfg, x: Array, cache: dict, pos: Array, active: Array,
    valid: Array,
) -> tuple[Array, dict, Array]:
    if kind not in ATTN_ONLY_KINDS:
        raise ValueError(f"chunked prefill supports attention blocks only, got {kind!r}")
    sub = {k: cache[k] for k in ("k", "v")}
    x, sub = attn.attn_prefill_chunk(p["attn"], cfg, x, sub, pos, active)
    new = dict(cache)
    new.update(sub)
    x, counts = _serve_ffn(kind, p, cfg, x, valid)
    return x, new, counts


def prefill_chunk(
    params: dict,
    cfg,
    tokens: Array,  # (B, C) chunk of prompt tokens per slot
    cache: list,
    pos: Array,  # (B,) valid ring rows per slot before this chunk
    active: Array,  # (B,) bool: slots taking a chunk this step
    last_ix: Array,  # (B,) index of each slot's last real token in the chunk
    *,
    batch_spec: P | None = None,
) -> tuple[Array, list, Array]:
    """Advance chunked prefill by one C-token chunk per active slot,
    writing KV rows at ``[pos, pos+C)`` directly into the engine cache
    (inactive slots' caches pass through untouched).  Rows of inactive
    slots and rows past ``last_ix`` route to no expert.  Returns (logits
    at each slot's ``last_ix`` chunk row, updated cache) — the logits
    matter only for slots whose prompt ends inside this chunk — and the
    counts of ``moe.STATS`` summed over the layers."""
    pos = jnp.asarray(pos)
    x = common.embed(params["embed"], tokens).astype(cfg.np_dtype)
    if cfg.pos_embed == "sinusoidal":
        positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
        x = x + common.sinusoidal_pos(positions, cfg.d_model).astype(cfg.np_dtype)
    valid = active[:, None] & (jnp.arange(tokens.shape[1])[None, :] <= last_ix[:, None])
    new_caches, total = [], moe.no_stats()
    for stage_params, stage_cache, (unit, count) in zip(
        params["stages"], cache, cfg.decoder_plan()
    ):

        def body(h, xs):
            unit_params, unit_cache = xs
            if batch_spec is not None:
                h = partition.constrain(h, batch_spec)
            new_unit, counts = {}, moe.no_stats()
            for i, kind in enumerate(unit):
                h, new_unit[f"b{i}"], c = _block_prefill_chunk(
                    kind, unit_params[f"b{i}"], cfg, h, unit_cache[f"b{i}"],
                    pos, active, valid,
                )
                counts = counts + c
            return h, (new_unit, counts)

        x, (new_stage, counts) = maybe_scan(body, x, (stage_params, stage_cache))
        new_caches.append(new_stage)
        total = total + counts.sum(axis=0)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    h_last = jnp.take_along_axis(x, last_ix[:, None, None], axis=1)  # (B,1,D)
    logits = _logits_chunk(params, cfg, h_last)[:, 0]
    return logits, new_caches, total
