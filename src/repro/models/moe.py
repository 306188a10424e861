"""Mixture-of-Experts layer: top-k router + two dispatch strategies.

Dispatch IS the paper's index-set rearrangement (§III-A / DESIGN.md §4):

* ``sort`` mode — tokens are permuted into expert-contiguous order through
  the IndexPlan engine (`core/index_plan.py`): ONE blocked masked gather
  (scalar-prefetched index table = constant-memory analogue, sentinel
  slots zero-filled in-kernel), experts run as a blocked einsum, and ONE
  fused gather+weighted-combine kernel restores token order.  This is the
  TPU-kernel path (single device / serving).
* ``dense`` mode — capacity-bucketed one-hot dispatch/combine einsums
  (the GSPMD-canonical formulation): expert axis sharded on 'model' turns
  the dispatch einsum into an all-to-all.  This is the distributed path
  and the one the dry-run compiles.

Auxiliary load-balancing loss (Switch-style) is returned alongside.

Serving uses a third form, :func:`moe_held`: one chip's share of the
experts (``MoEConfig.held``), routed over all of them and computed without
dropping any assignment — the masked gather into held-expert-sorted rows,
ONE grouped SwiGLU kernel over them (`kernels/grouped_matmul.py`), and the
fused gather-combine back to token order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import grouped_matmul, ops
from repro.models import common, mlp

Array = jax.Array


def moe_init(key, cfg) -> dict:
    mc = cfg.moe
    d = cfg.d_model
    f = mc.d_expert
    dt = cfg.np_dtype
    n_held = mc.held_range()[1]  # the router keeps all n_experts outputs
    keys = jax.random.split(key, 6)
    p = {
        "norm": common.norm_init(cfg.norm, d),
        "w_router": common.truncated_normal_init(keys[0], (d, mc.n_experts), 1.0, jnp.float32),
        "w_up": common.truncated_normal_init(keys[1], (n_held, d, f), 1.0, dt),
        "w_gate": common.truncated_normal_init(keys[2], (n_held, d, f), 1.0, dt),
        "w_down": common.truncated_normal_init(keys[3], (n_held, f, d), 1.0, dt),
    }
    if mc.n_shared:
        shared_cfg_ff = mc.d_expert * mc.n_shared
        p["shared"] = mlp.mlp_init(keys[4], cfg, d_ff=shared_cfg_ff)
    return p


def _route(p: dict, mc, h2: Array) -> tuple[Array, Array, Array, Array]:
    """h2: (T, D) -> (gates (T,k), idx (T,k), me (E,), ce (E,)).

    ``me``/``ce`` are the per-expert mean router probability and top-1
    assignment fraction over THESE tokens — kept separate from the aux-loss
    reduction so the expert-parallel path can ``pmean`` them across token
    shards before forming the (nonlinear) Switch loss.
    """
    logits = (h2.astype(jnp.float32) @ p["w_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, mc.top_k)
    if mc.normalize_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    e = mc.n_experts
    me = probs.mean(axis=0)  # (E,)
    onehot = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    ce = onehot.mean(axis=0)
    return gates, idx, me, ce


def _router(p: dict, mc, h2: Array) -> tuple[Array, Array, Array]:
    """h2: (T, D) -> (gates (T,k), idx (T,k), aux_loss)."""
    gates, idx, me, ce = _route(p, mc, h2)
    # Switch aux loss: E * sum_e f_e * P_e
    aux = mc.n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(p: dict, cfg, xe: Array) -> Array:
    """xe: (E, C, D) -> (E, C, D), blocked per-expert einsums."""
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    hidden = jax.nn.silu(gate) * up if cfg.act == "swiglu" else jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", hidden, p["w_down"])


def _slot_assignment(idx: Array, t: int, e: int, cap: int, k: int):
    """Capacity-bucketed rank of every (token, k) assignment.

    Returns ``(keep, slot, token_of)``: ``slot = expert*cap + rank`` in
    ``[0, E*C)``, ``keep`` marks assignments under capacity, ``token_of``
    maps flat assignment index to its token row.  Shared by BOTH dispatch
    engines (rowwise baseline and the §4 plan path) so the bucketing
    semantics cannot diverge between them.
    """
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)           # (T, k, E)
    flat = onehot.reshape(t * k, e)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = (pos * flat).sum(-1).reshape(t, k)                   # rank in expert
    keep = pos < cap
    slot = idx * cap + pos                                     # (T, k) in [0, E*C)
    token_of = jnp.arange(t * k, dtype=jnp.int32) // k
    return keep, slot, token_of


def _dispatch_tables(idx: Array, t: int, e: int, cap: int, k: int):
    """Sentinel-carrying dispatch tables for the §4 plan path.

    Returns ``(src, back, keep)``: ``src`` (E*cap,) maps each expert slot
    to its source token row (-1 sentinel = empty slot) and ``back`` (T, k)
    maps each assignment to its slot (-1 = dropped) — the in-kernel
    sentinel semantics that make dispatch ONE blocked masked gather and
    combine ONE fused kernel.
    """
    keep, slot, token_of = _slot_assignment(idx, t, e, cap, k)
    slot_or_dump = jnp.where(keep, slot, e * cap).reshape(-1)
    src = jnp.full((e * cap,), -1, jnp.int32).at[slot_or_dump].set(
        token_of, mode="drop"
    )
    back = jnp.where(keep, slot, -1).astype(jnp.int32)         # (T, k)
    return src, back, keep


def moe_dense(p: dict, cfg, x: Array, *, capacity: int | None = None) -> tuple[Array, Array]:
    """Capacity-bucketed dispatch, GShard-style *grouped by sequence*:
    capacity C = cf*S*k/E per batch row, so the dispatch one-hot is
    (B, S, E, C) and dispatch FLOPs stay ~2.5*S^2*D per row (~6% of the
    expert FFN) instead of scaling with GLOBAL tokens — a global capacity
    makes dispatch O(T^2) (the 7500s collective term the dry-run
    caught).  Expert axis shards on 'model' -> all-to-all."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import BATCH, constrain

    mc = cfg.moe
    b0, s0, d = x.shape
    h = common.apply_norm(cfg.norm, p["norm"], x)
    if getattr(cfg, "sp", False):
        h = constrain(h, P(BATCH, None, None))  # SP: gather before dispatch
    gates, idx, aux = _router(p, mc, h.reshape(-1, d))
    e, k = mc.n_experts, mc.top_k
    # fixed-size token groups (true GShard): capacity must not grow with
    # S, or the dispatch one-hots/einsums go quadratic at 32k+ prefill
    g_size = s0
    if s0 > 4096:
        for cand in (4096, 2048, 1024):
            if s0 % cand == 0:
                g_size = cand
                break
    b = b0 * (s0 // g_size)
    s = g_size
    h = h.reshape(b, s, d)
    gates = gates.reshape(b, s, k)
    idx = idx.reshape(b, s, k)

    cap = capacity or default_capacity(cfg, s)
    cap = min(cap, s * k)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)            # (B, S, k, E)
    flat = onehot.reshape(b, s * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                        # rank per (row, expert)
    pos = (pos.reshape(b, s, k, e) * onehot).sum(-1)             # (B, S, k)
    keep = pos < cap
    slot = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1, dtype=h.dtype)[..., :-1]
    oh = onehot.astype(h.dtype)
    disp = jnp.einsum("bske,bskc->bsec", oh, slot)               # (B, S, E, C)
    ge = oh * (gates * keep.astype(gates.dtype)).astype(h.dtype)[..., None]
    comb = jnp.einsum("bske,bskc->bsec", ge, slot)

    xe = jnp.einsum("bsec,bsd->ebcd", disp, h)                   # (E, B, C, D)
    espec = "model" if mc.shard == "expert" else None
    xe = constrain(xe, P(espec, BATCH, None, None))
    up = jnp.einsum("ebcd,edf->ebcf", xe, p["w_up"])
    gate = jnp.einsum("ebcd,edf->ebcf", xe, p["w_gate"])
    if mc.shard == "ffn":
        up = constrain(up, P(None, BATCH, None, "model"))
        gate = constrain(gate, P(None, BATCH, None, "model"))
    hidden = jax.nn.silu(gate) * up if cfg.act == "swiglu" else jax.nn.gelu(up)
    ye = jnp.einsum("ebcf,efd->ebcd", hidden, p["w_down"])
    ye = constrain(ye, P(espec, BATCH, None, None))
    y = jnp.einsum("bsec,ebcd->bsd", comb, ye.astype(comb.dtype)).astype(x.dtype)
    if "shared" in p:
        y = y + mlp.ffn_only(p["shared"], cfg, h.reshape(-1, d)).reshape(b, s, d)
    return x + y.reshape(b0, s0, d), aux


def moe_sort(
    p: dict, cfg, x: Array, *, capacity: int | None = None, engine: str = "plan"
) -> tuple[Array, Array]:
    """Capacity-blocked gather dispatch through the library's index-set
    kernels (paper §III-A): tokens are gathered into expert-contiguous
    (E, C, D) blocks with a scalar-prefetched source table, experts run as
    blocked einsums, and the combine restores token order.

    ``engine="plan"`` (default) routes through the IndexPlan engine
    (`core/index_plan.py`): dispatch is ONE blocked masked gather (dropped
    slots are in-kernel sentinel zeros — no sentinel-row concatenates) and
    the combine is ONE fused gather+weighted-combine kernel, so the whole
    dispatch+combine is exactly 2 `pallas_call`s.  ``engine="rowwise"``
    keeps the seed path — per-row gathers around two full-array sentinel
    concatenates and an unfused multiply/sum combine — as a baseline that
    no benchmark cell runs.
    """
    if engine not in ("plan", "rowwise"):
        raise ValueError(f"unknown moe_sort engine {engine!r}")
    mc = cfg.moe
    b, s, d = x.shape
    h = common.apply_norm(cfg.norm, p["norm"], x)
    h2 = h.reshape(-1, d)
    t = h2.shape[0]
    gates, idx, aux = _router(p, mc, h2)

    e, k = mc.n_experts, mc.top_k
    cap = capacity or default_capacity(cfg, t)

    if engine == "rowwise":
        keep, slot, token_of = _slot_assignment(idx, t, e, cap, k)
        slot_or_dump = jnp.where(keep, slot, e * cap).reshape(-1)  # dump at end
        # source table: slot -> source token row (sentinel row t = zeros)
        src = jnp.full((e * cap + 1,), t, jnp.int32).at[slot_or_dump].set(token_of)
        h2p = jnp.concatenate([h2, jnp.zeros((1, d), h2.dtype)], axis=0)
        xs = ops.gather_rows(h2p, src[: e * cap], engine="rowwise")
        ye = _expert_ffn(p, cfg, xs.reshape(e, cap, d)).reshape(e * cap, d)
        # gather back: token slot -> expert output row (dump -> zeros row)
        yep = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
        back = jnp.where(keep.reshape(-1), slot.reshape(-1), e * cap).astype(jnp.int32)
        yk = ops.gather_rows(yep, back, engine="rowwise").reshape(t, k, d)
        y = (yk * gates[..., None].astype(yk.dtype)).sum(axis=1).astype(x.dtype)
    else:
        # dispatch: slot -> token table with -1 sentinels for empty slots
        # (dropped assignments target the out-of-range slot e*cap and are
        # dropped by the scatter); the masked blocked gather zero-fills
        # sentinel rows in-kernel -> ONE pallas_call, no h2 concatenate.
        src, back, _ = _dispatch_tables(idx, t, e, cap, k)
        xs = ops.gather_rows(h2, src, masked=True)             # (E*C, D)
        ye = _expert_ffn(p, cfg, xs.reshape(e, cap, d)).reshape(e * cap, d)
        # combine: out[t] = sum_k gates[t,k] * ye[back[t,k]] fused into ONE
        # kernel (dropped assignments carry the -1 sentinel -> zero term)
        y = ops.gather_combine(ye, back, gates).astype(x.dtype)
    if "shared" in p:
        y = y + mlp.ffn_only(p["shared"], cfg, h2)
    return x + y.reshape(b, s, d), aux


def moe_sort_ep(
    p: dict,
    cfg,
    x: Array,
    *,
    mesh,
    axis: str = "model",
    capacity: int | None = None,
) -> tuple[Array, Array]:
    """Expert-parallel sort dispatch: the §4 blocked kernels sandwich a
    capacity-bucketed ``all_to_all`` pair (DESIGN.md §10).

    Tokens shard over mesh ``axis`` (``T`` divisible by its size ``P``) and
    so do experts (``E = P * E_local``).  Per shard: route the local tokens,
    dispatch them into global-expert-major (E, C, D) slot blocks with ONE
    blocked masked gather (`core/index_plan.py` — identical kernel to
    single-device ``moe_sort``), exchange slot blocks with ONE tiled
    ``all_to_all`` so every shard receives exactly the rows its local
    experts own, run the local expert FFNs, ``all_to_all`` back, and
    restore token order with ONE fused gather+combine kernel.  The gathered
    intermediate never touches HBM (fused kernels) and only the
    ``(P-1)/P`` remote fraction of the fixed-size slot blocks touches the
    wire (capacity bucketing is what keeps the exchange fixed-size).

    ``capacity`` is per (source shard, expert); ``capacity >= T/P`` is
    dropless, making the result bit-identical to dropless single-device
    ``moe_sort`` (the aux loss is ``pmean``-reduced, equal to fp rounding).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import dist_plan
    from repro.sharding.partition import ep_param_specs

    mc = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mc.n_experts, mc.top_k
    p_sz = int(mesh.shape[axis])
    tl = t // p_sz
    cap = capacity or default_capacity(cfg, tl)
    plan = dist_plan.plan_dist_moe(
        dist_plan.mesh_key(mesh), axis, t, d, e, cap, k, x.dtype
    )
    if plan.strategy == "local":
        return moe_sort(p, cfg, x, capacity=cap)
    _, el, _, _ = plan.detail

    pspecs = ep_param_specs(p, axis)  # experts shard over the EP axis

    def f(pl_, xl):
        h2 = common.apply_norm(cfg.norm, pl_["norm"], xl)
        gates, idx, me, ce = _route(pl_, mc, h2)
        # global Switch aux: token shards are equal-sized, so the global
        # means are the pmean of the per-shard means
        me = jax.lax.pmean(me, axis)
        ce = jax.lax.pmean(ce, axis)
        aux = e * jnp.sum(me * ce)
        # local dispatch into global-expert-major slots: slot blocks for
        # destination shard q occupy rows [q*el*cap, (q+1)*el*cap)
        src, back, _ = _dispatch_tables(idx, tl, e, cap, k)
        xs = ops.gather_rows(h2, src, masked=True)              # (E*C, D)
        # wire: shard q receives every source's block q — afterwards rows
        # group as (source shard, local expert, capacity)
        xs = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0, tiled=True)
        # (P, el, cap, D) -> (el, P, cap, D): expert-major for the blocked
        # FFN einsums — a local §3 plan (one batched-transpose kernel)
        xe = ops.permute(xs.reshape(p_sz, el, cap, d), (1, 0, 2, 3))
        ye = _expert_ffn(pl_, cfg, xe.reshape(el, p_sz * cap, d))
        ye = ops.permute(ye.reshape(el, p_sz, cap, d), (1, 0, 2, 3))
        # wire back: every source shard gets its slots home, global-expert
        # order restored
        ye = jax.lax.all_to_all(
            ye.reshape(e * cap, d), axis, split_axis=0, concat_axis=0, tiled=True
        )
        y = ops.gather_combine(ye, back, gates).astype(xl.dtype)
        if "shared" in pl_:
            y = y + mlp.ffn_only(pl_["shared"], cfg, h2)
        return xl + y, aux

    y, aux = jax.shard_map(
        f, mesh=mesh, in_specs=(pspecs, P(axis, None)),
        out_specs=(P(axis, None), P()), check_vma=False,
    )(p, x.reshape(t, d))
    return y.reshape(b, s, d), aux


#: the counts :func:`moe_held` returns: real (token, held expert)
#: assignments, rows the grouped kernel computed, held experts given rows
STATS = ("moe_rows_real", "moe_rows_computed", "moe_groups")


def no_stats() -> Array:
    """The counts of a layer that computes no experts."""
    return jnp.zeros((len(STATS),), jnp.int32)


def route_all(p: dict, mc, h2: Array) -> tuple[Array, Array]:
    """The published router over all ``n_experts``: float32 logits at full
    matmul precision, softmax, top-k over the full width, the gates
    renormalised only where ``mc.normalize_gates`` says.  h2 (T, D) ->
    (gates (T, k) f32, expert ids (T, k))."""
    logits = jnp.dot(h2.astype(jnp.float32), p["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), mc.top_k)
    if mc.normalize_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def expert_tile_rows(tokens: int, top_k: int, n_experts: int) -> int:
    """Rows per tile of the grouped kernel: about what one expert receives
    of ``tokens`` under even routing, as a power of two in [16, 128] (16 is
    a bf16 sublane tile; 128 rows fill the MXU)."""
    want = max(1, tokens * top_k // n_experts)
    return min(128, max(16, 1 << (want - 1).bit_length()))


def held_experts(
    p: dict, cfg, h2: Array, gates: Array, local: Array, valid: Array
) -> tuple[Array, Array]:
    """The held experts' part of the layer, dropping no assignment.

    ``local`` (T, k) is each assignment's expert id less the first held
    one; assignments of rows not ``valid`` or to experts not held add
    nothing.  The held assignments are ranked within their expert and laid
    out expert by expert, each expert's run padded to whole tiles
    (`grouped_matmul.tile_groups`): the buffer holds the worst case (every
    row's assignments held) in static shape, and the kernel computes only
    the tiles in use.  Returns ``(y (T, D) in h2's dtype, counts)`` with
    the counts of :data:`STATS`."""
    t, k = local.shape
    n_held = p["w_up"].shape[0]
    held = valid[:, None] & (local >= 0) & (local < n_held)
    expert = jnp.where(held, local, n_held)  # n_held: a one-hot of zeros
    onehot = jax.nn.one_hot(expert, n_held, dtype=jnp.int32).reshape(t * k, n_held)
    sizes = onehot.sum(axis=0)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1).reshape(t, k)
    tm = expert_tile_rows(t, k, cfg.moe.n_experts)
    n_tiles = -(-t * min(k, n_held) // tm) + n_held  # sum over experts of ceil(n/tm)
    starts, tile_group, n_used = grouped_matmul.tile_groups(sizes, tm, n_tiles)
    slot = jnp.where(held, starts[jnp.minimum(expert, n_held - 1)] + rank, -1)
    rows = n_tiles * tm
    token_of = jnp.arange(t * k, dtype=jnp.int32) // k
    src = jnp.full((rows,), -1, jnp.int32).at[jnp.where(held, slot, rows).reshape(-1)].set(
        token_of, mode="drop")
    xs = ops.gather_rows(h2, src, masked=True)  # (rows, D), sentinels zero
    ye = ops.grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"], tile_group, n_used,
                            tm=tm)
    y = ops.gather_combine(ye, slot, gates)
    counts = jnp.stack([sizes.sum(), n_used * tm, (sizes > 0).sum()]).astype(jnp.int32)
    return y, counts


def moe_held(p: dict, cfg, x: Array, valid: Array | None = None) -> tuple[Array, Array]:
    """The serving MoE layer over this chip's experts (``cfg.moe.held``).

    Every token is routed over all ``n_experts`` (:func:`route_all`); the
    assignments that land on held experts are computed without dropping
    any (:func:`held_experts`); the shared experts run for every token.
    Rows where ``valid`` (x's leading shape) is False — padding — route
    nowhere, so a real token's output does not depend on what else is in
    the batch.  Returns ``(x + y, counts of STATS)``."""
    mc = cfg.moe
    if cfg.act != "swiglu":
        raise ValueError(f"the held-expert layer computes SwiGLU experts, not {cfg.act!r}")
    d = x.shape[-1]
    h2 = common.apply_norm(cfg.norm, p["norm"], x).reshape(-1, d)
    t = h2.shape[0]
    valid = jnp.ones((t,), bool) if valid is None else valid.reshape(t)
    gates, idx = route_all(p, mc, h2)
    y, counts = held_experts(p, cfg, h2, gates, idx - mc.held_range()[0], valid)
    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + mlp.ffn_only(p["shared"], cfg, h2)
    return x + y.reshape(x.shape), counts


def moe_apply(p: dict, cfg, x: Array, *, capacity: int | None = None) -> tuple[Array, Array]:
    """Route to the configured dispatch strategy (``sort`` or ``dense``)."""
    if cfg.moe.dispatch == "sort":
        return moe_sort(p, cfg, x, capacity=capacity)
    return moe_dense(p, cfg, x, capacity=capacity)


def default_capacity(cfg, tokens: int) -> int:
    """Per-expert buffer size for ``tokens`` routed tokens: the GShard
    formula ``max(1, int(capacity_factor * tokens * top_k / n_experts))``.
    The single definition every caller shares — the capacity-bucketed
    layers here, the benchmarks, and the ``repro.tune`` pre-warm CLI,
    whose whole point is warming the exact plan keys (``n_out = n_experts
    * capacity``) those layers will look up."""
    mc = cfg.moe
    return max(1, int(mc.capacity_factor * tokens * mc.top_k / mc.n_experts))


def decode_capacity(cfg, batch: int) -> int:
    """Lossless per-expert capacity for a decode step: worst case every
    token routes to the same expert.  ``jax.lax.top_k`` expert ids are
    distinct per token, so one expert receives at most ONE assignment per
    token — capacity ``batch`` is lossless.  (The seed returned
    ``batch * top_k``, sizing the decode dispatch gather k times too big.)
    """
    return batch
