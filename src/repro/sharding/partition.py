"""Parameter/activation/cache sharding rules (DP x TP + optional pod axis).

Rules are name-based on parameter paths — Megatron-style TP on the
'model' axis (column-parallel in, row-parallel out), experts EP- or
TP-sharded, batch on ('pod','data'), optional FSDP ('data' added to the
largest replicated weight axis), ZeRO-1 on optimizer moments.  All specs
are plain PartitionSpecs resolved against whatever mesh the caller
installs, so the same model code runs on 1 device (empty specs) or the
2x16x16 production mesh.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# parameter-name classes
_COL_LAST = {
    "w_qkv", "w_q", "w_kv", "w_o_gate", "w_zifo", "w_gate_branch",
    "w_rnn_in", "lm_head", "b_qkv",
}
_ROW_SECOND = {"w_o", "w_out"}
_REPLICATED = {"scale", "bias", "lam", "conv_w", "w_router", "r_zifo"}


def param_spec(path: str, shape: tuple[int, ...], *, cfg, mesh_axes: dict) -> P:
    """PartitionSpec for one parameter, by path pattern.  Stage-stacked
    leaves carry a leading (count,) axis which is never sharded."""
    model = "model"
    data = "data"
    msize = mesh_axes.get("model_size", 1)
    dsize = mesh_axes.get("data_size", 1)
    nd = len(shape)
    name = path.split("/")[-1]
    staged = "/stages/" in path or path.startswith("stages/")
    spec: list = [None] * nd

    def div(ax: int, size: int) -> bool:
        return size > 1 and shape[ax] % size == 0 and shape[ax] >= size

    is_expert = (
        cfg is not None
        and cfg.moe is not None
        and name in ("w_up", "w_gate", "w_down")
        and nd >= 3
        and "moe" in path
    )
    if is_expert:
        e_ax = nd - 3
        if cfg.moe.shard == "expert" and div(e_ax, msize):
            spec[e_ax] = model
        else:
            ff_ax = nd - 1 if name in ("w_up", "w_gate") else nd - 2
            if div(ff_ax, msize):
                spec[ff_ax] = model
    elif name in _COL_LAST or name in ("w_up", "w_gate"):
        if div(nd - 1, msize):
            spec[nd - 1] = model
    elif name in _ROW_SECOND or name == "w_down":
        if nd >= 2 and div(nd - 2, msize):
            spec[nd - 2] = model
    elif name == "tok":
        # Vocab-parallel. D-sharding would make the scatter-add gradient
        # comm-free, but XLA 0.8.2's SPMD partitioner mis-compiles the
        # dim-sharded gather inside the grad-accumulation while loop
        # ("Slice dim size > dynamic slice dimension" verifier error), so
        # V-sharding it is; the fp32 embed-grad all-reduce this causes is
        # a known, once-per-step cost.
        if div(0, msize):
            spec[0] = model
    elif name in _REPLICATED:
        return P(*spec)

    # FSDP: shard the largest remaining replicated axis over data
    if (
        cfg is not None
        and getattr(cfg, "fsdp", False)
        and nd >= 2
        and name != "tok"
        and name not in _REPLICATED
    ):
        start = 1 if staged else 0
        free = [i for i in range(start, nd) if spec[i] is None]
        if free:
            ax = max(free, key=lambda i: shape[i])
            if div(ax, dsize):
                spec[ax] = data
    return P(*spec)


def tree_pspecs(tree_shapes: Any, *, cfg, mesh_axes: dict) -> Any:
    """Map a params pytree (arrays or ShapeDtypeStructs) to PartitionSpecs."""

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, f"{path}/{i}") for i, v in enumerate(tree)]
            return type(tree)(t)
        return param_spec(path, tuple(tree.shape), cfg=cfg, mesh_axes=mesh_axes)

    return walk(tree_shapes, "")


def zero1_spec(pspec: P, shape: tuple[int, ...], *, data_axis: str, data_size: int) -> P:
    """ZeRO-1: additionally shard optimizer moments over the data axis on
    the first unsharded, divisible axis.  No-op when the param spec
    already uses the data axis (FSDP weights are already data-sharded)."""
    spec = list(pspec) + [None] * (len(shape) - len(pspec))
    used = any(
        s == data_axis or (isinstance(s, tuple) and data_axis in s) for s in spec
    )
    if data_size > 1 and not used:
        for i, s in enumerate(spec):
            if s is None and shape[i] % data_size == 0 and shape[i] >= data_size:
                spec[i] = data_axis
                break
    return P(*spec)


def opt_pspecs(param_specs: Any, param_shapes: Any, *, mesh_axes: dict) -> Any:
    """Optimizer-state specs: moments get ZeRO-1, step replicated."""
    dsize = mesh_axes.get("data_size", 1)

    def z1(spec, shp):
        return zero1_spec(spec, tuple(shp.shape), data_axis="data", data_size=dsize)

    mom = jax.tree.map(z1, param_specs, param_shapes)
    return {"m": mom, "v": mom, "step": P()}


def batch_pspec(batch_size: int, mesh) -> P | None:
    """Shard the batch axis over (pod, data) when divisible, else None."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    if total > 1 and batch_size % total == 0:
        return tuple(axes)
    # partial: try data only
    if "data" in mesh.axis_names and batch_size % mesh.shape["data"] == 0 and mesh.shape["data"] > 1:
        return ("data",)
    return None


def cache_leaf_spec(shape: tuple[int, ...], batch_axes, *, model_size: int) -> P:
    """Decode-cache leaf: (count, B, ...) — batch on data axes; one
    inner axis on 'model' (prefer heads, then sequence, then features)."""
    nd = len(shape)
    spec: list = [None] * nd
    if nd >= 2 and batch_axes is not None:
        spec[1] = batch_axes
    if model_size > 1:
        for ax in range(2, nd):
            if shape[ax] % model_size == 0 and shape[ax] >= model_size:
                spec[ax] = "model"
                break
    return P(*spec)


def filter_spec(spec: P, axis_names) -> P:
    """Drop mesh-axis names not present in the ambient mesh (lets model
    code write canonical specs like P(('pod','data'), 'model') that
    degrade gracefully on smaller meshes)."""

    def fix(el):
        if el is None:
            return None
        if isinstance(el, str):
            return el if el in axis_names else None
        t = tuple(a for a in el if a in axis_names)
        return t if t else None

    return P(*[fix(e) for e in spec])


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op without an ambient mesh
    and tolerant of missing axes."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        return jax.lax.with_sharding_constraint(
            x, filter_spec(spec, mesh.axis_names)
        )
    except Exception:
        return x


BATCH = ("pod", "data")


def residual_spec(cfg, ndim: int = 3) -> P:
    """Residual-stream spec between blocks.  With cfg.sp (Megatron
    sequence parallelism) the sequence dim is sharded on 'model': the
    row-parallel output all-reduce becomes reduce-scatter, and the
    (cheaper, bf16) all-gather happens after the norm — ~25% less wire
    traffic per layer and norms/residual ops run on S/tp tokens."""
    if getattr(cfg, "sp", False):
        return P(*([BATCH, "model"] + [None] * (ndim - 2)))
    return P(*([BATCH] + [None] * (ndim - 1)))


def replicated_spec(ndim: int = 3) -> P:
    """Batch-sharded, otherwise-replicated activation spec (the default
    residual-stream layout when sequence parallelism is off)."""
    return P(*([BATCH] + [None] * (ndim - 1)))


def ep_param_specs(params: Any, axis: str) -> Any:
    """Expert-parallel PartitionSpec tree for a MoE layer's params: the
    expert-stacked weights (``w_up``/``w_gate``/``w_down``) shard their
    leading expert axis over mesh ``axis``; router, norms, and any shared
    expert stay replicated.  This is the in_specs tree
    ``models.moe.moe_sort_ep`` feeds `shard_map` (DESIGN.md §10)."""
    specs = jax.tree.map(lambda _: P(), params)
    for name in ("w_up", "w_gate", "w_down"):
        if name in specs:
            specs[name] = P(axis)
    return specs
