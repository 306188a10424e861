"""Step functions + abstract input specs for every (arch x shape cell).

``input_specs`` returns weak-type-correct ShapeDtypeStructs (zero device
allocation) plus the matching NamedShardings; ``make_*_step`` return the
jit-able step callables the dry-run lowers and the trainer executes.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.launch import mesh as meshlib
from repro.models import transformer as tf
from repro.optim import adamw
from repro.sharding import partition

Array = jax.Array


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_inputs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Abstract (ShapeDtypeStruct) train-step batch for one shape cell."""
    b, s = cell.global_batch, cell.seq_len
    out = {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
    }
    if cfg.encoder_layers or cfg.n_frontend_tokens:
        out["frontend"] = _sds((b, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    return out


def prefill_inputs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Abstract prefill-step batch (tokens + optional frontend stream)."""
    b, s = cell.global_batch, cell.seq_len
    out = {"tokens": _sds((b, s), jnp.int32)}
    if cfg.encoder_layers or cfg.n_frontend_tokens:
        out["frontend"] = _sds((b, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    return out


def decode_inputs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Abstract decode-step batch: one token per row plus the KV cache."""
    b, s = cell.global_batch, cell.seq_len
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, b, s))
    out = {
        "token": _sds((b,), jnp.int32),
        "cache": cache,
        "pos": _sds((), jnp.int32),
    }
    if cfg.encoder_layers or cfg.n_frontend_tokens:
        out["frontend_src"] = _sds(
            (b, cfg.n_frontend_tokens, cfg.d_model), jnp.float32
        )
    return out


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Abstract inputs for any shape-cell kind (train/prefill/decode)."""
    if cell.kind == "train":
        return train_inputs(cfg, cell)
    if cell.kind == "prefill":
        return prefill_inputs(cfg, cell)
    return decode_inputs(cfg, cell)


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------


def input_shardings(cfg: ModelConfig, cell: ShapeCell, mesh) -> dict:
    """NamedShardings matching :func:`input_specs`: batch over (pod, data),
    decode-cache leaves per :func:`partition.cache_leaf_spec`."""
    info = meshlib.mesh_axes_info(mesh)
    baxes = partition.batch_pspec(cell.global_batch, mesh)
    ns = lambda spec: NamedSharding(mesh, spec)
    if cell.kind in ("train", "prefill"):
        out = {
            "tokens": ns(P(baxes, None)),
        }
        if cell.kind == "train":
            out["labels"] = ns(P(baxes, None))
        if cfg.encoder_layers or cfg.n_frontend_tokens:
            out["frontend"] = ns(P(baxes, None, None))
        return out
    # decode
    cache_shapes = jax.eval_shape(lambda: tf.init_cache(cfg, cell.global_batch, cell.seq_len))
    cache_spec = jax.tree.map(
        lambda l: ns(
            partition.cache_leaf_spec(
                tuple(l.shape), baxes, model_size=info["model_size"]
            )
        ),
        cache_shapes,
    )
    out = {
        "token": ns(P(baxes)),
        "cache": cache_spec,
        "pos": ns(P()),
    }
    if cfg.encoder_layers or cfg.n_frontend_tokens:
        out["frontend_src"] = ns(P(baxes, None, None))
    return out


def param_shardings(cfg: ModelConfig, mesh) -> Any:
    """NamedSharding tree for the params (partition rules on this mesh)."""
    info = meshlib.mesh_axes_info(mesh)
    shapes = tf.abstract_params(cfg)
    specs = partition.tree_pspecs(shapes, cfg=cfg, mesh_axes=info)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def opt_shardings(cfg: ModelConfig, mesh) -> Any:
    """NamedSharding tree for optimizer state (ZeRO-1 moments)."""
    info = meshlib.mesh_axes_info(mesh)
    shapes = tf.abstract_params(cfg)
    pspecs = partition.tree_pspecs(shapes, cfg=cfg, mesh_axes=info)
    ospecs = partition.opt_pspecs(pspecs, shapes, mesh_axes=info)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, oc: adamw.OptConfig, mesh, *, accum_steps: int = 1):
    """The jit-able train step (delegates to ``train.trainer``)."""
    from repro.train import trainer

    return trainer.make_train_step(cfg, oc, mesh, accum_steps=accum_steps)


def make_prefill_step(cfg: ModelConfig, mesh):
    """The jit-able prefill step for this config."""
    def prefill_step(params, batch):
        return tf.prefill(
            params, cfg, batch["tokens"], frontend=batch.get("frontend")
        )

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh):
    """The jit-able single-token decode step for this config.  Its MoE
    blocks take the mesh layer (``moe_layer="mesh"``), whose expert-axis
    sharding the partitioner lowers to the expert all-to-all."""
    def decode_step(params, batch):
        logits, cache, _ = tf.decode_step(
            params,
            cfg,
            batch["token"],
            batch["cache"],
            batch["pos"],
            frontend_src=batch.get("frontend_src"),
            moe_layer="mesh",
        )
        return logits, cache

    return decode_step


def resolve_dist(cfg: ModelConfig, mesh, *, serve_decode: bool = False) -> ModelConfig:
    """Pick the distribution policies for this mesh:
    - attention: head-sharded when head count divides the model axis,
      sequence-sharded otherwise (see attention._shard_qkv);
    - sequence-parallel residual (Megatron-SP) for train/prefill — not
      decode, where S == 1 (see partition.residual_spec)."""
    if mesh is None:
        return cfg
    info = meshlib.mesh_axes_info(mesh)
    ms = info["model_size"]
    if ms <= 1:
        return cfg
    policy = "head" if cfg.n_heads % ms == 0 else "seq"
    # Megatron-SP measured NEGATIVE on this XLA SPMD backend (collective
    # 7.83->8.32s on qwen2 train_4k: the partitioner keeps the AR and adds
    # reshards) — opt-in only.  EXPERIMENTS §Perf iteration 6.
    import os

    sp = os.environ.get("REPRO_SP", "0") == "1" and not serve_decode
    return cfg.with_(attn_shard=policy, sp=sp)


def make_step(cfg: ModelConfig, cell: ShapeCell, mesh, oc: adamw.OptConfig | None = None,
              *, accum_steps: int = 1):
    """Resolve distribution policies for the mesh and build the cell's step
    callable (train / prefill / decode)."""
    cfg = resolve_dist(cfg, mesh, serve_decode=cell.kind == "decode")
    if cell.kind == "train":
        return make_train_step(
            cfg, oc or adamw.OptConfig(), mesh, accum_steps=accum_steps
        )
    if cell.kind == "prefill":
        return make_prefill_step(cfg, mesh)
    return make_decode_step(cfg, mesh)
