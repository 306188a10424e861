"""Model/arch configuration schema.

Every assigned architecture is a ``ModelConfig``; the layer stack is
described by a *plan* — a sequence of (unit, count) pairs where a unit is
a tuple of block kinds executed in order and scanned ``count`` times.
Kinds:

  attn        self-attention (cfg.attn_kind: full|swa) + dense MLP
  attn_dense  self-attention + dense MLP with ``d_ff_dense`` (deepseek L0)
  attn_moe    self-attention + MoE FFN
  local       local (windowed) self-attention + dense MLP
  xattn       cross-attention (image/frames source) + dense MLP
  dec         decoder block: self-attn + cross-attn(encoder) + MLP
  enc         bidirectional self-attention + MLP (encoder stack)
  mlstm       xLSTM matrix-memory block (self-contained)
  slstm       xLSTM scalar-memory block (self-contained)
  rglru       RG-LRU recurrent block + dense MLP
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    normalize_gates: bool = True
    dispatch: str = "dense"  # dense (GSPMD all-to-all) | sort (gather kernels)
    shard: str = "expert"  # expert (EP on model axis) | ffn (TP inside experts)
    #: the experts this chip holds, ``(first, count)``, as the deployment
    #: divides them over chips; ``None`` holds all of them
    held: tuple[int, int] | None = None

    def held_range(self) -> tuple[int, int]:
        """``(first held expert, number held)``."""
        return self.held if self.held is not None else (0, self.n_experts)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    qkv_bias: bool = False
    act: str = "swiglu"  # swiglu | geglu | gelu | relu2
    norm: str = "rmsnorm"
    pos_embed: str = "rope"  # rope | sinusoidal | none
    rope_theta: float = 1_000_000.0
    attn_kind: str = "full"  # full | swa
    attn_shard: str = "none"  # none | head | seq — set by the launcher
    sp: bool = False  # sequence-parallel residual stream — set by the launcher
    window: int = 4096
    moe: MoEConfig | None = None
    unit: tuple[str, ...] = ("attn",)
    explicit_plan: tuple[tuple[tuple[str, ...], int], ...] | None = None
    encoder_layers: int = 0
    n_frontend_tokens: int = 0  # stub modality frontend (audio frames / image patches)
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    fsdp: bool = False
    remat: bool = True
    remat_policy: str = "nothing_saveable"  # see models.common.REMAT_POLICIES
    blockwise: bool = False  # blockwise-parallel training blocks (DESIGN §13)
    blockwise_chunk: int = 1024  # query/sequence chunk for blockwise attn+FFN
    loss_chunk: int = 2048
    attn_chunk: int = 512
    d_ff_dense: int | None = None
    subquadratic: bool = False  # may run long_500k
    source: str = ""  # provenance note

    # ---- derived ----
    @property
    def use_rope(self) -> bool:
        return self.pos_embed == "rope"

    @property
    def head_dim_resolved(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def np_dtype(self):
        return jnp.dtype(self.dtype)

    def layer_plan(self) -> tuple[tuple[tuple[str, ...], int], ...]:
        """[(unit, count), ...] covering exactly n_layers block entries."""
        if self.explicit_plan is not None:
            plan = self.explicit_plan
        else:
            u = len(self.unit)
            count, rem = divmod(self.n_layers, u)
            plan = (((self.unit), count),)
            if rem:
                plan = plan + ((self.unit[:rem], 1),)
        total = sum(len(unit) * cnt for unit, cnt in plan)
        if total != self.n_layers:
            raise ValueError(
                f"{self.name}: plan covers {total} layers, expected {self.n_layers}"
            )
        return plan

    def decoder_plan(self):
        return self.layer_plan()

    def encoder_plan(self):
        if not self.encoder_layers:
            return ()
        return ((("enc",), self.encoder_layers),)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


@dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_serve(self) -> bool:
        return self.kind in ("prefill", "decode")


SHAPE_CELLS = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    kw = dict(
        n_layers=max(
            len(cfg.unit) if cfg.explicit_plan is None else 2, len(cfg.unit)
        ),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=128,
        vocab=256,
        head_dim=16,
        window=16,
        loss_chunk=32,
        attn_chunk=32,
        encoder_layers=min(cfg.encoder_layers, 2),
        n_frontend_tokens=min(cfg.n_frontend_tokens, 8) if cfg.n_frontend_tokens else 0,
        fsdp=False,
        name=cfg.name + "-smoke",
    )
    if cfg.explicit_plan is not None:
        # shrink counts to 1 per unit kind
        kw["explicit_plan"] = tuple((unit, 1) for unit, _ in cfg.explicit_plan)
        kw["n_layers"] = sum(len(u) for u, _ in kw["explicit_plan"])
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(
            n_experts=8,
            top_k=min(cfg.moe.top_k, 2),
            d_expert=64,
            n_shared=min(cfg.moe.n_shared, 1),
            capacity_factor=8.0,  # dropless at smoke scale: decode tests exact
            normalize_gates=cfg.moe.normalize_gates,
            dispatch=cfg.moe.dispatch,
        )
        kw["d_ff_dense"] = 128 if cfg.d_ff_dense else None
    return cfg.with_(**kw)
