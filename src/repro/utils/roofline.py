"""Roofline math and the one home of the TPU v5e's peaks in the library
(the planners' ``roofline_s`` and the tuner's cost model read them).

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

cost_analysis() and the HLO text are per-device (post-SPMD) programs, so
a global formulation (global / (chips * bw)) reduces to these.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass

PEAK_BF16_FLOPS = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

# fixed-cost terms for the tuner's candidate scoring (DESIGN.md §11).
# Rough v5e figures: one pallas_call dispatch, the per-grid-step pipeline
# bubble (DMA issue + semaphore wait that double buffering cannot hide at
# the panel boundary), and the ICI latency of launching one collective.
KERNEL_LAUNCH_S = 2e-6
GRID_STEP_S = 2e-7
COLLECTIVE_LAUNCH_S = 5e-6


def movement_cost_s(
    bytes_moved: float,
    grid_steps: int = 1,
    *,
    wire_bytes: float = 0.0,
    collectives: int = 0,
) -> float:
    """Cost-model score (seconds) for one movement candidate: HBM traffic
    at bandwidth plus the fixed per-kernel/per-grid-step overheads, plus
    the wire term for distributed candidates.  This is the deterministic
    fallback the autotuner (``core/tune.py``) ranks candidates with when
    measured timing is unavailable (``REPRO_TUNE=off``, interpret mode, or
    no runner) — unlike the pure ``bytes / bw`` roofline it separates
    candidates that move the same useful bytes with different padding
    waste or grid granularity."""
    return (
        bytes_moved / HBM_BW
        + KERNEL_LAUNCH_S
        + grid_steps * GRID_STEP_S
        + wire_bytes / ICI_BW
        + collectives * COLLECTIVE_LAUNCH_S
    )


@dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops_global: float
    n_chips: int

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / PEAK_BF16_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of terms (perfect overlap assumption)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_dev * self.n_chips
        return self.model_flops_global / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        denom = self.step_time_s * PEAK_BF16_FLOPS * self.n_chips
        return self.model_flops_global / denom if denom else 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            compute_s=self.compute_s,
            memory_s=self.memory_s,
            collective_s=self.collective_s,
            bottleneck=self.bottleneck,
            step_time_s=self.step_time_s,
            useful_flops_ratio=self.useful_flops_ratio,
            mfu_bound=self.mfu_bound,
        )
        return d


def model_flops(cfg, cell) -> float:
    """6*N*D for training (fwd+bwd), 2*N*D for inference, N = active params."""
    n = active_params(cfg)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    tokens = cell.global_batch  # one step
    return 2.0 * n * tokens


def active_params(cfg) -> float:
    """Parameter count, using ACTIVE experts only for MoE."""
    d, v, l = cfg.d_model, cfg.vocab, cfg.n_layers
    hd = cfg.head_dim_resolved
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    total = emb
    for unit, count in cfg.decoder_plan():
        for kind in unit:
            total += count * _block_params(cfg, kind, d, hd)
    if cfg.encoder_layers:
        total += cfg.encoder_layers * _block_params(cfg, "enc", d, hd)
    return float(total)


def _block_params(cfg, kind: str, d: int, hd: int) -> float:
    qkv = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
    o = cfg.n_heads * hd * d
    def ffn(f, act=None):
        gated = (act or cfg.act) in ("swiglu", "geglu")
        return d * f * (3 if gated else 2)
    if kind in ("attn", "enc", "local"):
        return qkv + o + ffn(cfg.d_ff)
    if kind == "attn_dense":
        return qkv + o + ffn(cfg.d_ff_dense or cfg.d_ff)
    if kind == "attn_moe":
        mc = cfg.moe
        active = (mc.top_k + mc.n_shared) * ffn(mc.d_expert)
        return qkv + o + active + d * mc.n_experts
    if kind == "xattn":
        return d * cfg.n_heads * hd + d * 2 * cfg.n_kv_heads * hd + o + ffn(cfg.d_ff)
    if kind == "dec":
        cross = d * cfg.n_heads * hd + d * 2 * cfg.n_kv_heads * hd + o
        return qkv + o + cross + ffn(cfg.d_ff)
    if kind == "mlstm":
        return 3 * d * d + 2 * d * cfg.n_heads + 2 * d * d
    if kind == "slstm":
        return 4 * d * d + 4 * d * (d // cfg.n_heads) + d * d
    if kind == "rglru":
        return 4 * d * d + 4 * d + d * d + ffn(cfg.d_ff)
    raise ValueError(kind)
