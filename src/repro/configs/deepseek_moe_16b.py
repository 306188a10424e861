"""DeepSeekMoE-16B [arXiv:2401.06066; hf deepseek-ai/deepseek-moe-16b-base
``config.json``] — fine-grained MoE: 2 shared + 64 routed experts, top-6
softmax routing whose gates are not renormalised (``norm_topk_prob:
false``), expert dim 1408; layer 0 is a dense FFN (``intermediate_size``
10944, ``first_k_dense_replace`` 1)."""

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,  # routed expert dim (assigned spec)
    vocab=102400,
    head_dim=128,
    act="swiglu",
    norm="rmsnorm",
    rope_theta=10_000.0,
    tie_embeddings=False,
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        n_shared=2,
        capacity_factor=1.25,
        normalize_gates=False,
        dispatch="dense",
        shard="expert",  # 64 experts / 16-way model axis = 4 per shard
    ),
    d_ff_dense=10944,
    explicit_plan=((("attn_dense",), 1), (("attn_moe",), 27)),
    source="arXiv:2401.06066 (hf: deepseek-ai/deepseek-moe-16b-base)",
)
