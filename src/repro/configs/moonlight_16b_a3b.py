"""moonlight-16b-a3b — DeepSeek-V3 block: latent attention (MLA, no query
compression), 64 sigmoid-routed experts top-6 with 2 shared, one leading
dense layer, untied head
[hf: moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].

Every layer holds all 64 experts here (``experts_held`` = n_experts); a
chip's share of an expert-parallel layer is the same config with fewer
held and an offset (the benchmark's ``singlecell-moonlight``).  The
balance-loss weight is DeepSeek-V3's (arXiv:2412.19437, sec. 2.1.2): the
published config does not give one.
"""
from repro.models.config import MLA, ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,                  # the leading dense layer's SwiGLU
    vocab_size=163840,
    block_pattern=(MLA,),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    experts_held=64,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_k_dense=1,
    routed_scale=2.446,
    balance_alpha=1e-4,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    subquadratic=False,          # full attention => skip long_500k
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)
