"""qwen3-1.7b [dense]: 28L d_model=2048 16H (GQA kv=8) d_ff=6144
vocab=151936, qk_norm.  [hf:Qwen/Qwen3-1.7B config.json]"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-1.7b", family="dense",
        num_layers=28, d_model=2048, num_heads=16, num_kv_heads=8,
        d_ff=6144, vocab_size=151936, d_head=128,
        qk_norm=True, rope_theta=1e6, tie_embeddings=True,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-smoke", family="dense",
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=384, qk_norm=True, tie_embeddings=True,
        attn_chunk=32, remat=False,
    )
