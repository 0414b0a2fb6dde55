"""Qwen3 (``model_type`` ``qwen3``): the dense decoder with RMSNorm over each
head of q and k (``bench/dense.py``)."""
from bench import dense
from bench.dense import (decode_bytes, decode_flops, logits, make_params,
                         make_weights, model_config, prefill_flops)

__all__ = dense.__all__


def tiny(hf: dict) -> dict:
    """Two query heads to each KV head, as in Qwen3-1.7B's 16 and 8."""
    return dense.tiny(hf, kv_heads=2)
