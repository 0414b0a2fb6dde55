"""Llama architecture (``model_type`` ``llama``; Yi): the dense decoder with
no norm over the heads of q and k (``bench/dense.py``)."""
from bench import dense
from bench.dense import (decode_bytes, decode_flops, logits, make_params,
                         make_weights, model_config, prefill_flops)

__all__ = dense.__all__


def tiny(hf: dict) -> dict:
    """All query heads on one KV head, the nearest to Yi-6B's 32 to 4."""
    return dense.tiny(hf, kv_heads=1)
