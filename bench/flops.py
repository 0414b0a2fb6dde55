"""Operations and bytes that the served algorithm needs, from shapes alone.

Counts are of the model as stated, for the rows that carry requests: rows
that only pad a batch to its bucket, cache slots that are masked out and
copies of the cache that the program makes are work the algorithm does not
need, so a program that stops doing them moves closer to the roofline and
leaves these counts alone.  A multiply-add is two operations.
"""
from __future__ import annotations

from .weights import dims

#: bytes of one value in the served dtype
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_weights(n: dict) -> int:
    """Parameters of one layer's projections (norm weights left out)."""
    d, hq, hkv, dh, f = n["d"], n["hq"], n["hkv"], n["dh"], n["f"]
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def weight_bytes(hf: dict) -> int:
    """Bytes of every weight a decode step reads: each layer's projections
    and norms, the final norm and the output head (the embedding's rows that
    the step looks up are counted with the activations)."""
    n = dims(hf)
    norms = 2 * n["d"] + (2 * n["dh"] if hf["model_type"] == "qwen3" else 0)
    params = n["layers"] * (layer_weights(n) + norms) + n["d"] + n["d"] * n["v"]
    return params * DTYPE_BYTES[hf["torch_dtype"]]


def kv_bytes_per_token(hf: dict) -> int:
    """Bytes of one position's keys and values over all layers."""
    n = dims(hf)
    return (n["layers"] * 2 * n["hkv"] * n["dh"]
            * DTYPE_BYTES[hf["torch_dtype"]])


def prefill_flops(hf: dict, rows: int, prompt_len: int) -> float:
    """Prefill of ``rows`` prompts of ``prompt_len`` tokens: every layer at
    every position, causal attention, and the output head at the last
    position only (the program computes no other logits)."""
    n = dims(hf)
    p = prompt_len
    proj = 2 * n["layers"] * layer_weights(n) * p
    attn = n["layers"] * 2 * n["hq"] * n["dh"] * p * (p + 1)
    head = 2 * n["d"] * n["v"]
    return float(rows * (proj + attn + head))


def decode_flops(hf: dict, rows: int, pos: int) -> float:
    """One decode step of ``rows`` requests writing position ``pos``: every
    layer, attention over positions ``0..pos`` and the output head."""
    n = dims(hf)
    proj = 2 * n["layers"] * layer_weights(n)
    attn = n["layers"] * 4 * n["hq"] * n["dh"] * (pos + 1)
    head = 2 * n["d"] * n["v"]
    return float(rows * (proj + attn + head))


def decode_bytes(hf: dict, rows: int, pos: int) -> float:
    """Bytes one decode step must move: the weights once, the keys and
    values of positions ``0..pos`` of each row, the new position's keys and
    values written, and each row's embedding row."""
    n = dims(hf)
    kv = kv_bytes_per_token(hf)
    emb = n["d"] * DTYPE_BYTES[hf["torch_dtype"]]
    return float(weight_bytes(hf) + rows * (kv * (pos + 1) + kv + emb))


def min_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over peak compute and the bytes over peak memory bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
