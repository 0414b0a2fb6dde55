"""jit'd wrapper: pads sequence dims to block multiples (holes are masked
via INVALID_POS), dispatches the Pallas kernel, and unpads.

``interpret`` has no default: a caller picks the Pallas interpreter (CPU)
or the compiled kernel (TPU) itself.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import INVALID_POS, flash_attention


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention_op(q, k, v, q_positions, kv_positions, *,
                       interpret: bool, causal: bool = True,
                       window: int | None = None,
                       block_q: int = 128, block_k: int = 128):
    B, Sq, Hq, D = q.shape
    _, Skv, _, _ = k.shape
    bq, bk = min(block_q, max(Sq, 8)), min(block_k, max(Skv, 8))
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=INVALID_POS)
    out = flash_attention(
        q, k, v, q_positions, kv_positions,
        causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=interpret,
    )
    return out[:, :Sq]
