"""jit'd wrapper for the flash-decoding kernel.  ``interpret`` has no
default: a caller picks the Pallas interpreter (CPU) or the compiled kernel
(TPU) itself."""
from functools import partial

import jax
import jax.numpy as jnp

from .decode_attention import INVALID_POS, flash_decode


@partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode_op(q, k, v, q_positions, kv_positions, *,
                    interpret: bool, window=None, block_k: int = 512):
    B, W = kv_positions.shape
    bk = min(block_k, W)
    pad = (-W) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=INVALID_POS)
    return flash_decode(q, k, v, q_positions, kv_positions,
                        window=window, block_k=bk, interpret=interpret)
