"""jit'd wrapper for the RMSNorm Pallas kernel.  ``interpret`` has no
default: a caller picks the Pallas interpreter (CPU) or the compiled kernel
(TPU) itself."""
from functools import partial

import jax

from .rmsnorm import rmsnorm


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_op(x, w, *, interpret: bool, eps: float = 1e-6,
               block_rows: int = 256):
    return rmsnorm(x, w, eps=eps, block_rows=block_rows, interpret=interpret)
