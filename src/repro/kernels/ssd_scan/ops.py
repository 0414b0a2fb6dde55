"""jit'd wrapper for the SSD Pallas kernel.  ``interpret`` has no default:
a caller picks the Pallas interpreter (CPU) or the compiled kernel (TPU)
itself."""
from __future__ import annotations

from functools import partial

import jax

from .ssd_scan import ssd_scan


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, dt, a, B, C, *, interpret: bool, chunk: int = 128):
    return ssd_scan(x, dt, a, B, C, chunk=chunk, interpret=interpret)
