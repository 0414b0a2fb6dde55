"""Pallas kernel validation + timing on the TPU: each compiled kernel
against its pure-jnp oracle, every row naming the device it ran on.  Finds
no TPU, fails: the Pallas interpreter on a CPU is not what is deployed.

Only rmsnorm is here.  The TPU compiler refuses the flash_attention,
flash_decode and ssd_scan kernels for their block shapes (ROADMAP D3);
their interpret-mode tests stay in tests/test_kernels.py."""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.rmsnorm.ops import rmsnorm_op  # noqa: E402
from repro.kernels.rmsnorm.ref import rmsnorm_ref  # noqa: E402


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = True):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"kernel benchmark needs a TPU, JAX found {dev.platform!r}")
    device = f"{dev.platform}:{dev.device_kind}x{len(jax.devices())}"

    rows = []
    key = jax.random.PRNGKey(0)
    shape = (8, 256, 512) if quick else (16, 512, 2048)
    x = jax.random.normal(key, shape, jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], jnp.bfloat16)
    o = rmsnorm_op(x, w, interpret=False)
    r = rmsnorm_ref(x, w)
    err = float(np.abs(np.asarray(o, np.float32)
                       - np.asarray(r, np.float32)).max())
    us = _time(lambda: rmsnorm_op(x, w, interpret=False), reps=3)
    rows.append(("kernel_rmsnorm", us,
                 f"device={device};max_err={err:.2e};"
                 f"shape={'x'.join(map(str, shape))}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
