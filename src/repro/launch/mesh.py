"""Production mesh definition.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ("data", "model");
multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
composes with "data" for DP/FSDP so the same partition rules scale to N
pods (set pods=N).

Every mesh here has Auto axes: the model code places activations with
``with_sharding_constraint`` (repro.sharding.shard), which only accepts Auto
axes, while ``jax.make_mesh`` now defaults to Explicit ones."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    if multi_pod:
        shape = (pods, 16, 16)
        axes = ("pod", "data", "model")
    else:
        shape = (16, 16)
        axes = ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    data = n // model
    return auto_mesh((data, model), ("data", "model"))
