"""Logical-axis sharding: models annotate activations/params with *logical*
axis names; the launcher installs a rule set mapping logical names to mesh
axes.  Outside a mesh/rules context the annotations are no-ops, so the same
model code runs in single-device smoke tests and 512-chip dry-runs.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# Default logical->mesh rules for the production mesh
# ("pod", "data", "model") / ("data", "model").
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),   # batch dim: DP/FSDP axes
    "fsdp": ("pod", "data"),    # param dim sharded for FSDP
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "seq": "model",             # sequence sharding (activations)
    "attn_seq": None,           # row-parallel attention (heads indivisible)
    "layers": None,
}


def _rules() -> dict | None:
    return getattr(_state, "rules", None)


def _mesh() -> Mesh | None:
    """The mesh passed to ``use_sharding_rules``; None when none was, or
    while suspended (shard_map-local tracing)."""
    m = getattr(_state, "mesh", None)
    return None if m is False else m


@contextmanager
def suspend_sharding_rules():
    """Disable logical sharding constraints while tracing shard_map-local
    code (with_sharding_constraint does not apply to per-shard arrays)."""
    old_rules = getattr(_state, "rules", None)
    old_mesh = getattr(_state, "mesh", None)
    _state.rules = None
    _state.mesh = False  # sentinel: suspended, see _mesh()
    try:
        yield
    finally:
        _state.rules = old_rules
        _state.mesh = old_mesh


@contextmanager
def use_sharding_rules(rules: dict, mesh: Mesh | None = None):
    old_rules = getattr(_state, "rules", None)
    old_mesh = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules = old_rules
        _state.mesh = old_mesh


def resolve_spec(axes: tuple[str | None, ...], rules: dict | None = None,
                 mesh: Mesh | None = None) -> P:
    """Map logical axis names to a PartitionSpec under ``rules``, dropping
    mesh axes that do not exist in the current mesh."""
    rules = rules if rules is not None else (_rules() or {})
    mesh = mesh if mesh is not None else _mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    out = []
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        if m is None:
            out.append(None)
            continue
        if isinstance(m, str):
            out.append(m if m in mesh_axes else None)
        else:  # tuple of mesh axes
            kept = tuple(a for a in m if a in mesh_axes)
            out.append(kept if kept else None)
    return P(*out)


def shard(x, axes: tuple[str | None, ...]):
    """with_sharding_constraint by logical axes; no-op without rules/mesh.

    Divisibility-safe: a dim that does not divide its mapped mesh axes is
    left unsharded (e.g. the seq axis of a single decode token)."""
    rules = _rules()
    mesh = _mesh()
    if rules is None or mesh is None:
        return x
    spec = resolve_spec(axes, rules, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    safe = []
    for dim, entry in zip(x.shape, spec):
        if entry is None:
            safe.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        n = 1
        for a in names:
            n *= sizes.get(a, 1)
        safe.append(entry if (n and dim % n == 0) else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*safe)))


def named_sharding(mesh: Mesh, axes: tuple[str | None, ...],
                   rules: dict | None = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(axes, rules or DEFAULT_RULES, mesh))
