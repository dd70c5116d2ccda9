"""Process-global mesh context + activation sharding constraints.

Launch code installs the mesh once (``set_mesh``, inside ``jax.set_mesh``
for the same mesh); model code calls
``constrain(x, *axes)`` freely — it is a no-op when no mesh is installed
(CPU smoke tests) or when a named axis is absent from the installed mesh
(e.g. 'pod' on the single-pod mesh).
"""

from __future__ import annotations

from jax.sharding import Mesh, PartitionSpec as P

import jax

_MESH: Mesh | None = None

# canonical logical axes
BATCH = "__batch__"   # maps to ("pod", "data") when present
MODEL = "__model__"   # maps to ("model",)

# sequence parallelism: when enabled, layer-boundary activations shard their
# sequence dim over 'model' (GSPMD then lowers the Megatron-TP all-reduces
# to reduce-scatter + all-gather and shards the norm/residual compute)
_SEQUENCE_PARALLEL = False


def set_sequence_parallel(on: bool) -> None:
    global _SEQUENCE_PARALLEL
    _SEQUENCE_PARALLEL = on


def constrain_acts(x):
    """Layer-boundary activation constraint [B, S, d]."""
    if _SEQUENCE_PARALLEL:
        return constrain(x, BATCH, MODEL, None)
    return constrain(x, BATCH, None, None)


def set_mesh(mesh: Mesh | None) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Mesh | None:
    return _MESH


def _resolve(axis) -> tuple[str, ...] | None:
    if _MESH is None:
        return None
    names = set(_MESH.axis_names)
    if axis == BATCH:
        return tuple(a for a in ("pod", "data") if a in names) or None
    if axis == MODEL:
        return ("model",) if "model" in names else None
    if axis is None:
        return None
    if isinstance(axis, str):
        return (axis,) if axis in names else None
    got = tuple(a for a in axis if a in names)
    return got or None


def spec(*axes) -> P:
    """Build a PartitionSpec resolving logical axes against the mesh."""
    return P(*[_resolve(a) for a in axes])


def model_axis_size() -> int:
    if _MESH is None or "model" not in _MESH.axis_names:
        return 1
    return _MESH.shape["model"]


def _manual_axes() -> frozenset[str]:
    """Mesh axes currently under manual shard_map control (must be omitted
    from sharding constraints issued by model code running inside)."""
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def constrain(x, *axes):
    """with_sharding_constraint against the installed mesh (no-op without).

    Transparently drops axes that are manual in the enclosing shard_map —
    the same model code runs under pure GSPMD ("auto" sync) and inside the
    manual-DP region (explicit WRHT sync)."""
    if _MESH is None:
        return x
    manual = _manual_axes()
    resolved = []
    for a in axes:
        r = _resolve(a)
        if r is not None:
            r = tuple(n for n in r if n not in manual) or None
        resolved.append(r)
    # bare PartitionSpec resolves against the context mesh (``jax.set_mesh``,
    # which launch code enters around tracing) — required inside shard_map,
    # where axis types are Manual and a NamedSharding over the Auto-typed
    # concrete mesh would mismatch
    return jax.lax.with_sharding_constraint(x, P(*resolved))
