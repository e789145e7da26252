"""Named sharding rules + in-model constraint points.

A ``Rules`` table maps *logical* axis names ("batch", "heads", "vocab", ...)
to mesh axis names (or None for replicated, or a tuple of mesh axes).  Model
code never mentions mesh axes: it calls ``constrain(x, "batch", "seq", None)``
and the active rules (installed by :func:`use_rules`) decide the placement.
Outside a ``use_rules`` context ``constrain`` is the identity, so single-device
tests and eager code pay nothing.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


class Rules(dict):
    """Logical-axis -> mesh-axis table (plain dict with a type name)."""


def production_rules(multi_pod: bool = False) -> Rules:
    """Default rule table for the (data, model) production meshes.

    ``fsdp``/``expert``/``expert_mlp``/``seq_kv`` are filled in per-cell by
    ``launch.mesh.rules_for`` — their defaults here are the serving-friendly
    replicated choices.
    """
    return Rules(
        batch=("pod", "data") if multi_pod else "data",
        seq=None,                 # activations keep full sequence per shard
        seq_kv=None,              # long-context cells shard KV time instead
        vocab="model",
        heads="model",
        kv_heads="model",
        mlp="model",
        expert=None,
        expert_mlp=None,
        moe_capacity=None,
        fsdp=None,
    )


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (the sharding rules and
    ``constrain`` call sites leave placement to the compiler)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


# -- active-rules context ----------------------------------------------------

_ACTIVE: list[tuple[Rules, Optional[Mesh]]] = []


@contextlib.contextmanager
def use_rules(rules: Rules, mesh: Optional[Mesh] = None):
    """Install ``rules`` (+ optional mesh) for ``constrain`` call sites."""
    _ACTIVE.append((rules, mesh))
    try:
        yield rules
    finally:
        _ACTIVE.pop()


def current_rules() -> Optional[tuple[Rules, Optional[Mesh]]]:
    return _ACTIVE[-1] if _ACTIVE else None


def spec_for(rules: Rules, *axes) -> P:
    """PartitionSpec from logical axis names (None entries stay None)."""
    entries = []
    for a in axes:
        if a is None:
            entries.append(None)
        elif isinstance(a, str):
            entries.append(rules.get(a))
        else:                      # already a mesh-axis tuple
            entries.append(a)
    return P(*entries)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """``with_sharding_constraint`` by logical axis name; identity when no
    rules are active (single-device tests, eager code)."""
    ctx = current_rules()
    if ctx is None:
        return x
    rules, mesh = ctx
    spec = spec_for(rules, *axes)
    if all(e is None for e in spec):
        return x
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)
