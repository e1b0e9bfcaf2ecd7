"""The one call site of jax's mesh and shard_map surface.

``analysis.source_rules.NoRawCompatAPIs`` keeps every use of
``make_mesh`` / ``shard_map`` / ``set_mesh`` routed through here, so the
settings the codebase relies on (Auto axis types, no replication check)
are chosen in exactly one place.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map", "set_mesh"]


def make_mesh(axis_shapes, axis_names, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types (the implicit-sharding
    behavior every caller expects), over ``devices`` when given."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def shard_map(f, *, mesh=None, in_specs, out_specs):
    """``jax.shard_map`` without the replication check: the scan carries
    in ``blocked_topk`` are axis-agnostic and fail its inference.
    ``mesh=None`` uses the ambient mesh (installed via :func:`set_mesh`)."""
    kwargs = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False, **kwargs)


def set_mesh(mesh: jax.sharding.Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)
