"""Thin wrappers over the sharding API the repo uses.

Every module that builds a mesh or wraps a shard_map body goes through these
helpers, so the axis-type and replication-check choices live in one place.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def shard_map(
    f: Callable[..., Any],
    *,
    mesh: "jax.sharding.Mesh",
    in_specs: Any,
    out_specs: Any,
    check: bool = False,
) -> Callable[..., Any]:
    """``jax.shard_map``; ``check`` maps to ``check_vma`` (default False:
    the kNN bodies do manual collectives)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )
