"""The JAX mesh and ``shard_map`` spellings the repo uses (JAX 0.9 API).

Meshes are built with every axis ``AxisType.Auto``: sharding-in-types would
otherwise make the default Explicit, and the serving and training code
relies on the compiler propagating shardings.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map_norep(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off.

    Required whenever the mapped body may dispatch a ``pallas_call`` (no
    replication rule exists for it) — i.e. for every serving fused step,
    since Pallas eligibility is a static engine flag, not a trace property.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_auto_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(shape), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )
