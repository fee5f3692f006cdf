"""Single choke-point for jax API churn (jax 0.9).

* ``shard_map``          — ``jax.shard_map`` with the replication check
  spelled ``check_vma``.
* ``make_abstract_mesh`` — device-free ``AbstractMesh(axis_sizes,
  axis_names)`` for pure sharding-spec logic.
* ``cost_analysis_dict`` — ``Compiled.cost_analysis()`` as a dict, empty
  when the backend reports nothing.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_abstract_mesh(shape: tuple[int, ...], axis_names: tuple[str, ...]):
    """Device-free mesh for pure sharding-spec logic (no real devices)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(shape, axis_names)


def cost_analysis_dict(compiled) -> dict:
    return compiled.cost_analysis() or {}
