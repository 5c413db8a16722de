"""Sharding for the flow-table tier: the ('shard', 'data') mesh and the
placement of a sharded flow-table pytree on it.

Axes:
  shard  storage: flow-table buckets, shard s owning bucket % n_shards == s.
  data   batch parallelism over the partitioned classify lanes and the
         backend slices; registers replicate along it (DESIGN.md §16).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def named_sharding_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def flow_shard_mesh(n_shards: Optional[int] = None,
                    n_data: int = 1) -> Mesh:
    """2D ('shard', 'data') mesh for the sharded flow-table tier.

    'shard' partitions flow-table *buckets* (storage: each shard owns
    bucket % n_shards == s); 'data' is pure batch parallelism over the
    partitioned classify lanes and the backend slices — registers are
    replicated along it (DESIGN.md §16). ``n_data=1`` (the default)
    degenerates to the historical 1D behavior; ``n_shards`` defaults to
    every local device not consumed by 'data' — on a CPU host-platform
    run that is whatever ``--xla_force_host_platform_device_count``
    provided.
    """
    n = n_shards or max(1, jax.local_device_count() // n_data)
    return jax.make_mesh((n, n_data), ("shard", "data"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def as_flow_mesh(mesh: Mesh) -> Mesh:
    """Normalize a flow-table mesh to the 2D ('shard', 'data') form.

    A legacy 1D ('shard',) mesh gains a size-1 'data' axis (same
    devices, same shard blocks), so every shard_map body can reference
    both axes unconditionally; a 2D ('shard', 'data') mesh passes
    through. Anything else is not a flow-table mesh. The result has Auto
    axes whatever the input had: the streaming tier leaves the placement
    of everything outside its ``shard_map`` bodies to the compiler, which
    Explicit axes (``jax.make_mesh``'s default) forbid.
    """
    if mesh.axis_names == ("shard",):
        devices = mesh.devices.reshape(-1, 1)
    elif mesh.axis_names == ("shard", "data"):
        devices = mesh.devices
    else:
        raise ValueError(
            f"flow-table mesh must have axes ('shard',) or ('shard', 'data'), "
            f"got {mesh.axis_names}")
    return Mesh(devices, ("shard", "data"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def flow_table_sharding(mesh: Mesh, state_tree):
    """NamedSharding tree placing a sharded flow-table pytree on ``mesh``.

    Every leaf shards its leading (n_shards) dim over 'shard' and
    replicates the rest — registers are (n_shards, n_local), the epoch
    register is (n_shards,); both derive from ndim, so the rule survives
    new registers being added to the state. On a 2D ('shard', 'data')
    mesh the registers replicate along 'data' (the data axis parallelizes
    classify lanes and backend slices, never storage).
    """
    spec = jax.tree.map(
        lambda a: P("shard", *([None] * (a.ndim - 1))), state_tree)
    return named_sharding_tree(mesh, spec)

