"""The JAX surface the package is written against, named in one place.

The codebase targets JAX 0.9 (``jax.shard_map``, ``jax.make_mesh(...,
axis_types=...)``, ``jax.set_mesh``).  Internal code imports these names from
here rather than from ``jax`` directly, so a future API move is one edit:

* ``shard_map``, ``make_mesh``, ``set_mesh``, ``AxisType``,
  ``get_abstract_mesh``, ``process_count``, ``process_index`` — aliases of
  the JAX functions of the same name;
* ``distributed_initialize`` — ``jax.distributed.initialize`` that is a
  no-op for a single-process launch.
"""
from __future__ import annotations

from typing import Any

import jax

__all__ = [
    "AxisType",
    "distributed_initialize",
    "get_abstract_mesh",
    "make_mesh",
    "process_count",
    "process_index",
    "set_mesh",
    "shard_map",
]

AxisType = jax.sharding.AxisType
get_abstract_mesh = jax.sharding.get_abstract_mesh
make_mesh = jax.make_mesh
process_count = jax.process_count
process_index = jax.process_index
set_mesh = jax.set_mesh
shard_map = jax.shard_map


def distributed_initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    **kwargs: Any,
) -> bool:
    """``jax.distributed.initialize`` gated for single-process launches.

    Returns True iff a multi-process runtime actually came up.  A
    single-process launch (no coordinator, ``num_processes`` absent or 1) is
    a silent no-op — the same code path then runs on the local mesh, which
    is what lets the simulated-topology harness and a real cluster share one
    entry point (``repro.launch.mesh.init_distributed``).
    """
    if coordinator_address is None and num_processes in (None, 1):
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    return True
