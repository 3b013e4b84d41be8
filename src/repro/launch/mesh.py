"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set XLA_FLAGS
before any jax initialization.
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data×model single pod; (2, 16, 16) pod×data×model for two
    pods (512 chips).  The `pod` axis composes with `data` for the batch
    dimension and optionally joins parameter sharding (fsdp_pod rules)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def make_flow_mesh(num_shards: "int | None" = None):
    """1-D ``('data',)`` mesh for sharded flow serving: one shard of the
    flow table per device.  ``num_shards`` defaults to every local device
    (on CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before the first jax import to get N devices)."""
    avail = len(jax.devices())
    n = avail if num_shards is None else num_shards
    if n > avail:
        raise ValueError(
            f"num_shards={n} exceeds the {avail} visible device(s); on CPU "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    return _mesh((n,), ("data",))


def flow_shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: flow-table
    placement is explicit, so the checker adds nothing."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
