"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first jax init, and
the dry-run needs 512 host placeholder devices while tests need 1).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a ``pod`` axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (1x1, same axis names)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_fabric_mesh(n_shards: int | None = None):
    """1-D ``("shard",)`` mesh for the sharded index fabric
    (:mod:`repro.core.fabric`): the batched construction loop shard_maps
    its G axis over it and ``ShardedIndex`` places one route-key shard
    per device.  CPU-testable via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax import — ``repro.launch.shard_run`` handles that)."""
    n = jax.device_count() if n_shards is None else n_shards
    if not 1 <= n <= jax.device_count():
        raise ValueError(
            f"n_shards={n} needs 1..{jax.device_count()} devices")
    return _make_mesh((n,), ("shard",))
