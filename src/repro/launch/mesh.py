"""Production mesh builders (functions, not module constants, so importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU integration tests (xla_force_host_platform_device_count)."""
    return jax.make_mesh((data, model), ("data", "model"))


def client_mesh_size(n_clients: int, n_devices: int) -> int:
    """Largest divisor of ``n_clients`` that fits on ``n_devices``.

    Even client blocks per device are required by the GLASU shard_map round
    body; a non-dividing axis would leave ragged shards. With fewer devices
    than any divisor > 1, the mesh degenerates to one device (m_loc = M),
    which runs the identical collective code path trivially.
    """
    if n_clients < 1 or n_devices < 1:
        raise ValueError(f"need positive counts, got n_clients={n_clients} "
                         f"n_devices={n_devices}")
    return max(d for d in range(1, min(n_clients, n_devices) + 1)
               if n_clients % d == 0)


def make_client_mesh(n_clients: int, *, max_devices=None, devices=None):
    """One-axis ``('clients',)`` mesh for the sharded GLASU backend.

    Places each client (or an even block of clients) on its own device: the
    axis size is the largest divisor of ``n_clients`` the available devices
    allow, so ``shard_map`` blocks are always even. CPU-testable via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if max_devices is not None:
        if max_devices < 1:
            raise ValueError(f"max_devices must be >= 1, got {max_devices}")
        devs = devs[:max_devices]
    d = client_mesh_size(n_clients, len(devs))
    # Auto, not the Explicit default of newer JAX: the round builders mix
    # client-sharded params with replicated inputs under vmap/shard_map and
    # leave the placement to the compiler
    return jax.make_mesh((d,), ("clients",), devices=devs[:d],
                         axis_types=(jax.sharding.AxisType.Auto,))
