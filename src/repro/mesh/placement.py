"""Mesh-aware engine placement: the ``engine="auto"`` decision table.

Pure arithmetic over (client count, device count, per-replica footprint,
per-device memory budget) — jax is imported only to read the device's own
memory limit, so the table is unit-pinnable without devices.
``repro.api.engines.resolve_engine`` consults
:func:`choose_engine` with the process device count; launchers and dryrun
feed the footprint from :func:`repro.configs.shapes.replica_footprint_bytes`
(the spec carries it as the ``replica_bytes`` hint).

The rule, in order:

1. one device -> ``vmap`` (nothing to shard);
2. replica footprint known and over budget -> ``mesh_2d`` (the only engine
   that can split a replica), UNLESS the spec is adversarial — the robust /
   secure reductions are full-view and stay on the 1D engines;
3. multiple devices and a client axis worth sharding -> ``shard_map``;
4. otherwise ``vmap``.

The per-device budget is the ``bytes_limit`` that the first local device
reports through ``memory_stats()``. A backend that reports none (the CPU)
is an error, not a guess: the ``REPRO_DEVICE_MEM_BYTES`` env var (or an
explicit ``hbm_bytes``) then supplies it, which is also how CPU-simulated
meshes rehearse "does not fit" placements with byte-for-byte the
production logic.
"""
from __future__ import annotations

import math
import os

ENV_DEVICE_MEM = "REPRO_DEVICE_MEM_BYTES"


def device_memory_budget(default: int | None = None) -> int:
    """Per-device memory budget in bytes: the env override, else
    ``default``, else the device's reported ``bytes_limit``."""
    env = os.environ.get(ENV_DEVICE_MEM)
    if env:
        budget = int(env)
        if budget <= 0:
            raise ValueError(f"{ENV_DEVICE_MEM} must be positive, "
                             f"got {budget}")
        return budget
    if default is not None:
        return int(default)
    import jax
    device = jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(f"{device.platform} device {device.device_kind!r} "
                           f"reports no memory bytes_limit; set "
                           f"{ENV_DEVICE_MEM}")
    return int(limit)


def replica_fits(replica_bytes: int, hbm_bytes: int | None = None) -> bool:
    """Does one whole model replica (+ optimizer state) fit one device?"""
    return int(replica_bytes) <= device_memory_budget(hbm_bytes)


def n_client_shards(n_clients: int, n_devices: int) -> int:
    """Largest divisor of n_clients that fits in the device count — the 1D
    engine's client-axis size (it requires clients to divide exactly)."""
    return max(d for d in range(1, min(n_clients, n_devices) + 1)
               if n_clients % d == 0)


def model_shards_for(replica_bytes: int, n_devices: int,
                     hbm_bytes: int | None = None) -> int:
    """Smallest divisor ``dm`` of ``n_devices`` with ``replica_bytes / dm``
    under the per-device budget (``n_devices`` if even full sharding cannot
    cover it — best effort, the dryrun report flags the overflow)."""
    budget = device_memory_budget(hbm_bytes)
    for dm in range(1, n_devices + 1):
        if n_devices % dm == 0 and math.ceil(replica_bytes / dm) <= budget:
            return dm
    return n_devices


def choose_engine(n_clients: int, n_devices: int,
                  replica_bytes: int | None = None,
                  hbm_bytes: int | None = None,
                  adversarial: bool = False) -> str:
    """The ``engine="auto"`` decision (see module docstring for the table)."""
    if n_devices <= 1:
        return "vmap"
    if (replica_bytes is not None and not adversarial
            and not replica_fits(replica_bytes, hbm_bytes)):
        return "mesh_2d"
    if n_client_shards(n_clients, n_devices) > 1:
        return "shard_map"
    return "vmap"


def default_mesh_shape(n_clients: int, n_devices: int,
                       replica_bytes: int | None = None,
                       hbm_bytes: int | None = None) -> tuple[int, int]:
    """Default ``(dc, dm)`` split of the local devices.

    ``dm`` is the smallest model-axis size that brings a replica under the
    per-device budget (1 when no footprint is known — all devices go to
    client blocks); the remaining factor becomes client blocks, clamped to
    the client count (padding handles non-dividing clients, but blocks
    beyond ``n_clients`` would sit empty)."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    dm = (1 if replica_bytes is None
          else model_shards_for(replica_bytes, n_devices, hbm_bytes))
    dc = max(1, min(n_devices // dm, n_clients))
    return dc, dm
