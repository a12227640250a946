"""repro.mesh — the pod-scale 2D client x model execution plane.

The 1D ``shard_map`` engine shards only the *client* axis: every device must
hold whole model replicas, which caps the model size at one device's memory.
This subsystem generalizes the round to a 2D mesh ``(dc, dm)`` built by
:func:`repro.launch.mesh.make_mesh_2d`:

* the **client axis** (size ``dc``) stays MANUAL — each mesh slab owns a
  contiguous block of client replicas and the Eq.-7b aggregation is the one
  explicit collective over it, exactly as in the 1D engine;
* the **model axis** (size ``dm``) is left to GSPMD (shard_map partial-auto
  mode): weights and activations shard 1/dm per the logical-axis rules of
  :func:`repro.models.sharding.mesh2d_rules`, so a replica that does not fit
  one device trains across its slab with zero changes to the round math.

Clients that do not divide ``dc`` are padded with inert rows (``valid = 0``
weights drop them from every mean exactly); the degenerate mesh
``(dc, 1)`` delegates to the 1D builder and is bit-identical to
``engine="shard_map"``. :mod:`repro.mesh.placement` holds the
``engine="auto"`` decision table: configs whose per-replica footprint
(:func:`repro.configs.shapes.replica_footprint_bytes`) exceeds the
per-device budget place onto ``mesh_2d``, everything else keeps the local
1D logic. Select via ``FederationSpec(engine="mesh_2d", mesh_shape=...,
sharding_rules=...)``.
"""
from repro.mesh.engine import default_param_specs, make_mesh_2d_round
from repro.mesh.placement import (
    choose_engine,
    default_mesh_shape,
    device_memory_budget,
    model_shards_for,
    n_client_shards,
    replica_fits,
)

__all__ = [
    "choose_engine",
    "default_mesh_shape",
    "default_param_specs",
    "device_memory_budget",
    "make_mesh_2d_round",
    "model_shards_for",
    "n_client_shards",
    "replica_fits",
]
