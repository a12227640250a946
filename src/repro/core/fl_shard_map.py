"""shard_map DP-PASGD round: explicit collective schedule (Eq. 7a-7b).

The GSPMD engine in core/fl.py lets the partitioner place the round-boundary
all-reduce. This variant instead expresses the schedule explicitly with
``jax.shard_map``: each mesh slot along the ``client`` axis owns a contiguous
*block* of ``n_clients / mesh.shape[client]`` model replicas, runs tau local
noisy-SGD steps per replica with ZERO collectives, then one
``jax.lax.pmean`` over the client axis is the aggregation — byte-for-byte
the paper's protocol, and the single point where cross-client traffic can
exist. With fewer devices than clients the block is vmapped locally, so the
same engine runs 23-client CPU simulations and pod-scale slab-per-client
runs unchanged.

**New code should select this engine via ``repro.api``**
(``FederationSpec(engine="shard_map")``) rather than calling
:func:`make_shard_map_round` directly; the facade builds the client mesh and
unifies the call signature with the GSPMD engines. The per-step clip+noise
inside each shard follows ``FLConfig.kernel_backend`` (see
:mod:`repro.core.clipping`), identically to the GSPMD engines, and composes
under ``shard_map`` + ``vmap`` + ``scan``.

The built round also composes under the fused multi-round chunking of
:func:`repro.core.fl.make_chunked_round` (an outer ``lax.scan`` carrying
params/opt_state/key/residual with the per-round collective inside) and
under ``jax.jit`` buffer donation — every carried operand keeps its dtype
across the round, so donated client replicas are reused in place.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fl import (
    FLConfig,
    TOPOLOGIES,
    make_grad_fn,
    make_local_round,
    pipeline_round_keys,
)
from repro.optim.optimizers import Optimizer
from repro.utils.tree import tree_broadcast_axis0


def _shard_map(f, mesh, in_specs, out_specs, auto=frozenset()):
    """``jax.shard_map`` with replication checking off (the out_specs
    deliberately mix P(client) and P()).

    ``auto`` names mesh axes left under GSPMD control (partial-manual mode):
    the body is manual over the remaining axes only, and operands keep
    whatever sharding the partitioner gave them along the auto axes. The
    mesh_2d engine (repro.mesh) runs with ``auto={"model"}`` so model
    tensors stay sharded straight through the per-client round body."""
    manual = frozenset(mesh.axis_names) - frozenset(auto)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


def make_shard_map_round(loss_fn: Callable, optimizer: Optimizer,
                         cfg: FLConfig, mesh: Mesh,
                         client_axis: str = "client",
                         topology: str = "full_average", pipeline=None):
    """Build round_step(params, opt_state, batch, key, sigmas) on ``mesh``.

    params/opt_state carry a leading client axis sharded over ``client_axis``
    (local view inside the shard_map has leading dim n_clients / n_shards).
    batch leaves are (C, tau, B, ...); sigmas is (C,).

    With an :class:`repro.core.aggregation.AggregationPipeline` the built
    function takes ``(params, opt_state, batch, key, sigmas, mask, residual)``
    and returns ``(new_p, new_s, new_residual, metrics)`` — identical
    signature and per-client key/compressor streams as the GSPMD engines, so
    the three engines stay parity-testable under every pipeline setting. The
    mask, residual, and per-client compressor keys are sharded over
    ``client_axis`` like everything else; the cross-shard reduction is the
    same single ``lax.pmean``-class collective (a psum of masked block sums).
    """
    if topology not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                         f"got {topology!r}")
    if pipeline is not None and topology != "full_average":
        raise ValueError("the aggregation pipeline requires "
                         "topology='full_average'")
    n_shards = mesh.shape[client_axis]
    if cfg.n_clients % n_shards:
        raise ValueError(f"{cfg.n_clients} clients do not divide over "
                         f"{n_shards} '{client_axis}' mesh slots")
    block = cfg.n_clients // n_shards
    local_round = make_local_round(make_grad_fn(loss_fn, cfg), optimizer,
                                   cfg.tau)

    def per_shard(params, opt_state, batches, keys, sigmas):
        """Local view: leading axis = block (this slot's client replicas)."""
        new_p, new_s, ms = jax.vmap(local_round)(params, opt_state, batches,
                                                 keys, sigmas)
        ms = jax.tree.map(jnp.mean, ms)         # mean over the local block
        if topology == "full_average":
            # ---- Eq. (7b): THE collective — one pmean over the client axis
            # (local block mean first, so the pmean moves C/n_shards fewer
            # bytes than an all-gather would).
            pmean = lambda x: jax.lax.pmean(x, axis_name=client_axis)
            with jax.named_scope("dppasgd.average"):
                avg = jax.tree.map(lambda x: pmean(jnp.mean(x, axis=0)),
                                   new_p)
                new_p = tree_broadcast_axis0(avg, block)
                if cfg.average_opt_state:
                    avg_s = jax.tree.map(
                        lambda x: pmean(jnp.mean(x.astype(jnp.float32),
                                                 axis=0)).astype(x.dtype),
                        new_s)
                    new_s = tree_broadcast_axis0(avg_s, block)
        ms = jax.tree.map(lambda x: jax.lax.pmean(x, client_axis), ms)
        return new_p, new_s, ms

    def per_shard_pipeline(params, opt_state, batches, keys, agg_keys,
                           sigmas, mask, residual):
        """Pipeline variant: masked/compressed Eq.-7b with error feedback.
        The collective is one psum of the block's masked update sums —
        except under the adversarial extensions (robust aggregator /
        secure sum / update attack), whose reductions do not decompose
        into block partial sums: there the pipeline gathers the blocks
        into the full (C, ...) view via ``all_gather`` (tiled along the
        client axis, so row order matches the GSPMD engines) and every
        shard computes the identical global result."""
        new_p, new_s, ms = jax.vmap(local_round)(params, opt_state, batches,
                                                 keys, sigmas)
        psum = lambda x: jax.lax.psum(x, axis_name=client_axis)
        gather = lambda x: jax.lax.all_gather(x, client_axis, axis=0,
                                              tiled=True)
        new_p, new_s, residual = pipeline.aggregate(
            params, new_p, new_s, opt_state, residual, mask, agg_keys,
            all_sum=psum, all_gather=gather)
        ms = pipeline.masked_metrics(ms, mask, all_sum=psum)
        return new_p, new_s, residual, ms

    cspec = P(client_axis)
    if pipeline is None:
        smapped = _shard_map(
            per_shard, mesh,
            in_specs=(cspec, cspec, cspec, cspec, cspec),
            out_specs=(cspec, cspec, P()))

        def round_step(params, opt_state, batch, key, sigmas):
            keys = jax.random.split(key, cfg.n_clients)
            return smapped(params, opt_state, batch, keys, sigmas)

        return round_step

    smapped = _shard_map(
        per_shard_pipeline, mesh,
        in_specs=(cspec,) * 8,
        out_specs=(cspec, cspec, cspec, P()))

    def round_step_pipeline(params, opt_state, batch, key, sigmas, mask,
                            residual):
        keys, agg_keys = pipeline_round_keys(key, cfg.n_clients)
        return smapped(params, opt_state, batch, keys, agg_keys, sigmas,
                       mask, residual)

    return round_step_pipeline
