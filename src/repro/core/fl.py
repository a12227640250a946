"""DP-PASGD round engine (paper Eq. 7a–7b) as a composable JAX module.

One *round* = tau local noisy-SGD steps on each of C clients (zero
cross-client communication, expressed as a ``lax.scan``) followed by one
global model average over the client axis (Eq. 7b) — the single cross-client
collective per round. Params carry a leading client axis C on every leaf; on
a device mesh that axis is sharded over the derived ``client`` mesh axis, so
GSPMD lowers the round-boundary mean to exactly one all-reduce over the
client (and pod) axis.

The engine is generic over the model: it only needs ``loss_fn(params, batch)``.
The fully-local ablation (no averaging) is the same builder with
``topology="local_only"``; the explicit-collective variant lives in
``core/fl_shard_map.py``.

This module is the low-level building block. **New code should go through
``repro.api``** — a declarative :class:`repro.api.FederationSpec` selects
between this builder (engines ``"vmap"``/``"map"``), the shard_map variant
(engine ``"shard_map"``), and the topology, and the pure-functional
``init_state``/``run_round`` drive training. The mutable
:class:`repro.api.Federation` (re-exported here for back-compat) is a thin
wrapper over that functional core.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clipping import make_dp_grad_fn, make_plain_grad_fn
from repro.core.privacy import sigma_star
from repro.optim.optimizers import Optimizer
from repro.utils.tree import (
    tree_add,
    tree_broadcast_axis0,
    tree_mean_over_axis0,
)

TOPOLOGIES = ("full_average", "local_only")


@dataclass(frozen=True)
class FLConfig:
    """Configuration of one DP-PASGD federation."""
    n_clients: int
    tau: int                      # global aggregation period (local steps/round)
    clip_norm: float = 1.0        # G (sensitivity bound)
    dp: bool = True               # False -> PASGD (no noise, no clipping)
    num_microbatches: int = 1     # see clipping.py; =local batch -> per-example
    vmap_microbatches: bool = True
    grad_accumulate: str = "stack"  # "stack" (baseline) | "scan" (§Perf opt)
    average_opt_state: bool = True  # average optimizer state with the models
    vmap_clients: bool = True     # False -> lax.map (sequential clients; CPU sims)
    kernel_backend: str | None = None  # clip+noise path (clipping.py)
    #   None -> legacy double-rounding jnp path; "pallas"/"interpret"/
    #   "ref"/"auto" -> the leaf-wise single-rounding
    #   kernels.ops.dp_clip_noise_tree (repro.api default)


def make_grad_fn(loss_fn: Callable, cfg: FLConfig) -> Callable:
    """The per-step gradient: DP (clip + noise, Eq. 7a) or plain."""
    if cfg.dp:
        return make_dp_grad_fn(loss_fn, cfg.clip_norm, cfg.num_microbatches,
                               cfg.vmap_microbatches, cfg.grad_accumulate,
                               kernel_backend=cfg.kernel_backend)
    return make_plain_grad_fn(loss_fn)


def make_local_round(grad_fn: Callable, optimizer: Optimizer, tau: int,
                     unroll: int | bool = 1):
    """tau local DP-SGD steps of ONE client (Eq. 7a). No collectives.

    Returns ``local_round(params, opt_state, batches, key, sigma)`` ->
    ``(params, opt_state, metrics)`` with metrics averaged over the tau steps.
    Shared by the GSPMD/vmap engines here and the shard_map engines.

    ``unroll`` passes through to the tau scan — numerics are identical at
    any value. The mesh_2d engine builds with ``unroll=True`` (fully
    unrolled): on current jax/XLA the threefry custom partitioner aborts
    when RNG sits inside a while loop inside a partial-manual shard_map
    region (``Check failed: sharding.IsManualSubgroup()``), and unrolling
    removes the while loop without touching the values.
    """
    def local_round(params, opt_state, batches, key, sigma):
        keys = jax.random.split(key, tau)

        def step(carry, inp):
            p, s = carry
            mb, k = inp
            g, metrics = grad_fn(p, mb, k, sigma)
            upd, s = optimizer.update(g, s, p)
            return (tree_add(p, upd), s), metrics

        (params, opt_state), ms = jax.lax.scan(step, (params, opt_state),
                                               (batches, keys),
                                               unroll=unroll)
        return params, opt_state, jax.tree.map(jnp.mean, ms)

    return local_round


def tree_valid_mean_axis0(tree, valid, denom, all_sum=lambda x: x):
    """Mean over axis 0 of every leaf, weighted by the 0/1 ``valid`` vector
    and normalized by the (possibly cross-shard) ``denom`` count.

    The padded-client Eq.-7b boundary of the mesh_2d engine (repro.mesh):
    when C clients do not divide the client-block mesh axis, blocks are
    padded to Cp rows and pad rows carry ``valid = 0`` — this weighted form
    with ``denom = all_sum(sum(valid))`` reproduces the exact mean over the
    C real clients. Sums run in f32 and cast back per leaf (int leaves such
    as optimizer step counters round-trip exactly — weighted means of
    identical integers are integral). ``all_sum`` closes the cross-shard
    reduction, ``lax.psum`` over the client axis under shard_map."""
    def one(x):
        v = valid.reshape((-1,) + (1,) * (x.ndim - 1))
        s = all_sum(jnp.sum(v * x.astype(jnp.float32), axis=0))
        return (s / denom).astype(x.dtype)

    return jax.tree.map(one, tree)


def pipeline_round_keys(key, n_clients: int):
    """The per-round PRNG schedule of the pipeline engines: one local-round
    key and one aggregation (compressor) key per client, derived from a
    single round key. Shared by the GSPMD and shard_map builders so their
    key/compressor streams stay bit-identical and parity-testable."""
    key, agg_key = jax.random.split(key)
    keys = jax.random.split(key, n_clients)
    agg_keys = jax.random.split(agg_key, n_clients)
    return keys, agg_keys


def make_round_step(loss_fn: Callable, optimizer: Optimizer, cfg: FLConfig,
                    topology: str = "full_average", pipeline=None):
    """Build ``round_step(params, opt_state, batch, key, sigmas)``.

    params/opt_state : pytrees with leading client axis C on every leaf
    batch            : pytree with leading axes (C, tau, local_batch, ...)
    sigmas           : (C,) per-client per-step noise std (traced; Eq. 23)
    topology         : "full_average" (Eq. 7b averaging each round) or
                       "local_only" (ablation: fully-local training, no
                       cross-client communication ever)
    pipeline         : optional :class:`repro.core.aggregation
                       .AggregationPipeline`. ``None`` (the default) keeps
                       this builder bit-for-bit the seed protocol; with a
                       pipeline the returned function takes two extra
                       operands and threads the error-feedback residual:
                       ``round_step(params, opt_state, batch, key, sigmas,
                       mask, residual) -> (new_p, new_s, new_residual,
                       metrics)`` where ``mask`` is the 0/1 (C,)
                       participation mask sampled by the driver.
    returns          : (new_params, new_opt_state, metrics)
    """
    if topology not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                         f"got {topology!r}")
    if pipeline is not None and topology != "full_average":
        raise ValueError("the aggregation pipeline requires "
                         "topology='full_average'")
    local_round = make_local_round(make_grad_fn(loss_fn, cfg), optimizer,
                                   cfg.tau)

    def _local_rounds(params, opt_state, batch, keys, sigmas):
        if cfg.vmap_clients:
            return jax.vmap(local_round)(params, opt_state, batch,
                                         keys, sigmas)
        return jax.lax.map(lambda args: local_round(*args),
                           (params, opt_state, batch, keys, sigmas))

    def round_step(params, opt_state, batch, key, sigmas):
        keys = jax.random.split(key, cfg.n_clients)
        new_p, new_s, ms = _local_rounds(params, opt_state, batch, keys,
                                         sigmas)
        if topology == "full_average":
            # ---- Eq. (7b): periodic global averaging ----------------------
            with jax.named_scope("dppasgd.average"):
                avg = tree_mean_over_axis0(new_p)
                new_p = tree_broadcast_axis0(avg, cfg.n_clients)
                if cfg.average_opt_state:
                    # keep_dtype: int leaves (step counters) must come back
                    # as ints or the round is carry-unstable under scan
                    # chunking, undonatable, and retraced on its second call
                    new_s = tree_broadcast_axis0(
                        tree_mean_over_axis0(new_s, keep_dtype=True),
                        cfg.n_clients)
        ms = jax.tree.map(jnp.mean, ms)
        return new_p, new_s, ms

    def round_step_pipeline(params, opt_state, batch, key, sigmas, mask,
                            residual):
        keys, agg_keys = pipeline_round_keys(key, cfg.n_clients)
        new_p, new_s, ms = _local_rounds(params, opt_state, batch, keys,
                                         sigmas)
        new_p, new_s, residual = pipeline.aggregate(
            params, new_p, new_s, opt_state, residual, mask, agg_keys)
        ms = pipeline.masked_metrics(ms, mask)
        return new_p, new_s, residual, ms

    return round_step if pipeline is None else round_step_pipeline


def make_chunked_round(round_fn: Callable, *, pipeline: bool = False,
                       n_clients: int | None = None,
                       n_participants: int | None = None) -> Callable:
    """Fuse R rounds of ``round_fn`` into one ``lax.scan`` program (§Perf
    opt: the multi-round hot loop becomes device-resident — one XLA dispatch
    and one host sync per chunk instead of per round).

    Without a pipeline the returned function is

        chunk_fn(params, opt_state, batches, key, sigmas)
            -> (params, opt_state, key, metrics)

    with ``batches`` leaves shaped (R, C, tau, B, ...) — the R stacked round
    batches — and metrics leaves stacked (R,). With ``pipeline=True`` it is

        chunk_fn(params, opt_state, batches, key, sigmas, residual)
            -> (params, opt_state, key, residual, metrics, masks)

    where the per-round participation masks (returned stacked (R, C) so the
    host ledger can replay the realized sets) are sampled INSIDE the scan
    from the carried key with exactly ``repro.api.state.run_round``'s split
    schedule — the chunk is bit-identical to R sequential run_round calls.
    The chunk length R is read from ``batches`` at trace time, so one
    returned function serves every chunk size (jit retraces per R)."""
    if pipeline:
        if n_clients is None or n_participants is None:
            raise ValueError("pipeline chunking needs n_clients and "
                             "n_participants to sample masks inside the scan")
        from repro.core.aggregation import participation_mask

        def chunk_fn_pipeline(params, opt_state, batches, key, sigmas,
                              residual):
            def body(carry, batch):
                p, s, k, r = carry
                k, sub = jax.random.split(k)
                sub, mask_key = jax.random.split(sub)
                mask = participation_mask(mask_key, n_clients, n_participants)
                p, s, r, ms = round_fn(p, s, batch, sub, sigmas, mask, r)
                return (p, s, k, r), (ms, mask)

            (params, opt_state, key, residual), (ms, masks) = jax.lax.scan(
                body, (params, opt_state, key, residual), batches)
            return params, opt_state, key, residual, ms, masks

        return chunk_fn_pipeline

    def chunk_fn(params, opt_state, batches, key, sigmas):
        def body(carry, batch):
            p, s, k = carry
            k, sub = jax.random.split(k)
            p, s, ms = round_fn(p, s, batch, sub, sigmas)
            return (p, s, k), ms

        (params, opt_state, key), ms = jax.lax.scan(
            body, (params, opt_state, key), batches)
        return params, opt_state, key, ms

    return chunk_fn


def make_resident_chunked_round(round_fn: Callable, *, n_clients: int,
                                n_participants: int,
                                kernel_backend: str = "auto",
                                data_resident: bool = False) -> Callable:
    """:func:`make_chunked_round`'s pipeline form with a *fresh cohort per
    round*: the per-round cohorts' cache-slot indices are threaded into the
    scan as a stacked operand, and the error-feedback residual lives in the
    device-resident (S, D) cohort cache instead of a (K, D) carry (§Perf
    opt — the resident-population driver of
    :mod:`repro.population.resident`).

        chunk_fn(params, opt_state, batches, slots, key, sigmas, cache)
            -> (params, opt_state, key, cache, metrics, masks)

    ``batches`` leaves are (R, K, tau, B, ...), ``slots`` is the (R, K)
    int32 per-round cohort -> cache-slot map (host-precomputed from the
    same stateless ``(seed, round_idx)`` draw the per-round driver uses, so
    both drivers realize the identical cohort schedule), and ``cache`` is
    the (S, D) resident residual block. Each round gathers its cohort's K
    rows out of the cache, runs the unchanged pipeline round body with
    run_round's exact key-split schedule, and scatters the updated rows
    back — both movements through the fused ``cohort_gather_scatter``
    kernel (:mod:`repro.kernels.dispatch`), pure device ops: the chunk
    never blocks on the host for sticky state.

    ``data_resident=True`` is the stationary-population form: ``batches``
    is then the (S, tau, B, ...) warm-shard cache pytree (a scan constant,
    not a scanned operand) and each round's (K, tau, B, ...) batch is
    gathered from it by slot through the same kernel — the chunk reads NO
    per-round host-built data at all. Only exact when every client's shard
    is fixed (``ClientPopulation.stationary``); fresh-per-round sampling
    populations must stream ``batches`` as the stacked operand."""
    from repro.core.aggregation import participation_mask
    from repro.kernels.dispatch import resolve_backend
    from repro.kernels.ops import cohort_gather, cohort_scatter

    # resolve eagerly, at build time: capability probes run real arrays and
    # cannot run inside the traced scan body
    kernel_backend = resolve_backend("cohort_gather_scatter",
                                     kernel_backend or "auto")

    def chunk_fn(params, opt_state, batches, slots, key, sigmas, cache):
        def gather_shards(slot):
            # rows of every (S, ...) leaf for this round's cohort, moved by
            # the same slot-indexed kernel as the residual (leaves flatten
            # to (S, prod) row blocks; reshape is free)
            def one(x):
                rows = cohort_gather(x.reshape((x.shape[0], -1)), slot,
                                     backend=kernel_backend)
                return rows.reshape((slot.shape[0],) + x.shape[1:])
            return jax.tree.map(one, batches)

        def body(carry, operand):
            if data_resident:
                slot = operand
                batch = gather_shards(slot)
            else:
                batch, slot = operand
            p, s, k, c = carry
            k, sub = jax.random.split(k)
            sub, mask_key = jax.random.split(sub)
            mask = participation_mask(mask_key, n_clients, n_participants)
            # participation-only pipelines have no error-feedback state:
            # the cache carry is None (an empty pytree) and the round body
            # takes/returns residual=None, exactly like the dense form
            r = (cohort_gather(c, slot, backend=kernel_backend)
                 if c is not None else None)
            p, s, r, ms = round_fn(p, s, batch, sub, sigmas, mask, r)
            if c is not None:
                c = cohort_scatter(c, slot, r, backend=kernel_backend)
            return (p, s, k, c), (ms, mask)

        xs = slots if data_resident else (batches, slots)
        (params, opt_state, key, cache), (ms, masks) = jax.lax.scan(
            body, (params, opt_state, key, cache), xs)
        return params, opt_state, key, cache, ms, masks

    return chunk_fn


@dataclass
class Budgets:
    """Per-device budgets of the optimal-design problem (paper §5.3)."""
    c_th: float = float("inf")     # resource budget C_th
    eps_th: float = float("inf")   # privacy budget eps_th
    c1: float = 100.0              # comm cost / aggregation (paper §8.1 default)
    c2: float = 1.0                # compute cost / local step


def design_sigmas(k: int, clip_norm: float, batch_sizes: list[int],
                  eps_th: float, delta: float) -> np.ndarray:
    """Vector of Eq.-(23) optimal noise levels, one per client."""
    return np.asarray([sigma_star(k, clip_norm, x, eps_th, delta)
                       for x in batch_sizes], dtype=np.float32)


def __getattr__(name: str):
    # Back-compat: the stateful driver now lives in repro.api as a thin
    # wrapper over the functional core (imported lazily to avoid a cycle).
    if name == "Federation":
        from repro.api.federation import Federation
        return Federation
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
