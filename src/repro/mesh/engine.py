"""The mesh_2d round: DP-PASGD on a ("client", "model") 2D mesh.

Structure of one round (Eq. 7a-7b on the 2D mesh of
:func:`repro.launch.mesh.make_mesh_2d`):

* the **client axis** is MANUAL, exactly as in the 1D
  :mod:`repro.core.fl_shard_map` engine — each of the ``dc`` slabs owns a
  contiguous block of client replicas and the only cross-slab collective is
  the Eq.-7b reduction (a psum of block partial sums);
* the **model axis** is AUTO (shard_map partial-manual mode): inside the
  per-slab body every model tensor keeps whatever GSPMD sharding it carries
  from outside, so the tau-step local scan runs 1/dm-sharded over the slab's
  ``dm`` devices. The logical-axis rules
  (:func:`repro.models.sharding.mesh2d_rules` by default) and the
  :func:`default_param_specs` input constraints pin that layout.

Clients that do not divide ``dc`` are padded to ``Cp = ceil(C/dc) * dc``
rows. Pad rows are *copies of client 0's operands* — their local rounds
compute real (finite, same-dtype) values so nothing poisons a mean via
NaN * 0 — and a ``valid`` 0/1 vector drops them from every aggregate
exactly (:func:`repro.core.fl.tree_valid_mean_axis0`; the pipeline path
zero-pads the participation mask instead, which its masked sums already
handle). The degenerate mesh ``(dc, 1)`` with dividing clients delegates to
:func:`repro.core.fl_shard_map.make_shard_map_round` verbatim, making
bitwise identity with ``engine="shard_map"`` structural rather than
numerical luck.

The adversarial extensions (robust aggregators, secure sum, update attacks)
are full-view reductions over exactly ``n_clients`` gathered rows and do not
compose with the padded client axis — ``FederationSpec`` validation refuses
them on this engine (use ``engine="shard_map"``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fl import (
    FLConfig,
    TOPOLOGIES,
    make_grad_fn,
    make_local_round,
    pipeline_round_keys,
    tree_valid_mean_axis0,
)
from repro.core.fl_shard_map import _shard_map, make_shard_map_round
from repro.models.sharding import axis_rules, mesh2d_rules
from repro.optim.optimizers import Optimizer
from repro.utils.tree import tree_broadcast_axis0

CLIENT_AXIS = "client"
MODEL_AXIS = "model"


def default_param_specs(tree, dm: int, *, client_axis: str = CLIENT_AXIS,
                        model_axis: str = MODEL_AXIS):
    """Per-leaf PartitionSpecs for client-stacked state on the 2D mesh.

    Every leaf carries the leading client axis; with ``dm > 1`` the model
    axis lands on the LARGEST remaining dim divisible by ``dm`` (the dim
    whose sharding saves the most memory — for a (C, d_in, d_out) weight
    that is the bigger of the two matmul dims, matching what
    ``mesh2d_rules`` picks for annotated layers). Leaves with no shardable
    dim (per-client scalars like optimizer step counters) replicate over
    the model axis. Used to constrain params/opt_state at the shard_map
    boundary so GSPMD starts from the intended layout instead of
    discovering one per jit cache entry.
    """
    def one(x):
        spec: list = [client_axis] + [None] * (x.ndim - 1)
        if dm > 1:
            sizes = [(x.shape[i], i) for i in range(1, x.ndim)
                     if x.shape[i] % dm == 0 and x.shape[i] >= dm]
            if sizes:
                spec[max(sizes)[1]] = model_axis
        while len(spec) > 1 and spec[-1] is None:
            spec.pop()
        return P(*spec)

    return jax.tree.map(one, tree)


def _constrain(tree, mesh: Mesh, dm: int):
    specs = default_param_specs(tree, dm)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)), tree, specs)


def _replicate(tree, mesh: Mesh):
    """Pin every leaf fully replicated (wsc P()) before it enters the
    partial-auto shard_map region.

    Load-bearing, not an optimization: on current XLA, an operand whose
    producer op carries an inferred (non-fully-specified) sharding gets
    corrupted data movement at the partial-manual boundary — e.g. raw
    ``jax.random.split`` keys or concatenated masks arrive as garbage
    inside the body. An explicit replicated constraint is the one
    annotation that reliably survives the boundary for every dtype/rank
    tested; see the padding-parity pins in tests/test_mesh.py."""
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())), tree)


def _pad_one(x, pad: int, row0: bool):
    """Pad ``x`` to ``pad`` extra rows via dynamic_update_slice into a zero
    buffer (optionally re-writing row 0 into each pad row).

    DELIBERATELY not ``jnp.concatenate``/``broadcast_to``/``jnp.pad``/
    gather: on current XLA, operands built by those ops and fed into a
    partial-manual shard_map region come out with corrupted data movement
    (sharding propagation across the manual-subgroup boundary mishandles
    their producer shardings; the same family of bug as the
    IsManualSubgroup abort). DUS-built buffers round-trip exactly — pinned
    by the padding-parity tests in tests/test_mesh.py."""
    n = x.shape[0]
    buf = jax.lax.dynamic_update_slice(
        jnp.zeros((n + pad,) + x.shape[1:], x.dtype), x, (0,) * x.ndim)
    if row0:
        first = jax.lax.dynamic_slice(x, (0,) * x.ndim,
                                      (1,) + x.shape[1:])
        for i in range(pad):
            buf = jax.lax.dynamic_update_slice(
                buf, first, (n + i,) + (0,) * (x.ndim - 1))
    return buf


def _pad_rows(tree, pad: int):
    """Append ``pad`` copies of row 0 along axis 0 of every leaf (inert but
    numerically well-behaved pad clients)."""
    if pad == 0:
        return tree
    return jax.tree.map(lambda x: _pad_one(x, pad, row0=True), tree)


def _pad_zero_rows(tree, pad: int):
    if pad == 0 or tree is None:
        return tree
    return jax.tree.map(lambda x: _pad_one(x, pad, row0=False), tree)


def _unpad_rows(tree, n: int):
    return jax.tree.map(lambda x: x[:n], tree)


def _refuse_mosaic_under_model_axis(cfg: FLConfig, pipeline) -> None:
    """XLA cannot partition a Mosaic kernel over the auto model axis ("Mosaic
    kernels cannot be automatically partitioned"), so a model-sharded
    mesh_2d round takes the jnp clip+noise and compressor: say so when the
    round is built, not as a compile error on the chip."""
    from repro.kernels.dispatch import resolve_backend
    kernels = []
    if cfg.kernel_backend is not None:
        kernels.append(("dp_clip_noise", cfg.kernel_backend))
    compressor = getattr(pipeline, "compressor", None)
    if getattr(compressor, "kernel_backend", None) is not None:
        kernels.append(("quantize_decompress", compressor.kernel_backend))
    for name, backend in kernels:
        if resolve_backend(name, backend) == "pallas":
            raise ValueError(
                f"mesh_2d with a model axis cannot run the Mosaic {name} "
                f"kernel: XLA cannot partition it over the model axis. Use "
                f"kernel_backend='ref' (or a (C, 1) mesh).")


def make_mesh_2d_round(loss_fn: Callable, optimizer: Optimizer,
                       cfg: FLConfig, mesh: Mesh, *, rules=None,
                       topology: str = "full_average", pipeline=None,
                       constrain_params: bool = True):
    """Build ``round_step`` on a 2D ("client", "model") mesh.

    Signature and key/compressor streams are identical to the other engines:
    ``(params, opt_state, batch, key, sigmas) -> (new_p, new_s, metrics)``,
    or with ``pipeline`` the 7-operand masked/residual form. ``rules`` is a
    logical->mesh dict for the model annotations (default
    :func:`repro.models.sharding.mesh2d_rules`); ``constrain_params=False``
    skips the boundary layout constraints and lets GSPMD choose freely.
    """
    if topology not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                         f"got {topology!r}")
    if pipeline is not None and topology != "full_average":
        raise ValueError("the aggregation pipeline requires "
                         "topology='full_average'")
    if pipeline is not None and (pipeline.aggregator is not None
                                 or pipeline.secure is not None
                                 or pipeline.attack is not None):
        raise ValueError(
            "mesh_2d does not support the adversarial extensions (robust "
            "aggregator / secure sum / update attack): their full-view "
            "reductions do not compose with the padded client axis. Use "
            "engine='shard_map'.")
    dc = mesh.shape[CLIENT_AXIS]
    dm = mesh.shape[MODEL_AXIS]
    C = cfg.n_clients
    Cp = -(-C // dc) * dc
    pad = Cp - C
    if dm == 1 and pad == 0:
        # Degenerate mesh: the 1D engine body on the same devices. The
        # "model" axis (size 1) is manual-but-unused, which is bitwise
        # identical to the 1D ("client",) mesh — pinned by tests/test_mesh.
        return make_shard_map_round(loss_fn, optimizer, cfg, mesh,
                                    client_axis=CLIENT_AXIS,
                                    topology=topology, pipeline=pipeline)
    _refuse_mosaic_under_model_axis(cfg, pipeline)
    block = Cp // dc
    rules = mesh2d_rules() if rules is None else dict(rules)
    # unroll=True: RNG inside a while loop inside a partial-manual shard_map
    # region aborts XLA's sharding propagation (IsManualSubgroup check);
    # fully unrolling the tau scan removes the loop, values unchanged
    local_round = make_local_round(make_grad_fn(loss_fn, cfg), optimizer,
                                   cfg.tau, unroll=True)
    psum = lambda x: jax.lax.psum(x, axis_name=CLIENT_AXIS)

    def per_shard(params, opt_state, batches, keys, sigmas, valid):
        """Local view: leading axis = block; model tensors stay GSPMD-
        sharded over the (auto) model axis throughout."""
        new_p, new_s, ms = jax.vmap(local_round)(params, opt_state, batches,
                                                 keys, sigmas)
        denom = psum(jnp.sum(valid))
        if topology == "full_average":
            # ---- Eq. (7b) with pad rows weighted out: valid-weighted block
            # sums, one psum over the client axis, broadcast back.
            avg = tree_valid_mean_axis0(new_p, valid, denom, all_sum=psum)
            new_p = tree_broadcast_axis0(avg, block)
            if cfg.average_opt_state:
                avg_s = tree_valid_mean_axis0(new_s, valid, denom,
                                              all_sum=psum)
                new_s = tree_broadcast_axis0(avg_s, block)
        ms = tree_valid_mean_axis0(ms, valid, denom, all_sum=psum)
        return new_p, new_s, ms

    def local_rounds(params, opt_state, batches, keys, sigmas):
        """Stage 1 (partial-auto): the tau local steps of this slab's block,
        model tensors GSPMD-sharded, ZERO collectives."""
        return jax.vmap(local_round)(params, opt_state, batches, keys,
                                     sigmas)

    def aggregate_pipeline(params, new_p, new_s, opt_state, residual, mask,
                           agg_keys, ms):
        """Stage 2 (FULL-manual over both axes): the pipeline Eq.-7b seam.
        The compressor's flatten / top_k / scatter ops do not lower under
        the partial-auto partitioner, so this stage runs with the model
        axis manual-but-unused — every model device computes the reduction
        redundantly on gathered whole updates, exactly the 1D engine's
        semantics. Pad rows enter with mask = 0, so the masked sums /
        denominators of ``pipeline.aggregate`` drop them exactly."""
        new_p, new_s, residual = pipeline.aggregate(
            params, new_p, new_s, opt_state, residual, mask, agg_keys,
            all_sum=psum)
        ms = pipeline.masked_metrics(ms, mask, all_sum=psum)
        return new_p, new_s, residual, ms

    cspec = P(CLIENT_AXIS)
    auto = frozenset({MODEL_AXIS})
    if pipeline is None:
        smapped = _shard_map(per_shard, mesh,
                             in_specs=(cspec,) * 6,
                             out_specs=(cspec, cspec, P()),
                             auto=auto)

        def round_step(params, opt_state, batch, key, sigmas):
            keys = jax.random.split(key, C)
            with axis_rules(mesh, rules):
                params = _pad_rows(params, pad)
                opt_state = _pad_rows(opt_state, pad)
                if constrain_params:
                    params = _constrain(params, mesh, dm)
                    opt_state = _constrain(opt_state, mesh, dm)
                valid = _replicate(
                    _pad_one(jnp.ones((C,), jnp.float32), pad, row0=False),
                    mesh)
                new_p, new_s, ms = smapped(
                    params, opt_state,
                    _replicate(_pad_rows(batch, pad), mesh),
                    _replicate(_pad_rows(keys, pad), mesh),
                    _replicate(_pad_rows(sigmas, pad), mesh), valid)
                if constrain_params:
                    new_p = _constrain(new_p, mesh, dm)
                    new_s = _constrain(new_s, mesh, dm)
            return _unpad_rows(new_p, C), _unpad_rows(new_s, C), ms

        return round_step

    smapped_local = _shard_map(local_rounds, mesh,
                               in_specs=(cspec,) * 5,
                               out_specs=(cspec, cspec, cspec),
                               auto=auto)
    smapped_agg = _shard_map(aggregate_pipeline, mesh,
                             in_specs=(cspec,) * 8,
                             out_specs=(cspec, cspec, cspec, P()))

    def round_step_pipeline(params, opt_state, batch, key, sigmas, mask,
                            residual):
        keys, agg_keys = pipeline_round_keys(key, C)
        with axis_rules(mesh, rules):
            params = _pad_rows(params, pad)
            opt_state = _pad_rows(opt_state, pad)
            if constrain_params:
                params = _constrain(params, mesh, dm)
                opt_state = _constrain(opt_state, mesh, dm)
            new_p, new_s, ms = smapped_local(
                params, opt_state,
                _replicate(_pad_rows(batch, pad), mesh),
                _replicate(_pad_rows(keys, pad), mesh),
                _replicate(_pad_rows(sigmas, pad), mesh))
            new_p, new_s, residual, ms = smapped_agg(
                params, new_p, new_s, opt_state,
                _pad_zero_rows(residual, pad),
                _replicate(_pad_zero_rows(mask, pad), mesh),
                _replicate(_pad_rows(agg_keys, pad), mesh), ms)
            if constrain_params:
                new_p = _constrain(new_p, mesh, dm)
                new_s = _constrain(new_s, mesh, dm)
        return (_unpad_rows(new_p, C), _unpad_rows(new_s, C),
                _unpad_rows(residual, C), ms)

    return round_step_pipeline
