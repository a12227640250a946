"""Fused cohort gather/scatter over the device-resident shard cache.

The resident-cohort driver (:mod:`repro.population.resident`) keeps the
sticky per-client state of S "warm" virtual clients as a device block —
the (S, D) error-feedback residual cache — and draws a fresh cohort of K
slot indices every round inside the fused ``lax.scan``. The per-round
boundary then needs exactly two data movements, both expressed here as one
Pallas kernel pair instead of host numpy:

* **gather**: ``rows = cache[slots]`` — the cohort's K rows pulled into the
  round's (K, D) block;
* **scatter**: ``cache[slots] = rows`` — the round's updated rows written
  back in place (``input_output_aliases`` pins the cache buffer, so the
  scan carry never double-buffers the S-row cache).

Both are pure row copies — no arithmetic — so every backend (mosaic,
interpret, jnp oracle) is bit-identical by construction; the dispatch
probe checks exact equality, not tolerance.

Implementation: the slot vector rides as a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``), available to the BlockSpec index maps
before the body runs — the canonical TPU pattern for index-driven gathers
(the block for grid step i is ``cache[slots[i]]``, DMA'd directly; no
one-hot matmul, no full-cache stream). Grid is (K,): one row block per
sampled cohort slot, so the kernel touches K*D elements of the S*D cache.

Tiling: the 128-aligned head of each D-row is viewed as ``(R, 128)``
lane tiles, and the grid is (K, ceil(R / block_rows)): one
``(block_rows, 128)`` VMEM block per step, with the row dimension squeezed
out of the block. A block therefore fits VMEM at any row width D — a real
replica's residual row is hundreds of millions of elements — and
``block_rows`` is a whole sublane tile for 32-, 16- and 8-bit dtypes (or
all of R when R is smaller). The ragged tail of fewer than 128 columns,
when D is not a multiple of 128, moves through ``jnp.take`` /
``.at[].set``: padding the whole cache to the lane boundary would copy it,
and at real widths XLA's TPU compiler takes minutes over that relayout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
DEFAULT_BLOCK_ROWS = 512      # (512, 128) f32 block = 256 KiB of VMEM


def _copy_kernel(slots_ref, src_ref, out_ref):
    del slots_ref                  # consumed by the index maps
    out_ref[...] = src_ref[...]


def _scatter_kernel(slots_ref, rows_ref, cache_ref, out_ref):
    del slots_ref, cache_ref       # cache is aliased into out, never read
    out_ref[...] = rows_ref[...]


def _tiled(cache, slots, rows, block_rows, interpret):
    """The kernel pair on a cache whose row width is a multiple of 128."""
    s, d = cache.shape
    k = slots.shape[0]
    r = d // _LANES
    br = r if r <= block_rows else block_rows
    grid = (k, pl.cdiv(r, br))
    block = (pl.squeezed, br, _LANES)
    by_slot = pl.BlockSpec(block, lambda i, j, slots: (slots[i], j, 0))
    by_step = pl.BlockSpec(block, lambda i, j, slots: (i, j, 0))
    tiles = cache.reshape(s, r, _LANES)
    if rows is None:
        out = pl.pallas_call(
            _copy_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[by_slot], out_specs=by_step),
            out_shape=jax.ShapeDtypeStruct((k, r, _LANES), cache.dtype),
            interpret=interpret,
        )(slots, tiles)
        return out.reshape(k, d)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[by_step, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=by_slot),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, cache.dtype),
        # operand order is (slots, rows, cache): alias the cache (input 2)
        # into the output so the resident block updates in place
        input_output_aliases={2: 0},
        interpret=interpret,
    )(slots, rows.reshape(k, r, _LANES), tiles)
    return out.reshape(s, d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cohort_gather_scatter(cache, slots, rows=None, *,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = False):
    """Gather (``rows=None``) or scatter rows of the (S, D) cohort cache.

    gather:  ``cohort_gather_scatter(cache, slots)`` -> (K, D) rows
    scatter: ``cohort_gather_scatter(cache, slots, rows)`` -> (S, D) cache'

    ``slots`` is the cohort's (K,) int32 cache-slot vector — unique by the
    cohort-sampler contract, so the scatter is order-independent. The
    scatter aliases the cache operand into the output: under jit/scan the
    S-row cache updates in place (§Perf opt — the whole point of keeping
    the warm set resident) whenever D is a multiple of 128.
    """
    d = cache.shape[1]
    head = d - d % _LANES
    slots = slots.astype(jnp.int32)
    if rows is not None:
        rows = rows.astype(cache.dtype)
    if head == d:
        return _tiled(cache, slots, rows, block_rows, interpret)
    if rows is None:
        tail = jnp.take(cache[:, head:], slots, axis=0)
    else:
        tail = cache[:, head:].at[slots].set(rows[:, head:])
    if head == 0:
        return tail
    body = _tiled(cache[:, :head], slots,
                  None if rows is None else rows[:, :head],
                  block_rows, interpret)
    return jnp.concatenate([body, tail], axis=1)
