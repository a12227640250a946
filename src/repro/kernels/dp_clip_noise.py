"""Fused DP clip+noise Pallas kernel over one flat vector (Eq. 7a touches
every gradient element twice: norm pass + scale/noise pass).

It is the flat-vector primitive (``ops.dp_clip_noise_flat``), not the
training path's: with the noise as an operand it moves 16 bytes per f32
element, where the leaf-wise jnp form that training uses
(``ops.dp_clip_noise_tree``) draws the noise inside its one fused pass.

Two kernels over the flat vector laid out as ``(rows, 128)`` lane tiles and
streamed in ``(block_rows, 128)`` VMEM blocks:
  1. a partial reduction (``partial_reduce``): per-block sums of squares,
     folded to one ``(8, 128)`` f32 tile per grid step (a tile-legal
     partial; the host graph sums the partials).
  2. ``_scale_noise_kernel``: y = g * scale + sigma * noise, streaming blocks.

The layout is a reshape, not a copy, whenever N is a multiple of 8 * 128:
the grid's last block may run past the end of the array, the reduction
masks those rows, and Pallas drops the elementwise writes there. Padding
a replica-wide vector to whole blocks would instead copy it, and each
input and the output of a call would then cost another N floats of HBM.

The scalars (scale, sigma) ride in SMEM as a scalar-prefetch operand:
unlike an SMEM BlockSpec operand, that stays legal when ``jax.vmap``
batches the call over clients (Pallas then loops the kernel over the
batch). Noise is an input (generated by the caller's PRNG) so the kernel
stays deterministic and oracle-checkable; on TPU the random bits could
instead be drawn in-kernel with ``pltpu.prng_random_bits`` (documented
trade-off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# row alignment that is a whole sublane tile for 32-, 16- and 8-bit dtypes
ROW_ALIGN = 32
DEFAULT_BLOCK = 512 * LANES   # elements per block: 256 KiB of f32


def tile_layout(x, block: int):
    """Flat (N,) -> (rows, 128) lane tiles and the row count of one block.

    ``block`` is the tile size in elements (a multiple of 32 * 128). A
    vector of at least one block is only reshaped; just a ragged lane tail
    (N % 128) is zero-padded. A smaller vector is one block, zero-padded
    to whole (32, 128) tiles, so a 17-element vector is one (32, 128)
    block, not a full default block.
    """
    if block % (ROW_ALIGN * LANES):
        raise ValueError(f"block must be a multiple of {ROW_ALIGN * LANES} "
                         f"elements, got {block}")
    n = x.shape[0]
    rows = pl.cdiv(max(n, 1), LANES)
    block_rows = block // LANES
    if rows < block_rows:
        rows = block_rows = pl.cdiv(rows, ROW_ALIGN) * ROW_ALIGN
    pad = rows * LANES - n
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(rows, LANES), block_rows


def partial_reduce(x2, block_rows: int, fn, op, *, interpret: bool):
    """Per-block partials of ``op`` over ``fn`` of the f32 ``(rows, 128)``
    array: one ``(8, 128)`` tile per grid step, for the caller to finish
    with ``op`` over all of them. Rows past the end of the array (the last
    block's overhang) count as zeros, so ``op`` must treat zero as neutral
    for ``fn``'s values (sum of squares, max of magnitudes)."""
    rows = x2.shape[0]
    steps = pl.cdiv(rows, block_rows)

    def kernel(x_ref, out_ref):
        x = fn(x_ref[...].astype(jnp.float32))
        if rows % block_rows:
            row = (pl.program_id(0) * block_rows
                   + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
            x = jnp.where(row < rows, x, 0.0)
        out_ref[...] = op(x.reshape(-1, 8, LANES), axis=0)

    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * 8, LANES), jnp.float32),
        interpret=interpret,
    )(x2)


def scaled_call(kernel, scalars, *operands, block_rows: int,
                interpret: bool):
    """Stream (rows, 128) ``operands`` through ``kernel`` with the f32
    ``scalars`` vector in SMEM; the output has the first operand's
    shape and dtype."""
    spec = pl.BlockSpec((block_rows, LANES), lambda i, _scalars: (i, 0))
    rows = operands[0].shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block_rows),),
            in_specs=[spec] * len(operands),
            out_specs=spec),
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, operands[0].dtype),
        interpret=interpret,
    )(scalars.astype(jnp.float32), *operands)


def _scale_noise_kernel(scalars_ref, g_ref, n_ref, out_ref):
    scale = scalars_ref[0]
    sigma = scalars_ref[1]
    g = g_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    out_ref[...] = (g * scale + sigma * n).astype(out_ref.dtype)


def _scale_kernel(scalars_ref, g_ref, out_ref):
    # clip-only variant: no noise operand streamed, no dead multiply-add
    out_ref[...] = (g_ref[...].astype(jnp.float32) * scalars_ref[0]
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret"))
def dp_clip_noise(g, noise, clip_norm, sigma, *, block: int = DEFAULT_BLOCK,
                  interpret: bool = False):
    """g, noise: flat (N,) arrays. Returns (clipped+noised (N,), norm ()).

    ``noise=None`` selects the clip-only lowering: the noise operand is
    never materialized or streamed (the microbatch paths clip many times
    but noise once, so the zeros buffer would be pure waste).
    """
    n = g.shape[0]
    g2, block_rows = tile_layout(g, block)
    partial = partial_reduce(g2, block_rows, jnp.square, jnp.sum,
                             interpret=interpret)
    norm = jnp.sqrt(jnp.sum(partial))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))

    if noise is None:
        out = scaled_call(_scale_kernel, scale[None], g2,
                          block_rows=block_rows, interpret=interpret)
    else:
        n2, _ = tile_layout(noise, block)
        out = scaled_call(_scale_noise_kernel, jnp.stack([scale, sigma]),
                          g2, n2, block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:n], norm
