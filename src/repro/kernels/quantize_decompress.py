"""Fused QSGD quantize->dequantize Pallas kernel (stochastic rounding).

The compressed-communication hot-spot of the aggregation pipeline: each
participating client's flat update is stochastically quantized to
``bits``-bit levels and immediately dequantized (the repo simulates the wire
in dense arrays; see :mod:`repro.core.aggregation`). Fusing the round trip
means the update streams through VMEM once instead of materializing the
integer codes in HBM.

Two kernels over the ``(rows, 128)`` lane layout of dp_clip_noise, with
``(block_rows, 128)`` VMEM blocks and the scale in SMEM:
  1. a partial reduction (``dp_clip_noise.partial_reduce``): per-block
     max(|x|), folded to one ``(8, 128)`` tile per grid step (grid
     reduction -> scale).
  2. ``_quant_kernel``:  y = sign(x) * floor(|x|/scale + u) * scale,
     streaming tiles; ``u ~ U[0,1)`` drives the stochastic rounding.

The rounding randomness is an input (generated by the caller's PRNG) so the
kernel stays deterministic and oracle-checkable across backends; on TPU the
bits could instead be drawn in-kernel with ``pltpu.prng_random_bits`` +
``pltpu.stochastic_round`` (documented trade-off, same as dp_clip_noise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dp_clip_noise import (
    DEFAULT_BLOCK,
    partial_reduce,
    scaled_call,
    tile_layout,
)


def _quant_kernel(scale_ref, x_ref, u_ref, out_ref):
    scale = scale_ref[0]
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    level = jnp.floor(jnp.abs(x) / scale + u)
    out_ref[...] = (jnp.sign(x) * level * scale).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def quantize_decompress(x, u, bits: int, *, block: int = DEFAULT_BLOCK,
                        interpret: bool = False):
    """x, u: flat (N,) arrays (u ~ U[0,1)). Returns (dequantized (N,), scale).

    ``scale = max|x| / (2**bits - 1)`` is the uniform quantization step the
    wire format would ship alongside the integer codes; levels fall in
    [0, 2**bits - 1] so the codes genuinely fit ``bits`` bits. All-zero
    inputs round-trip to zeros (scale floored away from 0). Padding and
    the last block's overhang count as zeros, so they never raise the
    absmax.
    """
    levels = (1 << bits) - 1
    n = x.shape[0]
    x2, block_rows = tile_layout(x, block)
    u2, _ = tile_layout(u, block)
    partial = partial_reduce(x2, block_rows, jnp.abs, jnp.max,
                             interpret=interpret)
    scale = jnp.maximum(jnp.max(partial), 1e-30) / levels

    out = scaled_call(_quant_kernel, scale[None], x2, u2,
                      block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:n], scale
