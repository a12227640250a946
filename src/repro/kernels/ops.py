"""Public kernel wrappers, routed through the backend dispatch layer.

Every wrapper resolves its implementation via
:func:`repro.kernels.dispatch.get_kernel` (``backend="auto"`` by default):
Mosaic-compiled Pallas on a TPU — or an error there, never a silent
substitute — and the pure-jnp oracle everywhere else. ``backend=
"interpret"`` runs the Pallas body as jax ops, the CPU rehearsal of the
TPU kernel.

``dp_clip_noise_tree``, the DP-PASGD step's clip+noise, is plain jnp and
dispatches nothing: the flat ``dp_clip_noise`` kernel stays available as
``dp_clip_noise_flat``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import get_kernel
from repro.kernels.dp_clip_noise import DEFAULT_BLOCK
from repro.utils.tree import tree_sq_norm


def dp_clip_noise_flat(g, noise, clip_norm, sigma, block: int = DEFAULT_BLOCK,
                       backend: str = "auto"):
    """Fused clip+noise on flat (N,) arrays; returns (y, pre-clip norm)."""
    return get_kernel("dp_clip_noise", backend)(g, noise, clip_norm, sigma,
                                                block=block)


def dp_clip_noise_tree(grads, key, clip_norm, sigma):
    """Clip a gradient pytree to global L2 norm ``clip_norm`` and add
    N(0, sigma^2) noise, leaf by leaf, in each leaf's own shape and dtype.

    Returns (tree, pre-clip norm). Each output leaf is
    ``(g * scale + sigma * normal(k_i, g.shape)).astype(g.dtype)``, the
    product and sum in f32 and rounded once, with ``k_i`` the i-th of
    ``jax.random.split(key, n_leaves)`` in ``jax.tree.flatten`` order: the
    noise stream of ``tree_add_noise``. ``key=None`` clips only.

    Nothing is flattened: XLA fuses each leaf's draw, scale and add into
    one elementwise pass that reads the gradient and writes the result, so
    neither the noise nor an f32 copy of the leaf reaches memory. The flat
    ``dp_clip_noise`` kernel would need the noise as an operand, which
    moves more bytes than this pass does.
    """
    leaves, treedef = jax.tree.flatten(grads)
    norm = jnp.sqrt(tree_sq_norm(leaves))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    if key is None:
        out = [(g.astype(jnp.float32) * scale).astype(g.dtype)
               for g in leaves]
    else:
        keys = jax.random.split(key, len(leaves))
        out = [(g.astype(jnp.float32) * scale
                + sigma * jax.random.normal(k, g.shape, jnp.float32)
                ).astype(g.dtype)
               for k, g in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out), norm


def quantize_decompress_flat(x, u, bits: int, block: int = DEFAULT_BLOCK,
                             backend: str = "auto"):
    """Fused QSGD quantize->dequantize round trip on flat (N,) arrays.

    ``u ~ U[0,1)`` supplies the stochastic-rounding randomness (caller PRNG,
    like the noise operand of dp_clip_noise). Returns (y, scale)."""
    return get_kernel("quantize_decompress", backend)(x, u, bits, block=block)


def cohort_gather(cache, slots, backend: str = "auto"):
    """Gather the cohort's (K, D) rows out of the (S, D) resident cache."""
    return get_kernel("cohort_gather_scatter", backend)(cache, slots)


def cohort_scatter(cache, slots, rows, backend: str = "auto"):
    """Scatter the cohort's updated (K, D) rows back into the (S, D)
    resident cache (in place under jit: the pallas form aliases the cache
    operand, the oracle is a donated ``.at[slots].set``)."""
    return get_kernel("cohort_gather_scatter", backend)(cache, slots, rows)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    backend: str = "auto"):
    return get_kernel("flash_attention", backend)(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k)


def rwkv6_scan(r, k, v, w, u, s0=None, backend: str = "auto"):
    return get_kernel("rwkv6_scan", backend)(r, k, v, w, u, s0)


def mamba2_ssd(x, dt, a, b_in, c_in, *, chunk: int = 128,
               backend: str = "auto"):
    return get_kernel("mamba2_ssd", backend)(x, dt, a, b_in, c_in, chunk=chunk)
