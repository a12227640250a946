"""Public kernel wrappers, routed through the backend dispatch layer.

Every wrapper resolves its implementation via
:func:`repro.kernels.dispatch.get_kernel` (``backend="auto"`` by default):
Mosaic-compiled Pallas on a TPU — or an error there, never a silent
substitute — and the pure-jnp oracle everywhere else. ``backend=
"interpret"`` runs the Pallas body as jax ops, the CPU rehearsal of the
TPU kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import get_kernel
from repro.kernels.dp_clip_noise import DEFAULT_BLOCK


def dp_clip_noise_flat(g, noise, clip_norm, sigma, block: int = DEFAULT_BLOCK,
                       backend: str = "auto"):
    """Fused clip+noise on flat (N,) arrays; returns (y, pre-clip norm)."""
    return get_kernel("dp_clip_noise", backend)(g, noise, clip_norm, sigma,
                                                block=block)


def dp_clip_noise_tree(grads, key, clip_norm, sigma,
                       block: int = DEFAULT_BLOCK, backend: str = "auto"):
    """Tree-level fused clip+noise: flatten -> kernel -> unflatten.

    Drop-in replacement for core.clipping ``clip_tree`` + ``tree_add_noise``;
    preserves each leaf's dtype. ``key=None`` skips the noise draw entirely
    (clip-only kernel lowering — no noise buffer materialized). The noise is
    drawn per leaf from split keys — the same stream structure as
    ``tree_add_noise`` — so swapping backends (or swapping the legacy path
    for this one) only changes arithmetic order, never the sampled noise.
    """
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [x.size for x in leaves]
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])
    if key is None:
        noise = None
    else:
        keys = jax.random.split(key, len(leaves))
        noise = jnp.concatenate(
            [jax.random.normal(k, x.shape, jnp.float32).reshape(-1)
             for k, x in zip(keys, leaves)])
    out, norm = dp_clip_noise_flat(flat, noise, clip_norm, sigma,
                                   block=block, backend=backend)
    news = []
    off = 0
    for x, n in zip(leaves, sizes):
        news.append(out[off:off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, news), norm


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
