"""Sensitivity enforcement + Gaussian mechanism for DP-PASGD (paper Eq. 7a).

The paper assumes G-Lipschitz losses so that the stochastic-gradient
sensitivity is 2G/X_m (§5.2). For non-convex models we *enforce* that
assumption by clipping gradients to norm G before averaging, which yields the
identical privacy algebra. Three granularities:

  num_microbatches == batch   -> per-example clipping (faithful DP-SGD style)
  1 < num_microbatches < batch -> per-microbatch clipping
  num_microbatches == 1        -> flat clipping of the mean gradient
                                  (memory-tractable mode for billion-param runs)

After clipping, Gaussian noise b ~ N(0, sigma^2 I_d) is added to the averaged
gradient — exactly Eq. (7a).

Two ``jax.named_scope`` names mark this boundary in the compiled program's
op metadata, so a profiler trace attributes device time to it:
``dppasgd.local_grad`` (the local step's forward and backward) and
``dppasgd.clip_noise`` (the whole DP path: clip, noise draw and add).
Scopes change op metadata (at most an instruction's name), never the
optimized program.

``make_dp_grad_fn(..., kernel_backend=...)`` picks between two clip+noise
paths: the default ``None`` keeps the legacy ``clip_tree`` +
``tree_add_noise`` (each leaf rounded to its dtype after the clip and again
after the noise) bit-for-bit; any backend name selects
:func:`repro.kernels.ops.dp_clip_noise_tree`, which rounds once and which
XLA fuses into one pass per leaf, the noise drawn inside it. No Pallas
kernel runs on this path.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.utils.tree import (
    tree_add_noise,
    tree_scale,
    tree_sq_norm,
)

LOCAL_GRAD_SCOPE = "dppasgd.local_grad"
CLIP_NOISE_SCOPE = "dppasgd.clip_noise"


def _scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(name)``."""
    def run(*args):
        with jax.named_scope(name):
            return fn(*args)
    return run


def clip_tree(grads, clip_norm: float):
    """Scale a gradient pytree so its global L2 norm is <= clip_norm.
    Preserves each leaf's dtype (the scale is an f32 scalar)."""
    norm = jnp.sqrt(tree_sq_norm(grads))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    clipped = jax.tree.map(lambda x: (x * scale).astype(x.dtype), grads)
    return clipped, norm


def make_dp_grad_fn(
    loss_fn: Callable,
    clip_norm: float,
    num_microbatches: int = 1,
    vmap_microbatches: bool = True,
    accumulate: str = "stack",
    kernel_backend: str | None = None,
) -> Callable:
    """Build dp_grad(params, batch, key, sigma) -> (noisy_grad, metrics).

    ``loss_fn(params, batch)`` must return the mean loss over the leading batch
    axis of every leaf of ``batch``. ``sigma`` is a traced scalar so a single
    compiled step serves every noise level (the accountant varies sigma).

    ``accumulate`` (sequential path only):
      "stack": lax.map + mean — materializes num_microbatches gradient copies
               (paper-faithful baseline lowering).
      "scan":  running-sum scan carry — one gradient buffer regardless of the
               microbatch count (§Perf optimization).

    ``kernel_backend``: ``None`` keeps the legacy double-rounding path;
    any other value ("pallas" | "interpret" | "ref" | "auto", all alike
    here) selects the leaf-wise single-rounding ``dp_clip_noise_tree``.
    Both draw the noise from the identical per-leaf key stream, so the
    choice only changes where the result is rounded.
    """
    vg_fn = _scoped(LOCAL_GRAD_SCOPE, jax.value_and_grad(loss_fn))

    if kernel_backend is not None:
        from repro.kernels.ops import dp_clip_noise_tree

        def _clip(g):
            return dp_clip_noise_tree(g, None, clip_norm, 0.0)

        def _clip_noise(g, key, sigma):
            return dp_clip_noise_tree(g, key, clip_norm, sigma)
    else:
        def _clip(g):
            return clip_tree(g, clip_norm)

        def _clip_noise(g, key, sigma):
            clipped, norm = clip_tree(g, clip_norm)
            return tree_add_noise(key, clipped, sigma), norm

    _clip = _scoped(CLIP_NOISE_SCOPE, _clip)
    _clip_noise = _scoped(CLIP_NOISE_SCOPE, _clip_noise)

    def _one_microbatch(params, mb):
        loss, g = vg_fn(params, mb)
        clipped, norm = _clip(g)
        return clipped, loss, norm

    def dp_grad(params, batch, key, sigma):
        if num_microbatches == 1:
            # one clip+noise of the whole gradient (Eq. 7a)
            loss, g = vg_fn(params, batch)
            noisy, pre_norm = _clip_noise(g, key, sigma)
            metrics = {"loss": loss, "grad_norm_preclip": pre_norm}
            return noisy, metrics
        else:
            # reshape leading axis B -> (n_micro, B / n_micro)
            def _split(x):
                b = x.shape[0]
                if b % num_microbatches:
                    raise ValueError(
                        f"batch {b} not divisible by microbatches {num_microbatches}")
                return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])
            mbs = jax.tree.map(_split, batch)
            if vmap_microbatches:
                clipped_all, losses, norms = jax.vmap(partial(_one_microbatch, params))(mbs)
                clipped = jax.tree.map(lambda x: jnp.mean(x, axis=0), clipped_all)
                loss, pre_norm = jnp.mean(losses), jnp.mean(norms)
            elif accumulate == "scan":
                def body(carry, mb):
                    acc, loss_acc, norm_acc = carry
                    c, l, n = _one_microbatch(params, mb)
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), acc, c)
                    return (acc, loss_acc + l, norm_acc + n), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (acc, loss, pre_norm), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), mbs)
                clipped = jax.tree.map(
                    lambda a, p: (a / num_microbatches).astype(p.dtype),
                    acc, params)
                loss = loss / num_microbatches
                pre_norm = pre_norm / num_microbatches
            else:
                clipped_all, losses, norms = jax.lax.map(
                    partial(_one_microbatch, params), mbs)
                clipped = jax.tree.map(lambda x: jnp.mean(x, axis=0),
                                       clipped_all)
                loss, pre_norm = jnp.mean(losses), jnp.mean(norms)
        # microbatch paths clip per microbatch (leaf-wise when selected) and
        # noise the averaged gradient once, per the Eq. 7a mechanism
        with jax.named_scope(CLIP_NOISE_SCOPE):
            noisy = tree_add_noise(key, clipped, sigma)
        metrics = {"loss": loss, "grad_norm_preclip": pre_norm}
        return noisy, metrics

    return dp_grad


def make_plain_grad_fn(loss_fn: Callable) -> Callable:
    """Non-private gradient with the same signature (sigma ignored)."""
    vg_fn = _scoped(LOCAL_GRAD_SCOPE, jax.value_and_grad(loss_fn))

    def plain_grad(params, batch, key, sigma):
        del key, sigma
        loss, g = vg_fn(params, batch)
        return g, {"loss": loss, "grad_norm_preclip": jnp.sqrt(tree_sq_norm(g))}

    return plain_grad
