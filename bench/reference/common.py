"""Pieces both plain references share."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layers(params):
    """The layers' parameters in order, one dict each."""
    out = []
    for seg in params["segments"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        for i in range(n):
            for j in sorted(seg, key=int):
                out.append(jax.tree.map(lambda a, i=i: a[i], seg[j]))
    return out
