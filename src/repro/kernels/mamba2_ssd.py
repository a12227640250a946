"""Mamba2 SSD chunk-scan Pallas kernel (TPU target).

The TPU compiler refuses this kernel today: its (1, 1, chunk) ``dt``
block breaks the (8, 128) tiling rule. It runs in interpret mode only, and
``auto`` raises for it on a TPU. The model code does not call it.

Grid: (B*H, n_chunks) with the chunk axis sequential ("arbitrary" dimension
semantics on TPU) so the (P, N) SSM state lives in VMEM scratch and is
carried across chunk iterations — the inter-chunk recurrence never touches
HBM. Per chunk the kernel computes the intra-chunk quadratic form and the
state contribution:

    L      = cumsum(dt * a)                         (Q,)
    M[t,s] = (c_t . b_s) * exp(L_t - L_s) * dt_s * [s <= t]
    y      = M @ x  +  exp(L_t) * (c_t . state)
    state <- exp(L_Q) * state + sum_s exp(L_Q - L_s) dt_s x_s b_s^T

Tiles: x (Q, P)=(128, 64), b/c (Q, N)=(128, 64), state (P, N)=(64, 64) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, s_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)                    # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                  # (Q,)
    a = a_ref[0].astype(jnp.float32)                       # ()
    b = b_ref[0, 0].astype(jnp.float32)                    # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)                    # (Q, N)
    state = s_ref[...]                                     # (P, N)

    l = jnp.cumsum(dt * a)                                 # (Q,)
    l_last = l[-1]

    # intra-chunk
    scores = c @ b.T                                       # (Q, Q)
    decay = jnp.exp(l[:, None] - l[None, :])
    q = x.shape[0]
    tri = (jax.lax.iota(jnp.int32, q)[:, None]
           >= jax.lax.iota(jnp.int32, q)[None, :])
    m = jnp.where(tri, scores * decay, 0.0) * dt[None, :]
    y = m @ x                                              # (Q, P)

    # inter-chunk: contribution of the carried state
    y += jnp.exp(l)[:, None] * (c @ state.T)               # (Q, P)

    # state update
    w = jnp.exp(l_last - l) * dt                           # (Q,)
    new_state = jnp.exp(l_last) * state + (w[:, None] * x).T @ b
    s_ref[...] = new_state
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        sfin_ref[0] = new_state.astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd(x, dt, a, b_in, c_in, *, chunk: int = 128,
               interpret: bool = False):
    """x (B,S,H,P); dt (B,S,H) post-softplus; a (H,); b/c (B,S,N).
    Returns (y (B,S,H,P), final state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # layout: (B*H, nc, chunk, ...)
    xb = jnp.moveaxis(x, 2, 1).reshape(bsz * h, nc, chunk, p)
    dtb = jnp.moveaxis(dt, 2, 1).reshape(bsz * h, nc, chunk)
    ab = jnp.tile(a[None, :], (bsz, 1)).reshape(bsz * h)
    bb = jnp.broadcast_to(b_in[:, None], (bsz, h, s, n)
                          ).reshape(bsz * h, nc, chunk, n)
    cb = jnp.broadcast_to(c_in[:, None], (bsz, h, s, n)
                          ).reshape(bsz * h, nc, chunk, n)

    y, s_fin = pl.pallas_call(
        _ssd_kernel,
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xb, dtb, ab, bb, cb)

    y = y.reshape(bsz, h, s, p)
    y = jnp.moveaxis(y, 1, 2)                              # (B,S,H,P)
    return y, s_fin.reshape(bsz, h, p, n)
