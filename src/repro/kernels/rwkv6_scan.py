"""RWKV6 WKV recurrence Pallas kernel (TPU target).

The TPU compiler refuses this kernel today: its (1, hd) ``u`` block
breaks the (8, 128) tiling rule. It runs in interpret mode only, and
``auto`` raises for it on a TPU. The model code does not call it.

Grid: (B, H). Each program owns one head's (hd x hd) state in VMEM and walks
the sequence with a fori_loop:
    y_t = r_t . (S + u * k_t v_t^T);  S <- diag(w_t) S + k_t v_t^T
The state tile (hd, hd) = (64, 64) f32 = 16 KiB — deep in VMEM; inputs are
streamed per (b, h) as (S, hd) tiles.

Per-timestep rows are read/written with the ref-indexing API
(``ref[0, 0, pl.dslice(t, 1), :]``) — the tuple-index ``pl.load``/``pl.store``
form was dropped upstream. Selected through ``repro.kernels.dispatch``
(backend "pallas"/"interpret"), with ``ref.rwkv6_scan_ref`` as the
registered oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                 *, seq_len):
    u = u_ref[0].astype(jnp.float32)                      # (hd,)
    state0 = s0_ref[0, 0].astype(jnp.float32)             # (hd, hd)

    def _load_t(ref, t):
        row = ref[0, 0, pl.dslice(t, 1), :]               # (1, hd)
        return row[0].astype(jnp.float32)                 # (hd,)

    def body(t, state):
        rt = _load_t(r_ref, t)
        kt = _load_t(k_ref, t)
        vt = _load_t(v_ref, t)
        wt = _load_t(w_ref, t)
        kv = kt[:, None] * vt[None, :]                    # (hd, hd)
        y = ((state + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        y_ref[0, 0, pl.dslice(t, 1), :] = y[None].astype(y_ref.dtype)
        return state * wt[:, None] + kv

    state = jax.lax.fori_loop(0, seq_len, body, state0)
    sout_ref[0, 0] = state.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_scan(r, k, v, w, u, s0=None, *, interpret: bool = False):
    """r/k/v/w (B, H, S, hd); u (H, hd); s0 (B, H, hd, hd) or None.
    Returns (y (B, H, S, hd), final state (B, H, hd, hd))."""
    b, h, s, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, hd, hd), jnp.float32)

    kernel = functools.partial(_wkv6_kernel, seq_len=s)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, s, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, s, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, s, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, s, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, s, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, s_out
