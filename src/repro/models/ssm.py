"""Mamba2 (SSD) mixer, as Zamba2 uses it [arXiv:2405.21060, 2411.15242].

Per head h with state (P, N), reading group g(h) = h // (H / G) of B and C:
    H_t = exp(dt_t * A_h) * H_{t-1} + dt_t * x_t (P) outer B_t,g (N)
    y_t = H_t @ C_t,g + D_h * x_t
``w_in`` splits into z, x, B, C and dt (B and C of G x N each); x, B and C
pass a causal depthwise conv with bias and a SiLU; dt = softplus(dt +
dt_bias), A = -exp(a_log). The output is ``w_out(norm(y * silu(z)))``, an
RMSNorm over each group's d_inner / G channels.

Training and prefill use the chunked SSD algorithm (intra-chunk quadratic
form + inter-chunk state scan) at the configured chunk; decode is the plain
one-step recurrence.

Shapes: d_inner = H * P (P = headdim); the conv runs over
d_inner + 2 * G * N channels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init
from repro.models.scan_utils import maybe_scan
from repro.models.sharding import shard_hint


def init_mamba2(key, d_model: int, d_state: int, headdim: int, n_heads: int,
                n_groups: int, conv_kernel: int = 4, dtype=jnp.float32):
    d_inner = n_heads * headdim
    conv_dim = d_inner + 2 * n_groups * d_state
    ks = jax.random.split(key, 3)
    params = {
        # fused input projection: [z, x, B, C, dt]
        "w_in": _dense_init(ks[0], (d_model, d_inner + conv_dim + n_heads),
                            0, dtype),
        "conv_w": _dense_init(ks[1], (conv_kernel, conv_dim), 0, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((n_heads,), jnp.float32),        # A = -exp(a_log)
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": _dense_init(ks[2], (d_inner, d_model), 0, dtype),
    }
    axes = {
        "w_in": ("fsdp", "tp"),
        "conv_w": (None, "tp"),
        "conv_b": ("tp",),
        "a_log": ("tp",),
        "dt_bias": ("tp",),
        "d_skip": ("tp",),
        "norm_scale": ("tp",),
        "w_out": ("tp", "fsdp"),
    }
    return params, axes


def _split_proj(proj, d_inner: int, n_groups: int, d_state: int):
    """(z, xBC, dt) of the fused input projection."""
    conv_end = 2 * d_inner + 2 * n_groups * d_state
    return proj[..., :d_inner], proj[..., d_inner:conv_end], proj[..., conv_end:]


def _split_xbc(xbc, d_inner: int, n_groups: int, d_state: int):
    """(x, B, C) of the conv output; B and C as (..., G, N)."""
    gn = n_groups * d_state
    lead = xbc.shape[:-1]
    b_in = xbc[..., d_inner:d_inner + gn].reshape(*lead, n_groups, d_state)
    c_in = xbc[..., d_inner + gn:].reshape(*lead, n_groups, d_state)
    return xbc[..., :d_inner], b_in, c_in


def _causal_conv(xbc, conv_w, conv_b):
    """Depthwise causal conv over seq with bias, then SiLU, in float32.
    xbc (B,S,C); conv_w (K,C); conv_b (C,)."""
    k = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = conv_w.astype(jnp.float32)
    out = sum(pad[:, i:i + xbc.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out + conv_b.astype(jnp.float32))


def _gated_out(params, y, z, n_groups: int, eps: float):
    """``w_out(norm(y * silu(z)))``: y (B,S,H,P), z (B,S,d_inner); the
    RMSNorm runs over each group's channels."""
    b, s = y.shape[:2]
    y32 = y.reshape(b, s, n_groups, -1).astype(jnp.float32) * jax.nn.silu(
        z.astype(jnp.float32)).reshape(b, s, n_groups, -1)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    y32 = (y32 * jax.lax.rsqrt(var + eps)).reshape(b, s, -1)
    y32 = y32 * params["norm_scale"].astype(jnp.float32)
    w_out = shard_hint(params["w_out"], "tp", "fsdp")
    out = jnp.einsum("bsf,fd->bsd", y32.astype(w_out.dtype), w_out)
    return shard_hint(out, "batch", "seq", None)


def ssd_chunked(x, dt, a, b_in, c_in, chunk: int = 128, h0=None,
                unroll: bool = False):
    """Chunked SSD scan.

    x (B,S,H,P); dt (B,S,H) (post-softplus); a (H,) negative;
    b_in/c_in (B,S,G,N), head h reading group h // (H/G).
    Returns (y (B,S,H,P) float32, final state (B,H,P,N))."""
    with jax.named_scope("mamba2.ssd_scan"):
        bsz, s, h, p = x.shape
        g, n = b_in.shape[-2:]
        r = h // g
        if s % chunk:
            raise ValueError(f"seq {s} not divisible by ssd chunk {chunk}")
        nc = s // chunk

        xs = x.reshape(bsz, nc, chunk, g, r, p)
        dts = dt.reshape(bsz, nc, chunk, g, r)
        bs = b_in.reshape(bsz, nc, chunk, g, n)
        cs = c_in.reshape(bsz, nc, chunk, g, n)

        # log-decay within chunk: l[t] = cumsum(dt * a)
        l = jnp.cumsum(dts * a.reshape(g, r), axis=2)       # (B,nc,Q,G,R)
        l_last = l[:, :, -1:]                               # (B,nc,1,G,R)

        # ---- intra-chunk (quadratic within chunk) ---------------------------
        scores = jnp.einsum("bctgn,bcsgn->bctsg", cs, bs)   # (B,nc,Q,Q,G)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
        # masked before the exp: above the diagonal l_t - l_s > 0 grows
        # without bound over a chunk
        seg = jnp.where(tri, l[:, :, :, None] - l[:, :, None], -jnp.inf)
        m = scores[..., None] * jnp.exp(seg)                # (B,nc,Q,Q,G,R)
        y_intra = jnp.einsum("bctsgr,bcsgr,bcsgrp->bctgrp", m, dts, xs)

        # ---- chunk states ---------------------------------------------------
        # state contribution of chunk c: sum_s exp(l_last - l_s) dt_s x_s (x) B_s
        w = jnp.exp(l_last - l) * dts                       # (B,nc,Q,G,R)
        chunk_state = jnp.einsum("bcsgr,bcsgrp,bcsgn->bcgrpn", w, xs, bs)
        chunk_decay = jnp.exp(l_last[:, :, 0])              # (B,nc,G,R)

        # ---- inter-chunk state scan -----------------------------------------
        if h0 is None:
            h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
        h0 = h0.reshape(bsz, g, r, p, n)

        def step(carry, inp):
            st, dcy = inp                                   # (B,G,R,P,N), (B,G,R)
            new = carry * dcy[..., None, None] + st
            return new, carry                               # state BEFORE chunk

        states = jnp.moveaxis(chunk_state.astype(jnp.float32), 1, 0)
        decays = jnp.moveaxis(chunk_decay, 1, 0)
        h_final, h_prevs = maybe_scan(step, h0, (states, decays),
                                      unroll=unroll)
        h_prevs = jnp.moveaxis(h_prevs, 0, 1)               # (B,nc,G,R,P,N)

        # ---- inter-chunk contribution to outputs ----------------------------
        y_inter = jnp.einsum("bctgr,bctgn,bcgrpn->bctgrp",
                             jnp.exp(l), cs, h_prevs)
        y = (y_intra + y_inter).reshape(bsz, s, h, p)
        return y, h_final.reshape(bsz, h, p, n)


def mamba2_forward(params, x, *, d_state: int, headdim: int, n_groups: int,
                   chunk: int, eps: float, unroll: bool = False):
    """Full-sequence Mamba2 mixer. x (B,S,d) -> (B,S,d)."""
    out, _ = mamba2_forward_state(params, x, d_state=d_state,
                                  headdim=headdim, n_groups=n_groups,
                                  chunk=chunk, eps=eps, unroll=unroll)
    return out


def mamba2_forward_state(params, x, *, d_state: int, headdim: int,
                         n_groups: int, chunk: int, eps: float,
                         unroll: bool = False):
    """Full-sequence Mamba2 that also returns the decode cache (final SSM
    state + conv window)."""
    with jax.named_scope("mamba2.mixer"):
        n_heads = params["a_log"].shape[0]
        d_inner = n_heads * headdim
        proj = jnp.einsum("bsd,df->bsf", x, params["w_in"])
        z, xbc_raw, dt = _split_proj(proj, d_inner, n_groups, d_state)
        xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
        xin, b_in, c_in = _split_xbc(xbc, d_inner, n_groups, d_state)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        a = -jnp.exp(params["a_log"])
        bsz, s = x.shape[:2]
        xh = xin.reshape(bsz, s, n_heads, headdim)
        xh = shard_hint(xh, "batch", "seq", "tp", None)
        y, h_final = ssd_chunked(xh, dt, a, b_in, c_in,
                                 chunk=min(chunk, s), unroll=unroll)
        y = y + params["d_skip"][None, None, :, None] * xh
        out = _gated_out(params, y, z, n_groups, eps)
        cache = {"h": h_final,                          # (B,H,P,N)
                 "conv": xbc_raw[:, -(params["conv_w"].shape[0] - 1):]}
        return out, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_mamba2_cache(batch: int, d_state: int, headdim: int, n_heads: int,
                      n_groups: int, conv_kernel: int, dtype=jnp.float32):
    d_inner = n_heads * headdim
    return {
        "h": jnp.zeros((batch, n_heads, headdim, d_state), jnp.float32),
        "conv": jnp.zeros((batch, conv_kernel - 1,
                           d_inner + 2 * n_groups * d_state), dtype),
    }


def mamba2_decode(params, x, cache, *, d_state: int, headdim: int,
                  n_groups: int, eps: float):
    """One-token step. x (B,1,d)."""
    n_heads = params["a_log"].shape[0]
    d_inner = n_heads * headdim
    proj = jnp.einsum("bsd,df->bsf", x, params["w_in"])
    z, xbc, dt = _split_proj(proj, d_inner, n_groups, d_state)
    # conv over the cached window + this token
    win = jnp.concatenate([cache["conv"], xbc], axis=1)    # (B, K, C)
    conv_out = jnp.einsum("bkc,kc->bc", win.astype(jnp.float32),
                          params["conv_w"].astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out + params["conv_b"].astype(jnp.float32))
    xin, b_in, c_in = _split_xbc(conv_out, d_inner, n_groups, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]
    a = -jnp.exp(params["a_log"])
    bsz = x.shape[0]
    r = n_heads // n_groups
    xh = xin.reshape(bsz, n_groups, r, headdim)
    decay = jnp.exp(dt * a[None, :]).reshape(bsz, n_groups, r)
    upd = jnp.einsum("bgr,bgrp,bgn->bgrpn", dt.reshape(bsz, n_groups, r), xh,
                     b_in)
    h = cache["h"].reshape(bsz, n_groups, r, headdim, d_state)
    h_new = h * decay[..., None, None] + upd
    y = jnp.einsum("bgrpn,bgn->bgrp", h_new, c_in).reshape(
        bsz, n_heads, headdim)
    y = y + params["d_skip"][None, :, None] * xh.reshape(bsz, n_heads, headdim)
    out = _gated_out(params, y[:, None], z, n_groups, eps)
    return out, {"h": h_new.reshape(cache["h"].shape), "conv": win[:, 1:]}
