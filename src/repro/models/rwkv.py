"""RWKV6 ("Finch") mixer with data-dependent decay (paper-assigned ssm arch).

Time-mix (per head, state S of shape (hd, hd)):
    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(w0 + lora(x~_t))) data-dependent per channel.

Channel-mix: squared-ReLU MLP with token shift.

Training/prefill uses a lax.scan over the sequence (baseline; the Pallas
kernel in repro/kernels/rwkv6_scan.py is the TPU hot path); decode is the
one-step recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init
from repro.models.scan_utils import maybe_map, maybe_scan
from repro.models.sharding import shard_hint

WKV_SCAN_SCOPE = "rwkv6.wkv_scan"


def init_rwkv6_timemix(key, d_model: int, headdim: int = 64, lora_rank: int = 32,
                       dtype=jnp.float32):
    n_heads = d_model // headdim
    ks = jax.random.split(key, 8)
    params = {
        "mu_r": jnp.full((d_model,), 0.5, dtype),
        "mu_k": jnp.full((d_model,), 0.5, dtype),
        "mu_v": jnp.full((d_model,), 0.5, dtype),
        "mu_w": jnp.full((d_model,), 0.5, dtype),
        "mu_g": jnp.full((d_model,), 0.5, dtype),
        "w_r": _dense_init(ks[0], (d_model, d_model), 0, dtype),
        "w_k": _dense_init(ks[1], (d_model, d_model), 0, dtype),
        "w_v": _dense_init(ks[2], (d_model, d_model), 0, dtype),
        "w_g": _dense_init(ks[3], (d_model, d_model), 0, dtype),
        "w_o": _dense_init(ks[4], (d_model, d_model), 0, dtype),
        # data-dependent decay: w = exp(-exp(w0 + tanh(x @ a) @ b))
        "decay_w0": jnp.full((d_model,), -6.0, jnp.float32),
        "decay_a": _dense_init(ks[5], (d_model, lora_rank), 0, dtype),
        "decay_b": (_dense_init(ks[6], (lora_rank, d_model), 0, dtype) * 0.1),
        "bonus_u": jnp.zeros((n_heads, headdim), jnp.float32),
        "ln_scale": jnp.ones((d_model,), dtype),
    }
    axes = {
        "mu_r": (None,), "mu_k": (None,), "mu_v": (None,), "mu_w": (None,),
        "mu_g": (None,),
        "w_r": ("fsdp", "tp"), "w_k": ("fsdp", "tp"), "w_v": ("fsdp", "tp"),
        "w_g": ("fsdp", "tp"), "w_o": ("tp", "fsdp"),
        "decay_w0": (None,), "decay_a": ("fsdp", None), "decay_b": (None, "tp"),
        "bonus_u": ("tp", None), "ln_scale": (None,),
    }
    return params, axes


def _token_shift(x, last=None):
    """x_{t-1} with zero (or cached) init. x (B,S,d) -> (B,S,d)."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _tm_inputs(params, x, x_prev):
    mix = lambda mu: x + (x_prev - x) * mu
    r = jnp.einsum("bsd,df->bsf", mix(params["mu_r"]), params["w_r"])
    k = jnp.einsum("bsd,df->bsf", mix(params["mu_k"]), params["w_k"])
    v = jnp.einsum("bsd,df->bsf", mix(params["mu_v"]), params["w_v"])
    g = jnp.einsum("bsd,df->bsf", mix(params["mu_g"]), params["w_g"])
    xw = mix(params["mu_w"])
    lora = jnp.einsum("bsr,rd->bsd",
                      jnp.tanh(jnp.einsum("bsd,dr->bsr", xw, params["decay_a"])),
                      params["decay_b"])
    log_decay = -jnp.exp(params["decay_w0"] + lora.astype(jnp.float32))
    w = jnp.exp(log_decay)                                 # (B,S,d) in (0,1)
    return r, k, v, g, w, log_decay


def wkv6_scan(r, k, v, w, u, s0=None, unroll: bool = False):
    """Sequential WKV6 recurrence. r/k/v/w (B,S,H,hd); u (H,hd).
    Returns (y (B,S,H,hd), final state (B,H,hd,hd))."""
    bsz, s, h, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((bsz, h, hd, hd), jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp                                # (B,H,hd) each
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)
        y = jnp.einsum("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv)
        new = state * wt[..., None] + kv
        return new, y

    seq = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)
    s_final, ys = maybe_scan(step, s0, (seq(r), seq(k), seq(v), seq(w)),
                             unroll=unroll)
    return jnp.moveaxis(ys, 0, 1), s_final


def wkv6_chunked(r, k, v, log_decay, u, s0=None, chunk: int = 64,
                 unroll: bool = False):
    """Chunk-parallel WKV6 (fla-style): intra-chunk quadratic form + one
    state read/write per chunk instead of per token. Exact (all exponents
    are <= 0 under the causal mask, so no overflow).

    r/k/v/log_decay (B, S, H, hd); u (H, hd). Returns (y, final state)."""
    bsz, s, h, hd = r.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} % rwkv chunk {chunk}")
    nc = s // chunk
    if s0 is None:
        s0 = jnp.zeros((bsz, h, hd, hd), jnp.float32)

    f32 = lambda t: t.astype(jnp.float32)
    shp = (bsz, nc, chunk, h, hd)
    rc, kc, vc = (f32(t).reshape(shp) for t in (r, k, v))
    ld = f32(log_decay).reshape(shp)
    lc = jnp.cumsum(ld, axis=2)                     # L_t = sum_{s<=t} log w_s
    lcm1 = lc - ld                                  # L_{t-1}
    lq = lc[:, :, -1:]                              # L_Q (chunk total)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)   # s < t

    # Intra-chunk quadratic form, streamed over (head x channel-block) so the
    # (Q, Q, hd_block) decay tensor never exceeds a small VMEM-sized tile.
    # y = (sum_i A_i) v decomposes as sum over channel blocks of (A_blk v).
    hd_blk = min(8, hd)
    nblk = hd // hd_blk

    def blocked(t):                                 # (B,nc,Q,H,hd) ->
        t = t.reshape(bsz, nc, chunk, h, nblk, hd_blk)
        return jnp.moveaxis(t, (3, 4), (0, 1)).reshape(
            h * nblk, bsz, nc, chunk, hd_blk)

    v_rep = jnp.broadcast_to(jnp.moveaxis(vc, 3, 0)[:, None],
                             (h, nblk, bsz, nc, chunk, hd)
                             ).reshape(h * nblk, bsz, nc, chunk, hd)

    def per_block(args):
        rh, kh, lch, lcm1h, vh = args               # (B, nc, Q, hd_blk)
        # A[t,s] = sum_i r_t k_s exp(L_{t-1} - L_s), s < t   (exponent <= 0)
        diff = lcm1h[:, :, :, None, :] - lch[:, :, None, :, :]
        diff = jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
        a = jnp.einsum("bcti,bcsi,bctsi->bcts", rh, kh, jnp.exp(diff))
        return jnp.einsum("bcts,bcsj->bctj", a, vh)

    parts = maybe_map(per_block,
                      (blocked(rc), blocked(kc), blocked(lc),
                       blocked(lcm1), v_rep), unroll)
    parts = parts.reshape(h, nblk, bsz, nc, chunk, hd).sum(axis=1)
    y_intra = jnp.moveaxis(parts, 0, 3)

    # bonus (diagonal) term: (r_t . u k_t) v_t
    bonus = jnp.einsum("bcthi,hi,bcthi->bcth", rc, u.astype(jnp.float32), kc)
    y_intra = y_intra + bonus[..., None] * vc

    # inter-chunk: state scan, one (hd, hd) read/write per chunk
    r_tilde = rc * jnp.exp(lcm1)                    # exponent <= 0
    k_hat = kc * jnp.exp(lq - lc)                   # exponent <= 0
    chunk_states = jnp.einsum("bcthi,bcthj->bchij", k_hat, vc)
    chunk_decay = jnp.exp(lq[:, :, 0])              # (B, nc, H, hd)

    def step(carry, inp):
        st, dcy = inp                               # (B,H,hd,hd), (B,H,hd)
        new = carry * dcy[..., None] + st
        return new, carry                           # emit state BEFORE chunk

    sw = lambda t: jnp.moveaxis(t, 1, 0)
    s_final, s_prev = maybe_scan(step, s0,
                                 (sw(chunk_states), sw(chunk_decay)),
                                 unroll=unroll)
    s_prev = jnp.moveaxis(s_prev, 0, 1)             # (B,nc,H,hd,hd)
    y_state = jnp.einsum("bcthi,bchij->bcthj", r_tilde, s_prev)
    y = (y_intra + y_state).reshape(bsz, s, h, hd)
    return y, s_final


def _tm_output(params, y, g, d_model):
    bsz, s = y.shape[:2]
    y = y.reshape(bsz, s, d_model).astype(jnp.float32)
    # per-head group norm approximated by full-layer RMS norm
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6) * params["ln_scale"].astype(jnp.float32)
    y = y * jax.nn.silu(g.astype(jnp.float32))
    w_o = shard_hint(params["w_o"], "tp", "fsdp")
    out = jnp.einsum("bsf,fd->bsd", y.astype(w_o.dtype), w_o)
    return shard_hint(out, "batch", "seq", None)


def rwkv6_timemix_forward(params, x, headdim: int = 64, chunk: int = 0,
                          unroll: bool = False):
    out, _ = rwkv6_timemix_forward_state(params, x, headdim, chunk,
                                         unroll=unroll)
    return out


def rwkv6_timemix_forward_state(params, x, headdim: int = 64,
                                chunk: int = 0, unroll: bool = False):
    """Full-sequence time-mix that also returns the decode cache.
    chunk == 0 -> per-token lax.scan (baseline); chunk > 0 -> chunk-parallel
    WKV6 (§Perf optimization)."""
    d_model = x.shape[-1]
    n_heads = d_model // headdim
    x_prev = _token_shift(x)
    r, k, v, g, w, log_decay = _tm_inputs(params, x, x_prev)
    heads = lambda t: t.reshape(t.shape[0], t.shape[1], n_heads, headdim)
    # the recurrence's ops (and, through the transpose, their backward)
    # carry this scope in the profiler trace
    with jax.named_scope(WKV_SCAN_SCOPE):
        if chunk:
            y, s_final = wkv6_chunked(heads(r), heads(k), heads(v),
                                      heads(log_decay), params["bonus_u"],
                                      chunk=chunk, unroll=unroll)
        else:
            y, s_final = wkv6_scan(heads(r), heads(k), heads(v), heads(w),
                                   params["bonus_u"], unroll=unroll)
    out = _tm_output(params, y.astype(x.dtype), g, d_model)
    return out, {"wkv": s_final, "tm_last": x[:, -1:]}


def init_rwkv6_channelmix(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "mu_k": jnp.full((d_model,), 0.5, dtype),
        "mu_r": jnp.full((d_model,), 0.5, dtype),
        "w_k": _dense_init(k1, (d_model, d_ff), 0, dtype),
        "w_v": _dense_init(k2, (d_ff, d_model), 0, dtype),
        "w_r": _dense_init(k3, (d_model, d_model), 0, dtype),
    }
    axes = {
        "mu_k": (None,), "mu_r": (None,),
        "w_k": ("fsdp", "tp"), "w_v": ("tp", "fsdp"), "w_r": ("fsdp", "tp"),
    }
    return params, axes


def rwkv6_channelmix_forward(params, x, x_prev=None):
    xp = _token_shift(x, x_prev)
    xk = x + (xp - x) * params["mu_k"]
    xr = x + (xp - x) * params["mu_r"]
    k = jnp.einsum("bsd,df->bsf", xk, params["w_k"])
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(x.dtype)
    k = shard_hint(k, "batch", "seq", "tp")
    kv = jnp.einsum("bsf,fd->bsd", k, params["w_v"])
    r = jax.nn.sigmoid(
        jnp.einsum("bsd,df->bsf", xr, params["w_r"]).astype(jnp.float32))
    return (r * kv.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_rwkv6_cache(batch: int, d_model: int, headdim: int,
                     dtype=jnp.float32):
    n_heads = d_model // headdim
    return {
        "wkv": jnp.zeros((batch, n_heads, headdim, headdim), jnp.float32),
        "tm_last": jnp.zeros((batch, 1, d_model), dtype),
        "cm_last": jnp.zeros((batch, 1, d_model), dtype),
    }


def rwkv6_timemix_decode(params, x, cache, headdim: int = 64):
    """x (B,1,d)."""
    d_model = x.shape[-1]
    n_heads = d_model // headdim
    r, k, v, g, w, _ = _tm_inputs(params, x, cache["tm_last"])
    heads = lambda t: t.reshape(t.shape[0], 1, n_heads, headdim)
    y, s_new = wkv6_scan(heads(r), heads(k), heads(v), heads(w),
                         params["bonus_u"], s0=cache["wkv"])
    out = _tm_output(params, y.astype(x.dtype), g, d_model)
    cache = dict(cache, wkv=s_new, tm_last=x)
    return out, cache


def rwkv6_channelmix_decode(params, x, cache):
    out = rwkv6_channelmix_forward(params, x, cache["cm_last"])
    return out, dict(cache, cm_last=x)
