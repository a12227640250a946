"""RWKV6 ("Finch") mixer with data-dependent decay (paper-assigned ssm arch).

Time-mix (per head, state S of shape (hd, hd)):
    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(w0 + lora(x~_t))) data-dependent per channel.

Channel-mix: squared-ReLU MLP with token shift.

Training and prefill (any S) compute the WKV with the exact
chunk-parallel ``wkv6_chunked``, whose contractions are matmuls; decode
is the one-step recurrence ``wkv6_scan``, which is also the per-token
oracle of the tests. The Pallas kernel in
repro/kernels/rwkv6_scan.py is not on the model's path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _dense_init
from repro.models.scan_utils import maybe_scan
from repro.models.sharding import shard_hint

WKV_SCAN_SCOPE = "rwkv6.wkv_scan"
WKV_CHUNK = 64          # tokens a chunk of wkv6_chunked's state pass
WKV_SUBCHUNK = 16       # tokens a sub-chunk of its in-chunk form
HIGHEST = jax.lax.Precision.HIGHEST
# f32's exp() is 0 below this, so a product across a token whose log-decay
# is lower reads 0 either way; the floor keeps the in-chunk sums short
LOG_DECAY_FLOOR = -110.0


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


def _wkv_chunk(s: int) -> int:
    """Chunk length for a sequence of ``s`` tokens: WKV_CHUNK, or ``s``
    rounded up to a whole number of sub-chunks when it is shorter."""
    if s >= WKV_CHUNK:
        return WKV_CHUNK
    return -(-s // WKV_SUBCHUNK) * WKV_SUBCHUNK


def wkv6_chunked(r, k, v, log_decay, u, s0=None, unroll: bool = False):
    """Chunk-parallel WKV6, exact, with its contractions as matmuls.

    r/k/v/log_decay (B, S, H, hd); u (H, hd); s0 (B, H, hd, hd) or None.
    Returns (y (B, S, H, hd), final state (B, H, hd, hd)), as
    :func:`wkv6_scan` does with w = exp(log_decay).

    With L_t the log-decay summed from the start of the chunk through t,
    token t of a chunk reads
        y_t = (r_t * exp(L_{t-1})) S_prev
              + sum_{s<t} [sum_i r_ti k_si exp(L_{t-1,i} - L_{s,i})] v_s
              + (r_t . u k_t) v_t
    and the chunk leaves
        S = S_prev * exp(L_Q) + sum_s (k_s * exp(L_Q - L_s)) v_s^T.
    The chunk length Q follows from S (:func:`_wkv_chunk`); a ragged tail
    is padded with k = v = 0 and log-decay 0, which leaves every earlier
    output and the final state as they were.

    Inside a chunk the pairs (t, s) split by sub-chunk of WKV_SUBCHUNK
    tokens:
    - s and t in one sub-chunk: the exact three-way product over
      (q, q, hd), its exponent masked to -inf for s >= t before the exp;
    - s in an earlier sub-chunk than t's sub-chunk j, whose log-decay
      starts at b_j: (r_t * exp(L_{t-1} - b_j)) . (k_s * exp(b_j - L_s)),
      a matmul. L_{t-1} <= b_j <= L_s, so both exponents are <= 0.
    Every exponent is <= 0, so nothing overflows however strong the decay,
    and a factor that underflows stands for a product that does too. Each
    exponent sums only the log-decays between its two ends (prefix and
    suffix sums inside a sub-chunk, plus whole sub-chunks), never the
    difference of two long cumulative sums, to keep f32 cancellation
    small; log-decays are floored at LOG_DECAY_FLOOR, below which f32's
    exp is 0 anyway. The chunks' states pass in one scan of S/Q steps;
    every contraction is f32 at HIGHEST."""
    bsz, s, h, hd = r.shape
    q = WKV_SUBCHUNK
    chunk = _wkv_chunk(s)
    nc = -(-s // chunk)
    ns = chunk // q
    if s0 is None:
        s0 = jnp.zeros((bsz, h, hd, hd), jnp.float32)
    mm = functools.partial(jnp.einsum, precision=HIGHEST)

    def blocks(t):                                  # (B,S,H,hd) ->
        t = t.astype(jnp.float32)                   # (B,H,nc,ns,q,hd)
        t = jnp.pad(t, ((0, 0), (0, nc * chunk - s), (0, 0), (0, 0)))
        return jnp.moveaxis(t.reshape(bsz, nc, ns, q, h, hd), 4, 1)

    rb, kb, vb, ld = (blocks(t) for t in (r, k, v, log_decay))
    ld = jnp.maximum(ld, LOG_DECAY_FLOOR)
    # every log-decay sum below adds only the terms between its two ends
    zero = jnp.zeros_like(ld[:, :, :, :, :1])
    lsub = jnp.cumsum(ld, axis=4)                   # p <= t, in t's sub-chunk
    lsub_ex = jnp.concatenate([zero, lsub[:, :, :, :, :-1]], axis=4)  # p < t
    rsub_ex = jnp.concatenate(                      # p > s, in s's sub-chunk
        [jnp.cumsum(ld[:, :, :, :, :0:-1], axis=4)[:, :, :, :, ::-1], zero],
        axis=4)
    tot = lsub[:, :, :, :, -1]                      # (B,H,nc,ns,hd)
    tot_cum = jnp.cumsum(tot, axis=3)
    lq = tot_cum[:, :, :, -1]                       # L_Q, (B,H,nc,hd)
    b = jnp.concatenate([jnp.zeros_like(tot[:, :, :, :1]),
                         tot_cum[:, :, :, :-1]], axis=3)  # b_j: before j
    # span[j, m]: the whole sub-chunks strictly between m and j (j = ns: the
    # chunk's end), so b_j - L_s = span[j, m] + rsub_ex[s] for s in m < j
    j, m, n = np.ogrid[:ns + 1, :ns, :ns]
    between = jnp.asarray((m < n) & (n < j))[..., None]
    span = jnp.sum(jnp.where(between, tot[:, :, :, None, None], 0.0), axis=5)

    # s < t in one sub-chunk: (q, q, hd) product, masked before the exp
    tri = jnp.tril(jnp.ones((q, q), bool), k=-1)[:, :, None]
    diff = lsub_ex[:, :, :, :, :, None] - lsub[:, :, :, :, None]
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    a_diag = jnp.sum(rb[:, :, :, :, :, None] * kb[:, :, :, :, None] * decay,
                     axis=-1)                       # (B,H,nc,ns,q,q)
    y = mm("bhcnts,bhcnsj->bhcntj", a_diag, vb)
    # the bonus term (r_t . u k_t) v_t
    bonus = jnp.sum(rb * u.astype(jnp.float32)[None, :, None, None, None] * kb,
                    axis=-1, keepdims=True)
    y = y + bonus * vb

    # s in sub-chunk m < j = t's: k scaled by exp(b_j - L_s) for each
    # query sub-chunk j, zero for m >= j; r by exp(L_{t-1} - b_j)
    earlier = jnp.tril(jnp.ones((ns, ns), bool), k=-1)[:, :, None, None]
    k_exp = span[:, :, :, :ns, :, None] + rsub_ex[:, :, :, None]
    k_off = kb[:, :, :, None] * jnp.exp(
        jnp.where(earlier, k_exp, -jnp.inf))        # (B,H,nc,j,m,q,hd)
    a_off = mm("bhcjti,bhcjmsi->bhcjtms", rb * jnp.exp(lsub_ex), k_off)
    y = y + mm("bhcjtms,bhcmsk->bhcjtk", a_off, vb)

    # the chunks' states: one read and one write of (hd, hd) per chunk
    k_hat = kb * jnp.exp(span[:, :, :, ns, :, None] + rsub_ex)  # L_Q - L_s
    chunk_states = mm("bhcnsi,bhcnsj->bhcij", k_hat, vb)

    def step(carry, inp):
        st, lq_c = inp                              # (B,H,hd,hd), (B,H,hd)
        return carry * jnp.exp(lq_c)[..., None] + st, carry  # state BEFORE

    sw = lambda t: jnp.moveaxis(t, 2, 0)
    s_final, s_prev = maybe_scan(step, s0, (sw(chunk_states), sw(lq)),
                                 unroll=unroll)
    r_state = rb * jnp.exp(b[:, :, :, :, None] + lsub_ex)      # L_{t-1}
    y = y + mm("bhcnti,cbhij->bhcntj", r_state, s_prev)
    y = jnp.moveaxis(y, 1, 4).reshape(bsz, nc * chunk, h, hd)[:, :s]
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


def rwkv6_timemix_forward(params, x, headdim: int = 64,
                          unroll: bool = False):
    out, _ = rwkv6_timemix_forward_state(params, x, headdim, unroll=unroll)
    return out


def rwkv6_timemix_forward_state(params, x, headdim: int = 64,
                                unroll: bool = False):
    """Full-sequence time-mix, through the chunk-parallel WKV6, that also
    returns the decode cache."""
    d_model = x.shape[-1]
    n_heads = d_model // headdim
    x_prev = _token_shift(x)
    r, k, v, g, _, log_decay = _tm_inputs(params, x, x_prev)
    heads = lambda t: t.reshape(t.shape[0], t.shape[1], n_heads, headdim)
    # the recurrence's ops (and, through the transpose, their backward)
    # carry this scope in the profiler trace
    with jax.named_scope(WKV_SCAN_SCOPE):
        y, s_final = wkv6_chunked(heads(r), heads(k), heads(v),
                                  heads(log_decay), params["bonus_u"],
                                  unroll=unroll)
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
