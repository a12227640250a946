"""Plain RWKV-6 ("Finch", arXiv:2404.05892) language-model loss, written
from the paper for the check of ``correct``; it imports nothing of the
program. Straight ``jax.numpy`` in float32: a per-token loop over the
WKV recurrence, no kernel, no chunking of the loss.

One layer:
    time mix   x_prev = x shifted one token (zero first)
               r, k, v, g = lerp(x, x_prev, mu_*) @ W_*
               w_t = exp(-exp(w0 + tanh(lerp(x, x_prev, mu_w) @ A) @ B))
               per head:  y_t = r_t (S + diag(u) k_t v_t^T)
                          S   = diag(w_t) S + k_t v_t^T
               out = (norm(y) * ln_scale * silu(g)) @ W_o
    channel mix  sigmoid(lerp(mu_r) @ W_r) * (relu(lerp(mu_k) @ W_k)^2 @ W_v)
with pre-norm residuals (RMSNorm) and a head of its own (the embedding's
table where the configuration ties them).

Departures from the paper, which the configuration runs as stated in its
file: the output norm of the time mix is one RMSNorm over all heads (the
paper normalises each head's group), the token-shift interpolation is
static (no data-dependent LoRA on the mixing coefficients), and there is
no extra LayerNorm after the embedding.

Parameters are the program's tree by name (the benchmark makes them), as
float32; ``mm`` is the matmul of the caller's precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import layers, rms

HIGHEST = jax.lax.Precision.HIGHEST


def shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, hd); u: (H, hd). The recurrence, token by
    token, from a zero state."""
    b, _, h, hd = r.shape

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv,
                       precision=HIGHEST)
        return state * wt[..., None] + kv, y

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, hd, hd), jnp.float32),
                         (seq(r), seq(k), seq(v), seq(w)))
    return jnp.moveaxis(ys, 0, 1)


def layer(cfg, mm, lp, x):
    eps = cfg["layer_norm_epsilon"]
    hd = cfg["head_size"]
    b, s, d = x.shape
    m, f = lp["mixer"], lp["ffn"]

    h = rms(x, lp["norm1"]["scale"], eps)
    hp = shift(h)
    lerp = lambda mu: h + (hp - h) * mu
    r = mm("bsd,df->bsf", lerp(m["mu_r"]), m["w_r"])
    k = mm("bsd,df->bsf", lerp(m["mu_k"]), m["w_k"])
    v = mm("bsd,df->bsf", lerp(m["mu_v"]), m["w_v"])
    g = mm("bsd,df->bsf", lerp(m["mu_g"]), m["w_g"])
    lora = mm("bsr,rd->bsd",
              jnp.tanh(mm("bsd,dr->bsr", lerp(m["mu_w"]), m["decay_a"])),
              m["decay_b"])
    w = jnp.exp(-jnp.exp(m["decay_w0"] + lora))
    heads = lambda t: t.reshape(b, s, d // hd, hd)
    y = wkv(heads(r), heads(k), heads(v), heads(w), m["bonus_u"])
    y = rms(y.reshape(b, s, d), m["ln_scale"], eps) * jax.nn.silu(g)
    x = x + mm("bsf,fd->bsd", y, m["w_o"])

    h = rms(x, lp["norm2"]["scale"], eps)
    hp = shift(h)
    lerp = lambda mu: h + (hp - h) * mu
    kk = jnp.square(jax.nn.relu(mm("bsd,df->bsf", lerp(f["mu_k"]), f["w_k"])))
    rr = jax.nn.sigmoid(mm("bsd,df->bsf", lerp(f["mu_r"]), f["w_r"]))
    return x + rr * mm("bsf,fd->bsd", kk, f["w_v"])


def loss(params, batch, cfg, mm):
    """Mean next-token cross-entropy of ``batch`` ({"tokens", "labels"}:
    (B, S))."""
    emb = params["embed"]["embedding"]
    x = emb[batch["tokens"]]
    for lp in layers(params):
        x = jax.checkpoint(lambda lp, x: layer(cfg, mm, lp, x))(lp, x)
    x = rms(x, params["final_norm"]["scale"], cfg["layer_norm_epsilon"])
    if cfg["tie_word_embeddings"]:
        logits = mm("bsd,vd->bsv", x, emb)
    else:
        logits = mm("bsd,dv->bsv", x, params["embed"]["head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def param_count(cfg) -> int:
    """Parameters of the configuration (a tied table counts once)."""
    d, f, r = cfg["hidden_size"], cfg["intermediate_size"], cfg["decay_lora_rank"]
    time_mix = 5 * d + 5 * d * d + d + d * r + r * d + d + d
    channel_mix = 2 * d + 2 * d * f + d * d
    layer = time_mix + channel_mix + 2 * d
    head = 0 if cfg["tie_word_embeddings"] else d * cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d + head
            + d)


def flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs of one token's forward and backward pass: 6 per weight
    it multiplies (the head, not the embedding lookup), plus the WKV
    recurrence's two hd x hd contractions per head (r.S and the k v^T
    update, 4 hd^2 forward), times 3. Recomputation does not count."""
    d, f, r = cfg["hidden_size"], cfg["intermediate_size"], cfg["decay_lora_rank"]
    matmul = 5 * d * d + 2 * d * r + 2 * d * f + d * d
    wkv = 4 * cfg["head_size"] * d
    per_layer = 6 * matmul + 3 * wkv
    return float(cfg["num_hidden_layers"] * per_layer
                 + 6 * cfg["vocab_size"] * d)
