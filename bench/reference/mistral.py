"""Plain decoder loss of Mistral-Large-Instruct-2407's block (RMSNorm,
rotary positions, grouped-query attention, SwiGLU MLP, untied head),
written from the model card for the check of ``correct``; it
imports nothing of the program. Straight ``jax.numpy`` in float32: the
whole causal softmax at once, no kernel, no blocking.

One layer:
    h = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv;  rotate q, k (rotate-half
    form, theta = rope_theta);  a = softmax(q k^T / sqrt(hd) + causal) v,
    each query head reading key/value head (head // (H / KV));
    x = x + a Wo;  h = RMSNorm(x);  x = x + (silu(h Wg) * h Wu) Wd

Parameters are the program's tree by name (the benchmark makes them), as
float32; ``mm`` is the matmul of the caller's precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import layers, rms


def rope(x, theta):
    """x (B, S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def layer(cfg, mm, lp, x):
    eps = cfg["rms_norm_eps"]
    m, f = lp["mixer"], lp["ffn"]
    b, s, _ = x.shape
    h = rms(x, lp["norm1"]["scale"], eps)
    q = rope(mm("bsd,dhk->bshk", h, m["wq"]), cfg["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, m["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", h, m["wv"])
    kv_heads, hd = k.shape[2], k.shape[3]
    q = q.reshape(b, s, kv_heads, q.shape[2] // kv_heads, hd)
    scores = mm("bskgd,btkd->bkgst", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = mm("bkgst,btkd->bskgd", probs, v).reshape(b, s, -1, hd)
    x = x + mm("bshk,hkd->bsd", a, m["wo"])
    h = rms(x, lp["norm2"]["scale"], eps)
    u = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"])) * mm(
        "bsd,df->bsf", h, f["w_up"])
    return x + mm("bsf,fd->bsd", u, f["w_down"])


def loss(params, batch, cfg, mm):
    """Mean next-token cross-entropy of ``batch`` ({"tokens", "labels"}:
    (B, S))."""
    x = params["embed"]["embedding"][batch["tokens"]]
    for lp in layers(params):
        x = jax.checkpoint(lambda lp, x: layer(cfg, mm, lp, x))(lp, x)
    x = rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = mm("bsd,dv->bsv", x, params["embed"]["head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def param_count(cfg) -> int:
    """Parameters this chip holds (its heads, MLP units and vocabulary
    rows of each layer, every norm whole)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"] // cfg["tensor_parallel"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f + 2 * d
    vocab = cfg["vocab_size"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_hidden_layers"] * layer + vocab + d


def flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs of one token's forward and backward pass: 6 per weight
    it multiplies (the head, not the embedding lookup), plus causal
    attention's q k^T and p v (2 seq hd per head forward, half of the
    full square), times 3. Recomputation does not count."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"] // cfg["tensor_parallel"]
    matmul = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    attention = 2 * seq * hd * h
    return float(cfg["num_hidden_layers"] * (6 * matmul + 3 * attention)
                 + 6 * cfg["vocab_size"] * d)
