"""Plain loss of Zamba2 (Zyphra, arXiv:2411.15242; the equations of the
published ``config.json`` and of ``transformers``' Zamba2 modelling),
written for the check of ``correct``; it imports nothing of the program.
Straight ``jax.numpy`` in float32. Computed in blocks so that it fits one
chip at S 4096: attention by query blocks of 512, the state-space scan by
blocks of 64 positions carrying the state, the cross-entropy by 1024
positions; each block holds the whole of its mathematics.

A Mamba2 layer (``h -> h + Mamba2(RMSNorm(h + t))``, t from the shared
block in a hybrid layer, else 0):
    z, x, B, C, dt = split(u W_in)        B, C of G groups x N
    x, B, C = silu(causal depthwise conv(x, B, C) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(a_log)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t,g(h)^T;  y_t = S_t C_t,g(h) + D_h x_t
    out = W_out(RMSNorm_group(y * silu(z)))      one norm per group of heads
Hybrid layer k (its ``linear``, adapter, and shared block k mod 2):
    a = Attn(RMSNorm([h, e]))   RoPE, softmax scale (head_dim / 2)^-0.5
    f = RMSNorm(a W_o);  g, u = f (W_gate, W_up) + (f A_k)(Bg_k, Bu_k)
    t = ((gelu(g) * u) W_down) linear_k
The embedding e feeds every hybrid layer; the head is the embedding
(tied). ``transformers`` clamps dt at ``time_step_min`` on its torch path
only; the published training path (mamba_ssm) does not, nor does this.

Under the TP share (``tensor_parallel``) the layer's heads, groups and
MLP units held here are those the configuration gives; every norm, the
``linear`` and the vocabulary are whole. The partial outputs are what this
chip computes without the exchange, as the program does.

Parameters are the program's tree by name (the benchmark makes them), as
float32; ``mm`` is the matmul of the caller's precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import layers, rms

SSD_BLOCK = 64
ATTN_BLOCK = 512
CE_BLOCK = 1024


def hybrid_layers(cfg) -> list[int]:
    """Indices of the hybrid layers among the first num_hidden_layers."""
    types = cfg["layers_block_type"][: cfg["num_hidden_layers"]]
    return [i for i, t in enumerate(types) if t == "hybrid"]


def ff_units(cfg) -> int:
    return cfg["ffn_hidden_size"] // cfg["tensor_parallel"]


def causal_conv(xbc, w, b):
    """silu(depthwise causal conv + bias): xbc (B,S,C), w (K,C), b (C,)."""
    k, s = w.shape[0], xbc.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(pad[:, i:i + s] * w[i] for i in range(k)) + b)


def ssd(mm, x, dt, a, bm, cm):
    """The state-space recurrence, SSD_BLOCK positions at a time.
    x (B,S,H,P); dt (B,S,H); a (H,); bm, cm (B,S,H,N), each head's B and
    C. Returns y (B,S,H,P) without the D skip."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    q = min(SSD_BLOCK, s)
    blocks = [jnp.moveaxis(t.reshape(b, s // q, q, *t.shape[2:]), 1, 0)
              for t in (x, dt, bm, cm)]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]

    @jax.checkpoint
    def block(state, blk):
        xb, dtb, bb, cb = blk
        l = jnp.cumsum(dtb * a, axis=1)                      # (B,Q,H)
        decay = jnp.exp(jnp.where(causal, l[:, :, None] - l[:, None], -jnp.inf))
        g = mm("bthn,bshn->btsh", cb, bb) * decay * dtb[:, None]
        y = mm("btsh,bshp->bthp", g, xb)
        y = y + mm("bthn,bhpn->bthp", cb * jnp.exp(l)[..., None], state)
        w = jnp.exp(l[:, -1:] - l) * dtb                     # (B,Q,H)
        state = (state * jnp.exp(l[:, -1])[..., None, None]
                 + mm("bshp,bshn->bhpn", xb * w[..., None], bb))
        return state, y

    _, ys = jax.lax.scan(block, jnp.zeros((b, h, p, n), jnp.float32),
                         tuple(blocks))
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)


def mamba_mixer(cfg, mm, m, u):
    """The mixer on its normed input u (B,S,d): this chip's partial
    output."""
    h, p = cfg["n_mamba_heads"], cfg["mamba_headdim"]
    g, n = cfg["mamba_ngroups"], cfg["mamba_d_state"]
    di = h * p
    bsz, s, _ = u.shape
    proj = mm("bsd,df->bsf", u, m["w_in"])
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * g * n],
                  proj[..., 2 * di + 2 * g * n:])
    xbc = causal_conv(xbc, m["conv_w"], m["conv_b"])
    x = xbc[..., :di].reshape(bsz, s, h, p)
    per_head = lambda t: jnp.repeat(t.reshape(bsz, s, g, n), h // g, axis=2)
    bm = per_head(xbc[..., di:di + g * n])
    cm = per_head(xbc[..., di + g * n:])
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = ssd(mm, x, dt, -jnp.exp(m["a_log"]), bm, cm)
    y = y + m["d_skip"][:, None] * x
    y = (y.reshape(bsz, s, di) * jax.nn.silu(z)).reshape(bsz, s, g, -1)
    y = rms(y, 1.0, cfg["rms_norm_eps"]).reshape(bsz, s, di) * m["norm_scale"]
    return mm("bsf,fd->bsd", y, m["w_out"])


def rope(x, theta):
    """x (B, S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def attention(cfg, mm, ap, h):
    """Causal attention of the normed concatenation h (B,S,2d) through
    ``o_proj``: this chip's heads' partial output (B,S,d)."""
    theta = cfg["rope_theta"]
    q = rope(mm("bsd,dhk->bshk", h, ap["wq"]), theta)
    k = rope(mm("bsd,dhk->bshk", h, ap["wk"]), theta)
    v = mm("bsd,dhk->bshk", h, ap["wv"])
    b, s, heads, hd = q.shape
    kv = k.shape[2]
    scale = (hd / 2) ** -0.5
    qb = min(ATTN_BLOCK, s)
    q = q.reshape(b, s // qb, qb, kv, heads // kv, hd)

    @jax.checkpoint
    def one(i):
        scores = mm("bqkgd,btkd->bkgqt", q[:, i], k) * scale
        pos = i * qb + jnp.arange(qb)
        mask = pos[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return mm("bkgqt,btkd->bqkgd", probs, v)

    out = jax.lax.map(one, jnp.arange(s // qb))         # (nb,B,qb,kv,g,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, hd)
    return mm("bshk,hkd->bsd", out, ap["wo"])


def mlp(mm, mp, hp, f):
    """The adapted gated-GELU MLP on the normed f: this chip's units'
    partial output."""
    low = mm("bsd,dr->bsr", f, hp["adapter_a"])
    gate = mm("bsd,df->bsf", f, mp["w_gate"]) + mm("bsr,rf->bsf", low,
                                                    hp["adapter_gate"])
    up = mm("bsd,df->bsf", f, mp["w_up"]) + mm("bsr,rf->bsf", low,
                                                hp["adapter_up"])
    return mm("bsf,fd->bsd", jax.nn.gelu(gate, approximate=False) * up,
              mp["w_down"])


def shared_block(cfg, mm, bp, hp, x, e):
    """linear(block(x, e)): the hybrid layer's addition to its mixer input."""
    eps = cfg["rms_norm_eps"]
    h = rms(jnp.concatenate([x, e], axis=-1), bp["norm_in"]["scale"], eps)
    a = attention(cfg, mm, bp["attn"], h)
    f = rms(a, bp["norm_ff"]["scale"], eps)
    return mm("bsd,de->bse", mlp(mm, bp["mlp"], hp, f), hp["linear"])


def layer(cfg, mm, shared, block_id, lp, x, e):
    t = x
    if block_id is not None:
        t = x + shared_block(cfg, mm, shared[str(block_id)], lp["hybrid"],
                             x, e)
    u = rms(t, lp["norm1"]["scale"], cfg["rms_norm_eps"])
    return x + mamba_mixer(cfg, mm, lp["mixer"], u)


def hidden(params, tokens, cfg, mm):
    """Final-normed hidden states (B,S,d)."""
    e = params["embed"]["embedding"][tokens]
    hybrid = hybrid_layers(cfg)
    x = e
    for i, lp in enumerate(layers(params)):
        if (i in hybrid) != ("hybrid" in lp):
            raise ValueError(f"layer {i}: params and config disagree on "
                             "whether it is a hybrid layer")
        block_id = (hybrid.index(i) % cfg["num_mem_blocks"] if i in hybrid
                    else None)
        x = jax.checkpoint(lambda lp, x, e, b=block_id: layer(
            cfg, mm, params["shared"], b, lp, x, e))(lp, x, e)
    return rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg, mm):
    return mm("bsd,vd->bsv", hidden(params, tokens, cfg, mm),
              params["embed"]["embedding"])


def loss(params, batch, cfg, mm):
    """Mean next-token cross-entropy of ``batch`` ({"tokens", "labels"}:
    (B, S)), CE_BLOCK positions at a time."""
    x = hidden(params, batch["tokens"], cfg, mm)
    b, s, d = x.shape
    c = min(CE_BLOCK, s)
    xs = jnp.moveaxis(x.reshape(b, s // c, c, d), 1, 0)
    ls = jnp.moveaxis(batch["labels"].reshape(b, s // c, c), 1, 0)

    @jax.checkpoint
    def nll(xc, lc):
        z = mm("bsd,vd->bsv", xc, params["embed"]["embedding"])
        gold = jnp.take_along_axis(z, lc[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    return sum(nll(xs[i], ls[i]) for i in range(s // c)) / (b * s)


def _mamba_weights(cfg) -> int:
    """Multiplying weights of one Mamba2 mixer held here: w_in, conv, w_out."""
    d, h, p = cfg["hidden_size"], cfg["n_mamba_heads"], cfg["mamba_headdim"]
    gn = cfg["mamba_ngroups"] * cfg["mamba_d_state"]
    di = h * p
    conv = di + 2 * gn
    return d * (di + conv + h) + cfg["mamba_d_conv"] * conv + di * d


def _block_weights(cfg) -> int:
    """Multiplying weights of one shared block held here."""
    d, a = cfg["hidden_size"], cfg["attention_hidden_size"]
    hd = cfg["attention_head_dim"]
    q, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return a * (q + 2 * kv) * hd + q * hd * d + 3 * d * ff_units(cfg)


def _hybrid_weights(cfg) -> int:
    """One invocation's own weights: the adapter and the ``linear``."""
    d, r = cfg["hidden_size"], cfg["adapter_rank"]
    return d * r + 2 * r * ff_units(cfg) + d * d


def param_count(cfg) -> int:
    """Parameters this chip holds (its heads, groups and MLP units of each
    layer and block; every norm, each ``linear`` and the vocabulary
    whole)."""
    d, di = cfg["hidden_size"], cfg["n_mamba_heads"] * cfg["mamba_headdim"]
    h = cfg["n_mamba_heads"]
    conv = di + 2 * cfg["mamba_ngroups"] * cfg["mamba_d_state"]
    mamba = _mamba_weights(cfg) + conv + 3 * h + di + d
    block = _block_weights(cfg) + cfg["attention_hidden_size"] + d
    n_hybrid = len(hybrid_layers(cfg))
    return (cfg["num_hidden_layers"] * mamba
            + cfg["num_mem_blocks"] * block
            + n_hybrid * _hybrid_weights(cfg)
            + cfg["vocab_size"] * d + d)


def ssd_flops(cfg, seq: int) -> float:
    """Forward FLOPs a token of one Mamba2 layer spends in the SSD's own
    contractions at the configuration's chunk Q: the causal half of the
    intra-chunk C B^T (Q N per group) and of its product with x (Q P per
    head), and the chunk state's and the output's outer products (2 P N a
    head each)."""
    q = min(cfg["chunk_size"], seq)
    h, p = cfg["n_mamba_heads"], cfg["mamba_headdim"]
    n, g = cfg["mamba_d_state"], cfg["mamba_ngroups"]
    return float(q * n * g + q * p * h + 4 * h * p * n)


def attention_flops(cfg, seq: int) -> float:
    """Forward FLOPs a token spends in one invocation's causal q k^T and
    p v: 2 seq hd per head (half of the full square)."""
    return float(2 * seq * cfg["attention_head_dim"]
                 * cfg["num_attention_heads"])


def flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs of one token's forward and backward pass: 6 per weight
    it multiplies (every layer's mixer, each invocation's block, adapter
    and ``linear``, the tied head; not the embedding lookup), plus the
    SSD's and attention's own contractions, times 3. Recomputation does
    not count."""
    n_hybrid = len(hybrid_layers(cfg))
    weights = (cfg["num_hidden_layers"] * _mamba_weights(cfg)
               + n_hybrid * (_block_weights(cfg) + _hybrid_weights(cfg))
               + cfg["vocab_size"] * cfg["hidden_size"])
    mixers = (cfg["num_hidden_layers"] * ssd_flops(cfg, seq)
              + n_hybrid * attention_flops(cfg, seq))
    return float(6 * weights + 3 * mixers)

