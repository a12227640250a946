"""Attention: GQA/MQA/MHA with full-causal, sliding-window, and chunked
variants; blockwise (flash-style) lax implementation for train/prefill and a
cached single-token path for decode.

The blockwise path is the *compiled* baseline (works on any backend and keeps
the S x S score matrix tiled); the Pallas kernel in repro/kernels/flash_attention.py
is the TPU hot path and is validated against the same math.

Shapes: x (B, S, d); q (B, S, H, hd); k/v (B, S, KV, hd).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, apply_rope, rope_angles
from repro.models.scan_utils import maybe_map
from repro.models.sharding import shard_hint

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False, dtype=jnp.float32,
                   d_out: int | None = None):
    """Projections from ``d_model`` and, through ``wo``, back to ``d_out``
    (default ``d_model``)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "wq": _dense_init(k1, (d_model, n_heads, head_dim), 0, dtype),
        "wk": _dense_init(k2, (d_model, n_kv_heads, head_dim), 0, dtype),
        "wv": _dense_init(k3, (d_model, n_kv_heads, head_dim), 0, dtype),
        "wo": _dense_init(k4, (n_heads, head_dim, d_out or d_model), 2,
                          dtype),
    }
    axes = {
        "wq": ("fsdp", "tp", None),
        "wk": ("fsdp", "tp", None),
        "wv": ("fsdp", "tp", None),
        "wo": ("tp", None, "fsdp"),
    }
    if qkv_bias:
        params["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        params["bk"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        params["bv"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        axes["bq"] = ("tp", None)
        axes["bk"] = ("tp", None)
        axes["bv"] = ("tp", None)
    return params, axes


def _project_qkv(params, x, positions, use_rope: bool, rope_theta: float):
    wq = shard_hint(params["wq"], "wg", "tp", None)
    wk = shard_hint(params["wk"], "wg", "tp", None)
    wv = shard_hint(params["wv"], "wg", "tp", None)
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if use_rope:
        cos, sin = rope_angles(positions, q.shape[-1], rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard_hint(q, "batch", "seq", "tp", None)
    k = shard_hint(k, "batch", "seq", "tp", None)
    v = shard_hint(v, "batch", "seq", "tp", None)
    return q, k, v


def _sdpa(q, k, v, mask, scale=None):
    """Grouped scaled-dot-product attention on one (q-block, kv-block).

    q (B, Sq, H, hd); k/v (B, Skv, KV, hd); mask broadcastable to
    (B, 1, 1, Sq, Skv). Softmax in fp32 of the scores times ``scale``
    (None: 1 / sqrt(hd)).
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = (scores / math.sqrt(hd) if scale is None
              else scores * scale)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def blocked_causal_attention(q, k, v, *, window: int = 0, block_q: int = 512,
                             q_start: int = 0, causal_buckets: bool = False,
                             unroll: bool = False, scale=None):
    """Causal (optionally sliding-window) attention, tiled over q blocks.

    window == 0 -> full causal. window == W -> attend to the last W positions
    (inclusive of self). q_start offsets q positions relative to k positions
    (used when a prefix occupies the head of the kv sequence).

    causal_buckets: group q blocks into power-of-two buckets so bucket b only
    reads kv[0 : 2^(b+1) * block_q] — skips ~1/3 of the above-diagonal work
    with fully static shapes (§Perf optimization).
    """
    if causal_buckets and not window and q_start == 0:
        return _bucketed_causal_attention(q, k, v, block_q=block_q,
                                          unroll=unroll, scale=scale)
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    n_blocks = (sq + bq - 1) // bq
    pad = n_blocks * bq - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    kv_positions = jnp.arange(skv)

    # checkpointed per-q-block body: the backward pass recomputes the block's
    # scores/probs instead of saving an S x S softmax across all blocks
    @jax.checkpoint
    def one_block(i):
        qs = i * bq
        qb = jax.lax.dynamic_slice_in_dim(q, qs, bq, axis=1)
        q_pos = q_start + qs + jnp.arange(bq)
        if window and window + bq < skv:
            # only the last (window + bq) keys can be visible to this block
            kv_len = window + bq
            start = jnp.clip(q_start + qs + bq - kv_len, 0, skv - kv_len)
            kb = jax.lax.dynamic_slice_in_dim(k, start, kv_len, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, kv_len, axis=1)
            k_pos = start + jnp.arange(kv_len)
        else:
            kb, vb = k, v
            k_pos = kv_positions
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask = mask[None, None, None]
        return _sdpa(qb, kb, vb, mask, scale)

    out = maybe_map(one_block, jnp.arange(n_blocks), unroll)  # (nb,B,bq,H,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * bq, h, hd)
    return out[:, :sq]


def _bucketed_causal_attention(q, k, v, *, block_q: int,
                               unroll: bool = False, scale=None):
    """Causal attention with power-of-two kv buckets (static shapes).

    q block i needs kv[0 : (i+1) * bq]. Blocks with i+1 in (2^b/2, 2^b] share
    the padded kv span kv[0 : 2^b * bq]; each bucket runs its own lax.map.
    FLOPs = sum_b 2^(b-1) * 2^b * bq^2 ~ (2/3) S^2 vs S^2 for the full grid.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    assert sq % bq == 0, (sq, bq)
    nb = sq // bq

    outs = []
    start = 0
    span = 1
    while start < nb:
        count = min(span - start, nb - start)     # blocks in this bucket
        kv_len = min(span * bq, skv)
        kb, vb = k[:, :kv_len], v[:, :kv_len]
        k_pos = jnp.arange(kv_len)

        def one_block(i, kb=kb, vb=vb, k_pos=k_pos, start=start):
            qs = (start + i) * bq
            qb = jax.lax.dynamic_slice_in_dim(q, qs, bq, axis=1)
            q_pos = qs + jnp.arange(bq)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None, None]
            return _sdpa(qb, kb, vb, mask, scale)

        one_block = jax.checkpoint(one_block)
        out = maybe_map(one_block, jnp.arange(count), unroll)
        outs.append(jnp.moveaxis(out, 0, 1).reshape(b, count * bq, h, hd))
        start += count
        span *= 2
    return jnp.concatenate(outs, axis=1)


def chunked_causal_attention(q, k, v, chunk: int, unroll: bool = False,
                             scale=None):
    """Llama4-style chunked attention: tokens attend causally only within
    their own chunk. O(S * chunk)."""
    b, s, h, hd = q.shape
    if s <= chunk:
        pos = jnp.arange(s)
        mask = (pos[:, None] >= pos[None, :])[None, None, None]
        return _sdpa(q, k, v, mask, scale)
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    n = s // chunk
    kv_h = k.shape[2]

    @jax.checkpoint
    def per_chunk(args):
        qc, kc, vc = args
        pos = jnp.arange(chunk)
        mask = (pos[:, None] >= pos[None, :])[None, None, None]
        return _sdpa(qc, kc, vc, mask, scale)

    qc = jnp.moveaxis(q.reshape(b, n, chunk, h, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, n, chunk, kv_h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n, chunk, kv_h, hd), 1, 0)
    out = maybe_map(per_chunk, (qc, kc, vc), unroll)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def attention_forward(params, x, positions, *, kind: str = "full",
                      window: int = 0, chunk: int = 0, use_rope: bool = True,
                      rope_theta: float = 1e4, block_q: int = 512,
                      causal_buckets: bool = False, unroll: bool = False,
                      scale=None):
    """Full-sequence attention (train / prefill). Returns (B, S, d)."""
    out, _ = attention_forward_kv(
        params, x, positions, kind=kind, window=window, chunk=chunk,
        use_rope=use_rope, rope_theta=rope_theta, block_q=block_q,
        causal_buckets=causal_buckets, unroll=unroll, scale=scale)
    return out


def attention_forward_kv(params, x, positions, *, kind: str = "full",
                         window: int = 0, chunk: int = 0,
                         use_rope: bool = True, rope_theta: float = 1e4,
                         block_q: int = 512, causal_buckets: bool = False,
                         unroll: bool = False, scale=None):
    """Like attention_forward but also returns the (k, v) pair for prefill
    cache construction. ``scale`` multiplies the scores (None: 1/sqrt(hd))."""
    q, k, v = _project_qkv(params, x, positions, use_rope, rope_theta)
    if kind == "full":
        ctxv = blocked_causal_attention(q, k, v, window=0, block_q=block_q,
                                        causal_buckets=causal_buckets,
                                        unroll=unroll, scale=scale)
    elif kind == "swa":
        ctxv = blocked_causal_attention(q, k, v, window=window,
                                        block_q=block_q, unroll=unroll,
                                        scale=scale)
    elif kind == "chunk":
        ctxv = chunked_causal_attention(q, k, v, chunk=chunk, unroll=unroll,
                                        scale=scale)
    else:
        raise ValueError(f"unknown attention kind {kind}")
    wo = shard_hint(params["wo"], "tp", None, "fsdp")
    out = jnp.einsum("bshk,hkd->bsd", ctxv, wo)
    return shard_hint(out, "batch", "seq", None), (k, v)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def cache_len(kind: str, max_len: int, window: int, chunk: int) -> int:
    if kind == "swa":
        return min(window, max_len)
    if kind == "chunk":
        return min(chunk, max_len)
    return max_len


def init_kv_cache(batch: int, kind: str, max_len: int, n_kv_heads: int,
                  head_dim: int, window: int = 0, chunk: int = 0,
                  dtype=jnp.bfloat16):
    n = cache_len(kind, max_len, window, chunk)
    shape = (batch, n, n_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def fill_kv_cache(cache, k, v, kind: str, window: int = 0, chunk: int = 0):
    """Write a full prefill sequence into the cache (possibly ring-truncated).

    k/v (B, S, KV, hd). For swa/chunk caches only the tail that remains
    visible is stored, laid out in ring order (slot = pos % cache_len)."""
    n = cache["k"].shape[1]
    s = k.shape[1]
    if s <= n:
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1),
        }
        return cache
    tail_k, tail_v = k[:, s - n:], v[:, s - n:]
    # ring layout: position p lives at slot p % n
    slots = (jnp.arange(s - n, s)) % n
    order = jnp.argsort(slots)
    return {"k": tail_k[:, order], "v": tail_v[:, order]}


def init_paged_kv_cache(n_blocks: int, block_size: int, n_kv_heads: int,
                        head_dim: int, dtype=jnp.bfloat16):
    """Preallocated block pool for the paged serving cache.

    Unlike the dense per-sequence cache of :func:`init_kv_cache`, the pool
    is indexed by *physical block id*: a slot owns an arbitrary set of
    blocks through an engine-managed ``(slots, blocks_per_slot)`` block
    table, so recycled slots reuse whatever blocks are free rather than a
    fixed contiguous span. Layout inside a slot's span is natural
    (position ``p`` lives at logical offset ``p``; no ring truncation —
    swa/chunk visibility is enforced by the decode mask instead), which
    makes the pool literally the dense full-attention cache when one
    block spans ``max_len`` and the table is the identity."""
    shape = (n_blocks, block_size, n_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_decode_attention(params, x, cache, table, pos, *,
                           kind: str = "full", window: int = 0,
                           chunk: int = 0, use_rope: bool = True,
                           rope_theta: float = 1e4, scale=None):
    """One-token decode over B independent slots of a paged KV cache.

    x (B, 1, d); cache {"k"/"v": (NB, bs, KV, hd)} block pool; table
    (B, bps) int32 maps each slot's logical block l to a physical block;
    pos (B,) int32 per-slot position of this token. Writes each slot's
    k/v at (table[b, pos_b // bs], pos_b % bs), gathers the slot's full
    logical span back in position order, and masks entries beyond pos_b
    (plus the sliding-window / chunk visibility rule). With one block
    spanning the span and an identity table the gathered reads are
    bit-identical to the dense :func:`decode_attention` cache reads; with
    more blocks they are the same values in the same position order, so
    full-attention outputs stay bit-identical to the dense path.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(params, x, pos[:, None], use_rope, rope_theta)
    bs = cache["k"].shape[1]
    phys = table[jnp.arange(b), pos // bs]
    off = pos % bs
    ck = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
    span = table.shape[1] * bs
    kb = ck[table].reshape(b, span, *ck.shape[2:])
    vb = cv[table].reshape(b, span, *cv.shape[2:])
    p = jnp.arange(span)
    valid = p[None, :] <= pos[:, None]
    if kind == "swa":
        valid &= p[None, :] > pos[:, None] - window
    elif kind == "chunk":
        valid &= p[None, :] >= (pos[:, None] // chunk) * chunk
    mask = valid[:, None, None, None, :]
    ctxv = _sdpa(q, kb, vb, mask, scale)
    wo = shard_hint(params["wo"], "tp", None, "fsdp")
    out = jnp.einsum("bshk,hkd->bsd", ctxv, wo)
    return shard_hint(out, "batch", "seq", None), {"k": ck, "v": cv}


def decode_attention(params, x, cache, pos, *, kind: str = "full",
                     window: int = 0, chunk: int = 0, use_rope: bool = True,
                     rope_theta: float = 1e4, scale=None):
    """One-token decode. x (B, 1, d); pos scalar int32 = index of this token.
    Returns (out (B,1,d), updated cache)."""
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, positions, use_rope, rope_theta)
    n = cache["k"].shape[1]
    slot = pos % n
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    # entry at slot i currently holds position: the largest p <= pos with
    # p % n == i  ->  p = pos - ((pos - i) % n)
    slots = jnp.arange(n)
    entry_pos = pos - jnp.mod(pos - slots, n)
    valid = entry_pos >= 0
    if kind == "swa":
        valid &= entry_pos > pos - window
    elif kind == "chunk":
        valid &= entry_pos >= (pos // chunk) * chunk
    mask = valid[None, None, None, None, :]
    ctxv = _sdpa(q, ck, cv, mask, scale)
    wo = shard_hint(params["wo"], "tp", None, "fsdp")
    out = jnp.einsum("bshk,hkd->bsd", ctxv, wo)
    return shard_hint(out, "batch", "seq", None), {"k": ck, "v": cv}
