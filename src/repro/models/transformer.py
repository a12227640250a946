"""Transformer assembly: segments of scanned layer patterns, with train,
prefill, and decode paths. See configs/base.py for the segment machinery.

Zamba2's hybrid layers (``LayerSpec.shared_block >= 0``) invoke one of the
shared transformer blocks on the stream concatenated with
the original embeddings ``e``, and add that block's output, mapped by the
layer's own ``linear``, to the layer's mixer input only:

    h' = h + Mamba2(RMSNorm(h + linear(block(h, e))))
    block(h, e) = MLP_adapted(RMSNorm(Attn(RMSNorm([h, e]))))

with no residual inside the block. The MLP is gated GELU; its gate and up
projections carry the layer's own low-rank adapter.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    _dense_init,
    cross_entropy,
    embed,
    init_embed,
    init_mlp,
    init_rmsnorm,
    mlp,
    rmsnorm,
    unembed,
)
from repro.models.scan_utils import maybe_scan
from repro.models.sharding import shard_hint

AUX_WEIGHT = 0.01  # load-balance aux loss weight


def _prepend_axis(axes_tree, name=None):
    return jax.tree.map(lambda t: (name,) + t, axes_tree,
                        is_leaf=lambda t: isinstance(t, tuple))


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


class Transformer:
    """Functional model: params are explicit pytrees; methods are pure."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.has_shared = cfg.n_shared_blocks > 0
        # Zamba2's attention scale: its head dim is twice the per-head width
        # of the stream it attends over
        self._shared_scale = (cfg.resolved_head_dim / 2) ** -0.5

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_layer(self, spec: LayerSpec, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        d = cfg.d_model
        kmix, kffn = jax.random.split(key)
        params: dict[str, Any] = {}
        axes: dict[str, Any] = {}

        params["norm1"], axes["norm1"] = init_rmsnorm(d, dt)
        if spec.mixer == "attn":
            params["mixer"], axes["mixer"] = attn.init_attention(
                kmix, d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                cfg.qkv_bias, dt)
        elif spec.mixer == "mamba2":
            params["mixer"], axes["mixer"] = ssm_mod.init_mamba2(
                kmix, d, cfg.ssm_state, cfg.ssm_headdim,
                cfg.ssm_heads, cfg.ssm_ngroups, cfg.conv_kernel, dt)
        elif spec.mixer == "rwkv6":
            params["mixer"], axes["mixer"] = rwkv_mod.init_rwkv6_timemix(
                kmix, d, cfg.rwkv_headdim, max(4, cfg.lora_rank or 32), dt)
        else:
            raise ValueError(f"unknown mixer {spec.mixer}")

        if spec.shared_block >= 0:
            r = cfg.adapter_rank
            ks = jax.random.split(jax.random.fold_in(kmix, 1), 2)
            params["hybrid"] = {
                "adapter_a": _dense_init(ks[0], (d, r), 0, dt),
                "adapter_gate": jnp.zeros((r, cfg.d_ff), dt),
                "adapter_up": jnp.zeros((r, cfg.d_ff), dt),
                "linear": _dense_init(ks[1], (d, d), 0, dt),
            }
            axes["hybrid"] = {
                "adapter_a": ("fsdp", None), "adapter_gate": (None, "tp"),
                "adapter_up": (None, "tp"), "linear": ("fsdp", None),
            }

        if spec.ffn != "none":
            params["norm2"], axes["norm2"] = init_rmsnorm(d, dt)
        if spec.ffn == "mlp":
            params["ffn"], axes["ffn"] = init_mlp(kffn, d, cfg.d_ff, dt)
        elif spec.ffn == "moe":
            params["ffn"], axes["ffn"] = moe_mod.init_moe(
                kffn, d, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts, cfg.top_k,
                cfg.shared_expert, dt)
        elif spec.ffn == "rwkv_cm":
            params["ffn"], axes["ffn"] = rwkv_mod.init_rwkv6_channelmix(
                kffn, d, cfg.d_ff, dt)
        elif spec.ffn == "none":
            params["ffn"], axes["ffn"] = {}, {}
        else:
            raise ValueError(f"unknown ffn {spec.ffn}")
        return params, axes

    def _init_shared_block(self, key):
        """One shared transformer block: attention from the 2 d_model wide
        concatenation back to d_model, and the gated MLP."""
        cfg = self.cfg
        dt = _dtype(cfg)
        d = cfg.d_model
        ka, km = jax.random.split(key)
        params: dict[str, Any] = {}
        axes: dict[str, Any] = {}
        params["norm_in"], axes["norm_in"] = init_rmsnorm(2 * d, dt)
        params["attn"], axes["attn"] = attn.init_attention(
            ka, 2 * d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.qkv_bias, dt, d_out=d)
        params["norm_ff"], axes["norm_ff"] = init_rmsnorm(d, dt)
        params["mlp"], axes["mlp"] = init_mlp(km, d, cfg.d_ff, dt)
        return params, axes

    def _layer_axes(self, spec: LayerSpec):
        """Logical axes of one layer, without materializing params."""
        box = {}

        def probe(k):
            p, a = self._init_layer(spec, k)
            box["axes"] = a
            return p

        jax.eval_shape(probe, jax.random.PRNGKey(0))
        return box["axes"]

    def init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 3 + len(cfg.segments))
        params: dict[str, Any] = {}
        params["embed"], self._embed_axes = init_embed(
            keys[0], cfg.vocab, cfg.d_model, cfg.tie_head, dt)
        params["final_norm"], fn_axes = init_rmsnorm(cfg.d_model, dt)

        seg_params = []
        seg_axes = []
        for si, seg in enumerate(cfg.segments):
            layer_keys = jax.random.split(keys[2 + si],
                                          seg.n_steps * len(seg.pattern))
            layer_keys = layer_keys.reshape(seg.n_steps, len(seg.pattern), 2)
            pat_p: dict[str, Any] = {}
            pat_a: dict[str, Any] = {}
            for j, ls in enumerate(seg.pattern):
                stacked = jax.vmap(lambda k, ls=ls: self._init_layer(ls, k)[0]
                                   )(layer_keys[:, j])
                pat_p[str(j)] = stacked
                pat_a[str(j)] = _prepend_axis(self._layer_axes(ls), None)
            seg_params.append(pat_p)
            seg_axes.append(pat_a)
        params["segments"] = seg_params

        if self.has_shared:
            params["shared"], self._shared_axes = {}, {}
            for k, kb in enumerate(jax.random.split(keys[1],
                                                    cfg.n_shared_blocks)):
                params["shared"][str(k)], self._shared_axes[str(k)] = (
                    self._init_shared_block(kb))

        self._axes = {
            "embed": self._embed_axes,
            "final_norm": fn_axes,
            "segments": seg_axes,
        }
        if self.has_shared:
            self._axes["shared"] = self._shared_axes
        return params

    def param_axes(self):
        """Logical-axis tree matching init() output (init must run first,
        or call axes_only())."""
        if not hasattr(self, "_axes"):
            self.axes_only()
        return self._axes

    def axes_only(self):
        """Build the logical-axes tree without materializing params."""
        jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return self._axes

    # ------------------------------------------------------------------
    # layer application (full sequence)
    # ------------------------------------------------------------------

    def _shared_block(self, spec: LayerSpec, lparams, shared, x, attend):
        """``linear(block(x, e))`` of a hybrid layer, and what ``attend``
        returned beside the attention's output. ``shared`` is (the shared
        blocks' params, the original embeddings e); ``attend(p, h)`` runs
        the block's attention on the normed concatenation (full sequence,
        prefill or decode) and returns (out, cache)."""
        cfg = self.cfg
        blocks, emb = shared
        bp = blocks[str(spec.shared_block)]
        hp = lparams["hybrid"]
        with jax.named_scope("zamba2.shared_block"):
            h = rmsnorm(bp["norm_in"], jnp.concatenate([x, emb], axis=-1),
                        cfg.norm_eps)
            a, kv = attend(bp["attn"], h)
            h = rmsnorm(bp["norm_ff"], a, cfg.norm_eps)
            mp = bp["mlp"]
            low = jnp.einsum("...d,dr->...r", h, hp["adapter_a"])
            gate = (jnp.einsum("...d,df->...f", h, mp["w_gate"])
                    + jnp.einsum("...r,rf->...f", low, hp["adapter_gate"]))
            up = (jnp.einsum("...d,df->...f", h, mp["w_up"])
                  + jnp.einsum("...r,rf->...f", low, hp["adapter_up"]))
            u = jax.nn.gelu(gate.astype(jnp.float32), approximate=False)
            u = u.astype(up.dtype) * up
            out = jnp.einsum("...f,fd->...d", u, mp["w_down"])
            return jnp.einsum("...d,de->...e", out, hp["linear"]), kv

    def _apply_mixer(self, spec: LayerSpec, lparams, shared, h, positions):
        cfg = self.cfg
        if spec.mixer == "attn":
            return attn.attention_forward(
                lparams["mixer"], h, positions, kind=spec.attn_kind,
                window=cfg.window, chunk=cfg.chunk, use_rope=spec.use_rope,
                rope_theta=cfg.rope_theta, block_q=cfg.block_q,
                causal_buckets=cfg.causal_buckets, unroll=cfg.scan_unroll)
        if spec.mixer == "mamba2":
            return ssm_mod.mamba2_forward(
                lparams["mixer"], h, d_state=cfg.ssm_state,
                headdim=cfg.ssm_headdim, n_groups=cfg.ssm_ngroups,
                chunk=cfg.ssd_chunk, eps=cfg.norm_eps,
                unroll=cfg.scan_unroll)
        if spec.mixer == "rwkv6":
            return rwkv_mod.rwkv6_timemix_forward(lparams["mixer"], h,
                                                  cfg.rwkv_headdim,
                                                  unroll=cfg.scan_unroll)
        raise ValueError(spec.mixer)

    def _apply_ffn(self, spec: LayerSpec, lparams, shared, h):
        cfg = self.cfg
        if spec.ffn == "mlp":
            return mlp(lparams["ffn"], h), 0.0
        if spec.ffn == "moe":
            return moe_mod.moe_apply(
                lparams["ffn"], h, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, impl=cfg.moe_impl,
                iterative_topk=cfg.scan_unroll)
        if spec.ffn == "rwkv_cm":
            return rwkv_mod.rwkv6_channelmix_forward(lparams["ffn"], h), 0.0
        return None, 0.0

    def _mixer_input(self, spec: LayerSpec, lparams, shared, x, positions):
        """The mixer's normed input: of x, or of x plus the shared block's
        contribution in a hybrid layer."""
        cfg = self.cfg
        if spec.shared_block >= 0:
            out, _ = self._shared_block(
                spec, lparams, shared, x, lambda p, h: (attn.attention_forward(
                    p, h, positions, rope_theta=cfg.rope_theta,
                    block_q=cfg.block_q, causal_buckets=cfg.causal_buckets,
                    unroll=cfg.scan_unroll, scale=self._shared_scale), None))
            x = x + out
        return rmsnorm(lparams["norm1"], x, cfg.norm_eps)

    def _apply_layer(self, spec: LayerSpec, lparams, shared, x, positions):
        aux = jnp.zeros((), jnp.float32)
        h = self._mixer_input(spec, lparams, shared, x, positions)
        x = x + self._apply_mixer(spec, lparams, shared, h, positions)
        if spec.ffn != "none":
            h2 = rmsnorm(lparams["norm2"], x, self.cfg.norm_eps)
            out, a = self._apply_ffn(spec, lparams, shared, h2)
            x = x + out
            aux = aux + a
        # layer-boundary carry: d_model sharded over the model axis in
        # training ("act" rule) so the remat-saved per-layer activations are
        # 1/TP the size; serving maps "act" to None.
        x = shard_hint(x, "batch", "seq", "act")
        return x, aux

    # ------------------------------------------------------------------
    # full forward (train / prefill logits)
    # ------------------------------------------------------------------

    def _embed_tokens(self, params, tokens, prefix):
        cfg = self.cfg
        x = embed(params["embed"], tokens, cfg.embed_impl)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        if prefix is not None:
            x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        return x

    def forward(self, params, tokens, prefix=None, remat=None):
        """tokens (B, S) -> (logits (B, S, V), aux). prefix (B, P, d) stub
        embeddings are prepended (vlm / audio) and stripped from logits."""
        x, aux = self._hidden_states(params, tokens, prefix, remat)
        return unembed(params["embed"], x), aux

    def loss_fn(self, params, batch):
        """batch: {"tokens": (B,S), "labels": (B,S), ["prefix": (B,P,d)]}"""
        cfg = self.cfg
        prefix = batch.get("prefix")
        if cfg.loss_chunk:
            return self._chunked_loss(params, batch, prefix)
        logits, aux = self.forward(params, batch["tokens"], prefix)
        return cross_entropy(logits, batch["labels"]) + AUX_WEIGHT * aux

    def _chunked_loss(self, params, batch, prefix):
        """Cross-entropy computed per sequence chunk : never materializes the
        full (B, S, V) logits — default for large-vocab archs."""
        cfg = self.cfg
        x, aux = self._hidden_states(params, batch["tokens"], prefix)
        c = cfg.loss_chunk
        b, s, d = x.shape
        assert s % c == 0, f"seq {s} % loss_chunk {c} != 0"
        xs = jnp.moveaxis(x.reshape(b, s // c, c, d), 1, 0)
        ls = jnp.moveaxis(batch["labels"].reshape(b, s // c, c), 1, 0)

        def chunk_nll(carry, inp):
            xc, lc = inp
            logits = unembed(params["embed"], xc)
            return carry + cross_entropy(logits, lc) * c, None

        chunk_fn = jax.checkpoint(chunk_nll) if cfg.remat else chunk_nll
        total, _ = maybe_scan(chunk_fn, jnp.zeros((), jnp.float32),
                              (xs, ls), unroll=cfg.scan_unroll)
        return total / s + AUX_WEIGHT * aux

    def _shared_inputs(self, params, x):
        """What a hybrid layer reads besides its own params: the shared
        blocks and the original embeddings."""
        return (params["shared"], x) if self.has_shared else None

    def _hidden_states(self, params, tokens, prefix, remat=None):
        """Final-normed hidden states. Under remat each layer is its own
        checkpoint, so the backward pass recomputes one layer at a time."""
        cfg = self.cfg
        remat = cfg.remat if remat is None else remat
        x = self._embed_tokens(params, tokens, prefix)
        positions = jnp.arange(x.shape[1])
        shared = self._shared_inputs(params, x)
        aux = jnp.zeros((), jnp.float32)

        def layer(carry, p, ls):
            x, aux = carry
            x, a = self._apply_layer(ls, p, shared, x, positions)
            return x, aux + a

        for seg_params, seg in zip(params["segments"], cfg.segments):
            layers = [functools.partial(layer, ls=ls) for ls in seg.pattern]
            if remat:
                layers = [jax.checkpoint(f) for f in layers]

            def step(carry, p_step, layers=layers):
                for j, f in enumerate(layers):
                    carry = f(carry, p_step[str(j)])
                return carry, None
            (x, aux), _ = maybe_scan(step, (x, aux), seg_params,
                                     unroll=cfg.scan_unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if prefix is not None:
            x = x[:, prefix.shape[1]:]
        return x, aux

    # ------------------------------------------------------------------
    # serving: prefill + decode
    # ------------------------------------------------------------------

    def _layer_cache_shape(self, spec: LayerSpec, batch: int, max_len: int,
                           natural: bool = False):
        cfg = self.cfg
        dt = _dtype(cfg)
        cache: dict[str, Any] = {}
        if spec.mixer == "attn":
            # natural: full-length position-ordered cache even for swa /
            # chunk layers (no ring truncation) — the layout the paged
            # serving pool ingests; visibility is enforced by masks
            cache["mixer"] = attn.init_kv_cache(
                batch, "full" if natural else spec.attn_kind, max_len,
                cfg.n_kv_heads, cfg.resolved_head_dim, cfg.window,
                cfg.chunk, dt)
        elif spec.mixer == "mamba2":
            cache["mixer"] = ssm_mod.init_mamba2_cache(
                batch, cfg.ssm_state, cfg.ssm_headdim, cfg.ssm_heads,
                cfg.ssm_ngroups, cfg.conv_kernel, dt)
        elif spec.mixer == "rwkv6":
            n_heads = cfg.d_model // cfg.rwkv_headdim
            cache["mixer"] = {
                "wkv": jnp.zeros((batch, n_heads, cfg.rwkv_headdim,
                                  cfg.rwkv_headdim), jnp.float32),
                "tm_last": jnp.zeros((batch, 1, cfg.d_model), dt),
            }
        if spec.shared_block >= 0:
            # the shared block's attention: full causal, over every position
            cache["shared"] = attn.init_kv_cache(
                batch, "full", max_len, cfg.n_kv_heads, cfg.resolved_head_dim,
                dtype=dt)
        if spec.ffn == "rwkv_cm":
            cache["ffn"] = {"cm_last": jnp.zeros((batch, 1, cfg.d_model), dt)}
        else:
            cache["ffn"] = {}
        return cache

    def init_cache(self, batch: int, max_len: int, natural: bool = False):
        """Zeroed caches matching the segment structure. KV caches for swa /
        chunk layers are ring buffers of the window/chunk size (or full
        position-ordered buffers under ``natural``, the serving-ingest
        layout)."""
        caches = []
        for seg in self.cfg.segments:
            pat = {}
            for j, ls in enumerate(seg.pattern):
                one = self._layer_cache_shape(ls, batch, max_len, natural)
                pat[str(j)] = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (seg.n_steps,) + x.shape), one)
            caches.append(pat)
        return caches

    def init_paged_cache(self, n_slots: int, n_blocks: int, block_size: int):
        """Serving caches for a continuous-batching engine: attention
        layers get a physical block pool (block-table indexed, shared
        geometry across layers), recurrent layers keep per-slot state rows
        (their state is O(1) per slot — nothing to page)."""
        cfg = self.cfg
        caches = []
        for seg in cfg.segments:
            pat = {}
            for j, ls in enumerate(seg.pattern):
                one = self._layer_cache_shape(ls, n_slots, 1)
                pool = functools.partial(
                    attn.init_paged_kv_cache, n_blocks, block_size,
                    cfg.n_kv_heads, cfg.resolved_head_dim, _dtype(cfg))
                if ls.mixer == "attn":
                    one["mixer"] = pool()
                if ls.shared_block >= 0:
                    one["shared"] = pool()
                pat[str(j)] = jax.tree.map(
                    lambda x: jnp.zeros((seg.n_steps,) + x.shape, x.dtype),
                    one)
            caches.append(pat)
        return caches

    def cache_axes(self):
        """Logical axes for cache arrays (KV caches: batch + heads + seq)."""
        def attn_axes(kind):
            return {"k": (None, "batch", "cache_seq", "kv_tp", None),
                    "v": (None, "batch", "cache_seq", "kv_tp", None)}
        axes = []
        for seg in self.cfg.segments:
            pat = {}
            for j, ls in enumerate(seg.pattern):
                c: dict[str, Any] = {}
                if ls.mixer == "attn":
                    c["mixer"] = attn_axes(ls.attn_kind)
                elif ls.mixer == "mamba2":
                    c["mixer"] = {"h": (None, "batch", "tp", None, None),
                                  "conv": (None, "batch", None, "tp")}
                elif ls.mixer == "rwkv6":
                    c["mixer"] = {"wkv": (None, "batch", "tp", None, None),
                                  "tm_last": (None, "batch", None, None)}
                if ls.shared_block >= 0:
                    c["shared"] = attn_axes("full")
                c["ffn"] = ({"cm_last": (None, "batch", None, None)}
                            if ls.ffn == "rwkv_cm" else {})
                pat[str(j)] = c
            axes.append(pat)
        return axes

    def _decode_attention(self, p, h, kv_cache, pos, table, kind, use_rope,
                          scale=None):
        """One-token attention over the dense cache, or over the paged pool
        when ``table`` is given."""
        cfg = self.cfg
        kw = dict(kind=kind, window=cfg.window, chunk=cfg.chunk,
                  use_rope=use_rope, rope_theta=cfg.rope_theta, scale=scale)
        if table is None:
            return attn.decode_attention(p, h, kv_cache, pos, **kw)
        return attn.paged_decode_attention(p, h, kv_cache, table, pos, **kw)

    def _decode_layer(self, spec: LayerSpec, lparams, shared, cache, x, pos,
                      table=None):
        cfg = self.cfg
        new_cache = dict(cache)
        if spec.shared_block >= 0:
            out, new_cache["shared"] = self._shared_block(
                spec, lparams, shared, x,
                lambda p, h: self._decode_attention(
                    p, h, cache["shared"], pos, table, "full", True,
                    self._shared_scale))
            h = rmsnorm(lparams["norm1"], x + out, cfg.norm_eps)
        else:
            h = rmsnorm(lparams["norm1"], x, cfg.norm_eps)
        if spec.mixer == "attn":
            out, new_cache["mixer"] = self._decode_attention(
                lparams["mixer"], h, cache["mixer"], pos, table,
                spec.attn_kind, spec.use_rope)
        elif spec.mixer == "mamba2":
            out, mc = ssm_mod.mamba2_decode(
                lparams["mixer"], h, cache["mixer"], d_state=cfg.ssm_state,
                headdim=cfg.ssm_headdim, n_groups=cfg.ssm_ngroups,
                eps=cfg.norm_eps)
            new_cache["mixer"] = mc
        elif spec.mixer == "rwkv6":
            out, rc = rwkv_mod.rwkv6_timemix_decode(
                lparams["mixer"], h,
                {**cache["mixer"], "cm_last": None}, cfg.rwkv_headdim)
            new_cache["mixer"] = {"wkv": rc["wkv"], "tm_last": rc["tm_last"]}
        else:
            raise ValueError(spec.mixer)
        x = x + out

        if spec.ffn != "none":
            h2 = rmsnorm(lparams["norm2"], x, cfg.norm_eps)
            if spec.ffn == "rwkv_cm":
                out2, fc = rwkv_mod.rwkv6_channelmix_decode(
                    lparams["ffn"], h2, cache["ffn"])
                new_cache["ffn"] = fc
            else:
                out2, _ = self._apply_ffn(spec, lparams, shared, h2)
            x = x + out2
        return x, new_cache

    def decode_step(self, params, caches, tokens, pos, table=None):
        """One decode step. tokens (B,) int32; pos () int32 = position of
        this token (prefix-inclusive). Returns (logits (B, V), new caches).

        With ``table`` (B, blocks_per_slot) int32, ``caches`` are the paged
        pools of :meth:`init_paged_cache` and ``pos`` is a per-slot (B,)
        vector — the continuous-batching decode where every slot sits at
        its own position."""
        cfg = self.cfg
        x = embed(params["embed"], tokens[:, None], cfg.embed_impl)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        shared = self._shared_inputs(params, x)
        new_caches = []
        for seg_params, seg_cache, seg in zip(params["segments"], caches,
                                              cfg.segments):
            def step(x, xs, seg=seg):
                p_step, c_step = xs
                new_c = {}
                for j, ls in enumerate(seg.pattern):
                    x, new_c[str(j)] = self._decode_layer(
                        ls, p_step[str(j)], shared, c_step[str(j)], x, pos,
                        table)
                return x, new_c

            x, new_seg_cache = jax.lax.scan(step, x, (seg_params, seg_cache))
            new_caches.append(new_seg_cache)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x)[:, 0]
        return logits, new_caches

    def _prefill_states(self, params, tokens, prefix, max_len,
                        natural=False):
        """Shared prefill body: final-normed hidden states (B, S_total, d)
        plus the filled caches."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens, prefix)
        b, s_total = x.shape[:2]
        max_len = max_len or s_total
        positions = jnp.arange(s_total)
        shared = self._shared_inputs(params, x)
        caches = self.init_cache(b, max_len, natural)
        new_caches = []
        for seg_params, seg_cache, seg in zip(params["segments"], caches,
                                              cfg.segments):
            def step(x, xs, seg=seg):
                p_step, c_step = xs
                new_c = {}
                for j, ls in enumerate(seg.pattern):
                    x, new_c[str(j)] = self._prefill_layer(
                        ls, p_step[str(j)], shared, c_step[str(j)], x,
                        positions)
                return x, new_c

            x, new_seg_cache = jax.lax.scan(step, x, (seg_params, seg_cache))
            new_caches.append(new_seg_cache)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, s_total

    def prefill(self, params, tokens, prefix=None, max_len=None):
        """Run the full prompt, building caches. Returns (last-token logits
        (B, V), caches, next position)."""
        x, new_caches, s_total = self._prefill_states(params, tokens,
                                                      prefix, max_len)
        logits = unembed(params["embed"], x[:, -1:])[:, 0]
        return logits, new_caches, jnp.asarray(s_total, jnp.int32)

    def prefill_at(self, params, tokens, lengths, prefix=None,
                   max_len=None):
        """Bucketed prefill for the serving engine: tokens (B, S) are
        right-padded to a common bucket length, lengths (B,) int32 are the
        true prompt lengths. Returns (per-row logits at each row's last
        TRUE token, natural-layout caches, per-row next position).

        Rows' cache entries beyond their true length hold pad garbage;
        paged decode overwrites position p before the ``p <= pos`` mask
        ever exposes it, so right-padding is exact for attention layers.
        Recurrent state (mamba2 / rwkv6 / rwkv_cm) consumes pad tokens,
        so engines must prefill those archs at exact lengths.
        """
        p_len = 0 if prefix is None else prefix.shape[1]
        x, new_caches, s_total = self._prefill_states(
            params, tokens, prefix, max_len, natural=True)
        b = x.shape[0]
        idx = p_len + lengths - 1
        xg = x[jnp.arange(b), idx][:, None]
        logits = unembed(params["embed"], xg)[:, 0]
        return logits, new_caches, (p_len + lengths).astype(jnp.int32)

    def insert_prefill(self, paged, pre, table_rows, slots):
        """Scatter one prefill batch's natural-layout caches into the
        paged pools / slot state rows.

        paged: pools from :meth:`init_paged_cache`; pre: caches from
        :meth:`prefill_at` (attention rows in position order, length n);
        table_rows (nb, bps) int32 physical blocks of the target slots;
        slots (nb,) int32 slot ids. Only the blocks the prompt span
        covers are written — later blocks keep stale garbage that decode
        overwrites before the position mask exposes it. Duplicate rows
        (admission padding) must carry identical data: scatters with
        repeated indices then commute."""
        def scatter_blocks(pool, rows):
            # pool (T, NB, bs, KV, hd); rows (T, nb, n, KV, hd)
            bs = pool.shape[2]
            n = rows.shape[2]
            nb_blocks = -(-n // bs)
            pad = nb_blocks * bs - n
            if pad:
                rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0),
                                      (0, 0)))
            blocks = rows.reshape(rows.shape[0], rows.shape[1], nb_blocks,
                                  bs, *rows.shape[3:])
            return pool.at[:, table_rows[:, :nb_blocks]].set(
                blocks.astype(pool.dtype))

        out = []
        for seg_pre, seg_paged, seg in zip(pre, paged, self.cfg.segments):
            pat = {}
            for j, ls in enumerate(seg.pattern):
                cp, cg = seg_pre[str(j)], seg_paged[str(j)]
                new = {}
                if ls.shared_block >= 0:
                    new["shared"] = jax.tree.map(scatter_blocks,
                                                 cg["shared"], cp["shared"])
                if ls.mixer == "attn":
                    new["mixer"] = jax.tree.map(scatter_blocks,
                                                cg["mixer"], cp["mixer"])
                else:
                    new["mixer"] = jax.tree.map(
                        lambda g, p: g.at[:, slots].set(p.astype(g.dtype)),
                        cg["mixer"], cp["mixer"])
                new["ffn"] = jax.tree.map(
                    lambda g, p: g.at[:, slots].set(p.astype(g.dtype)),
                    cg["ffn"], cp["ffn"])
                pat[str(j)] = new
            out.append(pat)
        return out

    def _prefill_layer(self, spec: LayerSpec, lparams, shared, cache, x,
                       positions):
        cfg = self.cfg
        new_cache = dict(cache)
        if spec.shared_block >= 0:
            def attend(p, h):
                out, (k, v) = attn.attention_forward_kv(
                    p, h, positions, rope_theta=cfg.rope_theta,
                    block_q=cfg.block_q, scale=self._shared_scale)
                return out, attn.fill_kv_cache(cache["shared"], k, v, "full")

            out, new_cache["shared"] = self._shared_block(
                spec, lparams, shared, x, attend)
            h = rmsnorm(lparams["norm1"], x + out, cfg.norm_eps)
        else:
            h = rmsnorm(lparams["norm1"], x, cfg.norm_eps)
        if spec.mixer == "attn":
            out, (k, v) = attn.attention_forward_kv(
                lparams["mixer"], h, positions, kind=spec.attn_kind,
                window=cfg.window, chunk=cfg.chunk, use_rope=spec.use_rope,
                rope_theta=cfg.rope_theta, block_q=cfg.block_q)
            new_cache["mixer"] = attn.fill_kv_cache(
                cache["mixer"], k, v, spec.attn_kind, cfg.window, cfg.chunk)
        elif spec.mixer == "mamba2":
            out, st = ssm_mod.mamba2_forward_state(
                lparams["mixer"], h, d_state=cfg.ssm_state,
                headdim=cfg.ssm_headdim, n_groups=cfg.ssm_ngroups,
                chunk=cfg.ssd_chunk, eps=cfg.norm_eps)
            new_cache["mixer"] = st
        elif spec.mixer == "rwkv6":
            out, st = rwkv_mod.rwkv6_timemix_forward_state(
                lparams["mixer"], h, cfg.rwkv_headdim)
            new_cache["mixer"] = st
        else:
            raise ValueError(spec.mixer)
        x = x + out
        if spec.ffn != "none":
            h2 = rmsnorm(lparams["norm2"], x, cfg.norm_eps)
            if spec.ffn == "rwkv_cm":
                out2 = rwkv_mod.rwkv6_channelmix_forward(lparams["ffn"], h2)
                new_cache["ffn"] = {"cm_last": h2[:, -1:]}
            else:
                out2, _ = self._apply_ffn(spec, lparams, shared, h2)
            x = x + out2
        return x, new_cache
