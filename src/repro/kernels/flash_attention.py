"""Flash attention Pallas kernel (TPU target).

Grid: (batch*heads, n_q_blocks). Each program streams kv blocks for one q
tile with the online-softmax recurrence; running max/denominator/accumulator
live in VMEM scratch. Causal blocks beyond the diagonal are skipped
(`hi = ceil((q_idx+1)*bq / bk)`), and with a sliding window the lower bound
is raised too — the block-sparsity that makes SWA O(S*W).

BlockSpec tiling: q tile (bq, hd), kv tiles (bk, hd); MXU-aligned when
bq, bk, hd are multiples of 128 (hd=128 for most assigned archs).

kv streaming uses the ref-indexing API (``ref[0, pl.dslice(...), :]``) —
the tuple-index ``pl.load`` form was dropped upstream. Selected through
``repro.kernels.dispatch`` (backend "pallas"/"interpret"), with
``ref.flash_attention_ref`` as the registered oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, s, window, scale):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, hd)
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    n_kv = s // bk
    hi = jnp.minimum((qi + 1) * bq + bk - 1, s) // bk     # causal upper bound
    if window:
        lo = jnp.maximum(qi * bq - window, 0) // bk
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        scores = q @ k.T                                   # (bq, bk)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q/k/v (B, H, S, hd) (GQA pre-expanded). Returns (B, H, S, hd)."""
    assert causal, "only causal supported (decoder stacks)"
    b, h, s, hd = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(b * h, s, hd)
    kf = k.reshape(b * h, s, hd)
    vf = v.reshape(b * h, s, hd)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, s=s,
                               window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd)
