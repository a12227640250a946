"""Equivalence tests for the §Perf optimizations: every optimized lowering
must compute the same function as the paper-faithful baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clipping import make_dp_grad_fn
from repro.models.attention import blocked_causal_attention
from repro.models.layers import embed, init_embed
from repro.models.moe import init_moe, moe_dense, moe_scatter
from repro.models.rwkv import wkv6_chunked, wkv6_scan


def test_scan_accum_equals_stack():
    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (6, 3))}
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (8, 6)),
             "y": jax.random.normal(jax.random.PRNGKey(2), (8, 3))}
    key = jax.random.PRNGKey(3)
    g_stack, m1 = make_dp_grad_fn(loss, 1.0, 4, vmap_microbatches=False,
                                  accumulate="stack")(params, batch, key, 0.3)
    g_scan, m2 = make_dp_grad_fn(loss, 1.0, 4, vmap_microbatches=False,
                                 accumulate="scan")(params, batch, key, 0.3)
    np.testing.assert_allclose(np.asarray(g_stack["w"]),
                               np.asarray(g_scan["w"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)


def test_onehot_embed_equals_gather():
    params, _ = init_embed(jax.random.PRNGKey(0), 64, 16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 64)
    a = embed(params, toks, "gather")
    b = embed(params, toks, "one_hot")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("sq", [64, 96, 128])
def test_bucketed_causal_equals_full_grid(sq):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, sq, 4, 16)) for kk in ks)
    base = blocked_causal_attention(q, k, v, block_q=16)
    opt = blocked_causal_attention(q, k, v, block_q=16, causal_buckets=True)
    np.testing.assert_allclose(np.asarray(opt), np.asarray(base), rtol=2e-5,
                               atol=2e-6)


# per-token log-decay -exp(x), x uniform in a range: the benchmark cells'
# (w0 in [-6, -1]), strong ones down to -30 a token, extreme down to -4e4
WKV_DECAYS = {"mild": (-6.0, -1.0), "strong": (-6.0, 3.4),
              "extreme": (-6.0, 10.6)}


def _wkv_both(s, decay):
    """Outputs and gradients (in r, k, v, log-decay, u and s0) of the
    per-token recurrence and of the chunked form, B = 2, a nonzero s0."""
    b, h, hd = 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(s), 8)
    r, k, v = (jax.random.normal(kk, (b, s, h, hd)) for kk in ks[:3])
    lo, hi = WKV_DECAYS[decay]
    logw = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, hd), minval=lo,
                                       maxval=hi))
    u = 0.5 * jax.random.normal(ks[4], (h, hd))
    s0 = jax.random.normal(ks[5], (b, h, hd, hd))
    wy = jax.random.normal(ks[6], (b, s, h, hd))
    ws = jax.random.normal(ks[7], (b, h, hd, hd))

    def run(chunked):
        def f(r, k, v, logw, u, s0):
            if chunked:
                return wkv6_chunked(r, k, v, logw, u, s0)
            return wkv6_scan(r, k, v, jnp.exp(logw), u, s0)

        def obj(*args):
            y, st = f(*args)
            return jnp.sum(y * wy) + jnp.sum(st * ws)

        args = (r, k, v, logw, u, s0)
        return (*f(*args), *jax.grad(obj, argnums=tuple(range(6)))(*args))

    return run(chunked=False), run(chunked=True)


@pytest.mark.parametrize("decay", ["mild", "strong"])
@pytest.mark.parametrize("s", [1, 16, 64, 96, 256])
def test_wkv6_chunked_equals_scan_gradients(s, decay):
    """The chunked form (used in training and prefill) matches the
    per-token recurrence: output, final state, and the gradients in r, k,
    v, log-decay, u and s0, all finite. S = 96 leaves a ragged tail chunk,
    S = 16 a single sub-chunk, S = 1 (a one-token prefill) a padded one."""
    for want, got in zip(*_wkv_both(s, decay)):
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("s", [16, 64, 96, 256])
def test_wkv6_chunked_extreme_decay_finite(s):
    """Log-decays down to -4e4 a token: every output and gradient stays
    finite and near the recurrence's. Each in-chunk exponent carries the
    f32 rounding of a sub-chunk's summed log-decay (16 tokens, each
    floored at LOG_DECAY_FLOOR), so the match is to about 1e-4 of the
    largest value rather than to the mild cases' 1e-4 absolute."""
    for want, got in zip(*_wkv_both(s, "extreme")):
        got, want = np.asarray(got), np.asarray(want)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, want, rtol=1e-3,
                                   atol=1e-4 * np.abs(want).max())


def test_rwkv6_transformer_chunked_matches_per_token(monkeypatch):
    """An rwkv6 Transformer's loss and gradient through the chunked WKV
    equal those through the per-token recurrence (the oracle, patched in
    for this test only), at a small width and a ragged S = 40."""
    import dataclasses

    from repro.configs import get_arch, smoke_variant
    from repro.models import rwkv as rwkv_mod
    from repro.models.transformer import Transformer

    cfg = dataclasses.replace(smoke_variant(get_arch("rwkv6-1.6b")),
                              dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)

    def per_token(r, k, v, log_decay, u, s0=None, unroll=False):
        return wkv6_scan(r, k, v, jnp.exp(log_decay), u, s0, unroll=unroll)

    monkeypatch.setattr(rwkv_mod, "wkv6_chunked", per_token)
    loss_ref, grads_ref = jax.value_and_grad(model.loss_fn)(params, batch)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3,
                                   atol=1e-4 * np.abs(want).max() + 1e-12)


def test_moe_scatter_equals_dense_train_and_decode():
    p, _ = init_moe(jax.random.PRNGKey(0), 16, 32, n_experts=4, top_k=2,
                    shared_expert=True)
    for shape in ((2, 16, 16), (8, 1, 16)):      # train-ish and decode
        x = jax.random.normal(jax.random.PRNGKey(1), shape)
        y1, a1 = moe_scatter(p, x, top_k=2, capacity_factor=4.0)
        y2, a2 = moe_dense(p, x, top_k=2, capacity_factor=4.0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


def test_moe_decode_grouping_no_waste():
    """decode (S=1) groups the whole batch: capacity ~ B*K/E, not 8 per row."""
    from repro.models.moe import _regroup, capacity
    x = jnp.zeros((128, 1, 16))
    g = _regroup(x)
    assert g.shape == (1, 128, 16)
    assert capacity(128, 16, 2, 1.25) < 128    # vs 128 rows x cap 8 = 1024
