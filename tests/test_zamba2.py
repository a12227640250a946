"""Zamba2 in the program: the published layout, the grouped SSD against the
sequential oracle, serving's prefill and decode against the full forward
pass, and the DP step's view of the shared blocks, whose leaves one step
uses in several places."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.configs.base import LayerSpec, Segment, patterned_segments
from repro.core.clipping import make_dp_grad_fn
from repro.kernels import ref
from repro.models.layers import rmsnorm
from repro.models.ssm import ssd_chunked
from repro.models.transformer import Transformer

HYBRID_IDS = [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]


def test_published_layout():
    cfg = get_arch("zamba2-7b")
    specs = cfg.layer_specs()
    assert len(specs) == cfg.n_layers == 81
    assert all(ls.mixer == "mamba2" and ls.ffn == "none" for ls in specs)
    hybrid = [i for i, ls in enumerate(specs) if ls.shared_block >= 0]
    assert hybrid == HYBRID_IDS
    # blocks A and B in turn, by invocation
    assert [specs[i].shared_block for i in hybrid] == [
        k % 2 for k in range(len(hybrid))]
    assert (cfg.ssm_heads, cfg.ssm_ngroups, cfg.ssd_chunk) == (112, 2, 256)
    assert cfg.n_shared_blocks == 2
    assert (cfg.resolved_head_dim, cfg.norm_eps, cfg.adapter_rank) == (
        224, 1e-5, 128)
    # the cut to the first 12 layers: both blocks, each once
    cut = patterned_segments(12, cfg.segments[0].pattern)
    assert [ls.shared_block for s in cut for _ in range(s.n_steps)
            for ls in s.pattern] == [-1] * 6 + [0] + [-1] * 4 + [1]


def test_rmsnorm_takes_the_epsilon():
    x = jnp.full((1, 4), 1e-3, jnp.float32)
    p = {"scale": jnp.ones((4,))}
    got = rmsnorm(p, x, 1e-5)
    np.testing.assert_allclose(got, x / np.sqrt(1e-6 + 1e-5), rtol=1e-6)
    assert not np.allclose(got, rmsnorm(p, x))          # default 1e-6


@pytest.mark.parametrize("groups", [1, 2])
def test_grouped_ssd_matches_sequential_oracle(groups):
    """Each head reads its group's B and C (head h -> group h // (H/G))."""
    b, s, h, p, n = 2, 32, 4, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_in = jax.random.normal(ks[3], (b, s, groups, n))
    c_in = jax.random.normal(ks[4], (b, s, groups, n))
    got_y, got_s = ssd_chunked(x, dt, a, b_in, c_in, chunk=8)
    per_head = lambda t: jnp.repeat(t, h // groups, axis=2)
    for hh in range(h):
        want_y, want_s = ref.mamba2_ssd_ref(
            x[:, :, hh:hh + 1], dt[:, :, hh:hh + 1], a[hh:hh + 1],
            per_head(b_in)[:, :, hh], per_head(c_in)[:, :, hh])
        np.testing.assert_allclose(got_y[:, :, hh:hh + 1], want_y,
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(got_s[:, hh:hh + 1], want_s,
                                   rtol=1e-3, atol=1e-4)


def test_long_chunk_stays_finite():
    """A chunk of 256 with fast decay: the decay above the diagonal is
    masked before the exp, so neither the output nor its gradient
    overflows."""
    b, s, h, p, n = 1, 256, 2, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jnp.full((b, s, h), 2.0)
    a = jnp.array([-1.0, -16.0])
    bc = jax.random.normal(ks[1], (b, s, 1, n))

    def f(x):
        return jnp.sum(ssd_chunked(x, dt, a, bc, bc, chunk=256)[0])
    y, g = jax.value_and_grad(f)(x)
    assert np.isfinite(float(y)) and np.isfinite(np.asarray(g)).all()


def _smoke():
    cfg = smoke_variant(get_arch("zamba2-7b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # the adapters' second matrices start at zero: give them a value so
    # every path of the block carries signal
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (x + 0.05 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), x.shape)
            if "adapter_" in jax.tree_util.keystr(path) else x), params)
    return cfg, model, params


def test_smoke_invokes_both_blocks_in_each_segment():
    cfg, _, _ = _smoke()
    for seg in cfg.segments:
        assert sorted(ls.shared_block for ls in seg.pattern
                      if ls.shared_block >= 0) == [0, 1]


def test_prefill_then_decode_matches_forward():
    """Prefill fills the grouped SSD state, the conv window and the shared
    blocks' KV caches; each decode step's logits equal the full forward
    pass's at that position."""
    cfg, model, params = _smoke()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    want, _ = jax.jit(model.forward)(params, toks)
    got, caches, pos = jax.jit(
        lambda p, t: model.prefill(p, t, max_len=16))(params, toks[:, :8])
    np.testing.assert_allclose(got, want[:, 7], rtol=1e-4, atol=1e-4)
    step = jax.jit(model.decode_step)
    for i in range(8, 15):
        got, caches = step(params, caches, toks[:, i], pos)
        pos = pos + 1
        np.testing.assert_allclose(got, want[:, i], rtol=1e-4, atol=1e-4)


def _untied(cfg, params):
    """The same model with the second segment's invocations given blocks
    of their own (2 and 3), holding copies of blocks 0 and 1."""
    seg0, seg1 = cfg.segments
    pattern = tuple(replace(ls, shared_block=ls.shared_block + 2)
                    if ls.shared_block >= 0 else ls for ls in seg1.pattern)
    cfg2 = replace(cfg, segments=(seg0, Segment(seg1.n_steps, pattern)))
    p2 = dict(params, shared=dict(params["shared"], **{
        "2": params["shared"]["0"], "3": params["shared"]["1"]}))
    return Transformer(cfg2), p2


def test_shared_leaf_gradient_sums_its_invocations_and_is_clipped_whole():
    cfg, model, params = _smoke()
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 17), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    untied, p2 = _untied(cfg, params)
    g = jax.grad(model.loss_fn)(params, batch)
    g2 = jax.grad(untied.loss_fn)(p2, batch)
    for tied, first, second in (("0", "0", "2"), ("1", "1", "3")):
        summed = jax.tree.map(jnp.add, g2["shared"][first],
                              g2["shared"][second])
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6), g["shared"][tied], summed)
        assert float(jnp.abs(g2["shared"][second]["attn"]["wq"]).max()) > 0

    # the per-leaf clip scales the summed leaf by the norm of the whole
    # tree, of which the sum (not each use) is a part
    clip = 1e-3
    dp = make_dp_grad_fn(model.loss_fn, clip, kernel_backend="auto")
    noisy, metrics = dp(params, batch, jax.random.PRNGKey(0), 0.0)
    norm = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    np.testing.assert_allclose(float(metrics["grad_norm_preclip"]), norm,
                               rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b * clip / norm, rtol=1e-4, atol=1e-9), noisy["shared"],
        g["shared"])


def test_round_program_builds_the_shared_blocks_once():
    """The shared blocks are one set of leaves, outside the scanned
    segments; each invocation's adapter and linear are the layer's own."""
    cfg, model, params = _smoke()
    assert sorted(params["shared"]) == ["0", "1"]
    for seg, seg_p in zip(cfg.segments, params["segments"]):
        for j, ls in enumerate(seg.pattern):
            assert ("hybrid" in seg_p[str(j)]) == (ls.shared_block >= 0)
    assert LayerSpec().shared_block == -1
