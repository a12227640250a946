"""Backend-parity harness: every registered kernel × the interpret backend
× a shape/dtype sweep, checked against the ref.py pure-jnp oracles.

The backends are fixed, not probed at collection: a kernel whose Pallas
body stops running in interpret mode fails here instead of dropping out
of the sweep. Property tests run on both CPU backends (interpret, ref),
under real hypothesis or the ``_hypothesis_compat`` replay shim
(installed by conftest).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.dispatch import (
    available_backends,
    get_kernel,
    kernel_names,
)
from repro.kernels.ops import dp_clip_noise_tree

# the backends every CPU run must have; ref IS the oracle, so the parity
# sweep compares interpret against it
CPU_BACKENDS = ("interpret", "ref")


def _assert_trees_close(got, want, tol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# case sweep per kernel: (case_id, build) where build() -> (args, kwargs,
# {dtype: tol}). args/kwargs are passed identically to every backend; the
# oracle adapters swallow the tuning kwargs.
# --------------------------------------------------------------------------

def _dp_case(n, dtype, scale):
    def build():
        g = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * scale
        noise = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        return (g, noise, 1.0, 0.5), {"block": 4096}, tol
    return build


def _dp_clip_only_case(n):
    def build():
        g = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32) * 50
        # noise=None selects the clip-only lowering (microbatch clip path)
        return (g, None, 1.0, 0.0), {"block": 4096}, 1e-5
    return build


def _quant_case(n, bits, dtype, scale):
    def build():
        x = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * scale
        u = jax.random.uniform(jax.random.PRNGKey(1), (n,), jnp.float32)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        return (x, u, bits), {"block": 4096}, tol
    return build


def _flash_case(s, bq, bk, dtype, window=0):
    def build():
        b, h, hd = 2, 3, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, hd), dtype) for kk in ks)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        return (q, k, v), {"window": window, "block_q": bq,
                           "block_k": bk}, tol
    return build


def _rwkv_case(s, dtype):
    def build():
        b, h, hd = 2, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        r, k, v = (jax.random.normal(kk, (b, h, s, hd), dtype)
                   for kk in ks[:3])
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, hd))
                           ).astype(dtype)
        u = jax.random.normal(ks[4], (h, hd), jnp.float32)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        return (r, k, v, w, u), {}, tol
    return build


def _rwkv_state_case():
    def build():
        b, h, s, hd = 1, 1, 5, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        r, k, v = (jax.random.normal(kk, (b, h, s, hd)) for kk in ks[:3])
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, hd)))
        u = jax.random.normal(ks[4], (h, hd))
        s0 = jnp.ones((b, h, hd, hd), jnp.float32) * 0.3
        return (r, k, v, w, u, s0), {}, 1e-4
    return build


def _mamba_case(s, chunk, dtype):
    def build():
        b, h, p, n = 2, 3, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (b, s, h, p), dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))
                             ).astype(jnp.float32)
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        b_in = jax.random.normal(ks[3], (b, s, n), dtype)
        c_in = jax.random.normal(jax.random.PRNGKey(9), (b, s, n), dtype)
        tol = 6e-2 if dtype == jnp.bfloat16 else 1e-3
        return (x, dt, a, b_in, c_in), {"chunk": chunk}, tol
    return build


def _cohort_case(s, k, d, dtype, scatter=False, block_rows=None):
    tuning = {} if block_rows is None else {"block_rows": block_rows}

    def build():
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        cache = jax.random.normal(ks[0], (s, d), dtype)
        slots = jax.random.permutation(ks[1], s)[:k].astype(jnp.int32)
        if scatter:
            rows = jax.random.normal(ks[2], (k, d), dtype)
            # pure row copy: exact on every backend, no tolerance
            return (cache, slots, rows), tuning, 0.0
        return (cache, slots), tuning, 0.0
    return build


CASES = {
    "dp_clip_noise": [
        (f"n{n}-{np.dtype(d).name if d != jnp.bfloat16 else 'bf16'}-x{s}",
         _dp_case(n, d, s))
        for n in (17, 1024, 64 * 1024 + 3)
        for d in (jnp.float32, jnp.bfloat16)
        for s in (100.0, 1e-3)
    ] + [
        ("clip-only-n1000", _dp_clip_only_case(1000)),
        # 40 rows in 32-row blocks: no padding, the last block overhangs
        ("overhang-n5120", _dp_case(40 * 128, jnp.float32, 100.0)),
        ("clip-only-overhang-n5120", _dp_clip_only_case(40 * 128)),
    ],
    "quantize_decompress": [
        (f"n{n}-b{bits}-{np.dtype(d).name if d != jnp.bfloat16 else 'bf16'}",
         _quant_case(n, bits, d, s))
        for n, bits, d, s in [
            (17, 8, jnp.float32, 1.0),
            (1024, 4, jnp.float32, 50.0),
            (64 * 1024 + 3, 8, jnp.float32, 1e-3),
            (1024, 8, jnp.bfloat16, 1.0),
            (255, 1, jnp.float32, 1.0),
            (40 * 128, 8, jnp.float32, 1.0),       # last block overhangs
        ]
    ],
    "flash_attention": [
        ("s128-b64", _flash_case(128, 64, 64, jnp.float32)),
        ("s256-b128.64", _flash_case(256, 128, 64, jnp.float32)),
        ("s64-b64", _flash_case(64, 64, 64, jnp.float32)),
        ("s128-bf16", _flash_case(128, 64, 64, jnp.bfloat16)),
        ("window32", _flash_case(256, 64, 64, jnp.float32, window=32)),
        ("window100", _flash_case(256, 64, 64, jnp.float32, window=100)),
    ],
    "rwkv6_scan": [
        ("s1", _rwkv_case(1, jnp.float32)),
        ("s7", _rwkv_case(7, jnp.float32)),
        ("s64", _rwkv_case(64, jnp.float32)),
        ("s7-bf16", _rwkv_case(7, jnp.bfloat16)),
        ("init-state", _rwkv_state_case()),
    ],
    "mamba2_ssd": [
        ("s32-c8", _mamba_case(32, 8, jnp.float32)),
        ("s64-c16", _mamba_case(64, 16, jnp.float32)),
        ("s16-c16", _mamba_case(16, 16, jnp.float32)),
        ("s32-c8-bf16", _mamba_case(32, 8, jnp.bfloat16)),
    ],
    "cohort_gather_scatter": [
        ("gather-s9-d5", _cohort_case(9, 3, 5, jnp.float32)),
        ("scatter-s9-d5", _cohort_case(9, 3, 5, jnp.float32, scatter=True)),
        # d > 128 exercises the lane-padding path of the Pallas kernel
        ("gather-s64-d130", _cohort_case(64, 8, 130, jnp.float32)),
        ("scatter-s64-d130", _cohort_case(64, 8, 130, jnp.float32,
                                          scatter=True)),
        ("gather-s16-d33-bf16", _cohort_case(16, 4, 33, jnp.bfloat16)),
        ("scatter-s16-d33-bf16", _cohort_case(16, 4, 33, jnp.bfloat16,
                                              scatter=True)),
        # several D blocks, the last one partial, plus a ragged lane tail
        ("gather-s8-d5123-br16", _cohort_case(8, 3, 40 * 128 + 3,
                                              jnp.float32, block_rows=16)),
        ("scatter-s8-d5120-br16", _cohort_case(8, 3, 40 * 128, jnp.float32,
                                               scatter=True, block_rows=16)),
    ],
}


def _parity_params():
    for name in sorted(CASES):
        for case_id, build in CASES[name]:
            yield pytest.param(name, "interpret", build,
                               id=f"{name}-interpret-{case_id}")


def test_case_sweep_covers_registry():
    assert set(CASES) == set(kernel_names()), \
        "case sweep drifted from the dispatch registry"


@pytest.mark.parametrize("name,backend,build", list(_parity_params()))
def test_kernel_backend_parity(name, backend, build):
    args, kwargs, tol = build()
    got = get_kernel(name, backend)(*args, **kwargs)
    # oracle adapters take the same kwargs: semantic ones (window, causal)
    # apply, tuning ones (block sizes) are swallowed
    want = get_kernel(name, "ref")(*args, **kwargs)
    _assert_trees_close(got, want, tol)


def test_every_kernel_has_ref_backend():
    for name in kernel_names():
        assert "ref" in available_backends(name)


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_kernel_probe_passes_in_interpret_mode(name):
    """Each registered probe (the TPU resolution check) passes on the
    interpret backend, so a failure on the chip is the compiler's."""
    from repro.kernels.dispatch import probe_error
    assert probe_error(name, "interpret") is None


# ------------------------- dp_clip_noise properties ------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 5000), clip=st.floats(0.01, 10.0),
       seed=st.integers(0, 2**30))
def test_dp_clip_noise_norm_bound(n, clip, seed):
    """sigma=0: output norm <= clip_norm for ALL inputs (Eq. 7a sensitivity
    bound), on every available backend."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32) * 10.0
    noise = jnp.zeros((n,), jnp.float32)
    for backend in CPU_BACKENDS:
        got, norm = get_kernel("dp_clip_noise", backend)(
            g, noise, clip, 0.0, block=4096)
        out_norm = float(jnp.linalg.norm(got.astype(jnp.float32)))
        assert out_norm <= min(clip, float(norm)) * (1 + 1e-4)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 2000), seed=st.integers(0, 2**30))
def test_dp_clip_noise_passthrough_below_clip(n, seed):
    """Gradients already inside the clip ball pass through untouched."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)
    g = g / jnp.maximum(jnp.linalg.norm(g), 1e-12) * 0.5   # norm 0.5 < 1
    noise = jnp.zeros((n,), jnp.float32)
    for backend in CPU_BACKENDS:
        got, norm = get_kernel("dp_clip_noise", backend)(
            g, noise, 1.0, 0.0, block=4096)
        np.testing.assert_allclose(np.asarray(got), np.asarray(g),
                                   rtol=1e-6, atol=1e-7)
        assert float(norm) <= 0.5 * (1 + 1e-5)


def test_dp_clip_noise_tree_dtype_preservation():
    """Mixed bf16/f32 trees keep every leaf's dtype through the fused path."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (9, 4),
                                   jnp.bfloat16) * 10,
            "b": jax.random.normal(jax.random.PRNGKey(1), (7,), jnp.float32)}
    out, norm = dp_clip_noise_tree(tree, jax.random.PRNGKey(2), 1.0, 0.3)
    assert jax.tree.map(lambda x: x.dtype, out) == \
        jax.tree.map(lambda x: x.dtype, tree)
    assert float(norm) > 0


@pytest.mark.parametrize("noise", [True, False], ids=["noise", "clip-only"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dp_clip_noise_tree_leafwise_formula(dtype, noise):
    """Each leaf is exactly ``(g * scale + sigma * normal(k_i)).astype``,
    rounded once, with ``k_i`` the i-th of ``split(key, n_leaves)`` in
    flatten order (or ``g * scale`` for ``key=None``); the norm is the
    f32 norm of the whole tree."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    tree = {"a": jax.random.normal(ks[0], (33, 7), dtype) * 4,
            "b": [jax.random.normal(ks[1], (5,), dtype),
                  jax.random.normal(ks[2], (2, 3, 4), dtype) * 9]}
    key, sigma, clip = jax.random.PRNGKey(11), 0.7, 1.5
    got, norm = dp_clip_noise_tree(tree, key if noise else None, clip, sigma)

    leaves = jax.tree.leaves(tree)
    want_norm = np.sqrt(sum(np.sum(np.square(np.asarray(x, np.float64)))
                            for x in leaves))
    np.testing.assert_allclose(float(norm), want_norm, rtol=1e-6)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    keys = jax.random.split(key, len(leaves))
    for i, (g, y) in enumerate(zip(leaves, jax.tree.leaves(got))):
        want = g.astype(jnp.float32) * scale
        if noise:
            want = want + sigma * jax.random.normal(keys[i], g.shape,
                                                    jnp.float32)
        want = want.astype(g.dtype)
        assert y.dtype == g.dtype and y.shape == g.shape
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(want, np.float32))


def test_dp_clip_noise_tree_builds_no_flat_copy():
    """The tree path works leaf by leaf: its program concatenates nothing
    into a flat vector and calls no Pallas kernel."""
    tree = {"w": jnp.ones((16, 128), jnp.bfloat16),
            "b": jnp.ones((7,), jnp.float32)}
    for key in (jax.random.PRNGKey(0), None):
        jaxpr = str(jax.make_jaxpr(
            lambda t, s: dp_clip_noise_tree(t, key, 1.0, s))(tree, 0.5))
        assert "concatenate" not in jaxpr and "pallas_call" not in jaxpr


@pytest.mark.parametrize("name", ["dp_clip_noise", "quantize_decompress"])
def test_overhang_mask_under_client_vmap(name):
    """The engines vmap the kernels over clients, which adds a grid axis:
    the last block's overhang (NaN-filled in interpret mode) must still be
    masked by the block's step along the vector, not by the client."""
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (3, 40 * 128), jnp.float32) * 50
    y = jax.random.uniform(ks[1], (3, 40 * 128), jnp.float32)
    extra = ((1.0, jnp.asarray([0.1, 0.2, 0.3])) if name == "dp_clip_noise"
             else (8,))

    def run(backend):
        kernel = get_kernel(name, backend)
        if name == "dp_clip_noise":
            return jax.vmap(lambda a, b, s: kernel(a, b, extra[0], s,
                                                   block=4096))(x, y, extra[1])
        return jax.vmap(lambda a, b: kernel(a, b, extra[0], block=4096))(x, y)

    _assert_trees_close(run("interpret"), run("ref"), 1e-5)


def test_dp_clip_noise_tree_matches_core():
    from repro.core.clipping import clip_tree
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (37, 5)) * 8,
            "b": {"c": jax.random.normal(jax.random.PRNGKey(1), (11,))}}
    key = jax.random.PRNGKey(2)
    want, wnorm = clip_tree(tree, 1.0)
    got, norm = dp_clip_noise_tree(tree, key, 1.0, 0.0)
    _assert_trees_close(got, want, 1e-5)
    np.testing.assert_allclose(float(norm), float(wnorm), rtol=1e-5)


def test_dp_clip_noise_tree_noise_matches_tree_add_noise():
    """The fused path draws the SAME noise stream as the legacy
    clip_tree + tree_add_noise path (per-leaf split keys)."""
    from repro.core.clipping import clip_tree
    from repro.utils.tree import tree_add_noise
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (13, 3)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (21,))}
    key = jax.random.PRNGKey(5)
    clipped, _ = clip_tree(tree, 1.0)
    want = tree_add_noise(key, clipped, 0.7)
    got, _ = dp_clip_noise_tree(tree, key, 1.0, 0.7)
    _assert_trees_close(got, want, 1e-6)


# ------------------------- kernels vs model baselines -----------------------

def test_flash_attention_matches_model_blocked_attention():
    """Dispatch kernel == the lax blockwise attention used in the model."""
    from repro.models.attention import blocked_causal_attention
    from repro.kernels.ops import flash_attention
    b, h, s, hd = 1, 4, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, hd), jnp.float32)
               for kk in ks)
    lax_out = blocked_causal_attention(q, k, v, block_q=32)
    disp_out = flash_attention(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(disp_out, 1, 2)),
                               np.asarray(lax_out), rtol=2e-4, atol=2e-5)


def test_rwkv6_kernel_matches_model_scan():
    """Dispatch kernel == models.rwkv.wkv6_scan (the lax baseline)."""
    from repro.models.rwkv import wkv6_scan
    from repro.kernels.ops import rwkv6_scan
    b, h, s, hd = 2, 3, 12, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    # model layout (B, S, H, hd)
    r, k, v = (jax.random.normal(kk, (b, s, h, hd)) for kk in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, hd)))
    u = jax.random.normal(ks[4], (h, hd))
    y_model, s_model = wkv6_scan(r, k, v, w, u)
    perm = lambda t: jnp.moveaxis(t, 2, 1)  # -> (B, H, S, hd)
    y_k, s_k = rwkv6_scan(perm(r), perm(k), perm(v), perm(w), u)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(y_k, 1, 2)),
                               np.asarray(y_model), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_model),
                               rtol=1e-4, atol=1e-5)


def test_ssd_chunked_model_matches_sequential_ref():
    """models.ssm.ssd_chunked (lax baseline) == sequential oracle."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_in = jax.random.normal(ks[3], (b, s, n))
    c_in = jax.random.normal(ks[4], (b, s, n))
    # one B/C group: (B, S, 1, N) to the model, (B, S, N) to the oracle
    got_y, got_s = ssd_chunked(x, dt, a, b_in[:, :, None], c_in[:, :, None],
                               chunk=8)
    want_y, want_s = ref.mamba2_ssd_ref(x, dt, a, b_in, c_in)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-3, atol=1e-4)
