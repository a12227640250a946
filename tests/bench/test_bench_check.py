"""``correct`` on a tiny cell on the CPU: the harness's whole run past its
look for a chip. The sound program passes; the float8 control and each
fault a training cell can have, planted in the timed path, fail."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import harness
from conftest import tiny_cell


def measure(cell, seed=2**31 + 11):
    return harness.measure(cell, seed, 0.5, False, jax.devices(),
                           time.monotonic())


def failed(result):
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("family", ["rwkv6", "mistral"])
def test_sound_program_is_correct(family):
    result = measure(tiny_cell(family))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"


def test_drift_gap_reads_a_gradient_lost_under_the_noise():
    # two leaves; the drift d, a noise 100 times larger on both sides
    rng = np.random.default_rng(0)
    d = [rng.normal(size=1000) * 1e-3, rng.normal(size=10) * 1e-4]
    noise = [rng.normal(size=1000) * 0.1, rng.normal(size=10) * 0.1]
    want = [n - x for n, x in zip(noise, d)]
    keep = np.array([True, True])
    gap = lambda got: compare.drift_gap([got], [want], [d], keep)
    assert gap(want) == 0.0
    assert gap(noise) == pytest.approx(1.0)                 # every d dropped
    assert gap([noise[0] - 3 * d[0], want[1]]) == pytest.approx(2.0)
    # the small leaf's own drift counts against the largest leaf's
    assert gap([want[0], noise[1]]) == pytest.approx(
        np.linalg.norm(d[1]) / np.linalg.norm(d[0]), rel=1e-4)  # float32


def test_float8_control_fails(tiny):
    seed = 2**31 + 12
    _, _, _, _, make_weights, key = harness.build(tiny, seed)
    refs = harness.References(tiny)
    ref = refs.record(seed, make_weights, key)
    ctl = refs.record(seed, make_weights, key, "control")
    checks = compare.against(compare.numbers(ctl, ref),
                             tiny.workload["limits"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _unchanged_state(monkeypatch):
    import repro.api.state as st
    real = st.round_fn_for

    def round_fn_for(spec):
        fn = real(spec)

        def keep(params, opt_state, *rest):
            copy = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, metrics = fn(params, opt_state, *rest)
            return copy[0], copy[1], metrics
        return keep
    monkeypatch.setattr(st, "round_fn_for", round_fn_for)


def _half_batch(monkeypatch):
    import traffic
    real = traffic.Traffic.sampler

    def sampler(self, client, tau, rng=None):
        rows = real(self, client, tau, rng)
        return {k: v[:, : v.shape[1] // 2] for k, v in rows.items()}
    monkeypatch.setattr(traffic.Traffic, "sampler", sampler)


def _no_exchange(monkeypatch):
    import repro.core.fl as fl
    monkeypatch.setattr(fl, "tree_mean_over_axis0",
                        lambda a, keep_dtype=False: jax.tree.map(
                            lambda x: x[0], a))


def _gradient_fault(monkeypatch, change):
    """The clip+noise step's output rebuilt as its noise plus
    ``change(clipped gradients)``, after the norm: the noise is kept."""
    import repro.kernels.ops as ops
    real = ops.dp_clip_noise_tree

    def dp_clip_noise_tree(grads, key, *a, **k):
        clip, norm = real(grads, None, *a, **k)
        noise, _ = real(jax.tree.map(jnp.zeros_like, grads), key, *a, **k)
        leaves, treedef = jax.tree.flatten(noise)
        clipped = [c.astype(jnp.float32) for c in jax.tree.leaves(clip)]
        leaves = [(n.astype(jnp.float32) + c).astype(n.dtype)
                  for n, c in zip(leaves, change(clipped))]
        return jax.tree.unflatten(treedef, leaves), norm
    monkeypatch.setattr(ops, "dp_clip_noise_tree", dp_clip_noise_tree)


def _altered(monkeypatch):
    def reverse_largest(clipped):
        big = max(range(len(clipped)), key=lambda i: clipped[i].size)
        return [-c if i == big else c for i, c in enumerate(clipped)]
    _gradient_fault(monkeypatch, reverse_largest)


def _noise_only(monkeypatch):
    _gradient_fault(monkeypatch, lambda clipped: [c * 0 for c in clipped])


@pytest.mark.parametrize("fault, plant", [
    ("state_unchanged", _unchanged_state),
    ("half_batch", _half_batch),
    ("no_exchange", _no_exchange),
    ("altered_gradient", _altered),
    ("noise_only", _noise_only),
])
def test_fault_in_the_timed_path_reads_not_correct(tiny, monkeypatch, fault,
                                                    plant):
    plant(monkeypatch)
    result = measure(tiny)
    assert not result["correct"], (fault, result["checks"])
    assert failed(result), fault
