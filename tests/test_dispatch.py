"""Tests for the kernel backend dispatch layer and the auto-selection
logic: registry behavior, ``resolve_backend`` (env override + monkeypatched
capability probes), ``resolve_engine`` (monkeypatched device counts), and
the acceptance gate — vmap/map/shard_map rounds numerically identical
whether ``kernel_backend`` is "ref" or "interpret"."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.dispatch as dispatch
from repro.api import FederationSpec, init_state, resolve_engine, run_round
from repro.kernels.dispatch import (
    KERNEL_BACKENDS,
    available_backends,
    backend_works,
    get_kernel,
    kernel_names,
    register_kernel,
    resolve_backend,
)
from repro.models.linear import init_linear, logreg_loss
from repro.optim import sgd

C, TAU, DIM, B = 4, 3, 8, 4


def _spec(**kw):
    base = dict(n_clients=C, tau=TAU, loss_fn=logreg_loss, optimizer=sgd(0.2),
                clip_norm=1.0, dp=True, sigmas=(0.5,) * C,
                batch_sizes=(B,) * C)
    base.update(kw)
    return FederationSpec(**base)


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=(C, TAU, B, DIM)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 2, size=(C, TAU, B)), jnp.int32)}


# ---------------------------- registry --------------------------------------

def test_registry_contents(monkeypatch):
    monkeypatch.delenv(dispatch.KERNEL_DISABLE_ENV, raising=False)
    assert set(kernel_names()) == {"dp_clip_noise", "flash_attention",
                                   "rwkv6_scan", "mamba2_ssd",
                                   "quantize_decompress",
                                   "cohort_gather_scatter"}
    with pytest.raises(KeyError):
        get_kernel("nope")
    for name in kernel_names():
        # off the TPU every kernel runs interpreted and as its oracle;
        # the listing is ordered best-first
        assert available_backends(name) == ("interpret", "ref")


def test_register_kernel_roundtrip():
    calls = []
    register_kernel("_test_kernel", ref=lambda x, **_: calls.append(x) or x)
    try:
        assert "_test_kernel" in kernel_names()
        assert available_backends("_test_kernel") == ("ref",)
        assert resolve_backend("_test_kernel", "auto") == "ref"
        assert get_kernel("_test_kernel")(5) == 5 and calls == [5]
        with pytest.raises(ValueError):   # no pallas impl registered
            get_kernel("_test_kernel", "interpret")
    finally:
        dispatch._REGISTRY.pop("_test_kernel")
        dispatch.backend_works.cache_clear()


# ---------------------------- resolve_backend -------------------------------

def test_resolve_backend_explicit_wins(monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_BACKEND_ENV, "ref")
    # explicit non-auto ignores both the env var and the probes
    assert resolve_backend("dp_clip_noise", "interpret") == "interpret"
    assert resolve_backend("dp_clip_noise", "ref") == "ref"
    with pytest.raises(ValueError):
        resolve_backend("dp_clip_noise", "bogus")


def test_resolve_backend_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_BACKEND_ENV, "ref")
    assert resolve_backend("dp_clip_noise", "auto") == "ref"
    monkeypatch.setenv(dispatch.KERNEL_BACKEND_ENV, "bogus")
    with pytest.raises(ValueError):
        resolve_backend("dp_clip_noise", "auto")
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV)
    assert resolve_backend("dp_clip_noise", "auto") in ("pallas", "interpret",
                                                        "ref")


def test_resolve_backend_probe_fallback(monkeypatch):
    """On TPU, auto resolves to pallas or raises: a failed pallas probe is
    an error even when interpret (or the oracle) would run — the chip
    never silently runs a kernel off the Mosaic path."""
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def works(table):
        return lambda name, backend: table.get(backend, backend == "ref")

    monkeypatch.setattr(dispatch, "backend_works",
                        works({"pallas": True, "interpret": True}))
    assert resolve_backend("dp_clip_noise", "auto") == "pallas"
    for table in ({"pallas": False, "interpret": True},
                  {"pallas": False, "interpret": False}):
        monkeypatch.setattr(dispatch, "backend_works", works(table))
        monkeypatch.setattr(dispatch, "probe_error",
                            lambda name, backend: ValueError("refused"))
        with pytest.raises(RuntimeError, match="pallas probe") as info:
            resolve_backend("dp_clip_noise", "auto")
        assert isinstance(info.value.__cause__, ValueError)
    # explicit backends still win on TPU (tests and tooling)
    assert resolve_backend("dp_clip_noise", "interpret") == "interpret"
    assert resolve_backend("dp_clip_noise", "ref") == "ref"


def test_resolve_backend_ref_outranks_interpret_off_tpu(monkeypatch):
    """ROADMAP open item (closed): on non-TPU backends the auto probe ranks
    the jnp oracle ABOVE pallas interpret mode (~100x slower on CPU) — a
    working interpret backend no longer captures the engine hot path."""
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV, raising=False)

    def works(table):
        return lambda name, backend: table.get(backend, backend == "ref")

    for platform in ("cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        monkeypatch.setattr(dispatch, "backend_works",
                            works({"pallas": False, "interpret": True}))
        assert resolve_backend("dp_clip_noise", "auto") == "ref"
    # explicit interpret (arg or env) still reachable for the parity suites
    assert resolve_backend("dp_clip_noise", "interpret") == "interpret"
    monkeypatch.setenv(dispatch.KERNEL_BACKEND_ENV, "interpret")
    assert resolve_backend("dp_clip_noise", "auto") == "interpret"


def test_backend_works_probe_failure_reads_as_unavailable(monkeypatch):
    """A probe that raises reads as unavailable in backend_works, keeps
    its exception in probe_error, and — on a TPU — makes auto raise with
    that exception attached instead of falling back."""
    entry = dispatch._entry("dp_clip_noise")
    drift = AttributeError("simulated pallas API drift")

    def boom(_impl):
        raise drift

    monkeypatch.setitem(dispatch._REGISTRY, "dp_clip_noise",
                        dispatch.KernelEntry(name=entry.name,
                                             pallas_fn=entry.pallas_fn,
                                             ref_fn=entry.ref_fn,
                                             probe=boom))
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV, raising=False)
    dispatch.backend_works.cache_clear()
    try:
        assert backend_works("dp_clip_noise", "interpret") is False
        assert dispatch.probe_error("dp_clip_noise", "interpret") is drift
        assert backend_works("dp_clip_noise", "ref") is True
        assert resolve_backend("dp_clip_noise", "auto") == "ref"   # off TPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError) as info:
            resolve_backend("dp_clip_noise", "auto")
        assert info.value.__cause__ is drift
    finally:
        dispatch.backend_works.cache_clear()


def test_pallas_backend_gated_on_tpu(monkeypatch):
    dispatch.backend_works.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert backend_works("dp_clip_noise", "pallas") is False
    dispatch.backend_works.cache_clear()


def test_disable_env_simulates_oracle_only(monkeypatch):
    """KERNEL_DISPATCH_DISABLE makes probes report the named backends
    unavailable — the knob CI's ref leg uses to rehearse a broken pallas."""
    monkeypatch.setenv(dispatch.KERNEL_DISABLE_ENV, "pallas,interpret")
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV, raising=False)
    dispatch.backend_works.cache_clear()
    try:
        assert available_backends("dp_clip_noise") == ("ref",)
        assert resolve_backend("dp_clip_noise", "auto") == "ref"
        assert backend_works("dp_clip_noise", "ref") is True  # not disableable
        # on a TPU a disabled pallas is an error, never a demotion to ref
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="disabled"):
            resolve_backend("dp_clip_noise", "auto")
    finally:
        dispatch.backend_works.cache_clear()


# ---------------------------- spec plumbing ---------------------------------

def test_spec_kernel_backend_validation_and_engine_key():
    with pytest.raises(ValueError):
        _spec(kernel_backend="bogus")
    s = _spec(kernel_backend="ref")
    assert s.fl_config().kernel_backend == "ref"
    assert s.engine_key() != _spec(kernel_backend="interpret").engine_key()
    assert s.replace(eps_th=4.0).engine_key() == s.engine_key()


def test_flconfig_default_keeps_legacy_path():
    from repro.core.fl import FLConfig
    assert FLConfig(n_clients=2, tau=1).kernel_backend is None


# ---------------------------- engine auto selection -------------------------

def test_engine_auto_selection_by_device_count(monkeypatch):
    fake_dev = [object()] * 4
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fake_dev)
    assert resolve_engine(_spec(engine="auto")) == "shard_map"
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fake_dev[:1])
    assert resolve_engine(_spec(engine="auto")) == "vmap"
    # explicit engine is never overridden
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fake_dev)
    assert resolve_engine(_spec(engine="map")) == "map"


def test_engine_auto_with_kernel_backend_auto(monkeypatch):
    """The two auto knobs compose: resolved engine + resolved backend both
    concrete, and the spec-built round runs."""
    monkeypatch.delenv(dispatch.KERNEL_BACKEND_ENV, raising=False)
    spec = _spec(engine="auto", kernel_backend="auto")
    assert resolve_engine(spec) in ("vmap", "map", "shard_map")
    assert resolve_backend("dp_clip_noise", spec.kernel_backend) in (
        "pallas", "interpret", "ref")
    state = init_state(spec, init_linear(DIM))
    state, rec = run_round(spec, state, _batch(), check_budgets=False)
    assert np.isfinite(rec["loss"])


# ---------------------------- acceptance: engine × backend parity -----------

@pytest.mark.parametrize("engine", ["vmap", "map", "shard_map"])
def test_engine_round_parity_ref_vs_interpret(engine):
    """vmap/map/shard_map rounds are numerically identical (atol 1e-5)
    whether the clip+noise hot path runs on "ref" or "interpret"."""
    params0 = init_linear(DIM)
    batch = _batch()

    def run(backend):
        spec = _spec(engine=engine, kernel_backend=backend)
        state = init_state(spec, params0)
        recs = []
        for _ in range(2):
            state, rec = run_round(spec, state, batch, check_budgets=False)
            recs.append(rec)
        return state, recs

    ref_state, ref_recs = run("ref")
    got_state, got_recs = run("interpret")
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(got_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for ra, rb in zip(ref_recs, got_recs):
        assert rb["loss"] == pytest.approx(ra["loss"], rel=1e-5)
