"""repro.mesh — the pod-scale 2D client x model sharding plane.

Three layers of coverage:

* placement (pure python): the engine="auto" decision table and default
  mesh-shape arithmetic of :mod:`repro.mesh.placement`, pinned value by
  value, plus the device-reported budget and the ``REPRO_DEVICE_MEM_BYTES``
  override.
* spec plumbing: FederationSpec validation of ``mesh_shape`` /
  ``sharding_rules`` / ``replica_bytes``, engine_key cache inclusion, and
  logical-axis rule resolution (mesh2d_rules dedupe).
* parity gates (need ``--xla_force_host_platform_device_count=8``):
  the degenerate mesh (dm=1, clients divide) is BITWISE identical to the
  1D shard_map engine, and padded client counts (C not divisible by dc)
  match the vmap oracle to fp32 tolerance — dense, participation and
  top-k compression pipelines.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FederationSpec, init_state, resolve_engine, run_round
from repro.mesh.placement import (
    ENV_DEVICE_MEM,
    choose_engine,
    default_mesh_shape,
    device_memory_budget,
    model_shards_for,
    n_client_shards,
    replica_fits,
)
from repro.models.linear import init_linear, logreg_loss
from repro.models.sharding import axis_rules, mesh2d_rules, resolve_spec
from repro.optim import sgd

TAU, DIM, B = 3, 8, 4

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _spec(n_clients=4, **kw):
    base = dict(n_clients=n_clients, tau=TAU, loss_fn=logreg_loss,
                optimizer=sgd(0.2), clip_norm=1.0, dp=True,
                sigmas=(0.5,) * n_clients, batch_sizes=(B,) * n_clients)
    base.update(kw)
    return FederationSpec(**base)


def _batch(n_clients=4, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=(n_clients, TAU, B, DIM)),
                             jnp.float32),
            "y": jnp.asarray(rng.integers(0, 2, size=(n_clients, TAU, B)),
                             jnp.int32)}


def _run(spec, batch, dim=DIM, rounds=2):
    state = init_state(spec, init_linear(dim))
    recs = []
    for _ in range(rounds):
        state, rec = run_round(spec, state, batch)
        recs.append(rec)
    return state, recs


# ---------------------------------------------------------------------------
# placement decision table (pure python — no devices needed)
# ---------------------------------------------------------------------------

GIB = 1024 ** 3


class _FakeDevice:
    """A device that reports ``memory_stats`` as a TPU does (or not)."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_budget_default_and_env(monkeypatch):
    monkeypatch.delenv(ENV_DEVICE_MEM, raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice({"bytes_limit": 15 * GIB})])
    assert device_memory_budget() == 15 * GIB     # the device's own limit
    assert device_memory_budget(default=7) == 7
    # a backend that reports no limit is an error, not a 16 GiB guess
    for stats in (None, {}):
        monkeypatch.setattr(jax, "devices", lambda *a, s=stats: [
            _FakeDevice(s)])
        with pytest.raises(RuntimeError, match=ENV_DEVICE_MEM):
            device_memory_budget()
    monkeypatch.setenv(ENV_DEVICE_MEM, str(2 * GIB))
    assert device_memory_budget() == 2 * GIB
    assert device_memory_budget(default=7) == 2 * GIB   # env wins
    monkeypatch.setenv(ENV_DEVICE_MEM, "0")
    with pytest.raises(ValueError):
        device_memory_budget()


def test_replica_fits(monkeypatch):
    assert replica_fits(GIB, hbm_bytes=2 * GIB)
    assert not replica_fits(3 * GIB, hbm_bytes=2 * GIB)
    monkeypatch.delenv(ENV_DEVICE_MEM, raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice({"bytes_limit": 4 * GIB})])
    assert replica_fits(4 * GIB)                        # device budget
    assert not replica_fits(4 * GIB + 1)


def test_n_client_shards_divisor_table():
    # largest divisor of C that is <= device count
    assert n_client_shards(8, 8) == 8
    assert n_client_shards(6, 8) == 6
    assert n_client_shards(6, 4) == 3
    assert n_client_shards(7, 4) == 1       # prime > devices: no useful split
    assert n_client_shards(4, 1) == 1


def test_model_shards_for_smallest_sufficient_divisor():
    # smallest divisor of n_devices whose shard fits the budget
    assert model_shards_for(GIB, 8, hbm_bytes=2 * GIB) == 1
    assert model_shards_for(3 * GIB, 8, hbm_bytes=2 * GIB) == 2
    assert model_shards_for(7 * GIB, 8, hbm_bytes=2 * GIB) == 4
    assert model_shards_for(15 * GIB, 8, hbm_bytes=2 * GIB) == 8
    # nothing fits: all devices (best effort)
    assert model_shards_for(100 * GIB, 8, hbm_bytes=2 * GIB) == 8


def test_choose_engine_decision_table():
    # single device: always vmap
    assert choose_engine(8, 1) == "vmap"
    # no footprint hint: 1D placement by divisibility
    assert choose_engine(8, 4) == "shard_map"
    assert choose_engine(7, 4) == "vmap"
    # replica exceeds per-device memory -> the 2D plane
    assert choose_engine(8, 8, replica_bytes=3 * GIB,
                         hbm_bytes=2 * GIB) == "mesh_2d"
    # fits -> fall through to the 1D table
    assert choose_engine(8, 8, replica_bytes=GIB,
                         hbm_bytes=2 * GIB) == "shard_map"
    # adversarial pipelines need the full client view: never mesh_2d
    assert choose_engine(8, 8, replica_bytes=3 * GIB, hbm_bytes=2 * GIB,
                         adversarial=True) == "shard_map"


def test_default_mesh_shape():
    assert default_mesh_shape(8, 8) == (8, 1)
    assert default_mesh_shape(8, 8, replica_bytes=3 * GIB,
                              hbm_bytes=2 * GIB) == (4, 2)
    assert default_mesh_shape(8, 8, replica_bytes=7 * GIB,
                              hbm_bytes=2 * GIB) == (2, 4)
    # dc never exceeds the client count
    assert default_mesh_shape(2, 8, replica_bytes=3 * GIB,
                              hbm_bytes=2 * GIB) == (2, 2)


def test_env_override_steers_choose_engine(monkeypatch):
    monkeypatch.setenv(ENV_DEVICE_MEM, str(2 * GIB))
    assert choose_engine(8, 8, replica_bytes=3 * GIB) == "mesh_2d"
    monkeypatch.setenv(ENV_DEVICE_MEM, str(64 * GIB))
    assert choose_engine(8, 8, replica_bytes=3 * GIB) == "shard_map"


# ---------------------------------------------------------------------------
# spec plumbing
# ---------------------------------------------------------------------------

def test_spec_mesh_fields_validation():
    s = _spec(engine="mesh_2d", mesh_shape=(2, 2))
    assert s.mesh_shape == (2, 2)
    with pytest.raises(ValueError):
        _spec(engine="vmap", mesh_shape=(2, 2))
    with pytest.raises(ValueError):
        _spec(engine="mesh_2d", mesh_shape=(0, 2))
    with pytest.raises(ValueError):
        _spec(engine="mesh_2d", mesh_shape=(2,))
    with pytest.raises(ValueError):
        _spec(engine="mesh_2d", replica_bytes=-1)
    # adversarial pipelines are refused at spec construction
    with pytest.raises(ValueError):
        _spec(engine="mesh_2d", attack="sign_flip", byzantine_fraction=0.25,
              aggregator="median")


def test_spec_mesh_fields_key_the_engine_cache():
    a = _spec(engine="mesh_2d", mesh_shape=(2, 2))
    b = _spec(engine="mesh_2d", mesh_shape=(4, 1))
    c = _spec(engine="auto", replica_bytes=GIB)
    d = _spec(engine="auto")
    keys = {a.engine_key(), b.engine_key(), c.engine_key(), d.engine_key()}
    assert len(keys) == 4


def test_sharding_rules_normalized():
    opt = sgd(0.2)
    a = _spec(engine="mesh_2d", optimizer=opt,
              sharding_rules={"fsdp": "model", "tp": None})
    b = _spec(engine="mesh_2d", optimizer=opt,
              sharding_rules=[("tp", None), ("fsdp", "model")])
    assert a.sharding_rules == b.sharding_rules
    assert a.engine_key() == b.engine_key()


def test_mesh2d_rules_resolve_dedupes_repeated_axis():
    # fsdp and tp both map to "model": a leaf annotated with both must not
    # emit PartitionSpec("model", "model") (invalid) — first dim wins
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("client", "model"))
    with axis_rules(mesh, mesh2d_rules()):
        assert resolve_spec(("fsdp", "tp")) == jax.sharding.PartitionSpec(
            "model")
        assert resolve_spec(("wg", "tp", None)) == jax.sharding.PartitionSpec(
            None, "model")
    # outside any rules context resolution is the identity placement
    assert resolve_spec(("fsdp", "tp")) == jax.sharding.PartitionSpec()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_mesh_2d_refuses_mosaic_kernels_under_model_axis(backend):
    """A Mosaic kernel cannot be partitioned over the auto model axis: the
    model-sharded round refuses it at build time, not at chip compile."""
    from repro.core.aggregation import QSGD
    from repro.core.fl import FLConfig
    from repro.mesh.engine import _refuse_mosaic_under_model_axis

    class _Pipe:
        compressor = QSGD(8, backend)

    for cfg, pipe in ((FLConfig(n_clients=2, tau=1, kernel_backend=backend),
                       None),
                      (FLConfig(n_clients=2, tau=1), _Pipe())):
        if backend == "pallas":
            with pytest.raises(ValueError, match="model axis"):
                _refuse_mosaic_under_model_axis(cfg, pipe)
        else:
            _refuse_mosaic_under_model_axis(cfg, pipe)


def test_resolve_engine_single_device_never_mesh(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [object()])
    assert resolve_engine(_spec(engine="auto",
                                replica_bytes=100 * GIB)) == "vmap"


# ---------------------------------------------------------------------------
# parity gates (8 host devices)
# ---------------------------------------------------------------------------

PIPELINES = {
    "dense": {},
    "participation": dict(participation=0.5, seed=7),
    "topk": dict(compressor="topk", compression_ratio=0.25),
}


def _assert_states_equal(sa, sb, *, exact: bool):
    for la, lb in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        if exact:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        else:
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sa.rho, sb.rho)
    assert sa.resource_spent == sb.resource_spent


@needs_8_devices
@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
@pytest.mark.parametrize("n_clients", [6, 8])
def test_degenerate_mesh_bitwise_vs_shard_map(pipeline, n_clients):
    """(C, 1) mesh with clients divisible: bit-identical to 1D shard_map."""
    kw = PIPELINES[pipeline]
    batch = _batch(n_clients)
    ref, ref_recs = _run(_spec(n_clients, engine="shard_map", **kw), batch)
    got, got_recs = _run(_spec(n_clients, engine="mesh_2d",
                               mesh_shape=(n_clients, 1), **kw), batch)
    _assert_states_equal(ref, got, exact=True)
    for ra, rb in zip(ref_recs, got_recs):
        np.testing.assert_array_equal(np.asarray(ra["loss"]),
                                      np.asarray(rb["loss"]))


@needs_8_devices
@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
def test_true_2d_mesh_matches_shard_map(pipeline):
    kw = PIPELINES[pipeline]
    batch = _batch(8)
    ref, _ = _run(_spec(8, engine="shard_map", **kw), batch)
    got, _ = _run(_spec(8, engine="mesh_2d", mesh_shape=(4, 2), **kw), batch)
    _assert_states_equal(ref, got, exact=False)


@needs_8_devices
@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
@pytest.mark.parametrize("n_clients", [3, 5, 7, 9])
def test_padded_client_axis_matches_vmap(pipeline, n_clients):
    """C not divisible by dc: pad rows must not perturb the valid clients."""
    kw = PIPELINES[pipeline]
    batch = _batch(n_clients)
    ref, _ = _run(_spec(n_clients, engine="vmap", **kw), batch)
    got, _ = _run(_spec(n_clients, engine="mesh_2d", mesh_shape=(4, 2), **kw),
                  batch)
    _assert_states_equal(ref, got, exact=False)


@needs_8_devices
def test_auto_resolves_mesh_2d_and_completes(monkeypatch):
    """Oversized replica hint routes auto -> mesh_2d and the round runs."""
    monkeypatch.setenv(ENV_DEVICE_MEM, str(256))     # tiny per-device budget
    spec = _spec(8, engine="auto", replica_bytes=100 * DIM)
    assert resolve_engine(spec) == "mesh_2d"
    state, recs = _run(spec, _batch(8), rounds=1)
    assert np.isfinite(float(recs[0]["loss"]))
    assert state.rounds_done == 1
