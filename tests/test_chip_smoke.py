"""CPU rehearsal of ``chip_smoke.py``: its phase functions at smoke size on
the Pallas interpret backend, and its refusals off the chip.

The script runs on a TPU at rwkv6-1.6b widths; here the same functions
run ``smoke_variant`` of that config so a wrong path, argument or check
fails before any chip time is spent.
"""
import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import smoke_variant
from repro.configs.rwkv6_1_6b import chip_share

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(chip_share(4))


def _phase(smoke, cfg, **pipeline):
    return smoke.train_phase(cfg, n_clients=2, batch=2, seq=16, tau=2,
                             rounds=2, kernel_backend="interpret", seed=0,
                             engine=smoke.ENGINE, **pipeline)


def test_chip_share_keeps_published_widths():
    full, cut = chip_share(24), chip_share(4)
    assert (cut.d_model, cut.d_ff, cut.vocab, cut.rwkv_headdim) == (
        2048, 7168, 65536, 64)
    assert cut.n_layers == 4 and len(cut.layer_specs()) == 4
    assert full.layer_specs() == [cut.layer_specs()[0]] * 24


def test_dense_phase_rehearsal(smoke, cfg):
    from repro.api import eval_params
    dense = _phase(smoke, cfg)
    assert dense["backends"] == {}      # clip+noise runs no kernel
    assert dense["engine"] == smoke.ENGINE
    smoke.check_training(dense, rounds=2, dense=True)
    width = sum(x.size for x in jax.tree.leaves(
        eval_params(dense["spec"], dense["state"])))
    flat = smoke.kernel_vs_oracle(width, "interpret", 0)
    assert (flat["entries"], flat["backend"]) == (width, "interpret")


def test_qsgd_phase_rehearsal(smoke, cfg):
    comp = _phase(smoke, cfg, participation=0.5, compressor="qsgd")
    assert comp["backends"] == {"quantize_decompress": "interpret"}
    smoke.check_training(comp, rounds=2, dense=False)
    assert smoke.qsgd_vs_oracle(40 * 128 + 3, "interpret", 0)["scale"] > 0


def test_serve_phase_rehearsal(smoke, cfg):
    from repro.models.transformer import Transformer
    model = Transformer(cfg)
    out = smoke.serve_phase(model, model.init(jax.random.PRNGKey(0)),
                            requests=3, slots=2, prompt=8, gen=4, seed=0)
    assert out["requests"] == 3 and out["tokens_out"] == 12
    assert np.all(np.asarray(out["first_tokens"]) < cfg.vocab)


def test_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_outside_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    proc = subprocess.run([sys.executable, str(alone)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_check_mesh_holds_the_engines_to_one_round(smoke):
    same = {"losses": {"shard_map": 11.09, "mesh_2d": 11.10},
            "param_rel_err": 1e-3}
    smoke.check_mesh(same)
    for bad in ({**same, "losses": {"shard_map": 11.09, "mesh_2d": 11.5}},
                {**same, "losses": {"shard_map": 11.09,
                                    "mesh_2d": float("nan")}},
                {**same, "param_rel_err": 0.05}):
        with pytest.raises(AssertionError):
            smoke.check_mesh(bad)


def test_param_distance_allows_rounding_not_a_wrong_update(smoke):
    """Two engines that round the local step and the client average in
    bf16 at different points pass, even where the average cancels to near
    zero; a round that drops a client does not."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    rng = np.random.default_rng(0)
    n, c = 1 << 14, 4
    p0 = (rng.normal(size=n) * 0.02).astype(bf16)
    u = rng.normal(size=(c, n)) * 0.05           # lr * sigma * noise
    local = (p0.astype(np.float64) + u).astype(bf16).astype(np.float64)

    def rounds(x):
        return np.broadcast_to(x.astype(bf16), (c, n))

    def bf(x):
        return x.astype(bf16).astype(np.float64)

    a = rounds(local.mean(0))                      # average in f64
    # a tree sum in bf16, as a psum of bf16 partials would add
    b = rounds(bf(bf(local[0] + local[1]) + bf(local[2] + local[3])) / c)
    rel, worst = smoke.param_distance(p0, a, b)
    smoke.check_mesh({"losses": {"shard_map": 1.0, "mesh_2d": 1.0},
                      "param_rel_err": rel})
    assert worst > 0                               # they do differ
    dropped = rounds(local[:3].mean(0))
    rel, _ = smoke.param_distance(p0, a, dropped)
    assert rel > 2 ** -5


@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_mesh_phase_rehearsal(smoke, cfg):
    """The ``--four-chips`` comparison at smoke size on CPU devices."""
    mesh = smoke.mesh_phase(cfg, seed=0)
    smoke.check_mesh(mesh)
    assert set(mesh["losses"]) == {"shard_map", "mesh_2d"}
