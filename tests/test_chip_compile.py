"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with jax; it compiles for a topology that is
described, not attached, and refuses what the chip would refuse: block
shapes off the (8, 128) tiling, kernels over VMEM, programs over HBM.
These tests compile the three Pallas streaming kernels at the width of one
client replica of ``chip_share(4)`` (the rwkv6-1.6b config ``chip_smoke.py``
runs) and ``flash_attention``, checking that each is a Mosaic kernel
(``tpu_custom_call``) that fits the chip; the training path's leaf-wise
clip+noise over a whole gradient tree, checking that it streams each leaf
once; and a whole DP-PASGD round program.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.rwkv6_1_6b import chip_share
from repro.configs.shapes import param_count_estimate

HBM = 16 * 1024 ** 3     # v5e: 16 GiB per chip
# the round programs' compiled peaks, rounded up: one more f32 copy of the
# gradient (1.3 GiB at this width) breaks them
ROUND_PEAK = {"none": 9.3 * 1024 ** 3, "qsgd": 9.3 * 1024 ** 3}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def replica_width():
    return param_count_estimate(chip_share(4))


def _hlo_and_peak(compiled):
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return compiled.as_text(), peak


def _compile(fn, *args):
    return _hlo_and_peak(jax.jit(fn).lower(*args).compile())


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_replica_width_is_the_chip_share():
    assert param_count_estimate(chip_share(4)) == 352_946_176


@pytest.mark.parametrize("noise", [True, False], ids=["noise", "clip-only"])
def test_dp_clip_noise_compiles(one_chip, replica_width, noise):
    from repro.kernels.dp_clip_noise import dp_clip_noise
    g = _sds(one_chip, (replica_width,))
    n = _sds(one_chip, (replica_width,)) if noise else None
    hlo, peak = _compile(lambda g, n: dp_clip_noise(g, n, 1.0, 0.5), g, n)
    assert "tpu_custom_call" in hlo and peak <= HBM


def test_dp_clip_noise_compiles_under_client_vmap(one_chip, replica_width):
    """The round engine vmaps the kernel over clients (SMEM scalars)."""
    from repro.kernels.dp_clip_noise import dp_clip_noise
    g = _sds(one_chip, (2, replica_width), jnp.bfloat16)
    n = _sds(one_chip, (2, replica_width))
    s = _sds(one_chip, (2,))
    hlo, peak = _compile(jax.vmap(lambda g, n, s: dp_clip_noise(g, n, 1.0,
                                                                 s)), g, n, s)
    assert "tpu_custom_call" in hlo and peak <= HBM


def test_dp_clip_noise_tree_streams_each_leaf_once(one_chip):
    """The training path's clip+noise over a whole gradient tree (the
    benchmark's rwkv6-1.6b-3l: ``chip_share(3)`` with the untied head and
    the decay LoRA, 432.9M elements) compiles to fused passes over the
    leaves: at most 10 bytes accessed per element (a bf16 leaf's norm read,
    its read and its write are 6), temporaries below one leaf, no Mosaic
    kernel."""
    from dataclasses import replace

    from repro.kernels.ops import dp_clip_noise_tree
    from repro.models.transformer import Transformer

    cfg = replace(chip_share(3), tie_head=False, lora_rank=64)
    p0 = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    grads = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), p0)
    leaves = jax.tree.leaves(grads)
    n = sum(x.size for x in leaves)
    assert n == 432_875_520
    compiled = jax.jit(lambda g, k, s: dp_clip_noise_tree(g, k, 1.0, s)).lower(
        grads, _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, ())).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 10 * n
    largest = max(x.size * x.dtype.itemsize for x in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < largest
    assert "tpu_custom_call" not in compiled.as_text()


def test_quantize_decompress_compiles(one_chip, replica_width):
    from repro.kernels.quantize_decompress import quantize_decompress
    x = _sds(one_chip, (replica_width,))
    hlo, peak = _compile(lambda x, u: quantize_decompress(x, u, 8), x, x)
    assert "tpu_custom_call" in hlo and peak <= HBM


@pytest.mark.parametrize("scatter", [False, True],
                         ids=["gather", "scatter"])
def test_cohort_gather_scatter_compiles(one_chip, replica_width, scatter):
    """S = 2 resident residual rows of one replica's width, K = 1. The
    (S, D) -> (S, R, 128) lane view is a relayout copy of the whole cache
    under XLA's default layout, so a larger S at this width outgrows the
    chip (PERF.md, Open questions)."""
    from repro.kernels.cohort_gather import cohort_gather_scatter
    cache = _sds(one_chip, (2, replica_width))
    slots = _sds(one_chip, (1,), jnp.int32)
    if scatter:
        rows = _sds(one_chip, (1, replica_width))
        hlo, peak = _compile(cohort_gather_scatter, cache, slots, rows)
    else:
        hlo, peak = _compile(cohort_gather_scatter, cache, slots)
    assert "tpu_custom_call" in hlo and peak <= HBM


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _sds(one_chip, (1, 8, 1024, 128), jnp.bfloat16)
    hlo, peak = _compile(flash_attention, q, q, q)
    assert "tpu_custom_call" in hlo and peak <= HBM


@pytest.mark.parametrize("compressor", ["none", "qsgd"])
def test_round_program_compiles(one_chip, compressor):
    """One whole phase-(a)/(b) DP-PASGD round of ``chip_smoke.py``, with the
    kernels steered to Mosaic here (off the chip ``auto`` would pick the
    oracle)."""
    import importlib.util
    from pathlib import Path

    from repro.api import FederationSpec, round_fn_for
    from repro.models.transformer import Transformer
    from repro.optim import sgd

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    loader = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(smoke)
    qsgd = compressor == "qsgd"
    c = smoke.QSGD_CLIENTS if qsgd else smoke.CLIENTS
    b, s, tau = smoke.BATCH, smoke.SEQ, smoke.TAU
    model = Transformer(chip_share(4))
    pipeline = {"participation": 0.5, "compressor": "qsgd"} if qsgd else {}
    spec = FederationSpec(
        n_clients=c, tau=tau, loss_fn=model.loss_fn, optimizer=sgd(smoke.LR),
        engine=smoke.ENGINE, dp=True, clip_norm=smoke.CLIP,
        num_microbatches=1,
        sigmas=(0.5,) * c, batch_sizes=(b,) * c, kernel_backend="pallas",
        **pipeline)
    p0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    clients = lambda t: jax.tree.map(
        lambda x: _sds(one_chip, (c,) + x.shape, x.dtype), t)
    args = [clients(p0), clients(jax.eval_shape(spec.optimizer.init, p0)),
            {k: _sds(one_chip, (c, tau, b, s), jnp.int32)
             for k in ("tokens", "labels")},
            _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, (c,))]
    if spec.has_pipeline():
        residual = jax.eval_shape(spec.aggregation_pipeline().init_residual,
                                  p0)
        args += [_sds(one_chip, (c,)),
                 jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype),
                              residual)]
    hlo, peak = _hlo_and_peak(round_fn_for(spec).lower(*args).compile())
    # the compressor's quantize_decompress (norm pass, round trip) is the
    # only Mosaic kernel: clip+noise is leaf-wise jnp
    kernels = hlo.count("tpu_custom_call")
    assert kernels >= 2 if qsgd else kernels == 0
    # chip_smoke sizes C and B so a round fills more than half the chip;
    # the round compiles to 9.14 (dense, C=4) and 9.22 GiB (qsgd, C=1)
    assert HBM // 2 < peak <= ROUND_PEAK[compressor] <= HBM
