"""The program's profiler scopes and spans: device scopes in the compiled
round's op metadata (and nowhere else in the program), host spans in a
``jax.profiler`` trace of ``train``."""
import contextlib
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import FederationSpec, init_state, train
from repro.api.engines import get_engine, resolve_engine
from repro.api.state import round_batch, sigmas_for
from repro.configs import get_arch, smoke_variant
from repro.configs.rwkv6_1_6b import chip_share
from repro.models.linear import init_linear, logreg_loss
from repro.models.transformer import Transformer
from repro.optim import sgd

SCOPES = ("dppasgd.local_grad", "dppasgd.clip_noise", "rwkv6.wkv_scan")
C, TAU, B, S = 2, 2, 2, 16


def canonical_hlo(text: str) -> str:
    """Optimized HLO text without metadata or stack-frame tables, and with
    every instruction and computation renamed by order of appearance: XLA
    names an instruction after its op, and the scope can change that name
    (``min_jit.0`` for ``jit.0``) but not the program."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.*\n)*?\n", "", text, flags=re.M)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    names: dict = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: f"%v{names.setdefault(m.group(1), len(names))}",
                  text)


@pytest.fixture
def no_persistent_cache():
    """A compile that must not come from the persistent cache, whose key
    ignores metadata."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _rwkv_round(cfg=None, engine="map", n_clients=C):
    """A tiny DP federation's round function and its operands (RWKV-6
    unless ``cfg`` names another model)."""
    cfg = cfg or smoke_variant(chip_share(1))
    model = Transformer(cfg)
    spec = FederationSpec(
        n_clients=n_clients, tau=TAU, loss_fn=model.loss_fn,
        optimizer=sgd(0.01), engine=engine, dp=True, clip_norm=1.0,
        num_microbatches=1, kernel_backend="auto", sigmas=(0.1,) * n_clients,
        batch_sizes=(B,) * n_clients)
    state = init_state(spec, model.init(jax.random.PRNGKey(0)),
                       key=jax.random.PRNGKey(1))

    def sampler(m, tau, rng):
        t = rng.integers(0, cfg.vocab, size=(tau, B, S + 1)).astype(np.int32)
        return {"tokens": t[..., :-1], "labels": t[..., 1:]}

    batch = round_batch(spec, sampler, np.random.default_rng(0))
    return spec, (state.params, state.opt_state, batch, state.key,
                  sigmas_for(spec))


def _compiled_text(spec, args) -> str:
    jax.clear_caches()
    fn = jax.jit(get_engine(resolve_engine(spec))(spec))
    return fn.lower(*args).compile().as_text()


def test_round_scopes_only_change_metadata(no_persistent_cache, monkeypatch):
    spec, args = _rwkv_round()
    scoped = _compiled_text(spec, args)
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in SCOPES:
        assert any(scope in name.split("/") for name in op_names), scope
    # the backward scan counts under its forward scope
    assert any("transpose(" in n and "rwkv6.wkv_scan" in n for n in op_names)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(spec, args)
    assert not any(scope in plain for scope in SCOPES)
    assert canonical_hlo(scoped) == canonical_hlo(plain)


def _op_names(spec, args) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', _compiled_text(spec, args))


@pytest.mark.parametrize("arch, engine, scopes", [
    ("zamba2-7b", "map", ("mamba2.mixer", "mamba2.ssd_scan",
                          "zamba2.shared_block", "dppasgd.average")),
    ("rwkv6-1.6b", "shard_map", ("dppasgd.average",)),
])
def test_round_carries_the_model_and_average_scopes(no_persistent_cache,
                                                    arch, engine, scopes):
    """Zamba2's mixer, SSD scan and shared block, and the Eq. 7b average
    (with its pmean under shard_map), in the compiled round's op names."""
    cfg = smoke_variant(get_arch(arch) if arch != "rwkv6-1.6b"
                        else chip_share(1))
    spec, args = _rwkv_round(cfg, engine, 1 if engine == "shard_map" else C)
    op_names = _op_names(spec, args)
    for scope in scopes:
        assert any(scope in name.split("/") for name in op_names), scope
    if "mamba2.ssd_scan" in scopes:
        # nested in the mixer, and in the backward pass too
        assert any("mamba2.mixer/mamba2.ssd_scan" in n for n in op_names)
        assert any("transpose(" in n and "zamba2.shared_block" in n
                   for n in op_names)


def test_canonical_hlo_keeps_the_program():
    a = ('%add.3 = f32[] add(%p.1, %p.2), metadata={op_name="jit(f)/x/add"}'
         '\n%mul.9 = f32[] multiply(%add.3, %p.1)')
    b = ('%add.7 = f32[] add(%p.4, %p.5)'
         '\n%mul.2 = f32[] multiply(%add.7, %p.4)')
    assert canonical_hlo(a) == canonical_hlo(b)
    # another operand, or another op, is another program
    assert canonical_hlo(a) != canonical_hlo(b.replace("%add.7, %p.4",
                                                       "%add.7, %p.5"))
    assert canonical_hlo(a) != canonical_hlo(b.replace("multiply",
                                                       "subtract"))


def _host_spans(trace_dir: Path) -> list[tuple]:
    """(thread, start_ns, end_ns, name, args) of every dppasgd.* span."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((line.name, e.start_ns, e.start_ns + e.duration_ns,
                            e.name, dict(e.stats)) for e in line.events
                           if e.name.startswith("dppasgd."))
    return out


def _sampler(m, tau, rng):
    return {"x": rng.normal(size=(tau, 4, 8)).astype(np.float32),
            "y": rng.integers(0, 2, size=(tau, 4)).astype(np.int32)}


@pytest.mark.parametrize("pipeline, reads", [({}, 2),
                                             ({"participation": 0.5}, 3)])
def test_train_rounds_carry_their_host_spans(tmp_path, pipeline, reads):
    spec = FederationSpec(n_clients=4, tau=2, loss_fn=logreg_loss,
                          optimizer=sgd(0.2), clip_norm=1.0, dp=True,
                          sigmas=(0.5,) * 4, batch_sizes=(4,) * 4,
                          **pipeline)
    state = init_state(spec, init_linear(8), key=jax.random.PRNGKey(0))
    train(spec, state, _sampler, max_rounds=1)     # compile outside
    state = init_state(spec, init_linear(8), key=jax.random.PRNGKey(0))
    with jax.profiler.trace(str(tmp_path)):
        train(spec, state, _sampler, max_rounds=2)
    spans = _host_spans(tmp_path)
    rounds = sorted((s for s in spans if s[3] == "dppasgd.round"),
                    key=lambda s: s[1])
    assert [r[4]["round"] for r in rounds] == [1, 2]
    for thread, lo, hi, _, _ in rounds:
        inside = [s[3] for s in spans
                  if s[0] == thread and lo <= s[1] and s[2] <= hi
                  and s[3] != "dppasgd.round"]
        assert inside.count("dppasgd.batch") == 1
        assert inside.count("dppasgd.ledger") >= 1
        assert inside.count("dppasgd.host_read") == reads
