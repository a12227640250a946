"""Serving-plane tests: paged-vs-dense cache exactness, the
continuous-batching engine's byte-identity with the static ``generate``
path, federated checkpoint flavors, and the hot-swap boundary."""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.launch.serve import generate, load_federated_params
from repro.models.transformer import Transformer
from repro.serve import (Request, SlotEngine, StepClock, model_pads_ok,
                         poisson_workload, serve_continuous, serve_static)


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = smoke_variant(get_arch(arch))
    model = Transformer(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(arch, params=None, **kw):
    model, p0 = _model(arch)
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 32)
    return model, SlotEngine(model, params if params is not None else p0,
                             **kw)


def _mixed_workload(arch, n=7, rate=2.0, seed=5, prompt_lens=(5, 8, 12),
                    gen_lens=(4, 9)):
    model, _ = _model(arch)
    return poisson_workload(n, rate, model.cfg.vocab, seed=seed,
                            prompt_lens=prompt_lens, gen_lens=gen_lens)


def _reference_tokens(arch, params, req):
    model, _ = _model(arch)
    out = generate(model, params, jnp.asarray(req.tokens)[None], req.max_gen)
    return np.asarray(out)[0].tolist()


# ------------------------- paged cache vs dense -----------------------------

def test_paged_matches_dense_one_block():
    """One block spanning max_len with an identity table IS the dense
    cache: prefill logits and every decode step must match bitwise."""
    model, params = _model("gemma3-4b")
    B, S, ML = 3, 6, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              model.cfg.vocab)
    logits_d, caches_d, pos_d = model.prefill(params, toks, max_len=ML)
    paged = model.init_paged_cache(B, B + 1, ML)
    table = jnp.arange(B, dtype=jnp.int32)[:, None]
    lengths = jnp.full((B,), S, jnp.int32)
    logits_p, pre, pos_p = model.prefill_at(params, toks, lengths,
                                            max_len=ML)
    np.testing.assert_array_equal(np.asarray(logits_p),
                                  np.asarray(logits_d))
    paged = model.insert_prefill(paged, pre, table,
                                 jnp.arange(B, dtype=jnp.int32))
    ld, lp = logits_d, logits_p
    for i in range(3):
        tok = jnp.argmax(ld, -1).astype(jnp.int32)
        ld, caches_d = model.decode_step(params, caches_d, tok, pos_d + i)
        lp, paged = model.decode_step(params, paged, tok, pos_p + i, table)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


def test_paged_matches_dense_shuffled_multiblock():
    """Real paging: 4 blocks per slot, physical blocks assigned in
    shuffled order — the block-table indirection must still reproduce
    dense reads bitwise (positions gather in logical order)."""
    model, params = _model("gemma3-4b")
    B, S, ML, bs = 3, 6, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              model.cfg.vocab)
    logits_d, caches_d, pos_d = model.prefill(params, toks, max_len=ML)
    bps = ML // bs
    perm = np.random.default_rng(3).permutation(B * bps)
    table = jnp.asarray(perm.reshape(B, bps), jnp.int32)
    paged = model.init_paged_cache(B, B * bps + 1, bs)
    logits_p, pre, pos_p = model.prefill_at(
        params, toks, jnp.full((B,), S, jnp.int32), max_len=ML)
    paged = model.insert_prefill(paged, pre, table,
                                 jnp.arange(B, dtype=jnp.int32))
    ld, lp = logits_d, logits_p
    for i in range(8):  # crosses two block boundaries
        tok = jnp.argmax(ld, -1).astype(jnp.int32)
        ld, caches_d = model.decode_step(params, caches_d, tok, pos_d + i)
        lp, paged = model.decode_step(params, paged, tok, pos_p + i, table)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


def test_right_padded_prefill_exact_for_attention():
    """Bucketed prefill right-pads prompts; for pure-attention archs the
    pad garbage sits behind the visibility mask, so each row's logits
    equal an exact-length single-row prefill.

    Compared at atol 1e-5, not bitwise: the (3, 6) batch and each
    exact-length row are separate executables, and XLA picks shape-
    dependent matmul tilings whose f32 sums associate differently (seen
    up to 2.4e-6 on jax 0.9's CPU backend). A pad token leaking through
    the mask moves these logits by orders of magnitude more."""
    model, params = _model("gemma3-4b")
    assert model_pads_ok(model)
    B, S = 3, 6
    lens = jnp.asarray([3, 6, 4], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0,
                              model.cfg.vocab)
    toks = jnp.where(jnp.arange(S)[None, :] < lens[:, None], toks, 0)
    logits, _, next_pos = model.prefill_at(params, toks, lens)
    np.testing.assert_array_equal(np.asarray(next_pos), np.asarray(lens))
    for r in range(B):
        row, _, _ = model.prefill(params, toks[r:r + 1, :int(lens[r])])
        np.testing.assert_allclose(np.asarray(logits[r]),
                                   np.asarray(row[0]), rtol=0, atol=1e-5)


def test_recurrent_archs_reject_padding():
    """mamba2 / rwkv6 state consumes pad tokens — the engine must demand
    exact-length prefill groups there (pad_ok False -> bucket == length,
    and mixing lengths in one admit group raises)."""
    model, engine = _engine("rwkv6-1.6b")
    assert not engine.pad_ok
    assert engine.bucket_len(5) == 5
    reqs = [Request(0, 0.0, np.zeros(5, np.int32), 2),
            Request(1, 0.0, np.zeros(7, np.int32), 2)]
    with pytest.raises(ValueError, match="mixed prefill buckets"):
        engine.admit(reqs)


# ------------------------- engine == generate gate --------------------------

def test_engine_byte_identical_to_generate_mixed_lengths():
    """THE exactness gate: the continuous-batching engine emits
    byte-identical tokens to the static generate path for every request
    in a mixed-length workload — including requests admitted mid-stream
    into recycled slots (workload > slots forces churn)."""
    model, engine = _engine("gemma3-4b", block_size=8)
    wl = _mixed_workload("gemma3-4b")
    engine.warmup(buckets=[r.prompt_len for r in wl])
    report = serve_continuous(engine, wl, clock=StepClock())
    assert len(report.requests) == len(wl)
    _, params = _model("gemma3-4b")
    for r in report.requests:
        assert len(r.out) == r.max_gen
        assert r.out == _reference_tokens("gemma3-4b", params, r), r.rid


def test_engine_byte_identical_recurrent_arch():
    """Same gate for a recurrent arch (rwkv6): exact-length prefill
    groups, per-slot state rows instead of paged blocks."""
    model, engine = _engine("rwkv6-1.6b", max_len=24, block_size=8)
    wl = _mixed_workload("rwkv6-1.6b", n=6, seed=3, prompt_lens=(5, 9),
                         gen_lens=(4, 7))
    engine.warmup(buckets=[r.prompt_len for r in wl])
    report = serve_continuous(engine, wl, clock=StepClock())
    _, params = _model("rwkv6-1.6b")
    for r in report.requests:
        assert r.out == _reference_tokens("rwkv6-1.6b", params, r), r.rid


def test_static_baseline_matches_engine_tokens():
    """serve_static shares generate's fused step — same tokens per
    request as the engine, only the schedule (convoy) differs."""
    model, params = _model("gemma3-4b")
    wl_a = _mixed_workload("gemma3-4b")
    wl_b = _mixed_workload("gemma3-4b")
    _, engine = _engine("gemma3-4b", block_size=8)
    engine.warmup(buckets=[r.prompt_len for r in wl_a])
    rep_a = serve_continuous(engine, wl_a, clock=StepClock())
    rep_b = serve_static(model, params, wl_b, clock=StepClock(), batch=3)
    assert len(rep_b.requests) == len(wl_b)
    for ra, rb in zip(rep_a.requests, rep_b.requests):
        assert ra.rid == rb.rid and ra.out == rb.out


# ------------------------- sampling / dispatch ------------------------------

def test_greedy_is_argmax_invariance():
    """The fused sample+decode step at temperature 0 must reproduce an
    explicit host-side argmax loop token for token."""
    model, params = _model("gemma3-4b")
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 5), 0,
                                 model.cfg.vocab)
    fused = np.asarray(generate(model, params, prompts, 6))
    logits, caches, pos = model.prefill(params, prompts, max_len=11)
    outs = []
    for i in range(6):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(np.asarray(tok))
        logits, caches = model.decode_step(params, caches, tok, pos + i)
    np.testing.assert_array_equal(fused, np.stack(outs, axis=1))


def test_sampled_generate_deterministic_per_seed():
    """Sampling lives inside the jitted step now; same seed -> same
    stream, different seed -> (almost surely) different."""
    model, params = _model("gemma3-4b")
    prompts = jax.random.randint(jax.random.PRNGKey(8), (2, 4), 0,
                                 model.cfg.vocab)
    a = np.asarray(generate(model, params, prompts, 8, temperature=1.0,
                            seed=1))
    b = np.asarray(generate(model, params, prompts, 8, temperature=1.0,
                            seed=1))
    c = np.asarray(generate(model, params, prompts, 8, temperature=1.0,
                            seed=2))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


# ------------------------- scheduler behavior -------------------------------

def test_slot_recycle_and_eos_early_stop():
    """An EOS engine frees the slot the step the token appears; the
    request keeps the EOS token as its last output."""
    model, params = _model("gemma3-4b")
    probe = _mixed_workload("gemma3-4b", n=1, seed=9, prompt_lens=(6,),
                            gen_lens=(8,))
    ref = _reference_tokens("gemma3-4b", params, probe[0])
    eos = ref[2]  # stop at the first occurrence of this token
    stop = ref.index(eos) + 1
    _, engine = _engine("gemma3-4b", eos=eos)
    engine.warmup(buckets=[6])
    report = serve_continuous(engine, probe, clock=StepClock())
    r = report.requests[0]
    assert r.out == ref[:stop] and r.out[-1] == eos
    assert engine.free_slots == engine.n_slots


def test_backpressure_stats_and_occupancy():
    """High offered load must show up in the stats: nonzero queue depth,
    high slot occupancy, slots all recycled at drain."""
    _, engine = _engine("gemma3-4b", block_size=8)
    wl = _mixed_workload("gemma3-4b", n=9, rate=50.0, seed=13)
    engine.warmup(buckets=[r.prompt_len for r in wl])
    report = serve_continuous(engine, wl, clock=StepClock())
    s = report.summary()
    assert s["max_queue_depth"] > 0
    assert s["occupancy_mean"] > 0.5
    assert s["tokens_out"] == sum(r.max_gen for r in wl)
    assert engine.free_slots == engine.n_slots
    assert s["p99_latency_s"] >= s["p50_latency_s"] > 0


def test_workload_deterministic_per_seed():
    a = poisson_workload(5, 2.0, 64, seed=4)
    b = poisson_workload(5, 2.0, 64, seed=4)
    for ra, rb in zip(a, b):
        assert ra.arrival == rb.arrival and ra.max_gen == rb.max_gen
        np.testing.assert_array_equal(ra.tokens, rb.tokens)
    # suffix of a longer workload regenerates the same requests
    c = poisson_workload(3, 2.0, 64, seed=4)
    for ra, rc in zip(a, c):
        np.testing.assert_array_equal(ra.tokens, rc.tokens)


def test_admission_guards():
    _, engine = _engine("gemma3-4b", n_slots=2, max_len=16)
    too_long = [Request(0, 0.0, np.zeros(12, np.int32), 8)]
    with pytest.raises(ValueError, match="exceed max_len"):
        engine.admit(too_long)
    three = [Request(i, 0.0, np.zeros(4, np.int32), 2) for i in range(3)]
    with pytest.raises(ValueError, match="free slots"):
        engine.admit(three)
    with pytest.raises(ValueError, match="max_len"):
        serve_continuous(engine, too_long, clock=StepClock())


# ------------------------- federated checkpoints ----------------------------

def _toy_loss(params, batch):
    # differentiable on ANY params tree (async init dispatches a real
    # local round, so the loss must accept transformer params)
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(params))


def _transformer_spec(model, n_clients=2, **kw):
    from repro.api import FederationSpec
    from repro.optim import sgd
    base = dict(n_clients=n_clients, tau=1, loss_fn=_toy_loss,
                optimizer=sgd(0.1), clip_norm=1.0, dp=True,
                sigmas=(0.5,) * n_clients, batch_sizes=(2,) * n_clients)
    base.update(kw)
    return FederationSpec(**base)


def _stacked_params(model, n_clients):
    inits = [model.init(jax.random.PRNGKey(10 + i))
             for i in range(n_clients)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *inits)


@pytest.mark.parametrize("topology", ["full_average", "local_only"])
def test_dense_checkpoint_serves_eval_params(tmp_path, topology):
    """save_state checkpoints serve bit-identically to eval_params under
    both collapse topologies (distinct per-client replicas make the
    collapse rule observable)."""
    from repro.api import eval_params, init_state, save_state
    from repro.launch.train import federation_meta
    model, _ = _model("gemma3-4b")
    spec = _transformer_spec(model, topology=topology)
    state = init_state(spec, model.init(jax.random.PRNGKey(3)))
    state = dataclasses.replace(state,
                                params=_stacked_params(model, spec.n_clients))
    save_state(str(tmp_path), state, extra=federation_meta(spec))
    served = load_federated_params(model, str(tmp_path))
    want = eval_params(spec, state)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_population_checkpoint_serves_eval_params(tmp_path):
    """save_population_state wraps save_state — the serving loader must
    see through the store sidecar and collapse the K-block params."""
    from repro.api import eval_params
    from repro.launch.train import federation_meta
    from repro.population import (init_population_state,
                                  save_population_state)
    model, _ = _model("gemma3-4b")
    spec = _transformer_spec(model, population=6, cohort_size=2)
    pstate = init_population_state(spec, model.init(jax.random.PRNGKey(3)))
    pstate = dataclasses.replace(
        pstate, fl=dataclasses.replace(
            pstate.fl, params=_stacked_params(model, spec.n_clients)))
    save_population_state(str(tmp_path), pstate,
                          extra=federation_meta(spec))
    served = load_federated_params(model, str(tmp_path))
    want = eval_params(spec, pstate.fl)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpoint_serves_global_params(tmp_path):
    """Async checkpoints store the already-collapsed server model under
    global_params; the loader must serve that, never the K in-flight
    slot storages."""
    from repro.asyncfl import init_async_state, save_async_state
    from repro.launch.train import federation_meta

    def sampler(vid, tau, rng):
        return {"x": rng.normal(size=(tau, 2, 4)).astype(np.float32),
                "y": rng.integers(0, 2, size=(tau, 2)).astype(np.int32)}

    model, _ = _model("gemma3-4b")
    spec = _transformer_spec(model, engine="async_buffered")
    params0 = model.init(jax.random.PRNGKey(3))
    state = init_async_state(spec, params0, sampler, check_budgets=False)
    # make the slot storages visibly different from the server model
    state = dataclasses.replace(
        state, fl=dataclasses.replace(
            state.fl, params=_stacked_params(model, spec.n_clients)))
    save_async_state(str(tmp_path), state, extra=federation_meta(spec))
    served = load_federated_params(model, str(tmp_path))
    for a, b in zip(jax.tree.leaves(served),
                    jax.tree.leaves(state.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------- hot-swap gate ------------------------------------

def test_hot_swap_mid_decode():
    """The hot-swap gate: swapping checkpoints mid-decode (a) completes
    every in-flight request without error, (b) leaves tokens emitted
    before the boundary byte-identical to the old checkpoint's reference,
    and (c) admissions after the swap serve the new checkpoint exactly."""
    model, pA = _model("gemma3-4b")
    pB = model.init(jax.random.PRNGKey(7))
    wl = _mixed_workload("gemma3-4b", n=6, rate=1.0, seed=11,
                         prompt_lens=(6, 10), gen_lens=(8,))
    _, engine = _engine("gemma3-4b", params=pA, block_size=8)
    engine.warmup(buckets=[r.prompt_len for r in wl])
    swap_at = 6.0
    report = serve_continuous(engine, wl, clock=StepClock(),
                              swap_at=swap_at, swap_params=pB)
    assert engine.swaps == 1
    assert len(report.requests) == len(wl)
    saw_boundary = False
    for r in report.requests:
        assert len(r.out) == r.max_gen
        refA = _reference_tokens("gemma3-4b", pA, r)
        n_pre = sum(1 for t in r.emit_times if t <= swap_at)
        assert r.out[:n_pre] == refA[:n_pre], r.rid
        saw_boundary |= 0 < n_pre < r.max_gen
    assert saw_boundary  # the workload actually straddled the swap

    # post-swap admissions serve the new checkpoint byte-identically
    wl2 = _mixed_workload("gemma3-4b", n=3, rate=2.0, seed=21,
                          prompt_lens=(6, 10), gen_lens=(8,))
    rep2 = serve_continuous(engine, wl2, clock=StepClock())
    for r in rep2.requests:
        assert r.out == _reference_tokens("gemma3-4b", pB, r), r.rid


def test_hot_swap_rejects_mismatched_tree():
    model, engine = _engine("gemma3-4b")
    with pytest.raises(ValueError, match="tree mismatch"):
        engine.swap_params({"not": jnp.zeros(3)})


# ------------------- CI smoke leg (REPRO_SMOKE_SERVE) -----------------------

@pytest.mark.skipif(not os.environ.get("REPRO_SMOKE_SERVE"),
                    reason="set REPRO_SMOKE_SERVE=1 to smoke the serving "
                           "plane on a hybrid arch")
def test_serve_smoke_hybrid_arch():
    """CI serve leg: the exactness gate on zamba2 (attention + mamba2
    hybrid — paged blocks and per-slot recurrent state in one model).
    Prompt lengths are multiples of the mamba2 SSD chunk (prefill
    constraint, same as the dense path)."""
    model, engine = _engine("zamba2-7b", max_len=24, block_size=8)
    wl = _mixed_workload("zamba2-7b", n=5, seed=17, prompt_lens=(8, 16),
                         gen_lens=(4, 6))
    engine.warmup(buckets=[r.prompt_len for r in wl])
    report = serve_continuous(engine, wl, clock=StepClock())
    _, params = _model("zamba2-7b")
    for r in report.requests:
        assert r.out == _reference_tokens("zamba2-7b", params, r), r.rid
