#!/usr/bin/env python3
"""Bring-up smoke run of DP-PASGD training and serving on one TPU chip.

Drives the main path through the calls a user makes — ``repro.launch.train
.build_federation`` + ``repro.api.train`` for training, ``repro.serve
.SlotEngine`` + ``serve_continuous`` for serving — at the published widths
of rwkv6-1.6b cut to 4 layers (``repro.configs.rwkv6_1_6b.chip_share``),
with random weights drawn from ``--seed``:

    python chip_smoke.py                # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips   # four chips: the mesh comparison

(a) dense DP-PASGD rounds (the ``map`` engine: clients in sequence) with
    the leaf-wise clip+noise; checks finite losses, moved params, the
    privacy ledger against a host accountant, and the flat Pallas
    clip+noise kernel against its oracle on a real-width gradient;
(b) the federation with participation 0.5 and qsgd compression, which
    puts the quantize_decompress kernel on the chip (one client: see
    ``QSGD_CLIENTS``);
(c) continuous-batching serving of phase (a)'s model: every request
    completes with in-vocabulary tokens.

``--four-chips`` runs only one ``mesh_2d`` (2, 2) round and one
``shard_map`` (4, 1) round on the same inputs and key, and compares them
(both with the jnp clip+noise: see ``mesh_phase``).

Measurements go to earlier lines; the last line of standard output is one
JSON object ``{"ok": true, "device": {...}}``. With no TPU, or outside the
repository, the script exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# phase (a)/(b) shape: C clients x batch B x seq S, tau local steps per
# round. C and B were sized from the v5e compile's memory_analysis so the
# round program's peak is above half of the chip's HBM (PERF.md, Cells):
# the dense round compiles to 9.14 GiB at C=4, 7.70 at C=3.
CLIENTS, BATCH, SEQ, TAU, ROUNDS = 4, 1, 1024, 2, 3
# The map engine, not engine="auto" (-> vmap on one chip): the v5e compile
# of the vmap round at C=2 takes 330-510 s against ~60 s for map, and two
# such compiles do not fit the run's time limit (PERF.md, Findings).
ENGINE = "map"
# The qsgd pipeline holds ~5 (C, D) f32 arrays at the aggregation
# boundary (D = 353M here): at C=2 the v5e compile needs 15.79 of 15.75
# GiB, so phase (b) runs one client (10.7 GiB peak).
QSGD_CLIENTS = 1
# phase (c): 8 requests on 4 slots, prompt 64, 16 greedy tokens each
REQUESTS, SLOTS, PROMPT, GEN = 8, 4, 64, 16
# four-chip comparison: 4 clients, one round per engine, one layer (the
# whole period: every sharded weight shape is there). The model-sharded
# region of mesh_2d lowers its layers straight-line, so its compile time
# grows with depth: minutes per layer (PERF.md, Findings).
MESH_LAYERS, MESH_CLIENTS, MESH_BATCH, MESH_SEQ, MESH_TAU = 1, 4, 1, 32, 1
MESH_LR = 0.1       # see check_mesh
# At batch 1 the design noise is sigma = 2.97 per coordinate per step; a
# small learning rate keeps three rounds of it from swamping the init.
EPS, DELTA, CLIP, LR = 10.0, 1e-4, 1.0, 1e-3


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def _fingerprint(params):
    """Per-leaf (sum, sum of squares) of client 0's replica, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    return np.asarray([[float(jnp.sum(x[0].astype(jnp.float32))),
                        float(jnp.sum(jnp.square(x[0].astype(jnp.float32))))]
                       for x in jax.tree.leaves(params)])


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def round_memory(spec, state, sampler) -> dict:
    """The round program's ``memory_analysis`` from this device's compiler,
    in bytes (the persistent compile cache usually hands back the program
    the rounds ran, so this costs no second compile)."""
    import jax
    import numpy as np

    from repro.api import round_batch, round_fn_for, sigmas_for

    batch = round_batch(spec, sampler, np.random.default_rng(0))
    args = [state.params, state.opt_state, batch, state.key, sigmas_for(spec)]
    if spec.has_pipeline():
        args += [np.ones(spec.n_clients, np.float32), state.residual]
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args)
    mem = round_fn_for(spec).lower(*shapes).compile().memory_analysis()
    return {k: getattr(mem, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp")}


def train_phase(cfg, *, n_clients: int, batch: int, seq: int, tau: int,
                rounds: int, kernel_backend: str, seed: int,
                engine: str = "auto", **pipeline) -> dict:
    """Build a federation the way ``launch/train`` does and train it.

    Returns the spec, final state and summary plus what was measured:
    the resolved backends of the round's kernels, compile and steady
    seconds, losses, and the host-accountant epsilon for the same schedule.
    """
    import jax
    import numpy as np

    from repro.api import resolve_engine, train
    from repro.core.fl import design_sigmas
    from repro.core.privacy import PrivacyAccountant
    from repro.kernels.dispatch import resolve_backend
    from repro.launch.train import build_federation

    # noise for one round more than will run: the last round then fits the
    # epsilon budget with room to spare, not by a float rounding
    sigmas = design_sigmas((rounds + 1) * tau, CLIP, [batch] * n_clients,
                           EPS, DELTA)
    rng = np.random.default_rng(seed)
    model, spec, state, sampler = build_federation(
        cfg, n_clients, tau, batch, seq, sigmas, lr=LR, clip_norm=CLIP,
        delta=DELTA, engine=engine, seed=seed, rng=rng, **pipeline)
    spec = spec.replace(eps_th=EPS, kernel_backend=kernel_backend)
    backends = {}
    if spec.compressor == "qsgd":
        backends["quantize_decompress"] = resolve_backend(
            "quantize_decompress", kernel_backend)
    before = _fingerprint(state.params)

    history: list[dict] = []
    t0 = time.perf_counter()
    state, out = train(spec, state, sampler, max_rounds=1, rng=rng,
                       history=history)
    jax.block_until_ready(state.params)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, out = train(spec, state, sampler, max_rounds=rounds, rng=rng,
                       history=history)
    jax.block_until_ready(state.params)
    steady_s = (time.perf_counter() - t0) / max(rounds - 1, 1)

    acc = PrivacyAccountant(clip_norm=CLIP, delta=DELTA)
    for m, s in enumerate(np.asarray(sigmas)):
        acc.register_client(m, batch, float(s))
    for _ in range(rounds):
        acc.step(tau)
    return {
        "model": model, "spec": spec, "state": state, "out": out,
        "engine": resolve_engine(spec), "backends": backends,
        "round_memory": round_memory(spec, state, sampler),
        "losses": [float(r["loss"]) for r in out["history"]],
        "moved": bool(np.all(_fingerprint(state.params) != before)),
        "host_epsilon": acc.max_epsilon(),
        # the first round pays the compile; the rest is steady state
        "compile_s": first_s - steady_s, "steady_s_per_round": steady_s,
    }


def check_training(result: dict, *, rounds: int, dense: bool) -> None:
    import numpy as np
    losses = result["losses"]
    assert result["out"]["rounds"] == rounds, result["out"]["rounds"]
    assert len(losses) == rounds and np.all(np.isfinite(losses)), losses
    assert result["moved"], "a parameter leaf did not move"
    if dense:
        # every client ran every round: the FLState ledger must equal the
        # host accountant's replay of the same schedule
        np.testing.assert_allclose(result["out"]["max_epsilon"],
                                   result["host_epsilon"], rtol=1e-12)


def kernel_vs_oracle(width: int, backend: str, seed: int) -> dict:
    """Fused clip+noise on ``backend`` vs the jnp oracle, on one flat f32
    gradient of ``width`` entries (a replica's parameter count; random
    normal, scaled so clipping is active), with the same noise vector on
    both sides.

    Tolerance: the two differ only in how the f32 norm is summed (per-block
    partials vs XLA's reduction), so the norms, and with them the clip
    scales, agree to 1e-5 relative. An element ``g * scale + sigma * n``
    may then differ by 1e-5 of ``|g| * scale``, plus two f32 roundings of
    its terms (a fused multiply-add rounds once, a multiply and an add
    twice). The bound is per element and absolute, because the sum of the
    two terms can cancel to near zero, where a relative bound means
    nothing.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.dispatch import resolve_backend
    from repro.kernels.ops import dp_clip_noise_flat

    sigma = 0.01
    kg, kn = jax.random.split(jax.random.PRNGKey(seed + 1))
    g = jax.random.normal(kg, (width,), jnp.float32) * 1e-3
    noise = jax.random.normal(kn, (width,), jnp.float32)
    got, got_norm = dp_clip_noise_flat(g, noise, CLIP, sigma, backend=backend)
    want, want_norm = dp_clip_noise_flat(g, noise, CLIP, sigma, backend="ref")
    np.testing.assert_allclose(float(got_norm), float(want_norm), rtol=1e-5)
    resolved = resolve_backend("dp_clip_noise", backend)

    @jax.jit
    def excess(got, want, g, noise, norm):
        clipped = jnp.abs(g) * jnp.minimum(1.0, CLIP / norm)
        f32 = jnp.finfo(jnp.float32).eps
        bound = 1e-5 * clipped + 2 * f32 * (clipped + sigma * jnp.abs(noise))
        diff = jnp.abs(got - want)
        return jnp.max(diff - bound), jnp.max(diff)

    over, worst = excess(got, want, g, noise, want_norm)
    assert float(over) <= 0.0, ("clip+noise off its oracle", float(over))
    return {"entries": width, "backend": resolved, "norm": float(want_norm),
            "max_abs_diff": float(worst)}


def qsgd_vs_oracle(width: int, backend: str, seed: int) -> dict:
    """8-bit QSGD quantize+dequantize on ``backend`` vs the jnp oracle, on
    one flat f32 update of ``width`` entries with the same rounding draws.

    Tolerance: the scale is a max, which no reduction order changes, over
    the level count; only that division may round differently (XLA may
    multiply by the reciprocal), so the scales agree to 2**-22 relative.
    An element's level is ``floor(|x| / scale + u)``, and
    where that f32 sum lands within a rounding of an integer the two sides
    may take neighbouring levels, so elements agree to one step (scale),
    plus up to 255 levels times the scales' difference (< 2**-12 scale).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import quantize_decompress_flat

    kx, ku = jax.random.split(jax.random.PRNGKey(seed + 3))
    x = jax.random.normal(kx, (width,), jnp.float32) * 1e-3
    u = jax.random.uniform(ku, (width,), jnp.float32)
    got, got_scale = quantize_decompress_flat(x, u, 8, backend=backend)
    want, scale = quantize_decompress_flat(x, u, 8, backend="ref")
    np.testing.assert_allclose(float(got_scale), float(scale), rtol=2 ** -22)
    worst, apart = jax.jit(lambda a, b, step: (
        jnp.max(jnp.abs(a - b)), jnp.sum(jnp.abs(a - b) > step / 2)))(
            got, want, scale)
    assert float(worst) <= float(scale) * (1 + 2 ** -12), (
        "qsgd off its oracle by more than one step", float(worst))
    return {"entries": width, "scale": float(scale),
            "max_abs_diff": float(worst), "levels_apart": int(apart)}


def serve_phase(model, params, *, requests: int, slots: int, prompt: int,
                gen: int, seed: int) -> dict:
    """Serve a Poisson workload as ``launch/serve`` does and check it."""
    import numpy as np

    from repro.serve import SlotEngine, poisson_workload, serve_continuous

    vocab = model.cfg.vocab
    engine = SlotEngine(model, params, n_slots=slots, max_len=prompt + gen,
                        temperature=0.0)
    workload = poisson_workload(requests, 4.0, vocab, seed=seed,
                                prompt_lens=(prompt,), gen_lens=(gen,))
    compile_s = engine.warmup(buckets=[r.prompt_len for r in workload])
    report = serve_continuous(engine, workload)
    assert len(report.requests) == requests, len(report.requests)
    for r in report.requests:
        out = np.asarray(r.out)
        assert out.shape == (gen,), (r.rid, out.shape)
        assert np.all((out >= 0) & (out < vocab)), r.rid
    summary = report.summary()
    return {"compile_s": compile_s, "requests": len(report.requests),
            "tokens_out": summary["tokens_out"],
            "tokens_per_s": summary["tokens_per_s"],
            "first_tokens": report.requests[0].out[:8]}


def mesh_phase(cfg, *, seed: int) -> dict:
    """One mesh_2d (2, 2) round and one shard_map (4, 1) round on the same
    inputs and key; returns both round losses and, over the parameter
    leaves, the largest distance between the two engines' parameters
    relative to the size of the terms they were summed from (see
    ``check_mesh``)."""
    from dataclasses import replace

    import jax
    import numpy as np

    from repro.api import round_batch, run_round
    from repro.launch.train import build_federation

    rng = np.random.default_rng(seed)
    sigmas = [0.5] * MESH_CLIENTS
    params, losses, seconds, peaks = {}, {}, {}, {}
    batch = None
    # a short sequence: the model-sharded region of mesh_2d lowers its
    # layer and WKV scans straight-line, so compile time grows with it
    cfg = replace(cfg, loss_chunk=min(cfg.loss_chunk, MESH_SEQ))
    for engine, shape in (("shard_map", None), ("mesh_2d", (2, 2))):
        ecfg = replace(cfg, scan_unroll=True) if engine == "mesh_2d" else cfg
        model, spec, state, sampler = build_federation(
            ecfg, MESH_CLIENTS, MESH_TAU, MESH_BATCH, MESH_SEQ, sigmas,
            lr=MESH_LR, clip_norm=CLIP, delta=DELTA, engine=engine,
            seed=seed, mesh_shape=shape)
        # mesh_2d cannot partition a Mosaic kernel over its model axis
        # (mesh.engine refuses it), so both engines take the jnp clip+noise
        # and the comparison is like for like
        spec = spec.replace(kernel_backend="ref")
        if batch is None:
            batch = round_batch(spec, sampler, rng)
        t0 = time.perf_counter()
        state, rec = run_round(spec, state, batch, check_budgets=False)
        jax.block_until_ready(state.params)
        seconds[engine] = time.perf_counter() - t0
        losses[engine] = float(rec["loss"])
        params[engine] = state.params
        peaks[engine] = [_peak_bytes(d) for d in jax.devices()]

    # every client started from the same init: the seed's params
    rel, worst = param_distance(model.init(jax.random.PRNGKey(seed)),
                                params["shard_map"], params["mesh_2d"])
    return {"losses": losses, "param_rel_err": rel,
            "param_max_abs_diff": worst,
            "first_round_s": seconds, "peak_bytes_per_device": peaks}


def param_distance(p0, a, b) -> tuple[float, float]:
    """For client-stacked params ``a`` and ``b`` that both started from the
    one replica ``p0``: the largest, over leaves, of ``|a - b|`` relative
    to ``|p0| + |a - p0|`` (2-norms over all clients), and the largest
    element-wise ``|a - b|``. Each leaf is gathered onto the first device
    and reduced there: the two engines' params live on different meshes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def terms(x0, xa, xb):
        x0, xa, xb = (x.astype(jnp.float32) for x in (x0, xa, xb))
        x0 = jnp.broadcast_to(x0, xa.shape)
        size = jnp.linalg.norm(x0) + jnp.linalg.norm(xa - x0)
        return jnp.linalg.norm(xa - xb) / size, jnp.max(jnp.abs(xa - xb))

    device = jax.devices()[0]
    rel, worst = 0.0, 0.0
    for leaves in zip(jax.tree.leaves(p0), jax.tree.leaves(a),
                      jax.tree.leaves(b)):
        r, w = terms(*(jax.device_put(x, device) for x in leaves))
        rel, worst = max(rel, float(r)), max(worst, float(w))
    return rel, worst


def check_mesh(mesh: dict) -> None:
    """The two engines run one round of the same math.

    Losses (tau = 1: the forward pass at the initial params) agree to 1e-2
    relative. The forward runs in bf16 with f32 accumulation, and mesh_2d
    splits each matmul over the model axis, so partial sums add in another
    order and an activation may round one bf16 step (2**-8) apart in each
    layer; a wrong sharding moves the loss by O(1).

    Parameters: each leaf of each engine is the exact round result plus
    bf16 roundings (2**-8 relative, half a step) of the local step's
    ``p0 + u_c`` and of the client average. Their size is bounded by the
    init ``p0`` and the clients' own updates ``u_c`` — not by the result,
    which can cancel to near zero, so a bound relative to each element's
    result fails by up to a rounding step of the update. The check is in
    norm, per leaf: ``|a - b| <= 2**-5 * (|p0| + |a - p0|)`` over all
    clients. The mean of C = 4 independent noise draws is half the size
    of one draw (``|u_c| ~ 2 |a - p0|``), so two engines with two
    roundings each stay within ``2 * 2 * 2**-8 * (|p0| + 2 |a - p0|)``,
    under the bound; the clipped gradients, which the engines sum in
    another order, add at most ``2 * lr * clip`` per client to the whole
    replica's distance, far below the noise's norm. An update
    that went wrong — a client dropped, noise or clipping missing or
    doubled — moves the large leaves by a sizable share of
    ``|a - p0|``: ``MESH_LR`` makes the update (lr * sigma = 0.05 per
    element and step) of the size of the init there.
    """
    import numpy as np
    sm, m2 = mesh["losses"]["shard_map"], mesh["losses"]["mesh_2d"]
    assert np.isfinite(sm) and np.isfinite(m2), mesh["losses"]
    np.testing.assert_allclose(m2, sm, rtol=1e-2)
    assert mesh["param_rel_err"] <= 2 ** -5, mesh["param_rel_err"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh_2d (2,2) vs shard_map (4,1) "
                         "comparison on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.env import enable_compile_cache

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    from repro.configs.rwkv6_1_6b import chip_share
    cfg = chip_share(MESH_LAYERS if args.four_chips else 4)
    log(device=device.device_kind, count=len(devices), arch=cfg.name,
        compile_cache=cache_dir,
        bytes_limit=(device.memory_stats() or {}).get("bytes_limit"))

    if args.four_chips:
        mesh = mesh_phase(cfg, seed=args.seed)
        log(phase="four_chips", **mesh)
        check_mesh(mesh)
    else:
        dense = train_phase(cfg, n_clients=CLIENTS, batch=BATCH, seq=SEQ,
                            tau=TAU, rounds=ROUNDS, kernel_backend="auto",
                            seed=args.seed, engine=ENGINE)
        check_training(dense, rounds=ROUNDS, dense=True)
        log(phase="a_dense", engine=dense["engine"],
            backends=dense["backends"], losses=dense["losses"],
            max_epsilon=dense["out"]["max_epsilon"],
            host_epsilon=dense["host_epsilon"],
            compile_s=dense["compile_s"],
            steady_s_per_round=dense["steady_s_per_round"],
            round_memory=dense["round_memory"], peak_bytes=_peak_bytes(device))
        from repro.api import eval_params
        served = eval_params(dense["spec"], dense["state"])
        model = dense["model"]
        del dense
        width = sum(x.size for x in jax.tree.leaves(served))
        flat = kernel_vs_oracle(width, "auto", args.seed)
        assert flat["backend"] == "pallas", flat
        log(phase="a_kernel_vs_oracle", **flat)

        comp = train_phase(cfg, n_clients=QSGD_CLIENTS,
                           batch=BATCH, seq=SEQ, tau=TAU, rounds=ROUNDS,
                           kernel_backend="auto", seed=args.seed,
                           engine=ENGINE, participation=0.5,
                           compressor="qsgd")
        assert set(comp["backends"].values()) == {"pallas"}, comp["backends"]
        check_training(comp, rounds=ROUNDS, dense=False)
        log(phase="b_qsgd", engine=comp["engine"], backends=comp["backends"],
            losses=comp["losses"], max_epsilon=comp["out"]["max_epsilon"],
            compile_s=comp["compile_s"],
            steady_s_per_round=comp["steady_s_per_round"],
            round_memory=comp["round_memory"], peak_bytes=_peak_bytes(device))
        del comp
        log(phase="b_kernel_vs_oracle",
            **qsgd_vs_oracle(width, "auto", args.seed))

        serve = serve_phase(model, served, requests=REQUESTS, slots=SLOTS,
                            prompt=PROMPT, gen=GEN, seed=args.seed)
        log(phase="c_serve", **serve, peak_bytes=_peak_bytes(device))

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
