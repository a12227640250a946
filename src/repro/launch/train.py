"""End-to-end DP-PASGD training launcher, driven by ``repro.api``.

Runs real training (allocates params) — use reduced/smoke configs or the
~100M example config on CPU; on a TPU pod the same driver runs the full
configs. The optimal-design solver (paper §7) can pick (K, tau, sigma) from
resource/privacy budgets before launch. The engine (vmap / map / shard_map)
is selected declaratively via ``FederationSpec.engine``.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --smoke \
        --rounds 5 --clients 4 --tau 5 --eps 10 --cth 2000

``--chunk-rounds R`` fuses R rounds per XLA dispatch (the run_rounds scan
driver — same math, bit-identical ledger, a fraction of the host overhead).

``--population M --cohort-size K`` switches to cohort execution over M
virtual clients (repro.population): each round trains a sampled cohort of
K devices, device memory is bounded by K independent of M, and the
per-virtual-client privacy ledger / error-feedback residuals live in the
host-side ClientStore. M = 10^5..10^6 runs on a laptop:

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --smoke \
        --rounds 10 --population 100000 --cohort-size 8 --tau 5 --eps 10

``--async-buffer B`` switches to buffered-async federation
(repro.asyncfl, engine ``async_buffered``): the server aggregates the
first B arrivals per flush on a simulated device clock
(``--latency-profile {uniform,lognormal,hetero}``) with staleness-damped
updates (``--staleness-alpha``) and dispatch-time privacy charging:

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --smoke \
        --rounds 10 --clients 8 --tau 5 --async-buffer 4 \
        --latency-profile hetero

``--env-profile {host,cpu-mesh}`` re-execs the launcher under the tuned
host environment (tcmalloc, XLA host-platform flags — see
``repro.launch.env``).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.api import FederationSpec, init_state, save_state, train
from repro.asyncfl import (
    LATENCY_PROFILES,
    init_async_state,
    latency_profile,
    save_async_state,
    train_async,
)
from repro.configs import get_arch, smoke_variant
from repro.launch.env import (
    add_env_profile_args,
    apply_env_profile,
    enable_compile_cache,
)
from repro.population import (
    HeterogeneousCohort,
    init_population_state,
    population_from_sampler,
    save_population_state,
    train_population,
)
from repro.core.convergence import ProblemConstants
from repro.core.design import DesignProblem, ResourceModel
from repro.core.fl import design_sigmas
from repro.data.tokens import FederatedTokenStream, TokenTaskConfig
from repro.models.transformer import Transformer
from repro.optim import sgd


def build_federation(cfg, n_clients: int, tau: int, batch_size: int,
                     seq_len: int, sigmas, lr: float = 0.1,
                     clip_norm: float = 1.0, delta: float = 1e-4,
                     engine: str = "auto", seed: int = 0,
                     participation: float = 1.0, compressor: str = "none",
                     compression_ratio: float = 0.1,
                     compression_bits: int = 8, population: int = 0,
                     buffer_size: int | None = None,
                     staleness_alpha: float = 0.0, latency_model=None,
                     aggregator: str = "mean", trim_fraction: float = 0.1,
                     norm_bound_factor: float = 3.0,
                     secure_agg: bool = False, secure_frac_bits: int = 16,
                     dp_accounting: str = "local", attack: str = "none",
                     byzantine_fraction: float = 0.0,
                     attack_scale: float = 10.0,
                     mesh_shape: tuple[int, int] | None = None,
                     replica_bytes: int | None = None,
                     rng=None):
    """Assemble the repro.api handles for a transformer federation.

    Returns ``(model, spec, state, sampler)`` — drive them with
    ``repro.api.train(spec, state, sampler, ...)``. The aggregation-pipeline
    knobs (participation / compressor) pass through to the spec.

    ``population=M > 0`` switches to cohort execution
    (:mod:`repro.population`): ``n_clients`` becomes the per-round cohort
    size K, the token stream spans all M virtual clients (lazy — only the
    sampled cohort's batches are ever synthesized), and the returned
    ``state`` is a :class:`repro.population.PopulationState` to drive with
    ``train_population`` (wrap the sampler via ``population_from_sampler``).

    ``engine="async_buffered"`` returns an
    :class:`repro.asyncfl.AsyncState` (generation 0 already dispatched —
    it consumes the first round batches from ``rng``, so pass the SAME
    ``rng`` to ``train_async``) to drive with ``train_async``;
    ``buffer_size``/``staleness_alpha``/``latency_model`` configure the
    flush and the simulated clocks.
    """
    model = Transformer(cfg)
    task = TokenTaskConfig(vocab=cfg.vocab, seq_len=seq_len,
                           n_clients=population or n_clients, seed=seed)
    stream = FederatedTokenStream(task, batch_size,
                                  prefix_len=cfg.prefix_len,
                                  d_model=cfg.d_model)
    params0 = model.init(jax.random.PRNGKey(seed))
    spec = FederationSpec(
        n_clients=n_clients, tau=tau, loss_fn=model.loss_fn,
        optimizer=sgd(lr), engine=engine, dp=True, clip_norm=clip_norm,
        num_microbatches=1,
        participation=participation, compressor=compressor,
        compression_ratio=compression_ratio,
        compression_bits=compression_bits,
        aggregator=aggregator, trim_fraction=trim_fraction,
        norm_bound_factor=norm_bound_factor, secure_agg=secure_agg,
        secure_frac_bits=secure_frac_bits, dp_accounting=dp_accounting,
        attack=attack, byzantine_fraction=byzantine_fraction,
        attack_scale=attack_scale,
        population=population or None,
        cohort_size=n_clients if population else None,
        buffer_size=buffer_size if engine == "async_buffered" else None,
        staleness_alpha=(staleness_alpha if engine == "async_buffered"
                         else 0.0),
        mesh_shape=mesh_shape, replica_bytes=replica_bytes,
        sigmas=tuple(float(s) for s in np.asarray(sigmas)),
        batch_sizes=(batch_size,) * n_clients, delta=delta, seed=seed)
    if population:
        state = init_population_state(spec, params0)
    elif spec.is_async():
        state = init_async_state(spec, params0, stream.sampler, rng=rng,
                                 latency_model=latency_model)
    else:
        state = init_state(spec, params0)
    return model, spec, state, stream.sampler


def federation_meta(spec) -> dict:
    """The spec scalars a serving driver needs to rebuild a ``like`` FLState
    for ``load_state`` (see ``repro.launch.serve.load_federated_params``)."""
    return {"n_clients": spec.n_clients, "tau": spec.tau,
            "compressor": spec.compressor,
            "compression_ratio": spec.compression_ratio,
            "compression_bits": spec.compression_bits,
            "participation": spec.participants_per_round(),
            "population": spec.population,
            "aggregator": spec.aggregator,
            "secure_agg": spec.secure_agg,
            "dp_accounting": spec.dp_accounting,
            "attack": spec.attack,
            "byzantine_fraction": spec.byzantine_fraction,
            "topology": spec.topology}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of the arch")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--tau", type=int, default=0,
                    help="0 = let the optimal-design solver choose")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--eps", type=float, default=10.0)
    ap.add_argument("--delta", type=float, default=1e-4)
    ap.add_argument("--cth", type=float, default=2000.0)
    ap.add_argument("--c1", type=float, default=100.0)
    ap.add_argument("--c2", type=float, default=1.0)
    ap.add_argument("--engine", default="auto",
                    choices=("vmap", "map", "shard_map", "mesh_2d",
                             "async_buffered", "auto"))
    ap.add_argument("--mesh-shape", default=None,
                    help="dc,dm devices for the mesh_2d engine (client x "
                         "model axes), e.g. 4,2; default: "
                         "repro.mesh.placement.default_mesh_shape")
    ap.add_argument("--replica-hint", action="store_true",
                    help="pass the arch's abstract param+opt-state bytes "
                         "(configs.shapes.replica_footprint_bytes) to the "
                         "spec so engine='auto' can pick mesh_2d when one "
                         "replica exceeds per-device memory")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="B > 0 switches to buffered-async federation "
                         "(repro.asyncfl): aggregate the first B arrivals "
                         "per flush on simulated device clocks, redispatch "
                         "immediately, pre-charge privacy at dispatch")
    ap.add_argument("--latency-profile", default="uniform",
                    choices=LATENCY_PROFILES,
                    help="simulated per-device latency distribution (async "
                         "mode); 'hetero' couples slowness to the "
                         "Beta-availability cohort model")
    ap.add_argument("--latency-scale", type=float, default=1.0,
                    help="nominal simulated seconds per dispatch")
    ap.add_argument("--staleness-alpha", type=float, default=0.0,
                    help="staleness damping w(s) = 1/(1+s)^alpha applied "
                         "to late arrivals at the flush")
    add_env_profile_args(ap)
    ap.add_argument("--chunk-rounds", type=int, default=1,
                    help="fuse this many rounds into one jitted lax.scan "
                         "dispatch (repro.api.run_rounds): >1 makes the hot "
                         "loop device-resident with <=1 host sync and a "
                         "prefetched batch pipeline per chunk; eval then "
                         "happens at chunk boundaries only")
    ap.add_argument("--population", type=int, default=0,
                    help="train over M virtual clients with cohort "
                         "execution (repro.population): only --cohort-size "
                         "devices are resident per round, device memory is "
                         "independent of M; 0 = dense resident clients")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="per-round cohort size K (population mode; "
                         "default: --clients)")
    ap.add_argument("--resident-cache", type=int, default=0,
                    help="S > 0 keeps a device-resident shard cache of S "
                         "warm virtual clients (repro.population.resident): "
                         "a fresh cohort is drawn every round inside the "
                         "fused scan (the per-round schedule, unlike plain "
                         "--chunk-rounds population runs which fix one "
                         "cohort per chunk) and steady-state chunks make "
                         "zero blocking host syncs; needs --population and "
                         "--chunk-rounds > 1, and S >= chunk_rounds * K")
    ap.add_argument("--cohort-hetero", action="store_true",
                    help="sample cohorts under the Beta-availability + "
                         "dropout heterogeneity model instead of uniform "
                         "K-of-M")
    ap.add_argument("--cohort-dropout", type=float, default=0.05,
                    help="mid-round dropout rate of the heterogeneity model")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round")
    ap.add_argument("--compressor", default="none",
                    choices=("none", "topk", "randk", "qsgd"))
    ap.add_argument("--compress-ratio", type=float, default=0.1)
    ap.add_argument("--compress-bits", type=int, default=8)
    ap.add_argument("--aggregator", default="mean",
                    choices=("mean", "median", "trimmed_mean", "norm_bound"),
                    help="Eq.-7b reduction over participant updates; the "
                         "robust choices bound a byzantine minority's pull "
                         "(repro.core.robust)")
    ap.add_argument("--trim-fraction", type=float, default=0.1,
                    help="per-end trim of --aggregator trimmed_mean")
    ap.add_argument("--norm-bound-factor", type=float, default=3.0,
                    help="--aggregator norm_bound rejects updates whose L2 "
                         "norm exceeds factor x median participant norm")
    ap.add_argument("--secure-agg", action="store_true",
                    help="pairwise-mask secure-aggregation simulation "
                         "(repro.core.secureagg): the server only ever "
                         "materializes the masked fixed-point SUM")
    ap.add_argument("--secure-frac-bits", type=int, default=16,
                    help="fixed-point fractional bits of --secure-agg")
    ap.add_argument("--dp-accounting", default="local",
                    choices=("local", "central"),
                    help="'central' (needs --secure-agg) accounts the "
                         "aggregate-only observer: per-step rho scales by "
                         "1/P for the P pooled participant noises")
    ap.add_argument("--attack", default="none",
                    choices=("none", "sign_flip", "scale"),
                    help="simulate byzantine upload corruption by a static "
                         "--byzantine-fraction subset of resident clients")
    ap.add_argument("--byzantine-fraction", type=float, default=0.0)
    ap.add_argument("--attack-scale", type=float, default=10.0,
                    help="multiplier of --attack scale")
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    apply_env_profile(args.env_profile, host_devices=args.host_devices)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    engine = args.engine
    if args.async_buffer > 0 and engine != "async_buffered":
        engine = "async_buffered"
    is_async = engine == "async_buffered"
    if is_async and args.population:
        raise SystemExit("--async-buffer and --population are mutually "
                         "exclusive (async fleets model heterogeneity via "
                         "--latency-profile hetero)")

    # in population mode the resident block is the cohort, not --clients
    n_resident = (args.cohort_size or args.clients if args.population
                  else args.clients)
    if args.population and not 0 < n_resident <= args.population:
        raise SystemExit(f"--cohort-size must be in [1, {args.population}]")

    if args.tau:
        tau, k = args.tau, args.rounds * args.tau
        sigmas = design_sigmas(k, args.clip, [args.batch] * n_resident,
                               args.eps, args.delta)
    else:
        # paper §7: solve for (K, tau, sigma) under the budgets
        consts = ProblemConstants(eta=args.lr, lam=0.5, lip=2.0, alpha=5.0,
                                  xi2=1.0, dim=1000, n_clients=n_resident)
        prob = DesignProblem(
            consts=consts, resource=ResourceModel(args.c1, args.c2),
            clip_norm=args.clip, batch_sizes=[args.batch] * n_resident,
            delta=args.delta, eps_th=args.eps, c_th=args.cth)
        sol = prob.solve()
        tau = sol.tau
        sigmas = np.asarray(sol.sigmas, np.float32)
        print(f"[design] K*={sol.k} tau*={tau} sigma*={sigmas[0]:.4f} "
              f"bound={sol.predicted_bound:.4f} cost={sol.cost:.0f}")

    latency_model = (latency_profile(args.latency_profile, seed=0,
                                     fleet=n_resident,
                                     scale=args.latency_scale)
                     if is_async else None)
    mesh_shape = None
    if args.mesh_shape:
        dc, dm = (int(x) for x in args.mesh_shape.split(","))
        mesh_shape = (dc, dm)
    if engine == "mesh_2d" and (mesh_shape is None or mesh_shape[1] > 1):
        # model-sharded region: XLA's partial-auto partitioner can't handle
        # while loops, so the layer scans must lower as straight-line HLO
        from dataclasses import replace as _replace
        cfg = _replace(cfg, scan_unroll=True)
    replica_bytes = None
    if args.replica_hint:
        from repro.configs.shapes import replica_footprint_bytes
        replica_bytes = replica_footprint_bytes(cfg, optimizer=sgd(args.lr))
        print(f"[mesh] replica footprint "
              f"{replica_bytes / 1024 ** 3:.2f} GiB (params + opt state)")

    rng = np.random.default_rng(0)
    model, spec, state, sampler = build_federation(
        cfg, n_resident, tau, args.batch, args.seq, sigmas, lr=args.lr,
        clip_norm=args.clip, delta=args.delta, engine=engine,
        mesh_shape=mesh_shape, replica_bytes=replica_bytes,
        participation=args.participation, compressor=args.compressor,
        compression_ratio=args.compress_ratio,
        compression_bits=args.compress_bits, population=args.population,
        buffer_size=args.async_buffer or None,
        staleness_alpha=args.staleness_alpha,
        latency_model=latency_model,
        aggregator=args.aggregator, trim_fraction=args.trim_fraction,
        norm_bound_factor=args.norm_bound_factor,
        secure_agg=args.secure_agg, secure_frac_bits=args.secure_frac_bits,
        dp_accounting=args.dp_accounting, attack=args.attack,
        byzantine_fraction=args.byzantine_fraction,
        attack_scale=args.attack_scale, rng=rng)
    spec = spec.replace(eps_th=args.eps, c_th=args.cth,
                        c1=args.c1, c2=args.c2)
    t0 = time.time()
    if is_async:
        state, out = train_async(spec, state, sampler, max_rounds=args.rounds,
                                 rng=rng, chunk_rounds=args.chunk_rounds,
                                 latency_model=latency_model)
    elif args.population:
        pop = population_from_sampler(args.population, sampler,
                                      name="federated-tokens")
        cohort_sampler = (HeterogeneousCohort(seed=spec.seed,
                                              dropout=args.cohort_dropout)
                          if args.cohort_hetero else None)
        state, out = train_population(spec, state, pop,
                                      cohort_sampler=cohort_sampler,
                                      max_rounds=args.rounds,
                                      chunk_rounds=args.chunk_rounds,
                                      resident_cache=args.resident_cache)
    else:
        state, out = train(spec, state, sampler, max_rounds=args.rounds,
                           chunk_rounds=args.chunk_rounds)
    dt = time.time() - t0
    summary = {
        "arch": cfg.name, "rounds": out["rounds"],
        "chunk_rounds": args.chunk_rounds,
        "final_loss": out["history"][-1]["loss"] if out["history"] else None,
        "max_epsilon": out["max_epsilon"],
        "resource_spent": out["resource_spent"],
        "wall_s": round(dt, 1),
    }
    if is_async:
        summary.update({
            "buffer_size": spec.resolved_buffer_size(),
            "latency_profile": args.latency_profile,
            "staleness_alpha": args.staleness_alpha,
            "sim_seconds": out["sim_seconds"],
        })
    if args.population:
        summary.update({
            "population": args.population, "cohort_size": n_resident,
            # sampled != realized under --participation < 1: the cohort
            # counter ticks for every sampled client, the rho ledger only
            # for clients that actually ran (and spent privacy)
            "distinct_sampled":
                int((state.store.rounds_participated > 0).sum()),
            "distinct_participants": int((state.store.rho > 0).sum()),
        })
        if "resident_cache" in out:
            summary["resident_cache"] = out["resident_cache"]
    print(json.dumps(summary, indent=2))
    if args.save:
        extra = {"history": out["history"], **federation_meta(spec)}
        if is_async:
            save_async_state(args.save, state, extra=extra)
        elif args.population:
            save_population_state(args.save, state, extra=extra)
        else:
            save_state(args.save, state, extra=extra)
        print(f"saved federation state to {args.save}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
