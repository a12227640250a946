"""Driver-throughput benchmark: per-round vs fused multi-round training.

Measures end-to-end ``repro.api.train`` throughput (rounds/s and
local-steps/s, batch building + prefetch + ledger included) on a fixed
small CPU reference federation, across engine x chunk_rounds x compressor,
and emits ``BENCH_throughput.json`` so every future PR has a perf
trajectory to beat. ``chunk_rounds=1`` is the per-round driver (one XLA
dispatch and >=1 blocking host sync per round); ``chunk_rounds=R`` lowers R
rounds into one ``lax.scan`` dispatch with at most one blocking sync per
chunk (``host_syncs_per_round`` reports that driver-structural count: the
materialize/mask fetch for the per-round driver, 1/R for the fused one).

Reading the numbers: the PIPELINE configs (compressor / partial
participation — the paper's resource-constrained IoT setting) are where
the fusion is structural: the per-round driver must block on the realized
participation mask every round, the fused driver once per chunk, giving a
stable ~2-4x. The dense full-participation protocol has no forced
per-round sync left (this PR's lazy records + cached ledger constants
removed them), so jax async dispatch already pipelines it and its fused
gain is whatever python/dispatch overhead remains on the host — real but
machine-dependent. ``--check`` therefore gates only the sync-bound
pipeline configs (threshold 0.8 for CI-runner noise; healthy margin is
>= 2x) and reports the dense rows informationally.

A second scenario tracks **cohort scaling** (repro.population): rounds/s
and the device-resident block bytes of a K = 8 cohort as the virtual
population M grows 10^3 -> 10^6. Both must be flat in M — the population
drivers gather only the sampled cohort, so M buys scenario scale, not
device memory or dispatch cost. ``--check`` gates the byte-flatness
exactly and the rounds/s within a noise margin. Cohort rows now carry an
honest ``host_syncs_per_round``: the chunk-boundary path is NOT the dense
fused driver's 1/chunk — each chunk pays the stacked-mask fetch plus the
ClientStore residual gather and scatter-back materialize, i.e. 3/chunk
under a pipeline spec. A companion **resident-cohort** scenario times the
same M = 10^5 workload through ``train_population(...,
resident_cache=S)`` (PR 8): sticky state and stationary data shards live
on device, cohorts are drawn per round inside the scan, and the
steady-state chunk makes zero blocking host syncs. ``--check`` pins the
resident row's ``host_syncs_per_round == 0`` and its rounds/s against the
chunk-boundary baseline (noise margin; the committed full-grid JSON shows
it strictly ahead).

A **mesh-plane** section (needs >= 8 devices; rows are skipped with a
reason otherwise, so the committed full-grid JSON must come from an
``--xla_force_host_platform_device_count=8`` run) times the 2D client x
model engine (repro.mesh) against the 1D shard_map plane on the same
federation: the degenerate ``(8, 1)`` mesh — bitwise the shard_map
protocol, so its throughput gap is pure engine overhead (padding plumbing
+ partial-auto lowering) — and the true ``(4, 2)`` mesh, which halves the
client axis to buy a model axis. On this single-host CPU benchmark the
(4, 2) row pays real cost (half the client parallelism, no memory win to
show for it); the row exists to track that cost, not to win. A
**too-big-model** companion pins the placement story end to end: a
replica footprint hint that exceeds a (tiny, env-injected) per-device
budget must route ``engine="auto"`` onto mesh_2d with enough model shards
that the per-device slice fits, and the round must actually run.
``--check`` gates the degenerate row within a noise margin of shard_map
and the too-big row's ``per_device_bytes <= budget < replica_bytes``
invariant plus a finite loss.

A third scenario tracks **buffered-async federation** (repro.asyncfl) on
a heterogeneous straggler fleet: the simulated seconds to land a target
amount of zCDP (equivalently, R sync rounds' worth of client updates) for
the sync barrier driver vs the B-of-K buffered-async driver under the
same :class:`HeteroLatency` clock. The async side runs the real
``train_async`` driver (fused flush+dispatch programs, chunked schedule
projection), so the row also reports host flushes/s. ``--check`` gates
``async_sim_seconds < sync_sim_seconds`` strictly — on a fleet whose
slowest device is ~7x the fastest, losing that gap means the buffer
semantics regressed to a barrier.

    PYTHONPATH=src python benchmarks/throughput.py            # full grid
    PYTHONPATH=src python benchmarks/throughput.py --smoke --check
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import FederationSpec, init_state, train
from repro.api.state import round_rho_charges
from repro.asyncfl import (
    HeteroLatency,
    init_async_state,
    sync_round_duration,
    train_async,
)
from repro.models.linear import init_linear, logreg_loss
from repro.optim import sgd
from repro.population import (
    UniformCohort,
    cohort_batch,
    device_block_bytes,
    init_population_state,
    synthetic_population,
    train_population,
)

# fixed CPU reference federation: small enough that driver overhead (the
# thing this benchmark tracks) dominates — per-round host cost is fixed
# while device compute scales with tau*dim*batch, so keep all three small,
# but big enough to do real math
C, TAU, DIM, BATCH = 8, 2, 32, 8
SIGMA, LR, CLIP = 0.5, 0.3, 1.0


def reference_spec(engine: str, compressor: str, participation: float,
                   **kw) -> FederationSpec:
    extra = {}
    if compressor != "none":
        extra["compression_ratio"] = 0.25
    # kernel_backend pinned to the jnp oracle so the measurement is
    # identical on every platform ("auto" now resolves to ref off-TPU
    # anyway — this benchmark is where the ~100x interpret-vs-oracle gap
    # was measured and the auto ranking fixed)
    extra.update(kw)
    extra.setdefault("kernel_backend", "ref")
    return FederationSpec(
        n_clients=C, tau=TAU, loss_fn=logreg_loss, optimizer=sgd(LR),
        engine=engine, dp=True, clip_norm=CLIP,
        participation=participation, compressor=compressor,
        sigmas=(SIGMA,) * C, batch_sizes=(BATCH,) * C, **extra)


def make_sampler(dim: int = DIM, batch: int = BATCH):
    def sampler(m, tau, rng):
        return {"x": rng.normal(size=(tau, batch, dim)).astype(np.float32),
                "y": rng.integers(0, 2, size=(tau, batch)).astype(np.int32)}
    return sampler


def time_driver(spec: FederationSpec, rounds: int, chunk_rounds: int,
                repeats: int) -> dict:
    """Best-of-``repeats`` wall time of ``train(..., chunk_rounds=...)``,
    after one untimed warm-up run that pays all XLA compiles (min filters
    scheduler noise; both drivers get the same treatment)."""
    sampler = make_sampler()

    def one_run(n_rounds: int) -> float:
        state = init_state(spec, init_linear(DIM))
        t0 = time.perf_counter()
        state, out = train(spec, state, sampler, max_rounds=n_rounds,
                           chunk_rounds=chunk_rounds)
        jax.block_until_ready(state.params)
        assert out["rounds"] == n_rounds
        return time.perf_counter() - t0

    one_run(min(rounds, max(1, chunk_rounds)))          # compile warm-up
    wall = min(one_run(rounds) for _ in range(repeats))
    # blocking syncs per round, from the driver structure: the per-round
    # driver materializes each record (plus the mask fetch under a
    # pipeline spec); the fused driver blocks once per chunk
    syncs = ((1.0 + (1.0 if spec.has_pipeline() else 0.0))
             if chunk_rounds <= 1 else 1.0 / chunk_rounds)
    return {
        "engine": spec.engine, "compressor": spec.compressor,
        "participation": spec.participation_fraction(),
        "chunk_rounds": chunk_rounds, "rounds": rounds,
        "wall_s": round(wall, 4),
        "rounds_per_s": round(rounds / wall, 2),
        "local_steps_per_s": round(rounds * TAU / wall, 2),
        "host_syncs_per_round": syncs,
    }


def time_cohort_driver(m: int, rounds: int, chunk_rounds: int,
                       repeats: int, resident: int = 0) -> dict:
    """Cohort-scaling row: train a K = C cohort drawn from M virtual
    clients (fused chunks, topk pipeline so the ClientStore residual path
    is on the clock) and record rounds/s plus the device-resident block
    bytes — both must be independent of M.

    ``resident=S`` routes the same workload through the PR-8
    device-resident driver (``resident_cache=S``, stationary population so
    the data shards cache on device too): per-round cohorts inside the
    fused scan, zero blocking host syncs per steady-state chunk.
    """
    spec, pop = _cohort_workload(m, resident)
    _cohort_run(spec, pop, max(1, chunk_rounds), chunk_rounds,
                resident)                       # compile warm-up
    wall = min(_cohort_run(spec, pop, rounds, chunk_rounds, resident)
               for _ in range(repeats))
    return _cohort_row(spec, pop, m, rounds, chunk_rounds, resident, wall)


def _cohort_workload(m: int, resident: int):
    spec = reference_spec("vmap", "topk", 1.0).replace(population=m,
                                                       cohort_size=C)
    pop = synthetic_population(m, dim=DIM, batch_size=BATCH, seed=0,
                               stationary=bool(resident))
    return spec, pop


def _cohort_run(spec, pop, n_rounds: int, chunk_rounds: int,
                resident: int) -> float:
    ps = init_population_state(spec, init_linear(DIM))
    t0 = time.perf_counter()
    ps, out = train_population(spec, ps, pop, max_rounds=n_rounds,
                               chunk_rounds=chunk_rounds,
                               resident_cache=resident)
    jax.block_until_ready(ps.fl.params)
    assert out["rounds"] == n_rounds
    return time.perf_counter() - t0


def _cohort_row(spec, pop, m: int, rounds: int, chunk_rounds: int,
                resident: int, wall: float) -> dict:
    ps = init_population_state(spec, init_linear(DIM))
    batch = cohort_batch(spec, pop, UniformCohort(spec.seed)(0, m, C),
                         np.random.default_rng(0))
    # honest driver-structural sync count. The chunk-boundary cohort path
    # is NOT the dense fused driver's 1/chunk: each chunk blocks on the
    # stacked participation-mask fetch (pipeline spec) AND pays the
    # ClientStore hop — residual gather when building the device block,
    # residual materialize at scatter-back — so 3 per chunk. The resident
    # driver keeps residuals/rho/data on device and, under full
    # within-cohort participation, the mask is the deterministic all-ones
    # constant (never fetched): zero forced syncs per steady-state chunk.
    # Partial participation would reintroduce the 1/chunk mask fetch.
    if resident:
        syncs = (0.0 if spec.participation_fraction() >= 1.0
                 else 1.0 / chunk_rounds)
    else:
        syncs = ((1.0 if spec.has_pipeline() else 0.0) + 2.0) / chunk_rounds
    row = {
        "mode": "resident" if resident else "chunk_boundary",
        "population": m, "cohort_size": C, "chunk_rounds": chunk_rounds,
        "rounds": rounds, "wall_s": round(wall, 4),
        "rounds_per_s": round(rounds / wall, 2),
        "host_syncs_per_round": round(syncs, 4),
        "device_block_bytes": device_block_bytes(ps, batch),
    }
    if resident:
        row["resident_cache"] = resident
    return row


def run_cohort_scaling(smoke: bool) -> list[dict]:
    if smoke:
        ms, rounds, chunk, repeats = [1_000, 100_000], 16, 8, 2
    else:
        ms, rounds, chunk, repeats = [1_000, 100_000, 1_000_000], 32, 8, 3
    rows = []
    for m in ms:
        r = time_cohort_driver(m, rounds, chunk, repeats)
        rows.append(r)
        print(f"population M={m:<9,} K={C} chunk={chunk:<3} "
              f"{r['rounds_per_s']:>8.1f} rounds/s "
              f"({r['host_syncs_per_round']:.3f} syncs/round, "
              f"{r['device_block_bytes']:,} device bytes)")
    return rows


def run_resident_cohort(smoke: bool) -> dict:
    """Resident-vs-chunk-boundary head-to-head at M = 10^5, K = 8.

    Both drivers are timed with INTERLEAVED repeats (best-of each) on the
    same round count (longer than the scaling rows — the ratio is the
    deliverable, so per-run fixed costs must not dominate). The baseline
    fixes ONE cohort per chunk and pays the 3 ClientStore syncs at every
    boundary; the resident row runs ``resident_cache=S`` with a
    stationary population so sticky state AND data rows are
    device-resident, cohorts are drawn per round INSIDE the fused scan,
    and the steady-state chunk makes no blocking host sync at all.

    chunk_rounds is deliberately asymmetric: for the resident driver it is
    a pure execution detail — the realized cohort schedule is per-round at
    ANY chunk_rounds (finer than the baseline offers at any setting) — so
    it runs at its natural larger chunk, which the zero-sync property is
    exactly what makes safe. The baseline stays at the scaling rows'
    chunk: raising it would coarsen its cohort schedule further, trading
    fidelity for speed rather than comparing drivers.
    """
    m = 100_000
    rounds, repeats = (32, 3) if smoke else (64, 5)
    chunk_base, chunk_res = 8, 32
    cache = chunk_res * C               # S = 256: one full chunk of warm slots
    spec_b, pop_b = _cohort_workload(m, 0)
    spec_r, pop_r = _cohort_workload(m, cache)
    _cohort_run(spec_b, pop_b, chunk_base, chunk_base, 0)       # compile
    _cohort_run(spec_r, pop_r, chunk_res, chunk_res, cache)
    # INTERLEAVED repeats, best-of each: machine-state noise (scheduler,
    # allocator phase) lands on both drivers alike instead of biasing
    # whichever ran second — the ratio is the deliverable here
    walls_b, walls_r = [], []
    for _ in range(repeats):
        walls_b.append(_cohort_run(spec_b, pop_b, rounds, chunk_base, 0))
        walls_r.append(_cohort_run(spec_r, pop_r, rounds, chunk_res, cache))
    base = _cohort_row(spec_b, pop_b, m, rounds, chunk_base, 0,
                       min(walls_b))
    res = _cohort_row(spec_r, pop_r, m, rounds, chunk_res, cache,
                      min(walls_r))
    speedup = res["rounds_per_s"] / base["rounds_per_s"]
    print(f"resident   M={m:<9,} K={C} S={cache:<4} "
          f"{res['rounds_per_s']:>8.1f} rounds/s "
          f"({res['host_syncs_per_round']:.3f} syncs/round, "
          f"{speedup:.2f}x chunk-boundary)")
    return {"baseline": base, "resident": res,
            "speedup_resident_vs_chunk": round(speedup, 2)}


def run_mesh_plane(smoke: bool) -> dict:
    """2D mesh engine vs the 1D shard_map plane, plus the too-big-model
    placement gate. See the module docstring for what each row means."""
    n_dev = jax.device_count()
    if n_dev < 8:
        return {"skipped": True,
                "reason": f"needs 8 devices for the (4,2) mesh, have {n_dev}"
                          " — run under "
                          "XLA_FLAGS=--xla_force_host_platform_device_count=8"}
    rounds, repeats = (16, 2) if smoke else (48, 3)
    chunk = 8
    rows = []
    for engine, shape in [("shard_map", None), ("mesh_2d", (8, 1)),
                          ("mesh_2d", (4, 2))]:
        kw = {"mesh_shape": shape} if shape else {}
        spec = reference_spec(engine, "none", 1.0, **kw)
        r = time_driver(spec, rounds, chunk, repeats)
        r["mesh_shape"] = list(shape) if shape else None
        rows.append(r)
        label = f"{engine}{list(shape) if shape else ''}"
        print(f"mesh {label:16s} chunk={chunk:<3} "
              f"{r['rounds_per_s']:>8.1f} rounds/s "
              f"({r['local_steps_per_s']:.0f} steps/s)")
    return {"rows": rows, "too_big_model": _run_mesh_too_big()}


def _run_mesh_too_big() -> dict:
    """Placement gate: a replica-footprint hint over the per-device budget
    must steer ``engine="auto"`` onto mesh_2d with enough model shards to
    fit, and the resulting program must train. The budget is injected via
    ``REPRO_DEVICE_MEM_BYTES`` (restored afterwards) and sized so a
    4-shard slice fits but the whole replica does not."""
    import os

    from repro.api import resolve_engine
    from repro.mesh.placement import (
        ENV_DEVICE_MEM,
        default_mesh_shape,
        device_memory_budget,
    )

    replica = 100 * DIM * 4                       # 12.8 KB synthetic hint
    budget = 4 * 1024                             # fits at dm=4, not at dm=1
    prev = os.environ.get(ENV_DEVICE_MEM)
    os.environ[ENV_DEVICE_MEM] = str(budget)
    try:
        spec = reference_spec("auto", "none", 1.0, replica_bytes=replica)
        engine = resolve_engine(spec)
        shape = default_mesh_shape(C, jax.device_count(),
                                   replica_bytes=replica)
        per_device = -(-replica // shape[1])
        sampler = make_sampler()
        state = init_state(spec, init_linear(DIM))
        state, out = train(spec, state, sampler, max_rounds=2)
        loss = float(out["history"][-1]["loss"])
        row = {
            "replica_bytes": replica,
            "budget_bytes": device_memory_budget(),
            "resolved_engine": engine,
            "mesh_shape": list(shape),
            "per_device_bytes": per_device,
            "final_loss": round(loss, 6),
        }
        print(f"mesh too-big     replica={replica} budget={budget} -> "
              f"{engine} {shape} ({per_device} B/device, "
              f"loss {loss:.4f})")
        return row
    finally:
        if prev is None:
            del os.environ[ENV_DEVICE_MEM]
        else:
            os.environ[ENV_DEVICE_MEM] = prev


def run_async_hetero(smoke: bool) -> dict:
    """Simulated-seconds-to-target-rho on a straggler fleet.

    The target is the total landed zCDP of ``rounds_sync`` full sync
    rounds (every client charged the Lemma-2 round rho each round). Sync
    reaches it in ``sum(max-over-fleet latency)`` simulated seconds; the
    async driver lands the same total after ``rounds_sync * K / B``
    flushes (dense spec: every arrival participates and carries the same
    charge), and its clock only ever waits for the B-th earliest arrival.
    """
    rounds_sync, buffer_size = (6, 2) if smoke else (12, 2)
    flushes = rounds_sync * C // buffer_size
    spec = reference_spec("async_buffered", "none", 1.0,
                          buffer_size=buffer_size, staleness_alpha=0.5,
                          eps_th=1e9, c_th=1e9)
    lat = HeteroLatency(0, fleet=C, slow_factor=6.0)
    target_rho = rounds_sync * float(round_rho_charges(spec).sum())
    sync_sim = sum(sync_round_duration(lat, C, r)
                   for r in range(rounds_sync))
    sampler = make_sampler()
    rng = np.random.default_rng(0)
    st = init_async_state(spec, init_linear(DIM), sampler, rng=rng,
                          latency_model=lat)
    t0 = time.perf_counter()
    st, out = train_async(spec, st, sampler, max_rounds=flushes, rng=rng,
                          chunk_rounds=8, latency_model=lat)
    jax.block_until_ready(st.global_params)
    wall = time.perf_counter() - t0
    assert out["rounds"] == flushes
    landed = float(np.sum(st.fl.rho))
    assert landed >= target_rho * (1 - 1e-9), (landed, target_rho)
    row = {
        "fleet": C, "buffer_size": buffer_size,
        "rounds_sync": rounds_sync, "flushes": flushes,
        "target_rho_landed": round(target_rho, 6),
        "sync_sim_seconds": round(sync_sim, 4),
        "async_sim_seconds": round(out["sim_seconds"], 4),
        "sim_speedup": round(sync_sim / out["sim_seconds"], 2),
        "wall_s": round(wall, 4),
        "flushes_per_s": round(flushes / wall, 2),
    }
    print(f"async hetero  K={C} B={buffer_size} target_rho="
          f"{row['target_rho_landed']:.3f}: sync {row['sync_sim_seconds']}s "
          f"vs async {row['async_sim_seconds']}s simulated "
          f"({row['sim_speedup']}x, {row['flushes_per_s']:.1f} flushes/s)")
    return row


def run_grid(smoke: bool) -> dict:
    if smoke:
        grid = [("vmap", "none", 1.0), ("vmap", "topk", 0.5)]
        chunks, rounds, repeats = (1, 8), 24, 3
    else:
        grid = [("vmap", "none", 1.0), ("vmap", "topk", 0.5),
                ("vmap", "qsgd", 1.0), ("map", "none", 1.0),
                ("shard_map", "none", 1.0), ("shard_map", "topk", 0.5)]
        chunks, rounds, repeats = (1, 2, 8), 64, 5
    results = []
    for engine, compressor, participation in grid:
        spec = reference_spec(engine, compressor, participation)
        for chunk in chunks:
            r = time_driver(spec, rounds, chunk, repeats)
            results.append(r)
            print(f"{engine:10s} {compressor:5s} q={participation:<4} "
                  f"chunk={chunk:<3} {r['rounds_per_s']:>8.1f} rounds/s "
                  f"({r['local_steps_per_s']:.0f} steps/s, "
                  f"{r['host_syncs_per_round']:.3f} syncs/round)")
    speedups = {}
    for engine, compressor, participation in grid:
        sel = {r["chunk_rounds"]: r["rounds_per_s"] for r in results
               if (r["engine"], r["compressor"], r["participation"])
               == (engine, compressor, float(participation))}
        base = sel[1]
        top = max(k for k in sel if k > 1)
        speedups[f"{engine}/{compressor}/q{participation}"] = round(
            sel[top] / base, 2)
    cohort_rows = run_cohort_scaling(smoke)
    return {
        "bench": "throughput",
        "config": {"n_clients": C, "tau": TAU, "dim": DIM, "batch": BATCH,
                   "sigma": SIGMA, "rounds": rounds, "smoke": smoke},
        "jax": jax.__version__,
        "device": str(jax.devices()[0]),
        "results": results,
        "speedup_fused_vs_per_round": speedups,
        "cohort_scaling": cohort_rows,
        "resident_cohort": run_resident_cohort(smoke),
        "async_hetero": run_async_hetero(smoke),
        "mesh_plane": run_mesh_plane(smoke),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid for CI (vmap only, 24 rounds)")
    ap.add_argument("--check", action="store_true",
                    help="fail if any fused config regresses below the "
                         "per-round driver (with a noise margin: speedup "
                         "< 0.8 fails — a real regression lands far below, "
                         "the healthy margin is >= 2x)")
    ap.add_argument("--out", default="BENCH_throughput.json")
    args = ap.parse_args(argv)

    report = run_grid(args.smoke)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.check:
        # gate only the pipeline configs, where the fused speedup is
        # structural (per-round mask sync vs 1/chunk) rather than
        # machine-dependent; 0.8 not 1.0 because the smoke walls are
        # sub-second and a scheduler stall on a shared CI runner can shave
        # tens of percent — a genuine chunking regression collapses the
        # ~3x margin entirely
        slow = {k: v for k, v in
                report["speedup_fused_vs_per_round"].items()
                if "/none/q1.0" not in k and v < 0.8}
        if slow:
            print(f"REGRESSION: fused driver slower than per-round: {slow}")
            return 1
        # cohort scaling: device bytes must be EXACTLY flat in M (the
        # K-block is the same program regardless of population), and
        # rounds/s flat within noise (0.5: the biggest M must not halve
        # throughput — a leak of M into the hot path collapses this)
        rows = report["cohort_scaling"]
        bytes_set = {r["device_block_bytes"] for r in rows}
        if len(bytes_set) != 1:
            print(f"REGRESSION: device block bytes vary with M: "
                  f"{[(r['population'], r['device_block_bytes']) for r in rows]}")
            return 1
        base_rps = rows[0]["rounds_per_s"]
        slow_pop = [r for r in rows if r["rounds_per_s"] < 0.5 * base_rps]
        if slow_pop:
            print(f"REGRESSION: cohort rounds/s degrades with M: {slow_pop}")
            return 1
        # resident cohort: the sync count is exact (0 is the whole point of
        # the device-resident driver — any nonzero means a forced fetch
        # crept back into the steady-state chunk). Throughput: the full
        # grid demands resident >= chunk-boundary outright (interleaved
        # best-of-5 timing makes that stable locally); the CI smoke run on
        # shared runners keeps a noise margin like the fused gate above
        rc = report["resident_cohort"]
        if rc["resident"]["host_syncs_per_round"] != 0:
            print(f"REGRESSION: resident driver reports host syncs: "
                  f"{rc['resident']}")
            return 1
        rc_margin = 0.85 if report["config"]["smoke"] else 1.0
        if (rc["resident"]["rounds_per_s"]
                < rc_margin * rc["baseline"]["rounds_per_s"]):
            print(f"REGRESSION: resident driver slower than the "
                  f"chunk-boundary path: {rc}")
            return 1
        # mesh plane (only when the device count admitted it): the
        # degenerate (8,1) mesh runs the shard_map protocol through the
        # mesh engine — large noise margin (0.5) because the padding
        # plumbing + partial-auto lowering cost is real and the walls are
        # sub-second, but a collapsed engine lands far below. The too-big
        # row's fit invariant is exact: per-device slice within the budget
        # the full replica exceeds, and the placed program trained.
        mp = report["mesh_plane"]
        if not mp.get("skipped"):
            by = {(r["engine"], tuple(r["mesh_shape"] or ())):
                  r["rounds_per_s"] for r in mp["rows"]}
            degen = by[("mesh_2d", (8, 1))]
            if degen < 0.5 * by[("shard_map", ())]:
                print(f"REGRESSION: degenerate mesh far below shard_map: "
                      f"{mp['rows']}")
                return 1
            tb = mp["too_big_model"]
            fit_ok = (tb["per_device_bytes"] <= tb["budget_bytes"]
                      < tb["replica_bytes"])
            if (tb["resolved_engine"] != "mesh_2d" or not fit_ok
                    or not np.isfinite(tb["final_loss"])):
                print(f"REGRESSION: too-big-model placement gate: {tb}")
                return 1
        # async vs sync simulated time: strict — the event schedule is
        # deterministic (no wall-clock noise), and on a ~7x-spread fleet
        # the buffered driver must beat the barrier outright
        ah = report["async_hetero"]
        if ah["async_sim_seconds"] >= ah["sync_sim_seconds"]:
            print(f"REGRESSION: buffered-async no faster than the sync "
                  f"barrier in simulated time: {ah}")
            return 1
        print("throughput gate passed: fused driver within margin "
              f"(speedups: {report['speedup_fused_vs_per_round']}); "
              f"cohort scaling flat over M "
              f"({[r['population'] for r in rows]}); "
              f"resident cohort 0 syncs/round at "
              f"{rc['speedup_resident_vs_chunk']}x chunk-boundary; "
              f"async {ah['sim_speedup']}x sync in simulated seconds; "
              + ("mesh plane skipped (device count)" if mp.get("skipped")
                 else "mesh plane placed + within margin"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
