"""One run of one cell: set up, check, measure, report.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Names the device. No TPU, or fewer chips than the cell asks for: exit
   1 and no result line.
2. Builds the cell's federation through the program's public API
   (``FederationSpec`` -> ``init_state``), as ``launch/train`` does, with
   the weights made on the device from the seed (``weights.py``) and the
   token batches drawn from it (``traffic.py``).
3. Set-up drives that same state through its first three rounds with
   ``repro.api.train`` (the first compiles or loads the round program
   from the persistent cache) and keeps what the check compares.
4. Measures: calls ``train`` one round at a time, with the host privacy
   ledger, until ``--seconds`` have passed; the rate is the client tokens
   of all completed rounds over all the time of those calls. With
   ``--trace 1`` the profiler records the first ``trace_rounds`` rounds
   instead, and the per-layer metrics are read from that trace.
5. Frees the program's state and runs the plain reference over the same
   three rounds (``reference/``): ``correct`` is every compared number
   within its limit (``compare.py``).
6. Prints the numbers compared, each beside its limit, as the last lines
   of standard error, and the result as the last line of standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import catalog
import compare
import flops
import xtrace
from traffic import Traffic, jax_seed
from weights import maker

CHECK_ROUNDS = 3


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def cache_dir() -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    points, else a fixed directory inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        catalog.ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    return path


class Compiles:
    """Counts the programs XLA built (compiled, or loaded from the
    persistent cache: JAX reports a backend compile for both) and the
    cache hits among them, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = self.cache_hits = 0
        self.compile_s = 0.0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.programs += 1
                self.compile_s += secs

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)


def arch_config(config: dict, workload: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs import get_arch
    from repro.configs.base import patterned_segments

    prog = dict(config["program"])
    base = get_arch(prog.pop("arch"))
    n_layers = prog.pop("n_layers")
    cfg = replace(base, name=config["name"], n_layers=n_layers,
                  segments=patterned_segments(n_layers,
                                              base.segments[0].pattern),
                  **prog)
    if "loss_chunk" in workload:
        cfg = replace(cfg, loss_chunk=int(workload["loss_chunk"]))
    return cfg


def build(cell, seed: int, base=None):
    """(model, spec, state, traffic, make_weights, key) for one seed;
    ``base`` (model, spec, make_weights) of an earlier seed is reused, so
    its compiled programs are too."""
    from repro.api import FederationSpec, init_state
    from repro.models.transformer import Transformer
    from repro.optim import sgd

    mix, config = cell.traffic, cell.config
    traffic = Traffic(mix, config["vocab_size"], seed)
    if base is None:
        model = Transformer(arch_config(config, cell.workload))
        c = traffic.clients
        spec = FederationSpec(
            n_clients=c, tau=traffic.tau, loss_fn=model.loss_fn,
            optimizer=sgd(float(mix["lr"])), engine=mix["engine"], dp=True,
            clip_norm=float(mix["clip"]), num_microbatches=1,
            kernel_backend="auto", sigmas=(float(mix["sigma"]),) * c,
            batch_sizes=(traffic.batch,) * c, delta=float(mix["delta"]),
            eps_th=float(mix["epsilon_budget"]))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        base = (model, spec, maker(shapes, config["init"]))
    model, spec, make_weights = base
    # weights from a key of their own; the state key seeds the DP noise
    key = jax.random.fold_in(jax.random.PRNGKey(jax_seed(seed)), 1)
    state = init_state(spec, make_weights(key),
                       key=jax.random.PRNGKey(jax_seed(seed)))
    return model, spec, state, traffic, make_weights, key


@jax.jit
def replica0(params):
    return jax.tree.map(lambda p: p[0], params)


@jax.jit
def delta_norms(params, params0):
    """Per-leaf 2-norms of (replica 0 of ``params``) - ``params0``."""
    return jnp.stack([
        jnp.linalg.norm((p[0].astype(jnp.float32)
                         - q.astype(jnp.float32)).ravel())
        for p, q in zip(jax.tree.leaves(params), jax.tree.leaves(params0))])


def checked_rounds(spec, state, traffic, make_weights, key):
    """Rounds 1..3 through ``train``: what the check compares, and the
    state, handed on to the window. ``record["copy_s"]`` is the time spent
    copying the parameters to the host for the check."""
    from repro.api import train

    history: list[dict] = []
    record = {"params_norms": [], "params": [], "copy_s": 0.0}
    for r in range(1, CHECK_ROUNDS + 1):
        state, out = train(spec, state, traffic.sampler, max_rounds=r,
                           history=history)
        if r in (1, CHECK_ROUNDS):
            record["params_norms"].append(
                np.asarray(delta_norms(state.params, make_weights(key))))
            jax.block_until_ready(state.params)
            t0 = time.monotonic()
            record["params"].append(jax.device_get(replica0(state.params)))
            record["copy_s"] += time.monotonic() - t0
    jax.block_until_ready(state.params)
    record["loss"] = [h["loss"] for h in history]
    record["gnorm"] = [h["grad_norm_preclip"] for h in history]
    record["epsilon"] = float(out["max_epsilon"])
    return state, record


def window(spec, state, traffic, seconds: float, trace_rounds: int | None,
           trace_dir: Path | None):
    """Train one round per call until ``seconds`` have passed (or, traced,
    until ``trace_rounds`` rounds are done). Returns the state, rounds
    completed, their seconds, and the window's losses."""
    from repro.api import train

    history: list[dict] = []
    rounds = 0
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.round"):
            state, _ = train(spec, state, traffic.sampler,
                             max_rounds=state.rounds_done + 1,
                             history=history)
        rounds += 1
        if trace_rounds is not None and rounds >= trace_rounds:
            break
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(state.params)
    elapsed = time.perf_counter() - t0
    span.__exit__(None, None, None)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return state, rounds, elapsed, [h["loss"] for h in history]


def peak_bytes(device) -> int:
    """The device's peak: the allocator's buffers at their peak plus the
    peak of what the runtime reserved for compiled programs' scratch,
    where it reports that (on a TPU the round's temporaries live there)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def device_info(devices, used: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak_bytes(d) for d in devices[:used])}


class References:
    """The plain reference of one cell, and the variants the calibration
    puts in the program's place, each built (and compiled) once."""

    def __init__(self, cell):
        self.cell = cell
        self._built: dict = {}

    def record(self, seed: int, make_weights, key, variant="reference"):
        """The reference's three rounds on the same weights and batches."""
        from reference.dppasgd import Reference

        cell = self.cell
        if variant not in self._built:
            family = catalog.reference_module(cell.config)
            self._built[variant] = Reference(family.loss, cell.config,
                                             cell.traffic, variant)
        params0 = make_weights(key)
        traffic = Traffic(cell.traffic, cell.config["vocab_size"], seed)
        out = self._built[variant].run(params0, traffic, jax_seed(seed),
                                       CHECK_ROUNDS)
        stacked = lambda tree: jax.tree.map(lambda x: x[None], tree)
        out["params_norms"] = [np.asarray(delta_norms(stacked(p), params0))
                               for p in out["params"]]
        return out


def run(argv=None, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = catalog.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = catalog.cell(args.workload)

    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, t_start)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float) -> dict:
    """Steps 2-5 of a run on ``devices``; returns the result line."""
    compiles = Compiles()
    _, spec, state, traffic, make_weights, key = build(cell, seed)
    state, program = checked_rounds(spec, state, traffic, make_weights, key)
    # the check's copies to the host are the check's time, not set-up's
    setup_s = time.monotonic() - t_start - program["copy_s"]
    log(phase="setup", workload=cell.name, seed=seed, setup_s=setup_s,
        check_copy_s=program["copy_s"], programs=compiles.programs,
        cache_hits=compiles.cache_hits, compile_s=compiles.compile_s,
        checked_losses=program["loss"])

    trace_dir = None
    if trace:
        trace_dir = catalog.ROOT / ".bench_out" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    before = compiles.programs
    state, rounds, elapsed, losses = window(
        spec, state, traffic, seconds,
        int(cell.traffic["trace_rounds"]) if trace else None, trace_dir)
    in_window = compiles.programs - before
    tokens = rounds * traffic.tokens_per_round
    device = device_info(devices, cell.chips)
    log(phase="window", rounds=rounds, seconds=elapsed, tokens=tokens,
        compiles_in_window=in_window, memory_peak_bytes=device[
            "memory_peak_bytes"], memory_stats=devices[0].memory_stats())

    del state
    gc.collect()
    reference = References(cell).record(seed, make_weights, key)
    numbers = compare.numbers(program, reference)
    checks = compare.against(numbers, cell.workload["limits"])
    finite = bool(np.all(np.isfinite(losses)))
    correct = finite and in_window == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": rounds,
              "failed": int(np.sum(~np.isfinite(losses))),
              "metrics": {}, "device": device}
    if trace:
        ctx = flops.context(cell, traffic, rounds, devices)
        breakdown = read_trace(trace_dir, ctx)
        result["device"]["busy_s"] = ctx["busy_s"]
        result["device"]["window_s"] = ctx["window_s"]
        for m in cell.per_layer:
            value = catalog.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = breakdown
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    result["checks"] = checks
    return result


def read_trace(trace_dir: Path, ctx: dict) -> dict:
    """Reduce the recorded trace into ``ctx`` and return the breakdown."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    trace = xtrace.load(str(path))
    devs = sorted(trace["devices"])[: ctx["chips"]]
    lo, hi = trace["window_ns"]
    ctx["trace"] = trace
    ctx["devices"] = devs
    ctx["window_s"] = (hi - lo) / 1e9
    ctx["busy_s"] = float(np.mean([xtrace.busy_ns(trace, d)
                                   for d in devs])) / 1e9
    return {"device_ops": xtrace.top_ops(trace),
            "idle_gaps": xtrace.idle_gaps(trace, devs[0])}
