"""Pluggable round-engine registry.

A *round engine* is a builder that turns a :class:`FederationSpec` into the
unified round function

    round_fn(params, opt_state, batch, key, sigmas)
        -> (new_params, new_opt_state, metrics)

with params/opt_state carrying a leading client axis C, batch leaves shaped
(C, tau, B, ...), and sigmas (C,). When the spec configures an aggregation
pipeline (``participation`` < 1 or ``compressor`` != "none"), every engine
instead builds the pipeline form

    round_fn(params, opt_state, batch, key, sigmas, mask, residual)
        -> (new_params, new_opt_state, new_residual, metrics)

where ``mask`` is the per-round 0/1 participation mask (sampled by
``run_round`` from the FLState RNG) and ``residual`` is the (C, D)
error-feedback state carried on :class:`repro.api.FLState`. Three engines
ship by default:

    "vmap"      GSPMD engine, clients vmapped (core/fl.py) — the default on
                one device and the lowering used for pod-scale GSPMD runs.
    "map"       same math with ``lax.map`` over clients (sequential; low
                peak memory for big-model CPU simulations).
    "shard_map" explicit collective schedule (core/fl_shard_map.py): one
                ``lax.pmean`` over the client mesh axis per round.
    "mesh_2d"   2D client x model plane (repro.mesh): shard_map's client
                blocking plus GSPMD model sharding within each client slab
                — the engine for replicas too big for one device.

``register_engine`` adds new execution strategies without touching the
drivers: everything upstream selects purely via ``FederationSpec.engine``.
The buffered-async engine ("async_buffered", :mod:`repro.asyncfl`) is
registered here too, but its builder returns a flush/dispatch *executor*
rather than a round_fn — ``round_fn_for``/``chunked_round_fn_for`` refuse
async specs and point at the ``repro.asyncfl`` drivers, and
``engine="auto"`` never resolves to it (async execution is always an
explicit choice).

Every engine's Eq.-7a clip+noise step is the leaf-wise
``kernels.ops.dp_clip_noise_tree`` (one fused pass per leaf, the noise
drawn inside it), whatever ``FederationSpec.kernel_backend`` names; that
backend, carried by ``spec.fl_config()``, runs the compressor's and the
resident cohort's kernels. It is part of ``spec.engine_key()``, so
switching backends recompiles rather than aliasing cached rounds.

Two jitted forms are cached: the single round (:func:`round_fn_for`, per
engine key) and the fused multi-round scan (:func:`chunked_round_fn_for`,
per engine key + participant count — the scan bakes the per-round mask
sampling in) that lowers a whole chunk of rounds into one XLA program. Both donate the
params/opt_state/residual operands — the input FLState's device buffers are
consumed and reused in place (§Perf opt: no double-buffered client
replicas); callers continue from the returned state.
"""
from __future__ import annotations

from typing import Any, Callable, Protocol

import jax
import numpy as np

from repro.api.spec import ENGINES, FederationSpec

RoundFn = Callable[..., tuple[Any, Any, dict]]


class RoundEngine(Protocol):
    """Builder protocol: spec -> round_fn (uncompiled; callers jit)."""

    def __call__(self, spec: FederationSpec) -> RoundFn: ...


_REGISTRY: dict[str, RoundEngine] = {}


def register_engine(name: str, builder: RoundEngine | None = None):
    """Register a round-engine builder under ``name``.

    Usable directly (``register_engine("x", build)``) or as a decorator
    (``@register_engine("x")``).
    """
    def _add(b: RoundEngine) -> RoundEngine:
        _REGISTRY[name] = b
        return b

    return _add if builder is None else _add(builder)


def available_engines() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_engine(spec: FederationSpec) -> str:
    """Map ``engine="auto"`` to a concrete engine for this process.

    The decision table lives in :mod:`repro.mesh.placement`: mesh_2d when
    the spec's ``replica_bytes`` footprint hint exceeds the per-device
    budget (a whole replica cannot fit, so the model axis must shard);
    shard_map when >1 device can each own a whole client block; otherwise
    the vmap GSPMD engine. Adversarial specs never place onto mesh_2d.
    """
    if spec.engine != "auto":
        return spec.engine
    from repro.mesh.placement import choose_engine
    return choose_engine(spec.n_clients, len(jax.devices()),
                         replica_bytes=spec.replica_bytes,
                         adversarial=spec.is_adversarial())


def get_engine(name_or_spec: str | FederationSpec) -> RoundEngine:
    """Look up an engine builder by name, or resolve it from a spec."""
    name = (resolve_engine(name_or_spec)
            if isinstance(name_or_spec, FederationSpec) else name_or_spec)
    if name == "auto":
        raise ValueError("pass a FederationSpec to resolve engine='auto'")
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown engine {name!r}; registered: "
                       f"{available_engines()}") from None


# ---------------------------------------------------------------------------
# built-in engines
# ---------------------------------------------------------------------------

def _n_client_shards(n_clients: int, n_devices: int) -> int:
    """Largest divisor of n_clients that fits in the local device count."""
    return max(d for d in range(1, min(n_clients, n_devices) + 1)
               if n_clients % d == 0)


@register_engine("vmap")
def build_vmap_engine(spec: FederationSpec) -> RoundFn:
    from repro.core.fl import make_round_step
    return make_round_step(spec.loss_fn, spec.optimizer,
                           spec.fl_config(vmap_clients=True),
                           topology=spec.topology,
                           pipeline=spec.aggregation_pipeline())


@register_engine("map")
def build_map_engine(spec: FederationSpec) -> RoundFn:
    from repro.core.fl import make_round_step
    return make_round_step(spec.loss_fn, spec.optimizer,
                           spec.fl_config(vmap_clients=False),
                           topology=spec.topology,
                           pipeline=spec.aggregation_pipeline())


@register_engine("async_buffered")
def build_async_engine(spec: FederationSpec):
    """Buffered-async engine (repro.asyncfl): returns the per-spec
    :class:`repro.asyncfl.engine.AsyncBufferedExecutor` — a flush/dispatch
    executor object, NOT a ``round_fn`` (async rounds have no single
    synchronous round function; ``round_fn_for`` refuses async specs and
    points at the ``repro.asyncfl`` drivers). Imported lazily: asyncfl
    builds on repro.api and a module-level import would cycle."""
    from repro.asyncfl.engine import AsyncBufferedExecutor
    return AsyncBufferedExecutor(spec)


@register_engine("shard_map")
def build_shard_map_engine(spec: FederationSpec) -> RoundFn:
    """Explicit-collective engine on a 1-D ("client",) mesh over the local
    devices; clients that outnumber devices are blocked per mesh slot."""
    from jax.sharding import Mesh

    from repro.core.fl_shard_map import make_shard_map_round
    n_shards = _n_client_shards(spec.n_clients, len(jax.devices()))
    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("client",))
    return make_shard_map_round(spec.loss_fn, spec.optimizer,
                                spec.fl_config(vmap_clients=True), mesh,
                                topology=spec.topology,
                                pipeline=spec.aggregation_pipeline())


@register_engine("mesh_2d")
def build_mesh_2d_engine(spec: FederationSpec) -> RoundFn:
    """2D client x model plane (repro.mesh): clients block over the manual
    "client" mesh axis, model tensors shard 1/dm over the GSPMD-controlled
    "model" axis. Mesh shape comes from the spec or the placement default
    (which reads the ``replica_bytes`` footprint hint); clients that do not
    divide the client axis are padded inside the engine."""
    from repro.launch.mesh import make_mesh_2d
    from repro.mesh.engine import make_mesh_2d_round
    from repro.mesh.placement import default_mesh_shape
    shape = spec.mesh_shape or default_mesh_shape(
        spec.n_clients, len(jax.devices()),
        replica_bytes=spec.replica_bytes)
    mesh = make_mesh_2d(shape)
    rules = dict(spec.sharding_rules) if spec.sharding_rules else None
    return make_mesh_2d_round(spec.loss_fn, spec.optimizer,
                              spec.fl_config(vmap_clients=True), mesh,
                              rules=rules, topology=spec.topology,
                              pipeline=spec.aggregation_pipeline())


# compiled-round caches: keyed on the engine-relevant slice of the spec, so
# budget edits (spec.replace(eps_th=...)) reuse the compiled function.
# Bounded LRU: engine keys hold loss/optimizer closures and XLA executables,
# so an unbounded map would leak across spec sweeps.
_ROUND_FN_CACHE: dict[tuple, RoundFn] = {}
_CHUNKED_FN_CACHE: dict[tuple, RoundFn] = {}
_ROUND_FN_CACHE_MAX = 32


def _cached(cache: dict, key, build) -> RoundFn:
    fn = cache.pop(key, None)
    if fn is None:
        fn = build()
        while len(cache) >= _ROUND_FN_CACHE_MAX:
            cache.pop(next(iter(cache)))
    cache[key] = fn                # (re)insert at MRU position
    return fn


def round_fn_for(spec: FederationSpec) -> RoundFn:
    """The jitted round function for ``spec`` (cached per engine key).

    Donation: the params / opt_state / error-feedback-residual operands are
    donated to XLA, so big-model client replicas update in place instead of
    double-buffering every round. Callers must treat the input FLState's
    device buffers as CONSUMED on a successful call — ``run_round`` returns
    the successor state; keep using that. (Host-side copies, e.g. a
    checkpoint written before the call, are unaffected.)
    """
    if spec.is_async():
        raise ValueError(
            "engine='async_buffered' has no synchronous round function: "
            "drive it with repro.asyncfl (init_async_state / "
            "run_async_cycle / train_async), not run_round/run_rounds")
    donate = (0, 1, 6) if spec.has_pipeline() else (0, 1)
    return _cached(
        _ROUND_FN_CACHE, spec.engine_key(),
        lambda: jax.jit(get_engine(resolve_engine(spec))(spec),
                        donate_argnums=donate))


def chunked_round_fn_for(spec: FederationSpec) -> RoundFn:
    """The jitted fused-multi-round scan for ``spec``: the engine's round
    body wrapped by :func:`repro.core.fl.make_chunked_round`, with the same
    donation contract as :func:`round_fn_for` (params / opt_state /
    residual update in place). One wrapper serves every chunk length — the
    scan reads R from the batches operand at trace time, so jit's own
    shape-keyed cache holds one executable per R. Operand/return shapes are
    documented on ``make_chunked_round``; ``repro.api.state.run_rounds`` is
    the driver that feeds it."""
    from repro.core.fl import make_chunked_round

    if spec.is_async():
        raise ValueError(
            "engine='async_buffered' has no fused sync scan: drive it with "
            "repro.asyncfl.train_async (its chunking is host-paced over "
            "the simulated event schedule)")
    pipeline = spec.has_pipeline()

    def build():
        raw = get_engine(resolve_engine(spec))(spec)
        chunk = make_chunked_round(
            raw, pipeline=pipeline, n_clients=spec.n_clients,
            n_participants=spec.participants_per_round())
        return jax.jit(chunk, donate_argnums=(0, 1, 5) if pipeline
                       else (0, 1))

    # unlike round_fn_for — where the mask is a runtime operand and
    # engine_key() is the whole story — the chunk samples masks inside the
    # scan, so the participant count is baked into the closure and must key
    # the cache, or a participation sweep would reuse the wrong protocol
    return _cached(_CHUNKED_FN_CACHE,
                   (spec.engine_key(), spec.participants_per_round()),
                   build)


_RESIDENT_FN_CACHE: dict[tuple, RoundFn] = {}


def resident_chunked_round_fn_for(spec: FederationSpec,
                                  data_resident: bool = False) -> RoundFn:
    """The jitted fused scan for the *resident-cohort* population path: the
    engine's pipeline round body wrapped by
    :func:`repro.core.fl.make_resident_chunked_round` — per-round cohort
    slot indices threaded into the scan, error-feedback residual gathered
    from / scattered into the device-resident (S, D) cohort cache via the
    ``cohort_gather_scatter`` kernel. Signature::

        fn(params, opt_state, batches, slots, key, sigmas, cache)
            -> (params, opt_state, key, cache, metrics, masks)

    Donation covers params / opt_state / cache (argnums 0, 1, 6): the
    resident cache updates in place across chunks, like the dense path's
    residual. ``data_resident=True`` selects the stationary-population
    form — ``batches`` becomes the warm-shard (S, tau, B, ...) cache
    pytree, NOT donated (it persists across chunks), and each round's
    batch is gathered from it inside the scan. Cached per (engine key,
    participant count, data_resident) like :func:`chunked_round_fn_for`;
    jit's shape cache handles S and R. Non-pipeline population specs have
    no device-resident sticky state — their resident driver reuses
    :func:`chunked_round_fn_for` directly, so this builder refuses them
    rather than compile a dead cache operand.
    """
    from repro.core.fl import make_resident_chunked_round

    if spec.is_async():
        raise ValueError(
            "engine='async_buffered' has no fused sync scan: drive it with "
            "repro.asyncfl.train_async")
    if not spec.has_pipeline():
        raise ValueError(
            "resident_chunked_round_fn_for is the pipeline (compressed /"
            " partial-participation) form; without a pipeline there is no "
            "device-resident sticky state — use chunked_round_fn_for")

    def build():
        raw = get_engine(resolve_engine(spec))(spec)
        chunk = make_resident_chunked_round(
            raw, n_clients=spec.n_clients,
            n_participants=spec.participants_per_round(),
            kernel_backend=spec.kernel_backend,
            data_resident=data_resident)
        return jax.jit(chunk, donate_argnums=(0, 1, 6))

    return _cached(_RESIDENT_FN_CACHE,
                   (spec.engine_key(), spec.participants_per_round(),
                    data_resident),
                   build)


assert set(ENGINES) - {"auto"} == set(_REGISTRY), "built-in engines drifted"
