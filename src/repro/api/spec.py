"""Declarative configuration of one DP-PASGD federation.

:class:`FederationSpec` is the single configuration surface of ``repro.api``:
it folds together the round structure (``FLConfig``), the privacy knobs
(eps_th / delta / per-client sigmas with auto Eq.-23 design), the resource
budgets (Eq. 8), the communication topology, and the execution engine. A
spec is frozen and hashable, so compiled round functions are cached per
spec and experiment sweeps are plain ``spec.replace(...)`` calls.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from repro.core.aggregation import (
    COMPRESSORS,
    compression_wire_ratio,
    validate_compression,
)
from repro.core.fl import TOPOLOGIES, Budgets, FLConfig, design_sigmas
from repro.kernels.dispatch import KERNEL_BACKENDS
from repro.optim.optimizers import Optimizer

ENGINES = ("vmap", "map", "shard_map", "mesh_2d", "async_buffered", "auto")


@dataclass(frozen=True)
class FederationSpec:
    """Everything needed to run DP-PASGD, in one frozen declarative object.

    ``loss_fn`` and ``optimizer`` are the only non-serializable fields — the
    model plugs in through them; every other field is a plain scalar/tuple.
    """
    # -- federation / round structure --------------------------------------
    n_clients: int
    tau: int                        # local steps per round (aggregation period)
    loss_fn: Callable[[Any, Any], Any]
    optimizer: Optimizer
    topology: str = "full_average"  # "full_average" | "local_only"
    engine: str = "auto"            # "vmap" | "map" | "shard_map" |
    #   "async_buffered" | "auto". "async_buffered" is the FedBuff-style
    #   buffered-async engine (repro.asyncfl): the server aggregates the
    #   first ``buffer_size`` arrivals per flush with staleness-weighted
    #   updates and redispatches immediately — driven by
    #   ``repro.asyncfl.train_async``, NOT by run_round/train (which raise
    #   for it). "auto" never resolves to it: async is always explicit.
    #   "mesh_2d" is the 2D client x model plane (repro.mesh): clients
    #   block over the mesh "client" axis exactly as under "shard_map",
    #   while model tensors shard 1/dm over the GSPMD-controlled "model"
    #   axis — the engine for replicas too big for one device. "auto"
    #   resolves to it when ``replica_bytes`` exceeds the per-device budget
    #   (repro.mesh.placement).
    kernel_backend: str = "auto"    # kernel backend
    #   ("pallas" | "interpret" | "ref" | "auto"): the compressor's and the
    #   resident cohort's kernels run on it ("auto" is the Mosaic kernel on
    #   a TPU, or an error there, and the jnp oracle elsewhere); every value
    #   gives the Eq.-7a step the leaf-wise single-rounding clip+noise
    #   (kernels.ops.dp_clip_noise_tree), which runs no kernel

    # -- aggregation pipeline (Eq. 7b boundary; core/aggregation.py) -------
    participation: float = 1.0      # fraction q in (0,1], or an int count of
    #   clients sampled per round (without replacement, from the FLState
    #   RNG); non-participants neither upload nor spend privacy that round.
    #   NOTE the type dispatch: participation=1.0 means ALL clients,
    #   participation=1 (int) means ONE client per round.
    compressor: str = "none"        # "none" | "topk" | "randk" | "qsgd"
    compression_ratio: float = 0.1  # fraction of coords kept (topk/randk)
    compression_bits: int = 8       # bits per coordinate (qsgd)
    amplify_participation: bool = False  # True: account q-amplified
    #   per-step rho (privacy.subsampled_rho — the marginal
    #   subsampled-Gaussian bound, valid in expectation over the
    #   participation draw, NOT for a realized-heavy client; opt in when
    #   the subsampling-blind adversary model fits). Default False charges
    #   realized participants the full Lemma-2 rho: the worst-case
    #   conditional ledger, sound for the executed mechanism. In population
    #   mode the amplification composes with the cohort ratio K/M
    #   (privacy.composed_subsampling_q): a client realizes a step only if
    #   sampled into the cohort AND participating within it.
    #   Accounting-only: not part of engine_key(), editable via replace()
    #   without recompiling.

    # -- adversarial fleet (core/robust.py + core/secureagg.py) ------------
    aggregator: str = "mean"        # "mean" | "median" | "trimmed_mean" |
    #   "norm_bound": the Eq.-7b reduction over participant updates.
    #   "mean" is the exact PR-3 pipeline; the robust choices bound the
    #   pull of a byzantine minority (Yin et al. 2018). Part of
    #   engine_key() — and with a robust aggregator the participant count
    #   becomes static too (the row gather bakes P in), so participation
    #   sweeps recompile there (unlike under "mean").
    trim_fraction: float = 0.1      # per-end trim of "trimmed_mean", [0,.5)
    norm_bound_factor: float = 3.0  # "norm_bound" rejects updates with
    #   L2 norm > factor * median participant norm
    secure_agg: bool = False        # pairwise-mask secure-aggregation
    #   simulation (core/secureagg.py): updates are fixed-point encoded,
    #   pairwise-masked, and only their modular SUM is ever materialized —
    #   requires aggregator="mean" (the server cannot compute a median of
    #   updates it never sees). Non-participants are the round's dropout
    #   set; their pair masks are reconstructed and subtracted.
    secure_frac_bits: int = 16      # fixed-point fractional bits (the one
    #   lossy step: quantization to a 2^-frac_bits grid at encode time)
    dp_accounting: str = "local"    # "local" | "central". "central" (needs
    #   secure_agg) accounts against the aggregate-only observer: the
    #   masked sum pools P participants' Gaussian noises, scaling the
    #   per-step rho by 1/P (secureagg.central_rho_scale — see its
    #   caveats). Accounting-only: NOT part of engine_key().
    attack: str = "none"            # "none" | "sign_flip" | "scale":
    #   byzantine upload corruption applied at the server boundary by a
    #   static set of round(byzantine_fraction * C) clients drawn from
    #   (seed, fraction) — resident federations only (the set binds to
    #   stable client identities; data-level label_flip for populations
    #   lives in repro.population.attacks.malicious_population).
    byzantine_fraction: float = 0.0
    attack_scale: float = 10.0      # multiplier of the "scale" attack

    # -- virtual client population (repro.population; cohort execution) ----
    population: int | None = None   # M virtual clients behind a lazy
    #   ClientPopulation; None -> the resident dense path. In population
    #   mode ``n_clients`` IS the per-round cohort size K — the device
    #   block holds K replicas and the population drivers gather only the
    #   sampled cohort, so device memory is bounded by K independent of M.
    cohort_size: int | None = None  # K; defaults to n_clients and must
    #   equal it (the one device-block size there is). Accounting-only
    #   like ``population``: M is NOT part of engine_key(), so population
    #   sweeps at fixed K reuse one compiled round.

    # -- 2D mesh plane (repro.mesh; engine="mesh_2d" or "auto") ------------
    mesh_shape: tuple[int, int] | None = None  # (dc, dm) client blocks x
    #   model shards over the local devices; None -> repro.mesh.placement
    #   .default_mesh_shape (all devices to client blocks unless
    #   ``replica_bytes`` forces a model axis). Part of engine_key(): the
    #   shape is the compiled collective layout.
    sharding_rules: Any = None      # logical->mesh axis overrides for the
    #   model annotations inside the mesh_2d body (dict or (name, axis)
    #   pairs; normalized to a sorted tuple of pairs so specs stay
    #   hashable). None -> repro.models.sharding.mesh2d_rules().
    replica_bytes: int | None = None  # per-replica params+opt-state
    #   footprint hint (repro.configs.shapes.replica_footprint_bytes) that
    #   drives the mesh-aware engine="auto" placement: over the per-device
    #   budget -> "mesh_2d". None -> placement never picks mesh_2d.

    # -- buffered-async federation (repro.asyncfl; engine="async_buffered")
    buffer_size: int | None = None  # B: arrivals aggregated per flush.
    #   None -> n_clients (the degenerate buffer whose zero-latency-spread
    #   alpha=0 run is bit-for-bit the sync vmap path — the identity gate).
    #   Part of engine_key(): B is the flush/dispatch block shape.
    staleness_alpha: float = 0.0    # staleness-weight exponent: an arrival
    #   that trained on a model s versions old is folded in with weight
    #   w(s) = 1 / (1 + s)^alpha (alpha=0: every arrival counts fully).
    #   Runtime operand, NOT in engine_key() — alpha sweeps reuse one
    #   compiled flush.

    # -- DP mechanism (Eq. 7a) ---------------------------------------------
    dp: bool = True
    clip_norm: float = 1.0          # G (sensitivity bound)
    num_microbatches: int = 1
    vmap_microbatches: bool = True
    grad_accumulate: str = "stack"  # "stack" | "scan" (§Perf opt)
    average_opt_state: bool = True

    # -- privacy accounting (§5.2) -----------------------------------------
    sigmas: tuple[float, ...] | None = None  # per-client σ; None -> design
    batch_sizes: tuple[int, ...] = ()        # X_m per client; () -> all 1
    eps_th: float = math.inf
    delta: float = 1e-4
    total_steps: int | None = None  # planned K for auto sigma design (Eq. 23)

    # -- resource budget (Eq. 8) -------------------------------------------
    c_th: float = math.inf
    c1: float = 100.0               # comm cost per aggregation
    c2: float = 1.0                 # compute cost per local step

    seed: int = 0

    def __post_init__(self):
        if self.n_clients <= 0:
            raise ValueError(f"n_clients must be positive, got {self.n_clients}")
        if self.tau <= 0:
            raise ValueError(f"tau must be positive, got {self.tau}")
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                             f"got {self.topology!r}")
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, "
                             f"got {self.engine!r}")
        if self.kernel_backend not in KERNEL_BACKENDS:
            raise ValueError(f"kernel_backend must be one of "
                             f"{KERNEL_BACKENDS}, got {self.kernel_backend!r}")
        validate_compression(self.compressor, self.compression_ratio,
                             self.compression_bits)
        if isinstance(self.participation, bool) or not (
                isinstance(self.participation, (int, float))):
            raise ValueError(f"participation must be a fraction in (0, 1] or "
                             f"an int count, got {self.participation!r}")
        if isinstance(self.participation, int):
            if not 1 <= self.participation <= self.n_clients:
                raise ValueError(
                    f"participation count must be in [1, {self.n_clients}], "
                    f"got {self.participation}")
        elif not 0.0 < self.participation <= 1.0:
            raise ValueError(f"participation fraction must be in (0, 1], "
                             f"got {self.participation}")
        from repro.core.robust import validate_aggregator, validate_attack
        from repro.core.secureagg import validate_secure
        validate_aggregator(self.aggregator, self.trim_fraction,
                            self.norm_bound_factor)
        validate_attack(self.attack, self.byzantine_fraction,
                        self.attack_scale)
        validate_secure(self.secure_frac_bits)
        if self.secure_agg and self.aggregator != "mean":
            raise ValueError(
                f"secure_agg only composes with aggregator='mean': the "
                f"server materializes nothing but the masked SUM, so it "
                f"cannot compute a {self.aggregator!r} of updates it never "
                f"sees")
        if self.dp_accounting not in ("local", "central"):
            raise ValueError(f"dp_accounting must be 'local' or 'central', "
                             f"got {self.dp_accounting!r}")
        if self.dp_accounting == "central" and not self.secure_agg:
            raise ValueError(
                "dp_accounting='central' accounts the aggregate-only "
                "observer of the masked sum and therefore requires "
                "secure_agg=True — without secure aggregation the server "
                "sees individual updates and only the local ledger is "
                "sound")
        if self.attack != "none" and self.population is not None:
            raise ValueError(
                "update attacks (sign_flip/scale) bind a static byzantine "
                "set to resident client identities; population cohort "
                "slots host different virtual clients every round. Model "
                "malicious populations at the data level instead "
                "(repro.population.attacks.malicious_population)")
        if self.is_adversarial() and self.engine == "async_buffered":
            raise ValueError(
                "engine='async_buffered' aggregates with its own "
                "staleness-weighted flush and does not route through "
                "AggregationPipeline.aggregate — robust aggregators, "
                "secure_agg, and update attacks are sync-engine features")
        if self.has_pipeline() and self.topology != "full_average":
            raise ValueError(
                "participation/compression/robust-secure aggregation shape "
                "the Eq.-7b aggregation and require "
                "topology='full_average' (local_only never communicates)")
        if self.engine == "async_buffered":
            if self.population is not None:
                raise ValueError(
                    "engine='async_buffered' does not compose with "
                    "population mode yet: in-flight slot state (pending "
                    "rho, residual, dispatch versions) is per resident "
                    "client, not per virtual id. Model fleet heterogeneity "
                    "through the latency side instead "
                    "(repro.asyncfl.HeteroLatency over a "
                    "HeterogeneousCohort's availability rates)")
            if self.topology != "full_average":
                raise ValueError("engine='async_buffered' aggregates "
                                 "arrivals into one global model and "
                                 "requires topology='full_average'")
            if self.buffer_size is None:
                object.__setattr__(self, "buffer_size", self.n_clients)
            if not 1 <= self.buffer_size <= self.n_clients:
                raise ValueError(
                    f"buffer_size must be in [1, {self.n_clients}] "
                    f"(at most one in-flight upload per client slot), "
                    f"got {self.buffer_size}")
        else:
            if self.buffer_size is not None:
                raise ValueError("buffer_size only applies to "
                                 "engine='async_buffered'")
            if self.staleness_alpha != 0.0:
                raise ValueError("staleness_alpha only applies to "
                                 "engine='async_buffered'")
        if self.staleness_alpha < 0.0:
            raise ValueError(f"staleness_alpha must be >= 0, "
                             f"got {self.staleness_alpha}")
        if self.engine not in ("mesh_2d", "auto"):
            if self.mesh_shape is not None:
                raise ValueError("mesh_shape only applies to "
                                 "engine='mesh_2d' (or 'auto', which may "
                                 "resolve to it)")
            if self.sharding_rules is not None:
                raise ValueError("sharding_rules only apply to "
                                 "engine='mesh_2d' (or 'auto')")
        if self.mesh_shape is not None:
            ms = tuple(int(x) for x in self.mesh_shape)
            if len(ms) != 2 or ms[0] < 1 or ms[1] < 1:
                raise ValueError(f"mesh_shape must be two positive ints "
                                 f"(dc, dm), got {self.mesh_shape!r}")
            object.__setattr__(self, "mesh_shape", ms)
        if self.sharding_rules is not None:
            items = (self.sharding_rules.items()
                     if isinstance(self.sharding_rules, dict)
                     else self.sharding_rules)
            norm = tuple(sorted(
                (str(k), tuple(v) if isinstance(v, (list, tuple)) else v)
                for k, v in items))
            object.__setattr__(self, "sharding_rules", norm)
        if self.replica_bytes is not None:
            if int(self.replica_bytes) <= 0:
                raise ValueError(f"replica_bytes must be positive, "
                                 f"got {self.replica_bytes}")
            object.__setattr__(self, "replica_bytes", int(self.replica_bytes))
        if self.engine == "mesh_2d" and self.is_adversarial():
            raise ValueError(
                "engine='mesh_2d' does not support the adversarial "
                "extensions (robust aggregator / secure sum / update "
                "attack): their full-view reductions gather exactly "
                "n_clients rows and do not compose with the padded client "
                "axis. Use engine='shard_map'")
        if self.cohort_size is not None and self.population is None:
            raise ValueError("cohort_size only makes sense with a "
                             "population (FederationSpec(population=M))")
        if self.population is not None:
            if self.cohort_size is None:
                object.__setattr__(self, "cohort_size", self.n_clients)
            if self.cohort_size != self.n_clients:
                raise ValueError(
                    f"cohort_size ({self.cohort_size}) must equal n_clients "
                    f"({self.n_clients}): in population mode n_clients IS "
                    f"the device cohort block")
            if self.population < self.n_clients:
                raise ValueError(
                    f"population ({self.population}) must be >= cohort size "
                    f"({self.n_clients})")
            if self.topology != "full_average":
                raise ValueError("cohort execution re-broadcasts one global "
                                 "model and requires topology='full_average'")
            if self.batch_sizes and len(set(self.batch_sizes)) > 1:
                raise ValueError(
                    "population mode needs uniform batch_sizes: cohort "
                    "slots host different virtual clients every round, so "
                    "per-slot heterogeneity has no client to bind to")
            if self.sigmas is not None and len(set(self.sigmas)) > 1:
                raise ValueError(
                    "population mode needs uniform sigmas (cohort slots "
                    "are not stable client identities)")
        # normalize sequences to hashable tuples
        if self.sigmas is not None:
            object.__setattr__(self, "sigmas",
                               tuple(float(s) for s in np.asarray(self.sigmas)))
            if len(self.sigmas) != self.n_clients:
                raise ValueError(f"sigmas has {len(self.sigmas)} entries for "
                                 f"{self.n_clients} clients")
        if self.batch_sizes:
            object.__setattr__(self, "batch_sizes",
                               tuple(int(x) for x in self.batch_sizes))
            if len(self.batch_sizes) != self.n_clients:
                raise ValueError(
                    f"batch_sizes has {len(self.batch_sizes)} entries for "
                    f"{self.n_clients} clients")

    # -- derived views ------------------------------------------------------
    def replace(self, **changes) -> "FederationSpec":
        return dataclasses.replace(self, **changes)

    def fl_config(self, vmap_clients: bool = True) -> FLConfig:
        """The engine-level FLConfig view of this spec."""
        return FLConfig(
            n_clients=self.n_clients, tau=self.tau, clip_norm=self.clip_norm,
            dp=self.dp, num_microbatches=self.num_microbatches,
            vmap_microbatches=self.vmap_microbatches,
            grad_accumulate=self.grad_accumulate,
            average_opt_state=self.average_opt_state,
            vmap_clients=vmap_clients,
            kernel_backend=self.kernel_backend)

    def budgets(self) -> Budgets:
        return Budgets(c_th=self.c_th, eps_th=self.eps_th,
                       c1=self.c1, c2=self.c2)

    # -- aggregation-pipeline views -----------------------------------------
    def participants_per_round(self) -> int:
        """The fixed per-round participant count (fraction q rounded to a
        count, floored at one client so every round aggregates something)."""
        if isinstance(self.participation, int):
            return self.participation
        return max(1, min(self.n_clients,
                          round(self.participation * self.n_clients)))

    def participation_fraction(self) -> float:
        """Realized q = participants / n_clients (drives amplification)."""
        return self.participants_per_round() / self.n_clients

    # -- async views ---------------------------------------------------------
    def is_async(self) -> bool:
        """Buffered-async execution (repro.asyncfl drivers)."""
        return self.engine == "async_buffered"

    def resolved_buffer_size(self) -> int:
        """B — arrivals aggregated per flush (n_clients unless set)."""
        if self.buffer_size is not None:
            return self.buffer_size
        return self.n_clients

    # -- population views ----------------------------------------------------
    def is_population(self) -> bool:
        """Cohort-execution mode: n_clients is a per-round cohort of K
        drawn from ``population`` virtual clients (repro.population)."""
        return self.population is not None

    def cohort_fraction(self) -> float:
        """K/M — the cohort subsampling rate over the population (1.0 in
        the resident dense mode, where every client is in every round's
        block)."""
        if self.population is None:
            return 1.0
        return self.n_clients / self.population

    def accounting_q(self) -> float:
        """The q the privacy ledger charges per realized step: 1.0 (full
        Lemma-2 rho, the sound conditional ledger) by default; with
        ``amplify_participation``, the composed probability that a given
        client realizes a step in a given round — cohort sampling (K/M)
        times within-cohort participation
        (:func:`repro.core.privacy.composed_subsampling_q`). Under
        ``dp_accounting="central"`` (secure aggregation's aggregate-only
        observer) the charge additionally scales by
        :func:`repro.core.secureagg.central_rho_scale` — 1/P for the P
        pooled participant noises; the factors compose multiplicatively
        because subsampling and noise pooling amplify independently."""
        q = 1.0
        if self.amplify_participation:
            from repro.core.privacy import composed_subsampling_q
            q = composed_subsampling_q(self.cohort_fraction(),
                                       self.participation_fraction())
        if self.dp_accounting == "central":
            from repro.core.secureagg import central_rho_scale
            q *= central_rho_scale(self.participants_per_round())
        return q

    def wire_ratio(self) -> float:
        """Compressed-update bytes as a fraction of the dense fp32 update
        (see :func:`repro.core.aggregation.compression_wire_ratio`)."""
        return compression_wire_ratio(self.compressor, self.compression_ratio,
                                      self.compression_bits)

    def comm_scale(self) -> float:
        """Eq.-8 comm-cost multiplier of the pipeline: wire_ratio * q."""
        return self.wire_ratio() * self.participation_fraction()

    def is_adversarial(self) -> bool:
        """Any adversarial-fleet feature active (robust aggregator, secure
        aggregation, or an update attack)? These are full-view reductions
        on the pipeline seam — ``has_pipeline()`` includes them."""
        return (self.aggregator != "mean" or self.secure_agg
                or self.attack != "none")

    def resolved_byzantine_flags(self) -> tuple[int, ...] | None:
        """The static 0/1 byzantine membership over the C resident clients
        (None without an attack) — deterministic per (seed, fraction), see
        :func:`repro.core.robust.byzantine_flags`."""
        if self.attack == "none":
            return None
        from repro.core.robust import byzantine_flags
        return byzantine_flags(self.n_clients, self.byzantine_fraction,
                               self.seed)

    def has_pipeline(self) -> bool:
        """Does this spec leave the seed all-clients/dense-mean protocol?
        When False, rounds are bit-for-bit the pre-pipeline engines."""
        return (self.compressor != "none"
                or self.participants_per_round() < self.n_clients
                or self.is_adversarial())

    def aggregation_pipeline(self):
        """The AggregationPipeline for this spec, or None for the default
        (full participation, dense updates) path."""
        if not self.has_pipeline():
            return None
        from repro.core.aggregation import AggregationPipeline, make_compressor
        from repro.core.robust import make_aggregator, make_attack
        from repro.core.secureagg import SecureMaskedSum
        flags = self.resolved_byzantine_flags()
        return AggregationPipeline(
            n_clients=self.n_clients,
            compressor=make_compressor(self.compressor, self.compression_ratio,
                                       self.compression_bits,
                                       self.kernel_backend),
            average_opt_state=self.average_opt_state,
            aggregator=make_aggregator(self.aggregator, self.trim_fraction,
                                       self.norm_bound_factor),
            secure=(SecureMaskedSum(self.n_clients, self.secure_frac_bits)
                    if self.secure_agg else None),
            attack=(make_attack(self.attack, flags, self.attack_scale)
                    if flags is not None else None),
            n_participants=self.participants_per_round())

    def round_cost(self) -> float:
        """Eq. (8) per round: c1 * comm_scale + c2 * tau — the pipeline
        scales only the aggregation (communication) term."""
        return self.c1 * self.comm_scale() + self.c2 * self.tau

    def resolved_batch_sizes(self) -> tuple[int, ...]:
        return self.batch_sizes or (1,) * self.n_clients

    def resolved_sigmas(self) -> np.ndarray:
        """Per-client noise std: explicit > auto-designed (Eq. 23) > zero.

        Auto design needs a finite ``eps_th`` and a planned ``total_steps``
        (the K of Eq. 23); it yields the smallest noise meeting eps_th at K.
        """
        if self.sigmas is not None:
            return np.asarray(self.sigmas, np.float32)
        if not self.dp:
            return np.zeros((self.n_clients,), np.float32)
        if not math.isfinite(self.eps_th) or self.total_steps is None:
            raise ValueError(
                "FederationSpec needs explicit sigmas, or a finite eps_th "
                "plus total_steps so Eq. 23 can design them")
        return design_sigmas(self.total_steps, self.clip_norm,
                             list(self.resolved_batch_sizes()),
                             self.eps_th, self.delta)

    def ledger_key(self) -> tuple:
        """Hash key of everything that shapes the privacy ledger's per-step
        charges and the device-resident sigma vector. ``repro.api.state``
        caches both per ledger key (the cached-sigma transfer and the
        incremental budget probe of the fused driver), so budget edits via
        ``replace(eps_th=..., c_th=...)`` with explicit sigmas reuse the
        cached constants, while any change to the mechanism (clip norm,
        sigmas, batch sizes) repopulates them.

        Memoized on the (frozen) instance: probing it several times per
        round must not re-run the O(C) Eq.-23 sigma design. ``replace()``
        builds a fresh instance, so edits never see a stale key."""
        cached = self.__dict__.get("_ledger_key")
        if cached is None:
            cached = (self.clip_norm, self.dp,
                      tuple(float(s) for s in self.resolved_sigmas()),
                      self.resolved_batch_sizes())
            object.__setattr__(self, "_ledger_key", cached)
        return cached

    def engine_key(self) -> tuple:
        """Hash key of everything that shapes the compiled round function.

        Budget / accounting fields (eps_th, c_th, delta,
        amplify_participation, ...) are excluded — changing them must NOT
        retrace or recompile the engine. Participation enters only as
        ``has_pipeline()``: the participant count itself is a runtime
        operand (the mask), so q sweeps reuse one compiled round. The
        population size M is excluded too: the compiled round only ever
        sees the K-block, so sweeping M at fixed K reuses one XLA program
        (that exclusion is what makes cohort execution memory-bounded by
        K, and the M == C identity gate literally the same executable).

        Exception to the participation-is-runtime rule: a robust
        aggregator bakes the STATIC participant count P into its gathered
        (P, D) block shape, so the key includes P exactly when
        ``aggregator != "mean"`` — q sweeps under the default mean still
        share one executable. ``dp_accounting`` is accounting-only
        (rides :meth:`accounting_q`) and stays excluded; the byzantine
        flag vector is included because it is baked into the compiled
        attack select (and captures the seed/fraction dependence).
        """
        return (self.loss_fn, self.optimizer, self.n_clients, self.tau,
                self.clip_norm, self.dp, self.num_microbatches,
                self.vmap_microbatches, self.grad_accumulate,
                self.average_opt_state, self.topology, self.engine,
                self.kernel_backend, self.has_pipeline(), self.compressor,
                self.compression_ratio, self.compression_bits,
                # async: B shapes the flush/dispatch blocks; staleness_alpha
                # deliberately excluded (a runtime weight operand)
                self.buffer_size,
                # 2D mesh plane: the mesh shape and logical rules ARE the
                # compiled layout; replica_bytes steers what engine="auto"
                # resolves to, so it must key the cache even though the
                # resolved engine ignores it
                self.mesh_shape, self.sharding_rules, self.replica_bytes,
                # adversarial fleets (PR 7)
                self.aggregator, self.trim_fraction, self.norm_bound_factor,
                (self.participants_per_round()
                 if self.aggregator != "mean" else None),
                self.secure_agg, self.secure_frac_bits,
                self.attack, self.attack_scale,
                self.resolved_byzantine_flags())
