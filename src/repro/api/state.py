"""Pure functional DP-PASGD core: FLState + init_state / run_round / train.

The state of a federation is one immutable :class:`FLState` value — model
replicas, optimizer state, PRNG key, privacy-accountant snapshot, and spent
resources. ``run_round`` maps (spec, state, batch) -> (state', metrics),
which makes checkpoint/resume (``save_state`` / ``load_state``), budget
probing, and jit-friendly outer drivers trivial. The mutable
:class:`repro.api.Federation` is a thin wrapper over these functions.

DONATION CONTRACT (§Perf opt): the value semantics are linear, not
persistent — ``run_round`` / ``run_rounds`` donate the input state's
params / opt_state / residual device buffers to XLA (client replicas
update in place instead of double-buffering), so a successful call CONSUMES
the input FLState; always continue from the returned state. To fork one
state down two paths (what-if probing), copy the donated leaves first
(``state.replace(params=jax.tree.map(jnp.copy, state.params), ...)``) or
rebuild via ``init_state``. Host-side data (checkpoints on disk, the rho
snapshot, np views taken earlier) is never affected.

PROFILER SPANS: the drivers mark their host work with
``jax.profiler.TraceAnnotation`` spans, which land in a ``jax.profiler``
trace beside the device's ops and cost about a microsecond each when no
profiler runs:

    dppasgd.round      one step of the driver loop (``round=`` the first
                       round it completes; ``rounds=`` under the chunked
                       driver): batch build, dispatch, ledger, host reads
    dppasgd.batch      building a round's (or a chunk's) host batch
    dppasgd.ledger     the host zCDP arithmetic: budget probes, charges
    dppasgd.host_read  one blocking device->host read (a metric value, a
                       participation mask)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.engines import chunked_round_fn_for, round_fn_for
from repro.api.spec import FederationSpec
from repro.core.aggregation import participation_mask
from repro.core.privacy import (
    PrivacyAccountant,
    gaussian_zcdp,
    grad_sensitivity,
    per_step_charges,
    zcdp_to_dp,
)
from repro.utils.tree import tree_broadcast_axis0, tree_mean_over_axis0


class BudgetExceeded(RuntimeError):
    """Raised by run_round when the next round would break a budget."""

    def __init__(self, which: str, message: str):
        super().__init__(message)
        self.which = which          # "resource" | "privacy"


class PrefetchFailed(RuntimeError):
    """The ``prefetch`` callback of :func:`run_rounds` raised AFTER the
    chunk was dispatched. The chunk's DP releases already executed, so the
    completed successor state and records are attached — recover via
    ``.state`` / ``.records`` (as ``train`` does) instead of discarding a
    ledger that was physically spent. The original exception is chained as
    ``__cause__``."""

    def __init__(self, cause: BaseException, state: "FLState",
                 records: list):
        super().__init__(f"run_rounds prefetch callback failed: {cause!r}")
        self.state = state
        self.records = records


@dataclass(frozen=True)
class FLState:
    """Complete training state of one federation (immutable).

    params/opt_state carry the leading client axis C on every leaf. The
    accountant snapshot (rho, steps) lives host-side as plain numpy — the
    zCDP ledger is exact closed-form math, not traced computation.
    """
    params: Any
    opt_state: Any
    key: jax.Array                  # PRNG key consumed one split per round
    rho: np.ndarray                 # (C,) spent zCDP per client (Lemma 1)
    steps: int = 0                  # local iterations accounted so far
    resource_spent: float = 0.0     # accumulated Eq.-(8) cost
    rounds_done: int = 0
    residual: Any = None            # (C, D) error-feedback residual of the
    #   aggregation pipeline (core/aggregation.py); None unless the spec
    #   sets a compressor. Checkpointed alongside params/opt_state.

    def replace(self, **changes) -> "FLState":
        return dataclasses.replace(self, **changes)


def init_state(spec: FederationSpec, params0: Any,
               key: jax.Array | None = None) -> FLState:
    """Fresh FLState: params0 (no client axis) replicated C times."""
    params = tree_broadcast_axis0(params0, spec.n_clients)
    opt_state = tree_broadcast_axis0(spec.optimizer.init(params0),
                                     spec.n_clients)
    if key is None:
        key = jax.random.PRNGKey(spec.seed)
    pipe = spec.aggregation_pipeline()
    residual = pipe.init_residual(params0) if pipe is not None else None
    return FLState(params=params, opt_state=opt_state, key=key,
                   rho=np.zeros((spec.n_clients,), np.float64),
                   residual=residual)


# ---------------------------------------------------------------------------
# per-spec host/device ledger constants (cached — the per-round rebuild /
# re-transfer of these was the dominant host overhead of the old driver)
# ---------------------------------------------------------------------------

_SIGMA_CACHE: dict[tuple, jax.Array] = {}
_RHO_STEP_CACHE: dict[tuple, np.ndarray] = {}
_LEDGER_CACHE_MAX = 128


def _ledger_cached(cache: dict, key, build):
    val = cache.get(key)
    if val is None:
        if len(cache) >= _LEDGER_CACHE_MAX:
            cache.clear()          # tiny (C,) vectors; simple bound suffices
        val = cache[key] = build()
    return val


def sigmas_for(spec: FederationSpec) -> jax.Array:
    """The device-resident (C,) f32 sigma vector for ``spec``, cached per
    ``spec.ledger_key()`` so rounds stop paying a host->device transfer of
    the same constants every dispatch."""
    return _ledger_cached(
        _SIGMA_CACHE, spec.ledger_key(),
        lambda: jnp.asarray(spec.resolved_sigmas(), jnp.float32))


def _rho_steps(spec: FederationSpec) -> np.ndarray:
    """(C,) per-local-step zCDP charge per client at q=1 — Lemma 2 with the
    §5.2 sensitivity, exactly as ``PrivacyAccountant`` computes it on
    registered clients. Cached per ledger key: the incremental budget probe
    and the per-round ledger update reuse these host constants instead of
    re-registering all C clients on every probe."""
    def build():
        sig = spec.resolved_sigmas()
        return np.asarray(
            [gaussian_zcdp(grad_sensitivity(spec.clip_norm, x), float(s))
             for x, s in zip(spec.resolved_batch_sizes(), sig)], np.float64)

    return _ledger_cached(_RHO_STEP_CACHE, spec.ledger_key(), build)


def round_rho_charges(spec: FederationSpec) -> np.ndarray:
    """(C,) worst-case per-round rho increments: tau steps at the spec's
    accounting rate — the same expression ``PrivacyAccountant.step`` charges
    a realized participant (``n_steps * subsampled_rho(rho_step, q)``,
    via the shared :func:`repro.core.privacy.per_step_charges`). Public:
    the population drivers (``repro.population.runtime``) charge the
    per-virtual-client ledger with exactly this vector."""
    return spec.tau * per_step_charges(_rho_steps(spec), spec.accounting_q())


def accountant_view(spec: FederationSpec,
                    state: FLState | None = None) -> PrivacyAccountant:
    """A PrivacyAccountant materialized from spec (+ optional state snapshot)."""
    acc = PrivacyAccountant(clip_norm=spec.clip_norm, delta=spec.delta)
    sig = spec.resolved_sigmas()
    for m, x in enumerate(spec.resolved_batch_sizes()):
        acc.register_client(m, x, float(sig[m]))
    if state is not None:
        for m in range(spec.n_clients):
            acc._rho[m] = float(state.rho[m])
        acc.steps = state.steps
    return acc


def max_epsilon(spec: FederationSpec, state: FLState) -> float:
    with TraceAnnotation("dppasgd.ledger"):
        return accountant_view(spec, state).max_epsilon()


def peek_epsilon_fast(spec: FederationSpec, state: FLState,
                      extra_steps: int) -> float:
    """Incremental budget probe: worst-client eps if every client took
    ``extra_steps`` more local iterations, computed from the state's rho
    snapshot plus the cached per-step charges — no O(C) accountant rebuild
    per probe. Bit-identical to
    ``accountant_view(spec, state).peek_epsilon(extra_steps,
    q=spec.accounting_q())`` (same per-element float expressions)."""
    extra = extra_steps * per_step_charges(_rho_steps(spec),
                                           spec.accounting_q())
    return zcdp_to_dp(float(np.max(state.rho + extra)), spec.delta)


def exceeds_budgets(spec: FederationSpec, state: FLState) -> str | None:
    """Would one more round break a budget? Returns "resource" / "privacy"
    or None. The privacy probe is the incremental
    :func:`peek_epsilon_fast` (identical math to
    ``PrivacyAccountant.peek_epsilon``), conservatively assuming the worst
    client participates next round (its per-step rho still carries the
    subsampling amplification factor)."""
    with TraceAnnotation("dppasgd.ledger"):
        if state.resource_spent + spec.round_cost() > spec.c_th:
            return "resource"
        if peek_epsilon_fast(spec, state, spec.tau) > spec.eps_th:
            return "privacy"
        return None


def rounds_within_budgets(spec: FederationSpec, state: FLState,
                          limit: int) -> tuple[int, str | None]:
    """How many consecutive future rounds are CERTAIN to fit the budgets,
    capped at ``limit``, plus the budget ("resource" / "privacy" / None)
    that would bind next.

    Replays ``exceeds_budgets``'s per-round probes with worst-case ledger
    growth (every client charged every round). Exact for full
    participation — bit-identical decisions to the per-round driver; under
    partial participation the realized ledger grows no faster than the
    projection, so a chunk sized by this bound never contains a round the
    per-round driver would have refused (it may end early; the training
    loop re-probes on the realized ledger and continues)."""
    with TraceAnnotation("dppasgd.ledger"):
        charges = round_rho_charges(spec)
        rho = state.rho
        spent = state.resource_spent
        cost = spec.round_cost()
        n = 0
        while n < limit:
            if spent + cost > spec.c_th:
                return n, "resource"
            if zcdp_to_dp(float(np.max(rho + charges)),
                          spec.delta) > spec.eps_th:
                return n, "privacy"
            rho = rho + charges
            spent = spent + cost
            n += 1
        return n, None


def _raise_budget(which: str, spec: FederationSpec):
    if which == "resource":
        raise BudgetExceeded("resource", f"round cost {spec.round_cost()} "
                             f"would exceed C_th={spec.c_th}")
    raise BudgetExceeded("privacy", f"tau={spec.tau} more steps would "
                         f"exceed eps_th={spec.eps_th}")


def run_round(spec: FederationSpec, state: FLState, batch: Any,
              check_budgets: bool = True) -> tuple[FLState, dict]:
    """One DP-PASGD round (Eq. 7a-7b): tau local steps + topology collective.

    batch leaves are (C, tau, B, ...). Returns the successor state and a
    metrics record; raises :class:`BudgetExceeded` (state untouched) when
    ``check_budgets`` and the round would overrun ``spec.c_th``/``eps_th``.

    The input state's params/opt_state/residual device buffers are DONATED
    to the round (updated in place, see :func:`repro.api.engines
    .round_fn_for`) — continue from the returned state. The record's metric
    values stay device-resident 0-d arrays (no forced sync before the next
    round can dispatch); call :func:`materialize_record` — as ``train``
    does at history-append time — to force them to host floats.
    """
    if check_budgets:
        which = exceeds_budgets(spec, state)
        if which is not None:
            _raise_budget(which, spec)
    key, sub = jax.random.split(state.key)
    sig = sigmas_for(spec)
    residual = state.residual
    if spec.has_pipeline():
        # pipeline round: sample this round's participant set from the
        # FLState RNG (host-visible — the ledger needs the realized set;
        # this mask fetch is the per-round driver's one blocking sync)
        sub, mask_key = jax.random.split(sub)
        mask = participation_mask(mask_key, spec.n_clients,
                                  spec.participants_per_round())
        with TraceAnnotation("dppasgd.host_read"):
            mask_np = np.asarray(mask)
        new_p, new_s, residual, ms = round_fn_for(spec)(
            state.params, state.opt_state, batch, sub, sig, mask,
            state.residual)
    else:
        new_p, new_s, ms = round_fn_for(spec)(state.params, state.opt_state,
                                              batch, sub, sig)
        mask_np = None
    # the host ledger runs while the device computes the round
    with TraceAnnotation("dppasgd.ledger"):
        per_round = round_rho_charges(spec)
        if mask_np is None:
            rho = state.rho + per_round
            n_participants = spec.n_clients
        else:
            rho = state.rho + np.where(mask_np > 0, per_round, 0.0)
            n_participants = int(mask_np.sum())
        round_epsilon = zcdp_to_dp(float(np.max(rho)), spec.delta)
    new_state = state.replace(
        params=new_p, opt_state=new_s, key=key, residual=residual, rho=rho,
        steps=state.steps + spec.tau,
        resource_spent=state.resource_spent + spec.round_cost(),
        rounds_done=state.rounds_done + 1)
    rec = dict(ms)                 # lazy: 0-d device arrays, no sync
    rec["round"] = new_state.rounds_done
    rec["iterations"] = new_state.rounds_done * spec.tau
    rec["max_epsilon"] = round_epsilon
    rec["resource_spent"] = new_state.resource_spent
    rec["participants"] = float(n_participants)
    return new_state, rec


def run_rounds(spec: FederationSpec, state: FLState, batches: Any,
               n_rounds: int | None = None, check_budgets: bool = True,
               prefetch: Callable[[], None] | None = None,
               ) -> tuple[FLState, list[dict]]:
    """A fused chunk of R rounds as ONE jitted ``lax.scan`` (§Perf opt).

    ``batches`` leaves are (R, C, tau, B, ...) — see :func:`round_batches`;
    ``n_rounds`` defaults to the leading axis. Bit-identical to R sequential
    :func:`run_round` calls (params, opt_state, rho ledger, error-feedback
    residual, RNG key, resource_spent — guarded by the chunk/loop identity
    gate in tests/test_fused_rounds.py): participation masks are sampled
    INSIDE the scan from the carried key with run_round's exact split
    schedule, and the realized masks come back stacked so the host replays
    the conditional ledger once per chunk
    (:meth:`PrivacyAccountant.step_many`) instead of 4x per round.

    Host-sync model: the chunk blocks the host at most ONCE (fetching the
    stacked masks under a pipeline spec; never for the default protocol) —
    per-round records are returned lazily, metric values as 0-d device
    slices of the stacked metrics (:func:`materialize_record` forces them).
    ``prefetch()``, if given, runs after the chunk is dispatched and before
    that sync, so callers overlap building the next chunk's host batches
    with device compute (``train``'s double-buffered driver). If it raises,
    the chunk it overlapped is NOT lost: :class:`PrefetchFailed` carries
    the completed successor state and records (the donated inputs are
    already consumed and the DP releases executed — discarding the ledger
    would un-account spent privacy).

    Donation: like run_round, the input state's device buffers are consumed.
    Raises BudgetExceeded (state untouched) when ``check_budgets`` and any
    of the R rounds could overrun a budget, judged by the worst-case
    projection of :func:`rounds_within_budgets` (exact for full
    participation, conservative under partial participation).
    """
    lead = int(jax.tree.leaves(batches)[0].shape[0])
    if n_rounds is None:
        n_rounds = lead
    if n_rounds <= 0:
        raise ValueError(f"n_rounds must be positive, got {n_rounds}")
    if n_rounds != lead:
        # the scan length comes from the batches — a mismatch would train
        # lead rounds while charging the ledger for n_rounds
        raise ValueError(f"n_rounds={n_rounds} != stacked batches leading "
                         f"axis {lead}")
    if check_budgets:
        ok, which = rounds_within_budgets(spec, state, n_rounds)
        if ok < n_rounds:
            _raise_budget(which, spec)
    sig = sigmas_for(spec)
    fn = chunked_round_fn_for(spec)
    prefetch_exc = None

    def _prefetch():
        # a raising prefetch must not lose the already-dispatched chunk
        # (donated inputs are consumed, the DP releases execute): defer the
        # error until the successor state exists and attach it
        nonlocal prefetch_exc
        if prefetch is not None:
            try:
                prefetch()
            except Exception as e:        # noqa: BLE001 — re-raised below
                prefetch_exc = e

    if spec.has_pipeline():
        new_p, new_s, key, residual, ms, masks = fn(
            state.params, state.opt_state, batches, state.key, sig,
            state.residual)
        _prefetch()
        with TraceAnnotation("dppasgd.host_read"):
            masks_np = np.asarray(masks)   # THE one blocking sync per chunk
        participants = masks_np.sum(axis=1)
    else:
        new_p, new_s, key, ms = fn(state.params, state.opt_state, batches,
                                   state.key, sig)
        residual = state.residual
        _prefetch()
        masks_np = None
        participants = np.full((n_rounds,), float(spec.n_clients))
    # exact ledger replay, hoisted to the chunk boundary: ONE accountant
    # materialization + one vectorized step_many over the realized masks
    with TraceAnnotation("dppasgd.ledger"):
        acc = accountant_view(spec, state)
        worst_rho = acc.step_many([spec.tau] * n_rounds, masks=masks_np,
                                  q=spec.accounting_q())
        rho = np.asarray([acc.rho(m) for m in range(spec.n_clients)],
                         np.float64)
    recs = []
    spent = state.resource_spent
    for r in range(n_rounds):
        spent = spent + spec.round_cost()   # repeated add: bit-identical to
        #   the per-round driver's accumulation
        rec = {k: v[r] for k, v in ms.items()}      # lazy 0-d device slices
        rec["round"] = state.rounds_done + r + 1
        rec["iterations"] = (state.rounds_done + r + 1) * spec.tau
        rec["max_epsilon"] = zcdp_to_dp(float(worst_rho[r]), spec.delta)
        rec["resource_spent"] = spent
        rec["participants"] = float(participants[r])
        recs.append(rec)
    new_state = state.replace(
        params=new_p, opt_state=new_s, key=key, residual=residual, rho=rho,
        steps=state.steps + n_rounds * spec.tau,
        resource_spent=spent,
        rounds_done=state.rounds_done + n_rounds)
    if prefetch_exc is not None:
        raise PrefetchFailed(prefetch_exc, new_state, recs) from prefetch_exc
    return new_state, recs


def materialize_record(rec: dict) -> dict:
    """Force any device-resident metric values of a round record to host
    floats — the drivers' one deliberate sync point. ``run_round`` /
    ``run_rounds`` return records lazily (loss etc. stay 0-d device
    arrays) so recording a round never blocks the next dispatch; convert
    at history-append or read time via this helper. Each read of a device
    value is one ``dppasgd.host_read`` span."""
    def host(v):
        if isinstance(v, jax.Array):
            with TraceAnnotation("dppasgd.host_read"):
                return float(v)
        return v if isinstance(v, (bool, int, float, str)) else float(v)

    return {k: host(v) for k, v in rec.items()}


# ---------------------------------------------------------------------------
# data plumbing + budget-aware driver
# ---------------------------------------------------------------------------

def round_batch(spec: FederationSpec, sampler: Callable, rng) -> Any:
    """Stack per-client samples into the (C, tau, B, ...) round batch.

    ``sampler(client, tau, rng)`` returns one client's pytree with leading
    axes (tau, B, ...).
    """
    with TraceAnnotation("dppasgd.batch"):
        return _stack_clients(spec, sampler, rng)


def _stack_clients(spec: FederationSpec, sampler: Callable, rng) -> Any:
    per_client = [sampler(m, spec.tau, rng) for m in range(spec.n_clients)]
    return jax.tree.map(lambda *xs: np.stack(xs), *per_client)


def round_batches(spec: FederationSpec, sampler: Callable, rng,
                  n_rounds: int) -> Any:
    """Stack ``n_rounds`` round batches into the (R, C, tau, B, ...) chunk
    operand of :func:`run_rounds`, drawing from ``rng`` in exactly the
    order ``n_rounds`` sequential :func:`round_batch` calls would (so a
    chunked driver consumes the sampler stream identically to the
    per-round one)."""
    with TraceAnnotation("dppasgd.batch"):
        rounds = [_stack_clients(spec, sampler, rng)
                  for _ in range(n_rounds)]
        return jax.tree.map(lambda *xs: np.stack(xs), *rounds)


def collapse_clients(params: Any, topology: str) -> Any:
    """Client-stacked params -> the single serving/eval model: any replica
    after full averaging, the cross-client mean under local_only. The one
    place the topology-collapse rule lives (eval_params and the serving
    driver both delegate here)."""
    if topology == "full_average":
        return jax.tree.map(lambda x: x[0], params)
    return tree_mean_over_axis0(params)


def eval_params(spec: FederationSpec, state: FLState) -> Any:
    """The single evaluation model for ``spec``'s topology."""
    return collapse_clients(state.params, spec.topology)


def budget_train_loop(*, state, max_rounds: int, eval_fn: Callable | None,
                      eval_every: int, history: list[dict],
                      chunk_rounds: int,
                      rounds_done: Callable[[Any], int],
                      exceeds: Callable[[Any], bool],
                      safe_rounds: Callable[[Any, int], int],
                      run_single: Callable[[Any], tuple],
                      build_chunk: Callable[[int, int], Any],
                      run_chunk: Callable[..., tuple],
                      run_tail: Callable[[Any, Any, int], tuple],
                      eval_model: Callable[[Any], Any]) -> tuple[Any, dict]:
    """THE budget-aware driver loop, shared by the dense :func:`train` and
    the cohort-execution ``repro.population.train_population`` (one copy of
    the double-buffered prefetch / tail-chunk / eval-boundary invariants;
    the two drivers differ only in how a round runs and how budgets probe).
    Parameterized over an opaque ``state`` and an opaque prepared ``chunk``:

        rounds_done(state) -> int          completed-round counter
        exceeds(state) -> bool             would one more round overrun?
        safe_rounds(state, cap) -> int     certain-to-fit round count
        run_single(state) -> (state, rec)  one round, building its own batch
        build_chunk(start, n) -> chunk     host-build + device_put n rounds
                                           starting at round index ``start``
        run_chunk(state, chunk, n, prefetch) -> (state, recs)
                                           fused scan; may raise
                                           PrefetchFailed carrying the
                                           completed state/records
        run_tail(state, chunk, r) -> (state, rec)
                                           row r of chunk via the per-round
                                           path
        eval_model(state) -> params        the eval_fn operand

    Tracks theta* = argmin of the evaluated loss (the paper uses the best
    model among K iterations); appends materialized records to ``history``;
    returns (state, best).
    """
    best = {"loss": float("inf"), "round": 0}

    def track_best(rec: dict, evaluated: bool):
        nonlocal best
        # theta* tracking: compare on eval loss when available, else train
        if eval_fn is None:
            crit = rec["loss"]
        elif evaluated:
            crit = rec["eval_loss"]
        else:
            crit = float("inf")
        if crit < best["loss"]:
            # rec AFTER the overrides: best["loss"] must stay the tracked
            # criterion (eval loss when eval_fn is given), not rec's train
            # loss, or a later genuinely-better eval never displaces it
            best = {**rec, "loss": crit, "round": rec["round"]}

    if chunk_rounds <= 1:
        while rounds_done(state) < max_rounds:
            if exceeds(state):
                break
            with TraceAnnotation("dppasgd.round",
                                 round=rounds_done(state) + 1):
                state, rec = run_single(state)
                rec = materialize_record(rec)
            history.append(rec)
            evaluated = False
            if eval_fn is not None and rounds_done(state) % eval_every == 0:
                rec.update(eval_fn(eval_model(state)))
                evaluated = True
            track_best(rec, evaluated)
        return state, best

    pending = None            # double buffer: (chunk, n) prefetched
    while rounds_done(state) < max_rounds:
        cap = min(2 * chunk_rounds, max_rounds - rounds_done(state))
        safe = safe_rounds(state, cap)
        if pending is not None:
            # prefetched chunks were sized by the post-chunk projection,
            # so they always fit (safe >= n); run them whole to keep the
            # sampler stream aligned with the per-round driver
            chunk, n = pending
            pending = None
        elif safe == 0:
            break
        else:
            n = min(chunk_rounds, safe)
            chunk = build_chunk(rounds_done(state), n)
        next_n = min(chunk_rounds, safe - n,
                     max_rounds - rounds_done(state) - n)
        next_start = rounds_done(state) + n

        def build_next(next_n=next_n, next_start=next_start):
            nonlocal pending
            if next_n > 0:
                pending = (build_chunk(next_start, next_n), next_n)

        deferred = None
        with TraceAnnotation("dppasgd.round", round=rounds_done(state) + 1,
                             rounds=n):
            if n < chunk_rounds:
                # tail chunk (budget/max_rounds edge): drive the rows
                # through the per-round path — the single compiled round
                # is reused for any tail size, instead of paying a one-shot
                # XLA compile of a fresh n-round scan for a few rounds
                recs = []
                for r in range(n):
                    state, rec = run_tail(state, chunk, r)
                    recs.append(rec)
            else:
                try:
                    state, recs = run_chunk(state, chunk, n, build_next)
                except PrefetchFailed as pf:
                    # the sampler failed building the NEXT chunk; keep the
                    # completed chunk's state/records, re-raise the
                    # original error after recording them (the per-round
                    # driver raises at the same point: after round r,
                    # before batch r+1)
                    state, recs, deferred = (pf.state, pf.records,
                                             pf.__cause__)
            recs = [materialize_record(r) for r in recs]
        history.extend(recs)
        evaluated = False
        if eval_fn is not None and (
                rounds_done(state) // eval_every
                > (rounds_done(state) - n) // eval_every):
            # an eval was due mid-chunk: run it once, at the boundary
            recs[-1].update(eval_fn(eval_model(state)))
            evaluated = True
        for rec in recs[:-1]:
            track_best(rec, False)
        track_best(recs[-1], evaluated)
        if deferred is not None:
            raise deferred
    return state, best


def train(spec: FederationSpec, state: FLState, sampler: Callable,
          max_rounds: int = 10_000, eval_fn: Callable | None = None,
          eval_every: int = 1, rng=None,
          history: list[dict] | None = None,
          chunk_rounds: int = 1) -> tuple[FLState, dict]:
    """Run rounds until a budget (resource or privacy) would be exceeded.

    Tracks theta* = argmin of the evaluated loss (the paper uses the best
    model among K iterations). Returns (final_state, summary) where summary
    carries best/rounds/resource_spent/max_epsilon/history.

    ``chunk_rounds=R > 1`` drives training in fused :func:`run_rounds`
    chunks (§Perf opt): R rounds lower to one XLA dispatch with at most one
    host sync per chunk, and the next chunk's round batches are built and
    ``device_put`` while the current chunk computes (double-buffered
    prefetch). Budget semantics are preserved: chunks are sized by
    :func:`rounds_within_budgets`, so no round runs that the per-round
    driver would have refused (under partial participation the sizing is
    conservative — a chunk may come up short and the loop re-probes on the
    realized ledger). The one semantic difference: ``eval_fn`` runs at
    chunk boundaries only (mid-chunk models never exist on the host), so
    evaluation happens every ~max(eval_every, R) rounds; train-loss theta*
    tracking stays per-round via the stacked metrics.
    """
    if rng is None:
        rng = np.random.default_rng(spec.seed)
    history = [] if history is None else history
    state, best = budget_train_loop(
        state=state, max_rounds=max_rounds, eval_fn=eval_fn,
        eval_every=eval_every, history=history, chunk_rounds=chunk_rounds,
        rounds_done=lambda s: s.rounds_done,
        exceeds=lambda s: exceeds_budgets(spec, s) is not None,
        safe_rounds=lambda s, cap: rounds_within_budgets(spec, s, cap)[0],
        run_single=lambda s: run_round(
            spec, s, round_batch(spec, sampler, rng), check_budgets=False),
        build_chunk=lambda start, n: jax.device_put(
            round_batches(spec, sampler, rng, n)),
        run_chunk=lambda s, chunk, n, prefetch: run_rounds(
            spec, s, chunk, n, check_budgets=False, prefetch=prefetch),
        run_tail=lambda s, chunk, r: run_round(
            spec, s, jax.tree.map(lambda x: x[r], chunk),
            check_budgets=False),
        eval_model=lambda s: eval_params(spec, s))
    return state, {
        "best": best, "rounds": state.rounds_done,
        "resource_spent": state.resource_spent,
        "max_epsilon": max_epsilon(spec, state),
        "history": history,
    }


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

def save_state(directory: str, state: FLState,
               extra: dict | None = None) -> None:
    """Persist an FLState (arrays + accountant snapshot) to ``directory``."""
    from repro.checkpoint import save_checkpoint
    meta = {
        "rho": [float(r) for r in state.rho],
        "steps": int(state.steps),
        "resource_spent": float(state.resource_spent),
        "rounds_done": int(state.rounds_done),
        **(extra or {}),
    }
    arrays = {"params": state.params, "opt_state": state.opt_state,
              "key": state.key}
    if state.residual is not None:
        arrays["residual"] = state.residual
    save_checkpoint(directory, arrays, step=state.rounds_done, extra=meta)


def load_state(directory: str, like: FLState) -> tuple[FLState, dict]:
    """Restore an FLState saved by :func:`save_state`.

    ``like`` supplies the pytree structure (e.g. a fresh ``init_state``).
    Returns (state, extra) with any caller metadata passed to save_state.
    """
    from repro.checkpoint import checkpoint_leaf_paths, load_checkpoint
    like_tree = {"params": like.params, "opt_state": like.opt_state,
                 "key": like.key}
    # ask for the residual only when BOTH sides have one: a dense-trained
    # checkpoint resumed under a compressor keeps like's zero residual, a
    # compressed checkpoint resumed dense drops it
    saved = checkpoint_leaf_paths(directory)
    has_residual = any(p == "residual" or p.startswith("residual/")
                       for p in saved)
    if like.residual is not None and has_residual:
        like_tree["residual"] = like.residual
    tree, _, extra = load_checkpoint(directory, like=like_tree)
    state = like.replace(
        params=tree["params"], opt_state=tree["opt_state"],
        key=jnp.asarray(tree["key"]),
        residual=(jnp.asarray(tree["residual"])
                  if "residual" in tree else like.residual),
        rho=np.asarray(extra["rho"], np.float64),
        steps=int(extra["steps"]),
        resource_spent=float(extra["resource_spent"]),
        rounds_done=int(extra["rounds_done"]))
    return state, extra
