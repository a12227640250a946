"""Plain DP-PASGD (arXiv:2003.12705, Eq. 7a-7b) over a model's loss, for
the check of ``correct``; it imports nothing of the program.

Each round: every client starts from the average, takes tau steps of

    g = grad loss(p);  g <- g * min(1, G / |g|) + sigma * n;  p <- p - lr g

with one global 2-norm over all leaves and n ~ N(0, I) drawn per leaf,
then the clients' parameters are averaged (Eq. 7b). Everything is
computed in float32 (matmuls at ``Precision.HIGHEST``); what a step
stores is held in the dtype the configuration states for the leaf, as
in any bfloat16 training: the gradient, the noised gradient, the update
-lr g and the new parameters are each rounded to it, and the average
once more.

The noise follows the DP-PASGD key schedule of this repository: the
state key ``PRNGKey(seed)`` splits once per round into (next, round);
the round key splits into one key per client, each client key into one
per local step, and each step key into one per leaf, in the order of the
flattened parameter tree.

The privacy ledger is zCDP: a step of sensitivity 2G/B under noise sigma
costs rho = (2G/B)^2 / (2 sigma^2); rho adds over steps, and
eps = rho + 2 sqrt(rho ln(1/delta)).

Beside the parameters, a run keeps their noise-free move, the drift:
the mean over clients of each client's summed lr * (clipped gradient),
added up over the rounds. The noise is the same draw on both sides, so
the program's parameters less the reference's, read along the drift,
show a gradient lost, added or reversed under a noise that is far larger
than it.

``variant`` puts a departure in the program's place, for the control and
the faults that the check must catch:
    "control"   matmul inputs and bfloat16 leaves rounded to float8_e4m3fn
    "half_batch"  only the first half of each client's batch rows
    "no_exchange" no average: each client keeps its own parameters
    "altered"   the largest leaf's clipped gradient reversed, its noise kept
    "noise_only"  every clipped gradient dropped after the norm, the noise
                kept
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOW = jnp.float8_e4m3fn
VARIANTS = ("reference", "control", "half_batch", "no_exchange", "altered",
            "noise_only")


def matmul(variant: str):
    def mm(eq, a, b):
        if variant == "control":
            a = a.astype(LOW).astype(jnp.float32)
            b = b.astype(LOW).astype(jnp.float32)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def store(x, dtype, variant: str):
    """Round a float32 leaf to its stated dtype (float8 in the control)."""
    if variant == "control" and dtype == jnp.bfloat16:
        x = x.astype(LOW)
    return x.astype(dtype)


def epsilon(steps: int, clip: float, batch: int, sigma: float, delta: float,
            dtype=np.float64) -> float:
    rho_step = dtype((2.0 * clip / batch) ** 2) / (dtype(2.0) * dtype(sigma) ** 2)
    rho = dtype(steps) * rho_step
    return float(rho + dtype(2.0) * np.sqrt(rho * dtype(math.log(1.0 / delta))))


class Reference:
    """Runs ``rounds`` DP-PASGD rounds of the loss ``loss(params, batch,
    cfg, mm)`` and records what the check compares."""

    def __init__(self, loss, cfg: dict, mix: dict, variant: str = "reference"):
        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}")
        self.variant = variant
        self.mix = mix
        lr, clip, sigma = (float(mix[k]) for k in ("lr", "clip", "sigma"))
        share = lr / int(mix["clients"])
        mm = matmul(variant)
        held = lambda x, dtype: store(x, dtype, variant).astype(jnp.float32)

        def step(params, drift, batch, key):
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
            value, grads = jax.value_and_grad(
                lambda p: loss(p, batch, cfg, mm))(p32)
            leaves, treedef = jax.tree.flatten(grads)
            leaves = [held(g, p.dtype)
                      for g, p in zip(leaves, jax.tree.leaves(params))]
            sq = [jnp.sum(g * g) for g in leaves]
            norm = jnp.sqrt(sum(sq))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
            keys = jax.random.split(key, len(leaves))
            sent = [g * scale for g in leaves]
            if variant == "noise_only":
                sent = [jnp.zeros_like(g) for g in sent]
            if variant == "altered":
                big = max(range(len(sent)), key=lambda i: sent[i].size)
                sent[big] = -sent[big]
            noisy = [g + sigma * jax.random.normal(k, g.shape, jnp.float32)
                     for g, k in zip(sent, keys)]
            drift = [(d.astype(jnp.float32) + share * g * scale).astype(d.dtype)
                     for d, g in zip(jax.tree.leaves(drift), leaves)]
            new = [store(p + held(-lr * held(g, p_in.dtype), p_in.dtype),
                         p_in.dtype, variant)
                   for p, g, p_in in zip(jax.tree.leaves(p32), noisy,
                                         jax.tree.leaves(params))]
            clipped = jnp.stack([jnp.sqrt(s) * scale for s in sq])
            return (jax.tree.unflatten(treedef, new),
                    jax.tree.unflatten(treedef, drift), value, norm, clipped)

        def average(clients):
            return jax.tree.map(
                lambda *xs: store(sum(x.astype(jnp.float32) for x in xs)
                                  / len(xs), xs[0].dtype, variant), *clients)

        self._step = jax.jit(step, donate_argnums=1)
        self._average = jax.jit(average)

    def run(self, params0, traffic, seed: int, rounds: int) -> dict:
        """Rounds 0..rounds-1 of ``traffic`` from ``params0``. Returns the
        per-round mean loss and pre-clip norm, the first step's clipped
        per-leaf gradient norms, the parameters and the drift after round 1
        and after the last round (client 0's replica, on the host), and
        epsilon."""
        mix = self.mix
        params = jax.tree.map(lambda x: store(x.astype(jnp.float32), x.dtype,
                                              self.variant), params0)
        state = jax.random.PRNGKey(seed)
        out = {"loss": [], "gnorm": [], "params": [], "drift": []}
        # bfloat16 holds the drift's direction and size to 3 digits, at
        # half the memory of float32 beside the reference's own state
        drift = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.bfloat16),
                             params0)
        first_clipped = None
        half = self.variant == "half_batch"
        starts = [params] * traffic.clients
        for r in range(rounds):
            state, sub = jax.random.split(state)
            client_keys = jax.random.split(sub, traffic.clients)
            batch = traffic.round_batch(r)
            ends, losses, norms = [], [], []
            for c in range(traffic.clients):
                p = starts[c]
                for t, k in enumerate(jax.random.split(client_keys[c],
                                                       traffic.tau)):
                    rows = {n: v[c, t] for n, v in batch.items()}
                    if half:
                        rows = {n: v[: v.shape[0] // 2] for n, v in rows.items()}
                    p, drift, value, norm, clipped = self._step(p, drift,
                                                                rows, k)
                    if first_clipped is None:
                        first_clipped = np.asarray(clipped)
                    losses.append(value)
                    norms.append(norm)
                ends.append(p)
            starts = (ends if self.variant == "no_exchange"
                      else [self._average(ends)] * traffic.clients)
            params = starts[0]
            out["loss"].append(float(np.mean([float(x) for x in losses])))
            out["gnorm"].append(float(np.mean([float(x) for x in norms])))
            if r == 0 or r == rounds - 1:
                out["params"].append(jax.device_get(params))
                out["drift"].append(jax.device_get(drift))
        # the noise drawn on the device has the float32 sigma's scale
        steps = rounds * int(mix["tau"])
        out["epsilon"] = epsilon(
            steps, float(mix["clip"]), int(mix["batch"]),
            float(np.float32(mix["sigma"])), float(mix["delta"]),
            np.float32 if self.variant == "control" else np.float64)
        out["first_clipped"] = first_clipped
        return out
