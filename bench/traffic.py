"""The one traffic generator: token batches of a federated round, from the
seed and the mix's parameters (``bench/traffic/<name>.json``).

Every client draws its tokens from a Zipf law over the vocabulary, each
client under its own permutation of the token ids (clients write about
different things: the split is non-iid). A round's batch depends only on
(seed, round, client), so the program and the reference see the same
rows, and every seed gets the same shapes.
"""
from __future__ import annotations

import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit PRNG seed for JAX from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        self.clients = int(mix["clients"])
        self.batch = int(mix["batch"])
        self.seq = int(mix["seq"])
        self.tau = int(mix["tau"])
        tokens = mix["tokens"]
        if tokens["kind"] != "zipf":
            raise ValueError(f"unknown token law {tokens['kind']!r}")
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(tokens["exponent"])
        self._cdf = np.cumsum(p / p.sum())
        self._perm: dict[int, np.ndarray] = {}
        self._calls = 0

    @property
    def tokens_per_round(self) -> int:
        return self.clients * self.tau * self.batch * self.seq

    def _permutation(self, client: int) -> np.ndarray:
        if client not in self._perm:
            rng = np.random.default_rng([self.seed, 1, client])
            self._perm[client] = rng.permutation(self.vocab).astype(np.int32)
        return self._perm[client]

    def client_batch(self, round_idx: int, client: int) -> dict:
        """{"tokens", "labels"}: (tau, B, S) int32 each."""
        rng = np.random.default_rng([self.seed, 2, round_idx, client])
        shape = (self.tau, self.batch, self.seq + 1)
        ranks = np.searchsorted(self._cdf, rng.random(shape), side="right")
        ids = self._permutation(client)[np.minimum(ranks, self.vocab - 1)]
        return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}

    def round_batch(self, round_idx: int) -> dict:
        """(C, tau, B, S) leaves: the round's batch for every client."""
        per = [self.client_batch(round_idx, c) for c in range(self.clients)]
        return {k: np.stack([b[k] for b in per]) for k in per[0]}

    def sampler(self, client: int, tau: int, rng=None) -> dict:
        """The program's sampler protocol, ``sampler(client, tau, rng)``:
        called for clients 0..C-1 in turn each round; ``rng`` is unused,
        the batch follows from (seed, round, client)."""
        del rng
        round_idx, expect = divmod(self._calls, self.clients)
        if client != expect or tau != self.tau:
            raise ValueError(f"sampler called out of order: client {client} "
                             f"tau {tau}, expected client {expect} tau "
                             f"{self.tau}")
        self._calls += 1
        return self.client_batch(round_idx, client)
