"""Slot-based continuous-batching decode engine over a paged KV cache.

The engine owns a fixed-capacity decode batch of ``n_slots`` slots. Every
attention layer reads/writes a preallocated physical block pool through a
per-slot block table (:func:`repro.models.attention.paged_decode_attention`);
recurrent layers (mamba2 / rwkv6 / rwkv channel-mix) keep per-slot state
rows — their state is O(1) per slot, there is nothing to page. The whole
decode step — token sample, cache update, per-slot done flags — is ONE
jitted program with the engine state donated, so steady-state serving is
one dispatch per generated-token wavefront regardless of temperature.

Exactness contract (pinned by ``tests/test_serve.py``): with greedy
decode the engine emits byte-identical tokens to the static
``launch.serve.generate`` path for every request, including requests
admitted mid-stream. This holds because (a) paged attention gathers
blocks in position order, so with natural-layout prefill the assembled
keys equal the dense cache bitwise, and (b) per-row batched compute is
bitwise independent of the other rows in the batch on this backend.

Paging: each admitted slot gets ``blocks_per_slot`` physical blocks from
a free list (shuffled by churn — the block table is real indirection,
not an identity map). One extra scratch block is reserved: released
slots' table rows all point at it, so their continued in-program decode
writes land somewhere harmless and are never read (the ``p <= pos``
visibility mask only exposes positions the owner actually wrote).

Right-padded bucketed prefill is exact for attention layers (pad-position
cache garbage is masked until decode overwrites it) but NOT for
recurrent state, which consumes pad tokens. The engine therefore pads
prompts up to power-of-two buckets only for pure-attention archs and
requires exact-length prefill groups otherwise (``pad_ok``).

Checkpoint hot-swap: :meth:`SlotEngine.swap_params` installs new params
via a param-donating jitted copy (same shapes -> no recompile, no second
resident copy). In-flight slots keep their KV built under the old
params; only tokens sampled after the swap boundary change.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.requests import Request


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def model_pads_ok(model) -> bool:
    """True when every layer is pure attention (no recurrent mixer, no
    rwkv channel-mix ffn) — the archs for which right-padded bucketed
    prefill is exact."""
    return all(ls.mixer == "attn" and ls.ffn != "rwkv_cm"
               for seg in model.cfg.segments for ls in seg.pattern)


class SlotEngine:
    """Continuous-batching decode engine. See module docstring.

    Parameters: ``n_slots`` decode batch capacity; ``max_len`` the cache
    span every slot must cover (prompt + generation); ``block_size``
    physical KV block length (default: one block spans ``max_len``, the
    dense-identical configuration); ``eos`` optional early-stop token;
    ``temperature``/``seed`` sampling controls baked into the step
    program; ``prefill_batch`` caps prefill rows per admission group
    (groups pad to the next power of two of their size, so recompiles
    are bounded by buckets x log2(prefill_batch), not group sizes).
    """

    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 block_size: int = 0, eos: int | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_batch: int = 0):
        if model.cfg.prefix_len:
            raise ValueError("SlotEngine serves token-only archs "
                             f"(prefix_len={model.cfg.prefix_len})")
        self.model = model
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size) or self.max_len
        self.blocks_per_slot = -(-self.max_len // self.block_size)
        self.eos = eos
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.prefill_batch = int(prefill_batch) or self.n_slots
        self.pad_ok = model_pads_ok(model)

        n_pool = self.n_slots * self.blocks_per_slot
        self.scratch_block = n_pool  # last pool index, never allocated
        self._free_blocks = list(range(n_pool))
        self._free_slots = list(range(self.n_slots))
        self._table_np = np.full((self.n_slots, self.blocks_per_slot),
                                 self.scratch_block, np.int32)
        self._table = jnp.asarray(self._table_np)
        self._slot_req: dict[int, Request] = {}
        self._active_np = np.zeros(self.n_slots, bool)

        self._params = params
        self._state = {
            "caches": model.init_paged_cache(self.n_slots, n_pool + 1,
                                             self.block_size),
            "logits": jnp.zeros((self.n_slots, model.cfg.vocab),
                                jnp.float32),
            "pos": jnp.zeros(self.n_slots, jnp.int32),
            "gen": jnp.zeros(self.n_slots, jnp.int32),
            "max_gen": jnp.ones(self.n_slots, jnp.int32),
            "active": jnp.zeros(self.n_slots, bool),
            "rid": jnp.zeros(self.n_slots, jnp.int32),
        }

        self._step_c = jax.jit(self._step_fn, donate_argnums=(1,))
        self._prefill_c = jax.jit(model.prefill_at)
        self._insert_c = jax.jit(self._insert_fn, donate_argnums=(0,))
        self._swap_c = jax.jit(
            lambda old, new: jax.tree.map(jnp.copy, new),
            donate_argnums=(0,))

        self.compile_s = 0.0
        self.steps = 0
        self.tokens_out = 0
        self.swaps = 0
        self._occupancy_sum = 0

    # ---------------------------------------------------------------- jit
    def _step_fn(self, params, state, table):
        """ONE decode wavefront: sample every slot's next token from its
        held logits, run the paged decode step, update gen counts and
        done flags. Inactive slots sample token 0 and write to scratch."""
        logits, active = state["logits"], state["active"]
        if self.temperature > 0:
            base = jax.random.PRNGKey(self.seed)
            keys = jax.vmap(lambda r, g: jax.random.fold_in(
                jax.random.fold_in(base, r), g))(state["rid"], state["gen"])
            tok = jax.vmap(lambda k, l: jax.random.categorical(
                k, l / self.temperature))(keys, logits)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = jnp.where(active, tok.astype(jnp.int32), 0)
        new_logits, caches = self.model.decode_step(
            params, state["caches"], tok, state["pos"], table)
        gen = state["gen"] + active.astype(jnp.int32)
        hit = gen >= state["max_gen"]
        if self.eos is not None:
            hit |= tok == jnp.int32(self.eos)
        done = active & hit
        new_state = {
            "caches": caches,
            "logits": new_logits,
            "pos": state["pos"] + 1,
            "gen": gen,
            "max_gen": state["max_gen"],
            "active": active & ~done,
            "rid": state["rid"],
        }
        return new_state, tok, done

    def _insert_fn(self, state, pre, logits, table_rows, slots, next_pos,
                   max_gen, rid, active):
        """Scatter one prefill batch into the engine state. Padded
        duplicate rows carry identical values, so repeated-index scatters
        commute (deterministic)."""
        return {
            "caches": self.model.insert_prefill(state["caches"], pre,
                                                table_rows, slots),
            "logits": state["logits"].at[slots].set(
                logits.astype(state["logits"].dtype)),
            "pos": state["pos"].at[slots].set(next_pos),
            "gen": state["gen"].at[slots].set(0),
            "max_gen": state["max_gen"].at[slots].set(max_gen),
            "active": state["active"].at[slots].set(active),
            "rid": state["rid"].at[slots].set(rid),
        }

    # --------------------------------------------------------------- host
    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def n_active(self) -> int:
        return int(self._active_np.sum())

    def bucket_len(self, n: int) -> int:
        """Prefill bucket for an n-token prompt: next power of two for
        pad-safe archs, the exact length otherwise."""
        return min(_pow2_ceil(n), self.max_len) if self.pad_ok else n

    def admit(self, reqs: list[Request]) -> None:
        """Admit one prefill group. All requests must share a bucket
        (scheduler's job); the group is padded to a power-of-two row
        count by repeating row 0, bounding compiles per bucket."""
        if not reqs:
            return
        if len(reqs) > self.free_slots:
            raise ValueError(f"admitting {len(reqs)} requests with only "
                             f"{self.free_slots} free slots")
        if len(reqs) > self.prefill_batch:
            raise ValueError(f"group of {len(reqs)} exceeds prefill_batch="
                             f"{self.prefill_batch}")
        buckets = {self.bucket_len(r.prompt_len) for r in reqs}
        if len(buckets) != 1:
            raise ValueError(f"mixed prefill buckets in one group: "
                             f"{sorted(buckets)}")
        bucket = buckets.pop()
        for r in reqs:
            if r.prompt_len + r.max_gen > self.max_len:
                raise ValueError(
                    f"request {r.rid}: {r.prompt_len}+{r.max_gen} tokens "
                    f"exceed max_len={self.max_len}")

        # pad rows to the next power of two of the group size (not the
        # full prefill batch): single-slot joins at saturation pay a
        # 1-row prefill, and compiles stay bounded by
        # |buckets| x log2(prefill_batch) programs (all warmed)
        n, p = len(reqs), min(self.prefill_batch, _pow2_ceil(len(reqs)))
        toks = np.zeros((p, bucket), np.int32)
        lengths = np.empty(p, np.int32)
        slots = np.empty(p, np.int32)
        rows = np.empty((p, self.blocks_per_slot), np.int32)
        next_pos = np.empty(p, np.int32)
        max_gen = np.empty(p, np.int32)
        rid = np.empty(p, np.int32)
        for i, r in enumerate(reqs):
            s = self._free_slots.pop()
            blocks = [self._free_blocks.pop()
                      for _ in range(self.blocks_per_slot)]
            self._table_np[s] = blocks
            toks[i, :r.prompt_len] = r.tokens
            lengths[i] = r.prompt_len
            slots[i] = s
            rows[i] = blocks
            next_pos[i] = r.prompt_len
            max_gen[i] = r.max_gen
            rid[i] = r.rid
            self._slot_req[s] = r
            self._active_np[s] = True
        for i in range(n, p):  # duplicate row 0: identical-value scatters
            toks[i], lengths[i], slots[i] = toks[0], lengths[0], slots[0]
            rows[i], next_pos[i] = rows[0], next_pos[0]
            max_gen[i], rid[i] = max_gen[0], rid[0]

        logits, pre, pos = self._prefill_c(
            self._params, jnp.asarray(toks), jnp.asarray(lengths))
        self._table = jnp.asarray(self._table_np)
        self._state = self._insert_c(
            self._state, pre, logits, jnp.asarray(rows), jnp.asarray(slots),
            jnp.asarray(next_pos), jnp.asarray(max_gen), jnp.asarray(rid),
            jnp.ones(p, bool))

    def step(self):
        """One decode wavefront. Appends each live slot's sampled token to
        its request's ``out`` and returns ``(emitted, finished)``: the
        requests that received a token this step, and the subset whose
        slot was recycled (EOS or generation budget hit)."""
        live = np.nonzero(self._active_np)[0]
        self._state, tok, done = self._step_c(self._params, self._state,
                                              self._table)
        tok = np.asarray(tok)
        done = np.asarray(done)
        emitted = []
        for s in live:
            r = self._slot_req[int(s)]
            r.out.append(int(tok[s]))
            emitted.append(r)
        finished = [self._release(int(s)) for s in np.nonzero(done)[0]]
        if finished:
            self._table = jnp.asarray(self._table_np)
        self.steps += 1
        self._occupancy_sum += len(emitted)
        self.tokens_out += len(emitted)
        return emitted, finished

    def _release(self, s: int) -> Request:
        self._free_blocks.extend(int(b) for b in self._table_np[s])
        self._table_np[s] = self.scratch_block
        self._active_np[s] = False
        self._free_slots.append(s)
        return self._slot_req.pop(s)

    def swap_params(self, new_params) -> None:
        """Install a new checkpoint without dropping in-flight slots: a
        param-donating jitted copy (same shapes -> no recompile, the old
        buffers are freed as the copy lands). Tokens sampled after this
        call use the new params; each slot's existing KV was built under
        the old ones — the standard continuous-serving boundary."""
        old_td = jax.tree.structure(self._params)
        new_td = jax.tree.structure(new_params)
        if old_td != new_td:
            raise ValueError("hot-swap params tree mismatch: "
                             f"{old_td} != {new_td}")
        self._params = self._swap_c(self._params, new_params)
        self.swaps += 1

    def warmup(self, buckets=()) -> float:
        """Compile the step and the prefill/insert path for each bucket
        before serving, so steady-state numbers exclude compile time.
        Runs against the live state: all slots are inactive and every
        table row points at the scratch block, so the warm-up writes are
        invisible (active=False inserts never activate a slot)."""
        t0 = time.perf_counter()
        self._state, tok, _ = self._step_c(self._params, self._state,
                                           self._table)
        jax.block_until_ready(tok)
        row_counts = []
        p = 1
        while p < self.prefill_batch:
            row_counts.append(p)
            p *= 2
        row_counts.append(self.prefill_batch)
        for bucket in sorted({self.bucket_len(b) for b in buckets}):
            for p in row_counts:
                toks = jnp.zeros((p, bucket), jnp.int32)
                lengths = jnp.ones(p, jnp.int32)
                logits, pre, _ = self._prefill_c(self._params, toks, lengths)
                rows = jnp.full((p, self.blocks_per_slot),
                                self.scratch_block, jnp.int32)
                zeros = jnp.zeros(p, jnp.int32)
                self._state = self._insert_c(
                    self._state, pre, logits, rows, zeros, zeros,
                    jnp.ones(p, jnp.int32), zeros, jnp.zeros(p, bool))
        jax.block_until_ready(self._state["logits"])
        self.compile_s = time.perf_counter() - t0
        return self.compile_s

    def stats(self) -> dict:
        return {
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "occupancy_mean": round(self._occupancy_sum / self.steps /
                                    self.n_slots, 3) if self.steps else 0.0,
            "swaps": self.swaps,
            "compile_s": round(self.compile_s, 3),
            "free_slots": self.free_slots,
        }
