"""Serving driver: continuous-batching engine by default, the static
prefill+decode batch kept as ``--static`` baseline.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
        --requests 8 --prompt-len 32 --gen 16

Serving a federated model: pass ``--fl-checkpoint DIR`` pointing at a
``repro.api.save_state`` checkpoint (e.g. from ``repro.launch.train
--save DIR``) and the driver loads it through ``FederationSpec`` /
``FLState`` / ``load_state`` and serves the aggregated model
(``repro.api.eval_params``) instead of random init.

Both paths warm up (compile) before the timed run, so ``tokens_per_s``
is steady-state; compile time is reported separately as ``compile_s``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_variant
from repro.models.transformer import Transformer


def load_federated_params(model: Transformer, directory: str):
    """The single serving model out of a DP-PASGD checkpoint directory.

    Reads the spec scalars the training launcher stored next to the arrays
    (``federation_meta``) and loads ONLY the params leaves — no optimizer
    state, no error-feedback residual, no C-way replica allocation — so
    checkpoints from any optimizer and any compressor serve alike at
    params-sized memory. The client axis collapses exactly as
    ``repro.api.eval_params``: any replica under ``full_average``, the
    cross-client mean under ``local_only``. Buffered-async checkpoints
    (``repro.asyncfl.save_async_state``) store the already-collapsed
    server model under ``global_params`` — serve that, never the K
    in-flight slot storages their ``params`` leaves hold.
    """
    from repro.api import collapse_clients
    from repro.checkpoint import checkpoint_leaf_paths, load_checkpoint

    with open(os.path.join(directory, "meta.json")) as f:
        meta = json.load(f)["extra"]
    # path donor only: load_checkpoint matches leaves by path, so the
    # single-replica init supplies the params/<leaf> paths and the stored
    # (C, ...) arrays come back untouched
    donor = model.init(jax.random.PRNGKey(0))
    if any(p.split("/", 1)[0] == "global_params"
           for p in checkpoint_leaf_paths(directory)):
        tree, _, _ = load_checkpoint(directory, like={"global_params": donor})
        return tree["global_params"]
    tree, _, _ = load_checkpoint(directory, like={"params": donor})
    return collapse_clients(tree["params"],
                            meta.get("topology", "full_average"))


@functools.lru_cache(maxsize=64)
def _decode_fns(model: Transformer, temperature: float, max_len: int):
    """The static path's two jitted programs: batch prefill, and ONE
    fused sample+decode step — greedy and sampled decode both dispatch
    once per token (the PRNG split happens inside the program, in the
    same order the old host loop used, so sampled outputs are
    unchanged). Cached per (model, temperature, max_len) so repeated
    generate/serve_static calls reuse the compiled programs instead of
    paying a fresh trace+compile each call."""
    prefill = jax.jit(lambda p, t, pre: model.prefill(p, t, pre,
                                                      max_len=max_len))

    def step(params, caches, logits, pos, key):
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = tok.astype(jnp.int32)
        logits, caches = model.decode_step(params, caches, tok, pos)
        return logits, caches, tok, key

    return prefill, jax.jit(step, donate_argnums=(1,))


def generate(model: Transformer, params, prompts, gen_tokens: int,
             prefix=None, temperature: float = 0.0, seed: int = 0):
    """prompts (B, S) int32 -> generated (B, gen_tokens) int32."""
    b, s = prompts.shape
    max_len = s + gen_tokens + (model.cfg.prefix_len or 0)
    prefill, step = _decode_fns(model, temperature, max_len)

    logits, caches, pos = prefill(params, prompts, prefix)
    key = jax.random.PRNGKey(seed)
    outs = []
    for i in range(gen_tokens):
        logits, caches, tok, key = step(params, caches, logits, pos + i,
                                        key)
        outs.append(tok)
    return jnp.stack(outs, axis=1)


def _run_static(model, params, args, cfg):
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)),
        jnp.int32)
    prefix = None
    if cfg.prefix_len:
        prefix = jnp.asarray(
            rng.standard_normal((args.batch, cfg.prefix_len, cfg.d_model)),
            jnp.float32) * 0.02

    t0 = time.perf_counter()
    out = generate(model, params, prompts, args.gen, prefix,
                   args.temperature)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    out = generate(model, params, prompts, args.gen, prefix,
                   args.temperature)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    steady = t2 - t1
    return {
        "mode": "static",
        "generated_shape": list(out.shape),
        "tokens_per_s": round(args.batch * args.gen / steady, 1),
        "compile_s": round((t1 - t0) - steady, 3),
        "sample": np.asarray(out[0, :8]).tolist(),
    }


def _run_engine(model, params, args, cfg):
    from repro.serve import (SlotEngine, poisson_workload, serve_continuous)

    max_len = args.prompt_len + args.gen
    engine = SlotEngine(model, params, n_slots=args.batch, max_len=max_len,
                        block_size=args.block_size,
                        temperature=args.temperature)
    workload = poisson_workload(args.requests, args.rate, cfg.vocab,
                                prompt_lens=(args.prompt_len,),
                                gen_lens=(args.gen,))
    engine.warmup(buckets=[r.prompt_len for r in workload])
    report = serve_continuous(engine, workload)
    first = report.requests[0]
    return {
        "mode": "continuous",
        **report.summary(),
        "sample": first.out[:8],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="pre-engine baseline: one static prefill+decode "
                         "batch (forced for prefix-conditioned archs)")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (engine) / batch rows (static)")
    ap.add_argument("--requests", type=int, default=8,
                    help="workload size of the engine mode")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (requests/sim-second)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged KV block length (0: one block per slot)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--fl-checkpoint", default=None,
                    help="serve the aggregated model of a repro.api "
                         "save_state checkpoint instead of random init")
    from repro.launch.env import (add_env_profile_args, apply_env_profile,
                                  enable_compile_cache)
    add_env_profile_args(ap)
    args = ap.parse_args(argv)
    apply_env_profile(args.env_profile, host_devices=args.host_devices)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = Transformer(cfg)
    if args.fl_checkpoint:
        params = load_federated_params(model, args.fl_checkpoint)
    else:
        params = model.init(jax.random.PRNGKey(0))

    if args.static or cfg.prefix_len:
        result = _run_static(model, params, args, cfg)
    else:
        result = _run_engine(model, params, args, cfg)
    print(json.dumps({
        "arch": cfg.name, "batch": args.batch,
        "params": "federated" if args.fl_checkpoint else "random-init",
        **result,
    }, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
