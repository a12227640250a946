import os
import sys as _sys
# only effective before jax initializes (the intended `python -m` entry);
# when imported into a live process (tests), mutating XLA_FLAGS would do
# nothing for jax and only pollute the env for later readers. Skipped when
# the user steers the device count themselves via --env-profile/--host-devices
# (repro.launch.env re-exec) or an explicit XLA_FLAGS — never clobber those.
if ("jax" not in _sys.modules
        and os.environ.get("REPRO_ENV_PROFILE_APPLIED") != "1"
        and "--env-profile" not in _sys.argv
        and "--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512"
                               ).strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production mesh, and extract the roofline terms
from the compiled artifact. No tensor is ever allocated (ShapeDtypeStruct
stand-ins only); the 512 host devices above exist only for this module.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out-dir experiments/dryrun
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import (
    ASSIGNED_ARCHS,
    get_arch,
    get_shape,
    input_specs,
    supports_shape,
)
from repro.api import FederationSpec, get_engine
from repro.launch.mesh import (
    default_n_clients,
    make_federated_mesh,
    make_production_mesh,
    make_serving_mesh,
)
from repro.models.sharding import (
    axis_rules,
    named_sharding_tree,
    serve_rules,
    train_rules,
)
from repro.models.transformer import Transformer
from repro.optim import sgd
from repro.utils.hlo import analyze_hlo, cost_analysis_dict
from repro.utils.roofline import (
    RooflineTerms,
    active_params,
    model_flops_estimate,
)

HBM_PER_CHIP = 16 * 1024 ** 3   # v5e: 16 GiB


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _stack_clients(sds_tree, c):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((c,) + x.shape, x.dtype), sds_tree)


def _prepend_client_axes(axes_tree):
    return jax.tree.map(lambda t: ("client",) + t, axes_tree,
                        is_leaf=lambda t: isinstance(t, tuple))


def _spec_sharding(mesh, tree, spec_fn):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda x: NamedSharding(mesh, spec_fn(x)), tree)


def _replicated(mesh, tree):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)


def param_count(params_sds) -> int:
    import math
    return sum(math.prod(x.shape) if x.shape else 1
               for x in jax.tree.leaves(params_sds))


# ---------------------------------------------------------------------------
# lowering builders
# ---------------------------------------------------------------------------

ACT_BUDGET_BYTES = 5e9   # per-device activation-carry budget for training


def _auto_microbatches(cfg, shape, n_clients: int, replica: int) -> int:
    """Split each client's local batch into sequential microbatches so the
    per-device remat carry (L x B_micro x S x d x 2B) stays under budget."""
    per_client_b = shape.global_batch // n_clients
    n_layers = sum(s.n_steps * len(s.pattern) for s in cfg.segments)
    d_act = cfg.d_model * (2 if cfg.ssm_state else 1)
    seq = shape.seq_len + cfg.prefix_len
    bytes_per_seq = seq * d_act * 2 * n_layers
    b_micro_dev = max(1, int(ACT_BUDGET_BYTES // bytes_per_seq))
    need = max(1, -(-per_client_b // (replica * b_micro_dev)))  # ceil
    # round up to a divisor of the per-client batch
    n_mb = need
    while per_client_b % n_mb:
        n_mb += 1
    return min(n_mb, per_client_b)


def lower_train(cfg, shape, mesh, n_clients: int, tau: int, lr: float = 0.1,
                microbatches: int | None = None,
                grad_accumulate: str = "stack",
                gather_weights: bool = False, ddp: bool = False):
    """Lower one DP-PASGD round (tau local steps + 1 averaging) — Eq. 7a-7b."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    fed_mesh = make_federated_mesh(mesh, n_clients)
    model = Transformer(cfg)
    key = jax.random.PRNGKey(0)
    params1 = jax.eval_shape(model.init, key)
    axes = model.param_axes()
    opt = sgd(lr)
    opt1 = jax.eval_shape(opt.init, params1)

    params_c = _stack_clients(params1, n_clients)
    opt_c = _stack_clients(opt1, n_clients)
    batch = input_specs(cfg, shape, n_clients=n_clients, tau=tau)

    replica = fed_mesh.shape["replica"]
    n_mb = microbatches or _auto_microbatches(cfg, shape, n_clients, replica)
    spec = FederationSpec(n_clients=n_clients, tau=tau, loss_fn=model.loss_fn,
                          optimizer=opt, engine="vmap", clip_norm=1.0,
                          dp=True, num_microbatches=n_mb,
                          vmap_microbatches=False,
                          grad_accumulate=grad_accumulate)
    round_step = get_engine("vmap")(spec)

    rules = train_rules()
    if gather_weights:
        rules["wg"] = None
    if ddp:
        # replicate params within the client group (no FSDP): removes the
        # contracting-dim sharding and its per-token activation all-reduces;
        # only valid when params fit replicated (<= ~6 GiB/device)
        rules["fsdp"] = None
        rules["wg"] = None
    with axis_rules(fed_mesh, rules):
        p_sh = named_sharding_tree(fed_mesh, _prepend_client_axes(axes),
                                   params_c)
        o_sh = jax.tree.map(
            lambda x: NamedSharding(fed_mesh, P("client")), opt_c)
        b_sh = jax.tree.map(
            lambda x: NamedSharding(
                fed_mesh,
                P("client", None, "replica")), batch)
        key_c = jax.random.PRNGKey(0)
        sig = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
        k_sh = NamedSharding(fed_mesh, P())
        s_sh = NamedSharding(fed_mesh, P("client"))

        jitted = jax.jit(round_step,
                         in_shardings=(p_sh, o_sh, b_sh, k_sh, s_sh),
                         out_shardings=(p_sh, o_sh, None))
        lowered = jitted.lower(params_c, opt_c, batch, key_c, sig)
    n_params = param_count(params1)
    tokens = shape.global_batch * shape.seq_len * tau
    return lowered, n_params, tokens, n_mb


def lower_prefill(cfg, shape, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    serve_mesh = make_serving_mesh(mesh)
    model = Transformer(cfg)
    params1 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_axes()
    batch = input_specs(cfg, shape)
    fsdp = _needs_param_sharding(params1, serve_mesh)

    with axis_rules(serve_mesh, serve_rules(fsdp_over_data=fsdp)):
        p_sh = named_sharding_tree(serve_mesh, axes, params1)
        b_sh = {k: NamedSharding(serve_mesh, P("data"))
                for k in batch}

        def prefill_step(params, batch):
            return model.prefill(params, batch["tokens"],
                                 batch.get("prefix"),
                                 max_len=shape.seq_len)

        jitted = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(params1, batch)
    n_params = param_count(params1)
    tokens = shape.global_batch * shape.seq_len
    return lowered, n_params, tokens


def lower_decode(cfg, shape, mesh, donate_cache: bool = True):
    from jax.sharding import NamedSharding, PartitionSpec as P
    serve_mesh = make_serving_mesh(mesh)
    model = Transformer(cfg)
    params1 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_axes()
    b = shape.global_batch
    caches = jax.eval_shape(lambda: model.init_cache(b, shape.seq_len))
    cache_axes = model.cache_axes()
    shard_seq = shape.name == "long_500k"
    fsdp = _needs_param_sharding(params1, serve_mesh)
    rules = serve_rules(fsdp_over_data=fsdp, shard_seq=shard_seq)
    # KV-cache placement: shard kv heads over the model axis when they
    # divide it; otherwise shard the cache sequence dim over "model"
    # (decode attention reduces over seq -> all-reduce, still cheap).
    kv_divides = cfg.n_kv_heads % serve_mesh.shape["model"] == 0
    if shard_seq:
        rules["batch"] = None     # batch=1: the data axis shards the cache seq
        rules["cache_seq"] = ("data", "model") if not kv_divides else "data"
        rules["kv_tp"] = "model" if kv_divides else None
    elif not kv_divides:
        rules["kv_tp"] = None
        rules["cache_seq"] = "model"

    with axis_rules(serve_mesh, rules):
        p_sh = named_sharding_tree(serve_mesh, axes, params1)
        c_sh = named_sharding_tree(serve_mesh, cache_axes, caches)
        tok = jax.ShapeDtypeStruct((b,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        t_sh = NamedSharding(serve_mesh,
                             P("data") if (not shard_seq and b % serve_mesh.shape["data"] == 0)
                             else P())

        def serve_step(params, caches, tokens, pos):
            return model.decode_step(params, caches, tokens, pos)

        jitted = jax.jit(serve_step,
                         in_shardings=(p_sh, c_sh, t_sh,
                                       NamedSharding(serve_mesh, P())),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,) if donate_cache else ())
        lowered = jitted.lower(params1, caches, tok, pos)
    n_params = param_count(params1)
    tokens = b   # one new token per sequence
    return lowered, n_params, tokens


def _needs_param_sharding(params_sds, serve_mesh) -> bool:
    """Shard params over the data axis too (serving FSDP) when a pure-TP
    placement would exceed ~60% of one chip's HBM."""
    n = param_count(params_sds)
    bytes_per_chip_tp = n * 2 / serve_mesh.shape["model"]
    return bytes_per_chip_tp > 0.6 * HBM_PER_CHIP


# ---------------------------------------------------------------------------
# mesh placement report (repro.mesh plane)
# ---------------------------------------------------------------------------


def mesh_report(archs, n_clients: int, n_devices: int,
                device_mem_bytes: int | None = None) -> list[dict]:
    """Per-arch 2D-mesh placement audit: one client replica's param +
    opt-state bytes (abstract, from ``configs.shapes.replica_footprint_bytes``)
    against the per-device budget under the mesh ``repro.mesh.placement``
    would choose — the static answer to "does engine='auto' pick mesh_2d
    here, and does each model shard actually fit?".
    """
    from repro.configs.shapes import replica_footprint_bytes
    from repro.mesh.placement import (
        choose_engine,
        default_mesh_shape,
        device_memory_budget,
    )
    from repro.optim import sgd

    # the fake CPU devices stand for the described v5e target
    budget = device_memory_budget(default=device_mem_bytes or HBM_PER_CHIP)
    opt = sgd(0.1)
    rows = []
    for arch in archs:
        cfg = get_arch(arch)
        replica = replica_footprint_bytes(cfg, optimizer=opt)
        engine = choose_engine(n_clients, n_devices, replica_bytes=replica,
                               hbm_bytes=budget)
        dc, dm = default_mesh_shape(n_clients, n_devices,
                                    replica_bytes=replica, hbm_bytes=budget)
        per_device = -(-replica // dm)    # ceil: largest model shard
        rows.append({
            "arch": arch,
            "replica_bytes": int(replica),
            "engine": engine,
            "mesh_shape": [dc, dm],
            "per_device_bytes": int(per_device),
            "budget_bytes": int(budget),
            "fits": bool(per_device <= budget),
            "n_clients": n_clients,
            "n_devices": n_devices,
        })
    return rows


def print_mesh_report(rows) -> None:
    hdr = (f"{'arch':<22} {'replica':>10} {'engine':>10} {'mesh':>7} "
           f"{'per-dev':>10} {'budget':>10} fits")
    print(hdr)
    print("-" * len(hdr))
    gib = 1024 ** 3
    for r in rows:
        dc, dm = r["mesh_shape"]
        print(f"{r['arch']:<22} {r['replica_bytes'] / gib:>9.2f}G "
              f"{r['engine']:>10} {dc:>3}x{dm:<3} "
              f"{r['per_device_bytes'] / gib:>9.2f}G "
              f"{r['budget_bytes'] / gib:>9.2f}G "
              f"{'yes' if r['fits'] else 'NO'}")


# ---------------------------------------------------------------------------
# run + report
# ---------------------------------------------------------------------------

OPTS = ("scan_accum", "onehot_embed", "causal_buckets", "moe_dense",
        "donate_cache", "gather_weights", "ddp")


def apply_opts(cfg, opts: tuple[str, ...]):
    """Beyond-paper §Perf optimizations, applied on top of the baseline."""
    from dataclasses import replace
    kw = {}
    if "onehot_embed" in opts:
        kw["embed_impl"] = "one_hot"
    if "causal_buckets" in opts:
        kw["causal_buckets"] = True
    if "moe_dense" in opts:
        kw["moe_impl"] = "dense"
    return replace(cfg, **kw) if kw else cfg


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            n_clients: int | None = None, tau: int = 4,
            compile_it: bool = True, microbatches: int | None = None,
            opts: tuple[str, ...] = ()) -> dict:
    cfg = apply_opts(get_arch(arch), opts)
    shape = get_shape(shape_name)
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if shape.kind == "train":
        c = default_n_clients(mesh, n_clients)
        lowered, n_params, tokens, n_mb = lower_train(
            cfg, shape, mesh, c, tau, microbatches=microbatches,
            grad_accumulate="scan" if "scan_accum" in opts else "stack",
            gather_weights="gather_weights" in opts, ddp="ddp" in opts)
        extra = {"n_clients": c, "tau": tau, "microbatches": n_mb,
                 "opts": list(opts)}
    elif shape.kind == "prefill":
        lowered, n_params, tokens = lower_prefill(cfg, shape, mesh)
        extra = {}
    else:
        lowered, n_params, tokens = lower_decode(
            cfg, shape, mesh, donate_cache="no_donate" not in opts)
        extra = {"opts": list(opts)} if opts else {}
    t_lower = time.time() - t0

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "status": "lowered",
        "n_params": n_params, "tokens_per_step": tokens,
        "lower_s": round(t_lower, 1), **extra,
    }
    if not compile_it:
        return rec

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["status"] = "compiled"

    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    # loop-aware accounting (cost_analysis counts scan bodies once; our
    # models scan over layers and tau, so we parse the HLO instead)
    model_cost = analyze_hlo(hlo)
    flops = float(model_cost.flops)
    hbm = float(model_cost.hbm_bytes)
    coll = {k: int(v) for k, v in model_cost.coll_breakdown.items()}
    coll["total"] = int(model_cost.coll_bytes)
    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            mem_rec[attr] = int(getattr(mem, attr))
    arg = mem_rec.get("argument_size_in_bytes", 0)
    tmp = mem_rec.get("temp_size_in_bytes", 0)
    out_b = mem_rec.get("output_size_in_bytes", 0)
    alias = mem_rec.get("alias_size_in_bytes", 0)
    live = arg + tmp + out_b - alias
    chips = mesh.size

    n_active = active_params(cfg, float(n_params))
    mf = model_flops_estimate(n_active, tokens, shape.kind)
    terms = RooflineTerms(flops=flops, hbm_bytes=hbm,
                          coll_bytes=float(coll.get("total", 0)),
                          model_flops=mf, chips=chips, coll_breakdown=coll)
    rec.update({
        "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                              if k in ("flops", "bytes accessed")},
        "memory_analysis": mem_rec,
        "live_bytes_per_device": int(live),
        "fits_hbm": bool(live <= HBM_PER_CHIP),
        "roofline": terms.as_dict(),
        "active_params": n_active,
    })
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--opt", default="",
                    help="comma list of §Perf optimizations: "
                         "scan_accum,onehot_embed,causal_buckets")
    ap.add_argument("--tag", default="",
                    help="suffix for output json (e.g. _opt)")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--mesh-report", action="store_true",
                    help="report per-device param+opt-state bytes for each "
                         "arch under the 2D mesh engine='auto' would pick "
                         "(repro.mesh.placement), instead of lowering")
    ap.add_argument("--device-mem-gb", type=float, default=None,
                    help="per-device HBM budget in GiB for --mesh-report "
                         "(default: REPRO_DEVICE_MEM_BYTES env or 16 GiB)")
    from repro.launch.env import add_env_profile_args, apply_env_profile
    add_env_profile_args(ap)
    args = ap.parse_args(argv)
    apply_env_profile(args.env_profile, host_devices=args.host_devices)

    os.makedirs(args.out_dir, exist_ok=True)

    if args.mesh_report:
        archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
        mem = (int(args.device_mem_gb * 1024 ** 3)
               if args.device_mem_gb else None)
        rows = mesh_report(archs, n_clients=args.clients or 8,
                           n_devices=len(jax.devices()),
                           device_mem_bytes=mem)
        print_mesh_report(rows)
        out = os.path.join(args.out_dir, "mesh_report.json")
        with open(out, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {out}")
        return 0 if all(r["fits"] for r in rows) else 1
    combos = ([(a, s) for a in ASSIGNED_ARCHS
               for s in ("train_4k", "prefill_32k", "decode_32k",
                         "long_500k")]
              if args.all else [(args.arch, args.shape)])

    opts = tuple(o for o in args.opt.split(",") if o)
    results = []
    for arch, shape in combos:
        tag = (f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
               + args.tag)
        print(f"=== dryrun {tag} ===", flush=True)
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod,
                          n_clients=args.clients, tau=args.tau,
                          compile_it=not args.lower_only,
                          microbatches=args.microbatches, opts=opts)
        except Exception as e:  # noqa: BLE001 - record failures, keep going
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}"}
        results.append(rec)
        with open(os.path.join(args.out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
        if rec.get("roofline"):
            r = rec["roofline"]
            print(f"  params={rec['n_params']/1e9:.2f}B "
                  f"flops/dev={r['flops_per_device']/1e12:.2f}T "
                  f"coll/dev={r['coll_bytes_per_device']/1e9:.3f}GB "
                  f"bottleneck={r['bottleneck']} "
                  f"useful={r['useful_flops_fraction']:.2%} "
                  f"fits_hbm={rec['fits_hbm']}", flush=True)
        else:
            print(f"  {rec['status']}: {rec.get('reason', rec.get('error', ''))}",
                  flush=True)
    bad = [r for r in results if r["status"] == "error"]
    print(f"done: {len(results)} combos, {len(bad)} errors")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
