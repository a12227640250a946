"""Zamba2's plain reference (``bench/reference/zamba2.py``) and its cell on
the CPU: the program against the reference at a tiny size (loss, every
leaf's gradient, a DP-PASGD round through the harness), the TP-2 share
against the uncut layer, the reference against ``transformers``' Zamba2,
and the parameter count of the cell's configuration."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import catalog
import compare
import harness
from conftest import TINY_LIMITS
from reference import zamba2 as rz
from reference.dppasgd import matmul
from weights import maker

CONFIG = catalog.load_json(catalog.BENCH / "configs" / "zamba2-7b-tp2-12l.json")


def tiny_config(tp: int = 2, dtype: str = "bfloat16") -> dict:
    """The cell's configuration at width 64, a TP-``tp`` share of a model
    of 8 Mamba2 heads in 2 groups, 4 attention heads of 32 (2 x 64 / 4)
    and 128 MLP units; the first 12 layers, vocab 256."""
    config = copy.deepcopy(CONFIG)
    config.update(hidden_size=64, n_mamba_heads=8 // tp, mamba_headdim=16,
                  mamba_ngroups=2 // tp, mamba_d_state=8, chunk_size=8,
                  attention_head_dim=32, attention_hidden_size=128,
                  num_attention_heads=4 // tp, num_key_value_heads=4 // tp,
                  ffn_hidden_size=128, tensor_parallel=tp, adapter_rank=4,
                  vocab_size=256)
    config["program"] = {
        "arch": "zamba2-7b", "n_layers": 12, "d_model": 64,
        "n_heads": 4 // tp, "n_kv_heads": 4 // tp, "head_dim": 32,
        "d_ff": 128 // tp, "vocab": 256, "ssm_heads": 8 // tp,
        "ssm_ngroups": 2 // tp, "ssm_headdim": 16, "ssm_state": 8,
        "ssd_chunk": 8, "adapter_rank": 4, "dtype": dtype}
    return config


# the tiny cell's readings over ten seeds (CPU): the program at most loss
# 9.6e-4, gnorm 0.024, update 0.035, change 0.021, drift 0.19; the float8
# control at least loss 2.3e-3, gnorm 0.19, update 3.6, change 1.8, drift
# 2.5. Twelve bfloat16 layers move the pre-clip norm more than the other
# tiny cells' one layer, so its limit is wider than theirs.
TINY_ZAMBA2_LIMITS = dict(TINY_LIMITS, gnorm_gap=0.06)


def tiny_cell():
    """1 client x batch 2 x 16 tokens x tau 2: the cell's protocol at a
    size a CPU test holds."""
    mix = catalog.load_json(catalog.BENCH / "traffic" / "silo-s4096.json")
    mix.update(batch=2, seq=16, trace_rounds=2, sigma=0.2)
    e2e = tuple(catalog.benchmark()["end_to_end"])
    return catalog.Cell("tiny-zamba2", 1, tiny_config(), mix,
                        {"limits": TINY_ZAMBA2_LIMITS, "loss_chunk": 16},
                        e2e, ())


def program_and_weights(config, seed=3):
    from repro.models.transformer import Transformer
    model = Transformer(harness.arch_config(config, {}))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, maker(shapes, config["init"])(jax.random.PRNGKey(seed))


def _batch(seed=1, s=16):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (2, s + 1), 0, 256)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _bf16_matmul(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _gaps(config, model, params, batch, mm):
    """(relative loss gap, worst leaf's gradient gap) of the program
    against the reference computed with the matmul ``mm``."""
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss_fn)(params, batch)
        lr, gr = jax.value_and_grad(
            lambda p: rz.loss(p, batch, config, mm))(params)
    leaf = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))
                                           / jnp.max(jnp.abs(b))), gp, gr)
    return abs(float(lp) - float(lr)) / abs(float(lr)), max(
        jax.tree.leaves(leaf))


# float32 program against the float32 reference: loss 1e-7 and leaves
# 2e-5 apart (CPU); the same reference with bfloat16 matmul inputs reads
# about 1e-3 and 1e-2
LOSS_TOL, GRAD_TOL = 1e-5, 1e-3


def test_program_matches_reference_loss_and_every_leaf_gradient():
    config = tiny_config(dtype="float32")
    model, params = program_and_weights(config)
    batch = _batch()
    loss_gap, grad_gap = _gaps(config, model, params, batch,
                               matmul("reference"))
    assert loss_gap < LOSS_TOL and grad_gap < GRAD_TOL, (loss_gap, grad_gap)
    # the tolerances are tight enough that bfloat16 compute fails them
    loss_gap, grad_gap = _gaps(config, model, params, batch, _bf16_matmul)
    assert loss_gap > LOSS_TOL or grad_gap > GRAD_TOL, (loss_gap, grad_gap)


def test_dp_round_is_correct_and_the_control_is_not():
    cell = tiny_cell()
    seed = 2**31 + 21
    result = harness.measure(cell, seed, 0.5, False, jax.devices(),
                             time.monotonic())
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    _, _, _, _, make_weights, key = harness.build(cell, seed)
    refs = harness.References(cell)
    ref = refs.record(seed, make_weights, key)
    ctl = refs.record(seed, make_weights, key, "control")
    checks = compare.against(compare.numbers(ctl, ref),
                             cell.workload["limits"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _share(tree_path, x, k, tp, uncut):
    """Share k of TP ``tp`` of the uncut leaf ``x`` at ``tree_path``."""
    if tree_path[0] == "segments":         # one layer of a stacked leaf
        return jnp.stack([_share(tree_path[1:], y, k, tp, uncut) for y in x])
    name = tree_path[-1]
    h, p = uncut["n_mamba_heads"], uncut["mamba_headdim"]
    g, n = uncut["mamba_ngroups"], uncut["mamba_d_state"]
    di = h * p
    part = lambda a, axis, size: jnp.take(
        a, jnp.arange(k * size // tp, (k + 1) * size // tp), axis=axis)

    def conv_channels(a, axis):           # x | B | C of the conv
        return jnp.concatenate([
            part(jax.lax.slice_in_dim(a, 0, di, axis=axis), axis, di),
            part(jax.lax.slice_in_dim(a, di, di + g * n, axis=axis), axis,
                 g * n),
            part(jax.lax.slice_in_dim(a, di + g * n, di + 2 * g * n,
                                      axis=axis), axis, g * n)], axis=axis)

    if name == "w_in":                    # z | xBC | dt
        return jnp.concatenate([
            part(x[:, :di], 1, di),
            conv_channels(x[:, di:2 * di + 2 * g * n], 1),
            part(x[:, 2 * di + 2 * g * n:], 1, h)], axis=1)
    if name in ("conv_w", "conv_b"):
        return conv_channels(x, x.ndim - 1)
    if name in ("a_log", "dt_bias", "d_skip"):
        return part(x, 0, h)
    if name in ("norm_scale", "w_out"):
        return part(x, 0, di)
    if name in ("wq", "wk", "wv"):
        return part(x, 1, x.shape[1])
    if name == "wo":
        return part(x, 0, x.shape[0])
    if name in ("w_gate", "w_up", "adapter_gate", "adapter_up"):
        return part(x, 1, x.shape[1])
    if name == "w_down":
        return part(x, 0, x.shape[0])
    return x                              # norms, adapter_a, linear: whole


def test_tp2_shares_add_up_to_the_uncut_layer():
    """The two shares' partial outputs of one Mamba2 mixer, and of one
    shared block's attention and MLP, add up to the uncut reference's;
    the whole ``linear`` and the norms are counted once, after the sum."""
    uncut = tiny_config(tp=1, dtype="float32")
    shares = [tiny_config(tp=2, dtype="float32") for _ in range(2)]
    _, params = program_and_weights(uncut)
    layers = [jax.tree_util.tree_map_with_path(
        lambda path, x, k=k: _share(
            [getattr(q, "key", getattr(q, "idx", q)) for q in path], x, k, 2,
            uncut), params) for k in range(2)]
    mm = matmul("reference")
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    e = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    lp = lambda p: jax.tree.map(lambda a: a[0], p["segments"][0]["6"])
    with jax.default_matmul_precision("highest"):
        whole = rz.mamba_mixer(uncut, mm, lp(params)["mixer"], u)
        parts = sum(rz.mamba_mixer(c, mm, lp(p)["mixer"], u)
                    for c, p in zip(shares, layers))
        np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)

        bp, hp = params["shared"]["0"], lp(params)["hybrid"]
        h = rz.rms(jnp.concatenate([u, e], -1), bp["norm_in"]["scale"],
                   1e-5)
        a = sum(rz.attention(c, mm, p["shared"]["0"]["attn"], h)
                for c, p in zip(shares, layers))
        np.testing.assert_allclose(a, rz.attention(uncut, mm, bp["attn"], h),
                                   rtol=1e-4, atol=1e-5)
        f = rz.rms(a, bp["norm_ff"]["scale"], 1e-5)
        m = sum(rz.mlp(mm, p["shared"]["0"]["mlp"], lp(p)["hybrid"], f)
                for p in layers)
        np.testing.assert_allclose(m, rz.mlp(mm, bp["mlp"], hp, f),
                                   rtol=1e-4, atol=1e-5)
        block = jnp.einsum("bsd,de->bse", m, hp["linear"])
        np.testing.assert_allclose(
            block, rz.shared_block(uncut, mm, bp, hp, u, e), rtol=1e-4,
            atol=1e-5)


def test_reference_matches_transformers_zamba2():
    """The reference against ``transformers``' ``Zamba2ForCausalLM``
    (torch, CPU) on logits, given the same weights at a tiny size with
    both shared blocks. The sequence is one SSD chunk: transformers'
    torch path sums the inter-chunk decay over the wrong axis (its Mamba2
    module transposes first), so it is exact within one chunk only; the
    chunked recurrence is held to the sequential oracle instead
    (tests/test_zamba2.py)."""
    try:
        import torch
        from transformers import Zamba2Config, Zamba2ForCausalLM
    except Exception as exc:            # noqa: BLE001
        pytest.skip(f"transformers' Zamba2 does not import: {exc}")
    types = ["mamba", "mamba", "hybrid", "mamba", "hybrid", "mamba"]
    d, ff, heads, s = 32, 48, 4, 16
    hf = Zamba2Config(
        vocab_size=96, hidden_size=d, num_hidden_layers=6,
        layers_block_type=types, mamba_d_state=8, mamba_d_conv=4,
        mamba_expand=2, mamba_ngroups=2, n_mamba_heads=4, chunk_size=s,
        num_attention_heads=heads, num_key_value_heads=heads,
        intermediate_size=ff, num_mem_blocks=2, adapter_rank=4,
        use_mem_rope=True, rope_theta=10000, rms_norm_eps=1e-5,
        hidden_act="gelu", attn_implementation="eager")
    torch.manual_seed(0)
    model = Zamba2ForCausalLM(hf).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("dt_bias"):
                # dt = softplus(-3 .. -1 + ~N(0, 1)) stays well above
                # time_step_min, where transformers' torch path clamps
                p.uniform_(-3.0, -1.0)
            elif p.dim() == 1:
                p.normal_(1.0, 0.1) if "norm" in name else p.normal_(0, 0.3)
            else:
                p.normal_(0, p.shape[-1] ** -0.5)
    sd = {k: v.numpy().astype(np.float32)
          for k, v in model.state_dict().items()}
    assert np.array_equal(sd["lm_head.weight"], sd["model.embed_tokens.weight"])
    hyb = [i for i, t in enumerate(types) if t == "hybrid"]
    hd = hf.attention_head_dim
    layers = []
    for i in range(len(types)):
        pre = f"model.layers.{i}." + ("mamba_decoder." if i in hyb else "")
        m = pre + "mamba."
        lp = {"norm1": {"scale": sd[pre + "input_layernorm.weight"]},
              "mixer": {"w_in": sd[m + "in_proj.weight"].T,
                        "conv_w": sd[m + "conv1d.weight"][:, 0, :].T,
                        "conv_b": sd[m + "conv1d.bias"],
                        "a_log": sd[m + "A_log"], "dt_bias": sd[m + "dt_bias"],
                        "d_skip": sd[m + "D"],
                        "norm_scale": sd[m + "norm.weight"],
                        "w_out": sd[m + "out_proj.weight"].T}}
        if i in hyb:
            a = (f"model.layers.{i}.shared_transformer.feed_forward."
                 f"gate_up_proj_adapter_list.{hyb.index(i)}.")
            up = sd[a + "1.weight"]
            lp["hybrid"] = {"adapter_a": sd[a + "0.weight"].T,
                            "adapter_gate": up[:ff].T, "adapter_up": up[ff:].T,
                            "linear": sd[f"model.layers.{i}.linear.weight"].T}
        layers.append(lp)
    shared = {}
    for k, i in enumerate(hyb):
        b = f"model.layers.{i}.shared_transformer."
        qkv = lambda w: sd[b + f"self_attn.{w}_proj.weight"].T.reshape(
            2 * d, heads, hd)
        gate_up = sd[b + "feed_forward.gate_up_proj.weight"]
        shared[str(k)] = {
            "norm_in": {"scale": sd[b + "input_layernorm.weight"]},
            "attn": {"wq": qkv("q"), "wk": qkv("k"), "wv": qkv("v"),
                     "wo": sd[b + "self_attn.o_proj.weight"].T.reshape(
                         heads, hd, d)},
            "norm_ff": {"scale": sd[b + "pre_ff_layernorm.weight"]},
            "mlp": {"w_gate": gate_up[:ff].T, "w_up": gate_up[ff:].T,
                    "w_down": sd[b + "feed_forward.down_proj.weight"].T}}
    params = {"embed": {"embedding": sd["model.embed_tokens.weight"]},
              "final_norm": {"scale": sd["model.final_layernorm.weight"]},
              "segments": [{str(j): jax.tree.map(lambda x: x[None], lp)
                            for j, lp in enumerate(layers)}],
              "shared": shared}
    cfg = {"hidden_size": d, "n_mamba_heads": 4,
           "mamba_headdim": hf.mamba_headdim, "mamba_ngroups": 2,
           "mamba_d_state": 8, "attention_head_dim": hd,
           "num_hidden_layers": len(types), "layers_block_type": types,
           "num_mem_blocks": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
    toks = np.random.default_rng(0).integers(0, 96, size=(2, s))
    with torch.no_grad():
        want = model(torch.tensor(toks)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = rz.logits(params, jnp.asarray(toks), cfg, matmul("reference"))
    # float32 on both sides in another order: 3e-5 at logits up to 3.6
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-4)


def test_parameter_count_of_the_cell():
    """The program's tree for the cell, counted from shapes, is what
    ``param_count`` reckons: 12 Mamba2 layers of 39.2M, two blocks of
    167.0M, two adapters and linears, the tied embedding."""
    from repro.models.transformer import Transformer
    model = Transformer(harness.arch_config(CONFIG, {}))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == rz.param_count(CONFIG) == 949_608_416
    assert model.cfg.n_layers == 12 and model.cfg.ssd_chunk == 256
