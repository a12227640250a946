"""rwkv6-1.6b [ssm]: RWKV-6 "Finch" — data-dependent decay [arXiv:2404.05892].

Assigned spec: 24L d_model=2048 (attn-free) d_ff=7168 vocab=65536.
Each layer = time-mix (WKV6 recurrence) + channel-mix. Sub-quadratic:
decode state is O(1) in sequence length -> long_500k runs.
"""
from dataclasses import replace

from repro.configs.base import ArchConfig, LayerSpec, register, uniform_segments

RWKV6_1_6B = register(ArchConfig(
    name="rwkv6-1.6b",
    arch_type="ssm",
    source="arXiv:2404.05892",
    d_model=2048,
    n_heads=32,              # = d_model / rwkv_headdim (bookkeeping only)
    n_kv_heads=32,
    d_ff=7168,
    vocab=65536,
    n_layers=24,
    segments=uniform_segments(24, LayerSpec(mixer="rwkv6", ffn="rwkv_cm")),
    rwkv_headdim=64,
    loss_chunk=1024,
    subquadratic=True,
))


def chip_share(n_layers: int = 4) -> ArchConfig:
    """rwkv6-1.6b cut to one v5e chip's share of a federation.

    reduced: depth 24 -> ``n_layers`` (one layer is the whole period, so
    any depth keeps the layer mix). assumed: every width as published —
    d_model 2048, d_ff 7168, vocab 65536, head dim 64, bf16. At 4 layers
    one client replica is 353M parameters (0.66 GiB, params only under
    plain SGD), so a few client replicas plus their DP gradient buffers
    fill a 16 GiB chip: the deployment is a cross-device federation whose
    clients are simulated side by side on one accelerator.
    """
    return replace(RWKV6_1_6B, name=f"rwkv6-1.6b-{n_layers}l",
                   n_layers=n_layers,
                   segments=uniform_segments(
                       n_layers, LayerSpec(mixer="rwkv6", ffn="rwkv_cm")))
