"""zamba2-7b [hybrid]: Zamba2-7B-Instruct (Zyphra, arXiv:2411.15242;
config: huggingface.co/Zyphra/Zamba2-7B-Instruct).

81 Mamba2 layers (112 heads of 64, 2 B/C groups, d_state 64, conv 4, SSD
chunk 256) and two shared-weight transformer blocks, invoked in turn at
the hybrid layers 6, 11, 17, 23, ..., 77. An invocation concatenates the
stream with the original embeddings (width 7168), applies RMSNorm, then
attention (32 heads of 224, RoPE theta 1e4, softmax scale (224/2)^-0.5)
and ``o_proj`` to 3584, a pre-FF RMSNorm and a gated-GELU MLP of 14336
whose gate/up projection has a rank-128 adapter per invocation. The
layer's own ``linear`` maps the block's output, which is added to that
layer's Mamba2 input only:
``h' = h + Mamba2(RMSNorm(h + linear(block(h, e))))``. RMSNorm eps 1e-5,
tied head.

Segments: the published first 12 layers (block A at 6, block B at 11),
then five 12-layer periods (A at the 6th, B at the 12th), then the last 9
layers (A at 77). A cut to the first n <= 12 layers is
``patterned_segments(n, segments[0].pattern)``.
"""
from repro.configs.base import ArchConfig, LayerSpec, Segment, register

_M = LayerSpec(mixer="mamba2", ffn="none")
_A = LayerSpec(mixer="mamba2", ffn="none", shared_block=0)
_B = LayerSpec(mixer="mamba2", ffn="none", shared_block=1)

ZAMBA2_7B = register(ArchConfig(
    name="zamba2-7b",
    arch_type="hybrid",
    source="arXiv:2411.15242",
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab=32000,
    n_layers=81,
    segments=(
        Segment(n_steps=1, pattern=(_M,) * 6 + (_A,) + (_M,) * 4 + (_B,)),
        Segment(n_steps=5, pattern=(_M,) * 5 + (_A,) + (_M,) * 5 + (_B,)),
        Segment(n_steps=1, pattern=(_M,) * 5 + (_A,) + (_M,) * 3),
    ),
    rope_theta=1e4,
    norm_eps=1e-5,
    ssm_state=64,
    ssm_headdim=64,
    ssm_heads=112,
    ssm_ngroups=2,
    ssd_chunk=256,
    adapter_rank=128,
    subquadratic=True,
))
