"""Zamba2-7B(-Instruct) — Mamba-2 backbone with two shared transformer
blocks [arXiv:2411.15242; huggingface.co/Zyphra/Zamba2-7B-Instruct
config.json].

81 Mamba-2 layers, d_model 3584: 112 SSM heads of 64, d_state 64, 2 B/C
groups, conv 4 over 7424 channels (with bias), gated RMSNorm per group.
A shared transformer block is applied before each layer of
``hybrid_layer_ids`` (13 applications); the applications alternate
between ``num_mem_blocks`` = 2 blocks (A B A B ...).  A block's input is
concat(x, x0), x0 the token embedding: RMSNorm over 7168, MHA of 32 heads
of 224 with RoPE and no bias (o_proj 7168 -> 3584), RMSNorm over 3584,
then a gated GELU MLP 3584 -> 2 x 14336 -> 3584 whose gate_up carries a
rank-128 LoRA adapter of each application's own; each application also
has its own 3584 x 3584 output linear.  The result T enters its Mamba
layer as x + mamba(norm(x + T)).  Vocabulary 32000, LM head tied to the
embedding.
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    ssm=SSMConfig(kind="mamba2", d_state=64, d_conv=4, expand=2, head_dim=64,
                  n_groups=2),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    tie_embeddings=True,
    source="arXiv:2411.15242",
)
