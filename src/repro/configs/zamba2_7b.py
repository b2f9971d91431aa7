"""zamba2-7b [hybrid]: 81 Mamba2 layers and two shared transformer blocks
used in turn (ABAB) at 13 hybrid sites, each block on concat(x, token
embeddings) with a per-site MLP adapter and output linear.  Source:
Zyphra/Zamba2-7B-Instruct config.json (arXiv:2411.15242); equations as
transformers' ``models/zamba2/modeling_zamba2.py`` writes them."""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    arch_id="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
    d_ff=14336, vocab=32000, head_dim=224,
    ssm_state=64, ssm_head_dim=64, ssm_expand=2, ssm_chunk=256,
    ssm_groups=2, conv_bias=True,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    n_shared_blocks=2, adapter_rank=128, attn_scale=(224 / 2) ** -0.5,
    activation="gelu", rope_theta=1e4, norm_eps=1e-5,
)
