"""jamba2-3b [hybrid].

28L d_model=2560 20H (MQA kv=1, head_dim 128) d_ff=8192 vocab=65536, tied
embeddings.  Attention at layer i where i % 14 == 7 (layers 7 and 21, from
``attn_layer_period``/``attn_layer_offset``), Mamba-1 mixers elsewhere
(d_state 16, d_conv 4, expand 2, dt_rank 160, conv bias); one expert, so a
dense SwiGLU MLP in every layer.  Attention has no positional encoding.
[hf:ai21labs/AI21-Jamba2-3B config.json]
"""

from repro.configs.base import ATTN, MAMBA, ArchConfig

CONFIG = ArchConfig(
    name="jamba2-3b",
    family="hybrid",
    num_layers=28,
    d_model=2560,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    # 14-layer period: 7 mamba, 1 attention, 6 mamba
    block_pattern=(MAMBA,) * 7 + (ATTN,) + (MAMBA,) * 6,
    mlp_activation="silu",
    use_rope=False,
    ssm_state_dim=16,
    ssm_conv_width=4,
    ssm_expand=2,
    tie_embeddings=True,
    norm_eps=1e-6,
)
