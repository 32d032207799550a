"""Zamba2-7B-Instruct, at its published widths
(https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json).

81 layers, each with its own Mamba2 (112 heads of 64, state 64, two B/C
groups, conv width 4 with bias, chunk 256).  Before the Mamba2 of the 13
hybrid layers (``hybrid_layer_ids``, 6, 11, 17, ... 77) one of two shared
transformer blocks is applied, in turn (``num_mem_blocks`` 2), to
[hidden, embedding] (``attention_hidden_size`` 7168): attention in 32 heads
of 224 with RoPE (theta 1e4), then a gated exact-GELU MLP of 14336 whose
gate/up projection carries the application's own rank-128 adapter
(``use_shared_mlp_adapter``), then the application's own output linear.
RMSNorm eps 1e-5.  Tied embeddings are assumed (the key is not in the
published config; it is the Zamba2 default).
"""
from .base import ArchConfig, SSMConfig, register

ZAMBA2_7B = register(
    ArchConfig(
        name="zamba2-7b",
        family="hybrid",
        n_layers=81,
        d_model=3584,
        n_heads=32,
        n_kv_heads=32,
        d_ff=14336,
        vocab_size=32000,
        head_dim=224,
        rope_theta=10000.0,
        mlp_act="gelu_glu",
        gelu_exact=True,
        tied_embeddings=True,
        ssm=SSMConfig(state_dim=64, conv_width=4, expand=2, head_dim=64, chunk=256,
                      n_groups=2),
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
        n_shared_blocks=2,
        adapter_rank=128,
        # the published modelling code scales the softmax by (head_dim/2)^-1/2,
        # its handling of the blocks' [hidden, embedding] input
        attn_scale_divisor=2.0,
        attn_input_dim=7168,
        norm_eps=1e-5,
        source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json",
    )
)
