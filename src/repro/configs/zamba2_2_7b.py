"""Zamba2-2.7B [arXiv:2411.15242]: Mamba2 backbone with two shared
transformer blocks applied in turn to [hidden, embedding], each use with
its own LoRA adapter and output linear (``models/zamba.py``).

The published ``config.json`` of this model is not in the repository, so
its widths and hybrid positions here are the repository's own: 54 Mamba2
layers with a shared-block application before every sixth (layers 5, 11,
..., 53), attention 5120 wide (2 x d_model) in 32 heads of 160.  The
catalogued Zamba2 at its published widths is ``zamba2-7b``."""
from .base import ArchConfig, SSMConfig, register

ZAMBA2_2_7B = register(
    ArchConfig(
        name="zamba2-2.7b",
        family="hybrid",
        n_layers=54,
        d_model=2560,
        n_heads=32,
        n_kv_heads=32,
        d_ff=10240,
        vocab_size=32000,
        head_dim=160,
        mlp_act="gelu_glu",
        gelu_exact=True,
        tied_embeddings=True,
        ssm=SSMConfig(state_dim=64, expand=2, head_dim=64, chunk=128, n_groups=1),
        hybrid_layer_ids=tuple(range(5, 54, 6)),
        n_shared_blocks=2,
        adapter_rank=128,
        # the published modelling code scales the softmax by (head_dim/2)^-1/2,
        # its handling of the blocks' [hidden, embedding] input
        attn_scale_divisor=2.0,
        attn_input_dim=5120,
        norm_eps=1e-5,
        source="arXiv:2411.15242; widths and hybrid positions the repository's own",
    )
)
