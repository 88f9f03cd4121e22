"""Mamba2-130M — attention-free SSD (state-space duality) [arXiv:2405.21060].

Widths from state-spaces/mamba2-130m's config.json (24 layers, d_model 768,
tied embeddings) and the Mamba-2 block defaults (d_state 128, d_conv 4,
expand 2, headdim 64, ngroups 1, chunk_size 256).  The vocabulary is the
published embedding's rows: vocab_size 50277 padded to a multiple of
pad_vocab_size_multiple (16), 50288.
"""
from .base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="mamba2-130m",
    family="ssm",
    num_layers=24,
    d_model=768,
    num_heads=1,       # unused (attention-free)
    num_kv_heads=1,
    head_dim=64,
    d_ff=0,            # no MLP: Mamba2 block subsumes it
    vocab_size=50288,  # 50277 padded to a multiple of 16
    tie_embeddings=True,
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64, n_groups=1, chunk=256),
    source="https://huggingface.co/state-spaces/mamba2-130m/blob/main/config.json; arXiv:2405.21060",
)
