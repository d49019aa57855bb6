"""Granite-4.0-H-Micro [huggingface.co/ibm-granite/granite-4.0-h-micro].

Hybrid: 36 Mamba-2 (SSD) layers and 4 GQA attention layers without
positional encoding, one attention layer in every 10 (layers 5, 15, 25,
35), every layer followed by a SwiGLU MLP.  40L · d_model 2048 · 32H (GQA
kv=8, head 64, softmax scale 1/64) · d_ff 8192 · vocab 100352 · SSD: 64
heads of 64 (d_inner 4096), state 128, one group, conv 4, chunk 256.
Embeddings times 12, every branch times 0.22, logits divided by 8; RMSNorm
eps 1e-5; tied embeddings.  Its Mamba-2 layers follow arXiv:2405.21060.
"""
from repro.models.config import ArchConfig, BlockKind

_PERIOD = (BlockKind.SSD,) * 5 + (BlockKind.ATTN_NOPE,) + (BlockKind.SSD,) * 4

FULL = ArchConfig(
    name="granite-4.0-h-micro",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100_352,
    pattern=_PERIOD,
    attn_scale=1 / 64,
    ssm_state=128,
    ssd_head_dim=64,
    ssd_expand=2,
    ssd_chunk=256,
    conv_width=4,
    use_rope=False,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    max_seq_len=131_072,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)

# one whole period at tiny widths (head 16, so the scale is 1/16)
SMOKE = FULL.scaled(
    num_layers=10, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
    vocab_size=256, attn_scale=1 / 16, ssm_state=16, ssd_head_dim=16,
    ssd_chunk=16, q_chunk=64, max_seq_len=512, dtype="float32", remat=False,
)
