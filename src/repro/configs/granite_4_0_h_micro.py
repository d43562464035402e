"""granite-4.0-h-micro [ssm] - Mamba-2 layers interleaved with NoPE GQA
attention, 3B [hf:ibm-granite/granite-4.0-h-micro].

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json

40 layers, d_model 2048, vocab 100352, tied embeddings. ``layer_types``
repeats one period of 10: 5 Mamba-2 layers, 1 attention layer, 4 Mamba-2
layers (36 Mamba-2, 4 attention). Mamba-2: 64 heads x 64, state 128, one
group, expand 2, conv 4 with bias, no projection bias, chunk 256.
Attention: 32 query heads, 8 KV heads, head_dim 64, no position embedding
(``position_embedding_type: "nope"``), no bias. Every layer is followed by
a SwiGLU MLP of width 8192 (``shared_intermediate_size``; no experts).
muP: embeddings x12, each residual branch x0.22, softmax scale 0.015625,
logits / 8. RMSNorm eps 1e-5. Departures from the source: none.
"""
from repro.models.config import ModelConfig

PATTERN = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="ssm",
    n_layers=40, d_model=2048, n_heads=32, n_kv=8, head_dim=64,
    d_ff=8192, vocab=100352, act="silu", glu=True, tie_embeddings=True,
    layer_pattern=PATTERN,
    ssm_state=128, ssm_heads=64, ssm_head_dim=64, ssm_expand=2,
    ssm_groups=1, ssm_conv=4, ssm_chunk=256,
    pos="none", norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
)
