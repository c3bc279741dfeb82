"""granite-8b [dense] — IBM Granite Code 8B (granite-8b-code-base).

Source: arXiv:2405.04324 ("Granite Code Models", Table 1: 36 layers,
hidden 4,096, 32 query heads with 8 key-value heads (GQA), SwiGLU with
an FFN width of 14,336, RMSNorm, RoPE, a 49,152-token vocabulary, a
4,096-token context); model card ``ibm-granite/granite-8b-code-base``
(a Llama-architecture config: no biases in attention or MLP, a tied
input/output embedding).  The paper gives RoPE without its base and
RMSNorm without its epsilon; the two values here are those of the model
card's ``config.json`` (``rope_theta`` 1e7, ``rms_norm_eps`` 1e-5).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-8b", family="dense",
    num_layers=36, d_model=4096, num_heads=32, num_kv_heads=8,
    head_dim=128, d_ff=14336, vocab_size=49152,
    rope_theta=10_000_000.0, norm_eps=1e-5,
    source="arXiv:2405.04324",
)
