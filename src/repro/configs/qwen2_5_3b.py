"""qwen2.5-3b [dense] — GQA with QKV bias, tied embeddings.

36L d_model=2048 16H (GQA kv=2) head_dim 128 d_ff=11008 vocab=151936, as in
the published Qwen/Qwen2.5-3B config.json: hidden_size 2048,
intermediate_size 11008, num_hidden_layers 36, num_attention_heads 16,
num_key_value_heads 2, vocab_size 151936, rope_theta 1e6, rms_norm_eps
1e-6, tie_word_embeddings true, torch_dtype bfloat16, hidden_act silu,
use_sliding_window false (so no window here).  The embedding and the tied
head hold 152064 rows: `configs.base.pad_vocab` rounds the vocabulary up
to a multiple of 256.
"""
from .base import AttnCfg, ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b",
    family="dense",
    n_layers=36,
    d_model=2048,
    d_ff=11008,
    vocab=151_936,
    block_pattern=(("attn", "dense"),),
    attn=AttnCfg(n_heads=16, n_kv_heads=2, head_dim=128, qkv_bias=True,
                 rope_theta=1_000_000.0),
    act="silu_glu",
    norm_eps=1e-6,
    param_dtype="bfloat16",
    tie_embeddings=True,
    optimizer="adamw",
    grad_accum=4,
    source="hf:Qwen/Qwen2.5-3B config.json",
)
