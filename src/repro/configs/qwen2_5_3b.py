"""qwen2.5-3b [dense] — GQA with QKV bias.

Sizes from the published ``config.json`` of ``Qwen/Qwen2.5-3B``
(https://huggingface.co/Qwen/Qwen2.5-3B/blob/main/config.json):
``num_hidden_layers`` 36, ``hidden_size`` 2048, ``num_attention_heads``
16, ``num_key_value_heads`` 2 (head size 2048 / 16 = 128),
``intermediate_size`` 11008, ``vocab_size`` 151936, ``rms_norm_eps``
1e-6, ``rope_theta`` 1e6, SiLU gated MLP, bias on q/k/v only. The
embedding is tied to the output head (``tie_word_embeddings``), as
``models/layers.unembed_apply`` does for every LM here.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b",
    family="dense",
    n_layers=36,
    d_model=2048,
    n_heads=16,
    n_kv_heads=2,
    d_ff=11008,
    vocab=151936,
    head_dim=128,
    qkv_bias=True,
    rope_theta=1e6,
    norm_eps=1e-6,
)
