"""Qwen1.5-32B [dense]: GQA kv=8, QKV bias, untied head, rope theta 1e6.
[hf:Qwen/Qwen1.5-32B, https://huggingface.co/Qwen/Qwen1.5-32B/blob/main/config.json]"""
from repro.configs.base import ArchConfig, register

QWEN15_32B = register(ArchConfig(
    name="qwen1.5-32b",
    family="dense",
    num_layers=64,
    d_model=5120,
    num_heads=40,
    num_kv_heads=8,
    d_ff=27392,
    vocab_size=152064,
    qkv_bias=True,
    rope_theta=1_000_000.0,
    norm_type="rmsnorm",
    act="silu",
    mlp_gated=True,
    shapes=("train_4k", "prefill_32k", "decode_32k"),
))
