"""The dense decoder: pre-norm blocks of grouped-query attention with
rotary positions and a SwiGLU MLP, RMS norms, an untied head."""
from __future__ import annotations

from typing import Sequence, Tuple

from harness import flops


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    m = cfg["model"]
    if m.get("partial_rotary_factor", 1.0) != 1.0 or m.get("use_qkv_bias"):
        raise ValueError(f"{cfg['name']}: the dense block rotates the whole "
                         "head and has no q/k/v biases")
    return ModelConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], group=("attn",),
        rope_theta=float(m["rope_theta"]), rms_eps=m["rms_norm_eps"],
        max_seq_len=m["max_position_embeddings"], dtype=m["torch_dtype"],
        use_pallas_kernels=True)


def _heads(m: dict) -> Tuple[int, int, int]:
    nq = m["num_attention_heads"]
    return nq, m["num_key_value_heads"], m["hidden_size"] // nq


def step_flops(cfg: dict, keys: Sequence[int]) -> int:
    m = cfg["model"]
    nq, nkv, dh = _heads(m)
    return flops.decode_step(keys, m["num_hidden_layers"], m["hidden_size"],
                             nq, nkv, dh, m["intermediate_size"],
                             m["vocab_size"])


def attention_cost(cfg: dict, keys: Sequence[int]) -> Tuple[int, int]:
    return flops.decode_attention(keys, *_heads(cfg["model"]))
