"""Operations and bytes that a kernel or a step needs, from its shapes.

Bytes are the logical bytes the algorithm reads and writes, not what a
padded layout moves: a K/V page whose head size 64 sits in 128 lanes
still counts 64.  So a share of a roofline cannot pass 100%, and a later
layout fix shows as a gain.  Only the rows that hold a request count.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence, Tuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def decode_attention(keys: Sequence[int], n_heads: int, n_kv: int,
                     d_head: int, itemsize: int = 2) -> Tuple[int, int]:
    """One decode-attention call (one layer, one query per row): row b
    scores ``keys[b]`` cached keys.  FLOPs: q.k and p.v, 2 each per
    head, key and head dim.  Bytes: each row's K and V, its query and
    its output."""
    flops = sum(4 * n_heads * d_head * k for k in keys)
    kv = sum(2 * k * n_kv * d_head * itemsize for k in keys)
    qo = 2 * len(keys) * n_heads * d_head * itemsize
    return flops, kv + qo


def masked_argmax(rows: int, vocab: int) -> Tuple[int, int]:
    """One packed masked argmax: per row, ``vocab`` f32 logits and
    ceil(vocab/32) uint32 mask words in, an index and a value out.
    FLOPs: one select and one compare per logit."""
    words = -(-vocab // 32)
    return 2 * rows * vocab, rows * (4 * vocab + 4 * words + 8)


def layer_matmul_params(d: int, n_heads: int, n_kv: int, d_head: int,
                        d_ff: int) -> int:
    """Weights of one dense attention + SwiGLU block that multiply."""
    return (d * n_heads * d_head + 2 * d * n_kv * d_head
            + n_heads * d_head * d + 3 * d * d_ff)


def decode_step(keys: Sequence[int], n_layers: int, d: int, n_heads: int,
                n_kv: int, d_head: int, d_ff: int, head_vocab: int) -> int:
    """FLOPs of one decode step over the rows that hold a request: the
    weight matmuls, the head over the whole (published) vocabulary, and
    attention over each row's ``keys``."""
    per_tok = 2 * (n_layers * layer_matmul_params(d, n_heads, n_kv,
                                                  d_head, d_ff)
                   + d * head_vocab)
    attn = n_layers * decode_attention(keys, n_heads, n_kv, d_head)[0]
    return len(keys) * per_tok + attn


def min_time(flops: float, nbytes: float, peak: Dict[str, float]
             ) -> Tuple[float, str]:
    """The least time the chip needs, and which roof bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
