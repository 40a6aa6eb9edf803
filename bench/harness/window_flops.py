"""Operations and bytes of one window-attention call: one layer of a
prefix-cache hit, whose tail of ``n`` tokens is prefilled as one query
window over the ``start`` keys the cache already holds.

As in ``flops.py`` the counts are logical: the real tail only (not its
padding to a bucket), each key and value read once (not once per query
tile), so a share of the roofline cannot pass 100%."""
from __future__ import annotations

from typing import Tuple


def heads(cfg: dict) -> Tuple[int, int, int]:
    """(query heads, KV heads, head size) of a dense configuration."""
    m = cfg["model"]
    nq = m["num_attention_heads"]
    return nq, m["num_key_value_heads"], m["hidden_size"] // nq


def window_attention(start: int, n: int, n_heads: int, n_kv: int,
                     d_head: int, itemsize: int = 2) -> Tuple[int, int]:
    """Tail query r (0 <= r < n) scores the ``start + r + 1`` keys up to
    itself.  FLOPs: q.k and p.v, 2 each per query head, key and head
    dim.  Bytes: K and V of the ``start + n`` keys, the queries and the
    outputs."""
    pairs = n * start + n * (n + 1) // 2
    flops = 4 * n_heads * d_head * pairs
    kv = 2 * (start + n) * n_kv * d_head * itemsize
    qo = 2 * n * n_heads * d_head * itemsize
    return flops, kv + qo
