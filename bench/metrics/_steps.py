"""Shared arithmetic of the readers that divide work by trace time."""
from __future__ import annotations

from typing import List, Optional, Tuple

DECODE_PROGRAM = r"^jit_decode_step\("


def steps(run) -> List[List[int]]:
    """Key counts of the resident rows at each traced step."""
    t = run.window.traced
    return [s["keys"] for s in t["steps"] if s["keys"]] if t else []


def dims(run) -> Tuple[int, int, int, int, int, int]:
    m = run.cell.config["model"]
    d, nq = m["hidden_size"], m["num_attention_heads"]
    return (m["num_hidden_layers"], d, nq, m["num_key_value_heads"],
            d // nq, m["intermediate_size"])


def share(min_s: float, dev_s: float) -> Optional[float]:
    return None if dev_s <= 0 else 100.0 * min_s / dev_s
