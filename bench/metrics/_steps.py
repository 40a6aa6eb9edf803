"""Shared arithmetic of the readers that divide work by trace time."""
from __future__ import annotations

from typing import List, Optional

DECODE_PROGRAM = r"^jit_decode_step\("


def steps(run) -> List[List[int]]:
    """Key counts of the resident rows at each traced step."""
    t = run.window.traced
    return [s["keys"] for s in t["steps"] if s["keys"]] if t else []


def share(min_s: float, dev_s: float) -> Optional[float]:
    return None if dev_s <= 0 else 100.0 * min_s / dev_s
