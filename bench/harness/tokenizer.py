"""The benchmark's tokenizer: the committed 16k merges, encoded fast.

``BPETokenizer.encode_bytes`` rescans the whole byte string once per
merge it applies, so a prompt of 9k tokens takes minutes.  ``train_bpe``
learns merges only inside chunks that start where a whitespace byte
follows a non-whitespace byte, so no merge ever spans such a boundary,
and encoding chunk by chunk gives exactly the ids of encoding the whole
string (``tests/test_bench_tokenizer.py`` checks it).  Chunks repeat, so
they are memoized.
"""
from __future__ import annotations

import os
from typing import Dict, Iterator, List, Tuple

from repro.tokenizer import BPETokenizer

TOKENIZER_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "tok16k.json")
_WS = frozenset(b" \n\t\r")


def chunks(data: bytes) -> Iterator[bytes]:
    """Split where a whitespace byte follows a non-whitespace byte, the
    rule ``train_bpe`` splits its corpus by."""
    start = 0
    for i in range(1, len(data)):
        if data[i] in _WS and data[i - 1] not in _WS:
            yield data[start:i]
            start = i
    if data:
        yield data[start:]


class FastBPE(BPETokenizer):
    """``BPETokenizer`` whose encoding goes chunk by chunk, memoized."""

    def __init__(self, merges: List[Tuple[int, int]]):
        super().__init__(merges)
        self._memo: Dict[bytes, List[int]] = {}

    def encode_bytes(self, data: bytes) -> List[int]:
        out: List[int] = []
        for c in chunks(data):
            ids = self._memo.get(c)
            if ids is None:
                ids = self._memo[c] = super().encode_bytes(c)
            out.extend(ids)
        return out


def load(path: str = TOKENIZER_FILE) -> FastBPE:
    return FastBPE.load(path)
