"""The benchmark's chunked encoder gives the ids of the program's own."""
import numpy as np
import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import tokenizer, traffic
from repro.tokenizer import BPETokenizer


@pytest.fixture(scope="module")
def tok():
    return tokenizer.load()


def _texts(tok):
    rng = np.random.default_rng(0)
    words = traffic.Words(tok)
    yield "System:" + words.text(rng, 60) + "\nUser:" + words.text(rng, 40)
    yield '{"thoughts": [{"step": "a b", "result": 12}],\n  "answer": -3.5e2}'
    with open(__file__.replace("test_bench_tokenizer.py", "_tiny.py")) as f:
        yield f.read()[:1500]
    yield "  leading\t\ttabs  and   runs \n\n\r\n of  whitespace  "
    yield "".join(chr(c) for c in rng.integers(32, 127, size=400))


def test_chunked_encode_matches(tok):
    plain = BPETokenizer(tok.merges)
    for text in _texts(tok):
        assert tok.encode(text) == plain.encode(text)


def test_vocabulary(tok):
    assert tok.vocab_size == 16387
    assert tok.eos_id == 16386
