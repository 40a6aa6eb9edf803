"""The benchmark's independent grammar checker agrees with the program's
DOMINO masks (unbounded lookahead) token for token, along random legal
walks through each of the benchmark's grammars."""
import numpy as np
import pytest

import _tiny  # noqa: F401
from harness import grammar_ref
from repro.core import grammars
from repro.core.domino import DominoDecoder
from repro.core.sampling import GrammarSampler
from repro.tokenizer import train_bpe

GRAMMARS = ("json", "json_gsm8k", "json_conll")


@pytest.fixture(scope="module")
def tok():
    corpus = b"".join(GrammarSampler(grammars.load(g), seed=i).corpus(60)
                      for i, g in enumerate(GRAMMARS))
    return train_bpe(corpus, vocab_size=500)


def _legal(state, vocab, eos):
    out = np.zeros(len(vocab), bool)
    for t, data in enumerate(vocab):
        if t == eos:
            out[t] = grammar_ref.complete(state)
        elif data:
            out[t] = grammar_ref.extend(state, data) is not None
    return out


@pytest.mark.parametrize("name", GRAMMARS)
def test_masks_agree(name, tok):
    g = grammars.load(name)
    vocab = list(tok.vocab)
    rng = np.random.default_rng(1)
    n_cmp = 0
    for walk in range(3):
        dec = DominoDecoder(g, vocab, tok.eos_id)
        state = grammar_ref.start(name)
        for _ in range(40):
            want = np.asarray(dec.mask(), bool)
            got = _legal(state, vocab, tok.eos_id)
            diff = np.nonzero(want != got)[0]
            assert not len(diff), [(vocab[t], want[t]) for t in diff[:5]]
            n_cmp += 1
            choices = np.nonzero(want)[0]
            choices = choices[choices != tok.eos_id]
            if not len(choices):
                break
            t = int(rng.choice(choices))
            assert dec.advance(t)
            state = grammar_ref.extend(state, vocab[t])
    assert n_cmp >= 40


def test_rejects():
    bad = [b'{"a" 1}', b"01", b"[1,]", b'{"a":1,}', b"1 2", b'"\\x"']
    for data in bad:
        assert grammar_ref.extend(grammar_ref.start("json"), data) is None
    assert not grammar_ref.complete(
        grammar_ref.extend(grammar_ref.start("json_gsm8k"),
                           b'{"thoughts": []'))
    assert grammar_ref.extend(grammar_ref.start("json_conll"),
                              b'{"entities": [{"text": "a", "type": "OX"'
                              ) is None
