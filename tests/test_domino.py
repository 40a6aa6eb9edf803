"""DOMINO decoder: Fig.-3 semantics, lookahead, minimal invasiveness,
equality with the online full-vocab baseline, opportunistic checks."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import grammars
from repro.core.baselines import OnlineParserDecoder, naive_greedy_decoder
from repro.core.domino import DominoDecoder
from repro.core.grammar import parse_grammar
from repro.core.retokenize import greedy_tokenize
from repro.core.sampling import GrammarSampler
from repro.core.scanner import Scanner
from repro.core.trees import TreeCache

FIG3 = parse_grammar(r'''
start: e
e: INT | "(" e ")" | e "+" e
INT: /[1-9][0-9]*|0+/
''')
VOCAB = [b"1", b"2", b"12", b"(", b")", b"+", b"+1", b"1(", b"((", b"))",
         None]
EOS = 10


def names(mask):
    return [VOCAB[i] if VOCAB[i] else b"<EOS>" for i in np.where(mask)[0]]


def test_fig3_start_mask():
    d = DominoDecoder(FIG3, VOCAB, eos_id=EOS)
    assert names(d.mask()) == [b"1", b"2", b"12", b"(", b"(("]
    m0 = d.mask(k=0)
    assert not m0[8], "'((' is a depth-2 bridge, needs k>=1"


def test_fig3_bridge_token_lookahead():
    d = DominoDecoder(FIG3, VOCAB, eos_id=EOS)
    assert d.advance(3) and d.advance(2)        # "(12"
    m, m0, m1 = d.mask(), d.mask(k=0), d.mask(k=1)
    assert m[6] and m1[6] and not m0[6], "'+1' included from k=1 (paper §3.4)"
    for i in (0, 1, 2, 4, 5):                   # digits, ')', '+' at k=0
        assert m0[i]
    assert not m[7] and not m[9] and not m[EOS]


def test_fig3_eos_and_continue():
    d = DominoDecoder(FIG3, VOCAB, eos_id=EOS)
    for t in (3, 2, 4):                          # "(12)"
        assert d.advance(t)
    m = d.mask()
    assert m[EOS] and m[5] and m[6] and not m[0]
    d2 = d.clone()
    assert d.advance(EOS) and d.finished
    assert d2.advance(6) and d2.advance(1)       # "(12)+11"
    assert d2.mask()[EOS]


def test_illegal_token_rejected():
    d = DominoDecoder(FIG3, VOCAB, eos_id=EOS)
    assert not d.advance(4)          # ")" at start
    assert not d.advance(EOS)
    assert d.advance(0)              # "1"
    assert not d.advance(3)          # "1(" illegal


def test_opportunistic_check_matches_mask():
    d = DominoDecoder(FIG3, VOCAB, eos_id=EOS)
    d.advance(3), d.advance(2)
    m = d.mask()
    for tok in range(len(VOCAB)):
        assert d.check_token(tok) == bool(m[tok]), VOCAB[tok]


@pytest.mark.parametrize("gname", ["json", "json_gsm8k", "xml_schema"])
def test_online_baseline_mask_equality(gname, small_tokenizer):
    """DOMINO(k=inf) masks == full-vocabulary online parser masks."""
    tok = small_tokenizer
    g = grammars.load(gname)
    d1 = DominoDecoder(g, tok.vocab, eos_id=tok.eos_id)
    d2 = OnlineParserDecoder(g, tok.vocab, eos_id=tok.eos_id)
    sampler = GrammarSampler(g, seed=5)
    text = sampler.sample()
    ids = greedy_tokenize(text, tok.vocab)[:12]
    for t in ids:
        m1, m2 = d1.mask(), d2.mask()
        assert (m1 == m2).all(), \
            [tok.vocab[i] for i in np.where(m1 != m2)[0]]
        assert m1[t]
        assert d1.advance(t) and d2.advance(t)


@pytest.mark.parametrize("gname", ["json", "json_gsm8k", "c", "xml_schema"])
def test_minimal_invasiveness(gname, small_tokenizer, rng):
    """Def 2.1 core property: any tokenization of any valid string is
    accepted token-by-token by DOMINO(k=inf), and EOS is legal at the end."""
    tok = small_tokenizer
    g = grammars.load(gname)
    cache = TreeCache(Scanner(g), list(tok.vocab))
    sampler = GrammarSampler(g, seed=23)
    for trial in range(4):
        text = sampler.sample()
        ids = (greedy_tokenize(text, tok.vocab) if trial % 2 == 0
               else _random_tokenize(text, tok, rng))
        d = DominoDecoder(g, tok.vocab, eos_id=tok.eos_id, tree_cache=cache)
        for t in ids:
            assert d.mask()[t], (gname, text, tok.vocab[t])
            assert d.advance(t)
        assert d.eos_legal(), (gname, text)


def _random_tokenize(text, tok, rng):
    """A random (non-canonical) segmentation of text into vocab tokens."""
    from repro.core.retokenize import prefix_tokens
    from repro.core.trees import VocabTrie
    trie = VocabTrie.build(list(tok.vocab))
    out, rest = [], text
    while rest:
        cands = prefix_tokens(trie, rest)
        t = rng.choice(cands)
        out.append(t)
        rest = rest[len(tok.vocab[t]):]
    return out


def test_naive_equals_k0(small_tokenizer):
    tok = small_tokenizer
    g = grammars.load("json")
    d = naive_greedy_decoder(g, tok.vocab, tok.eos_id)
    ref = DominoDecoder(g, tok.vocab, tok.eos_id, k=0)
    assert (d.mask() == ref.mask()).all()


def test_k_monotonicity(small_tokenizer):
    """Larger lookahead can only ADD legal tokens."""
    tok = small_tokenizer
    g = grammars.load("json_gsm8k")
    d = DominoDecoder(g, tok.vocab, eos_id=tok.eos_id)
    ids = greedy_tokenize(b'{"thoughts": [{"step": "a"', tok.vocab)
    for t in ids:
        prev = None
        for k in (0, 1, 2, None):
            m = d.mask(k=k)
            if prev is not None:
                assert (m | prev == m).all(), "mask must grow with k"
            prev = m
        assert d.advance(t)


def test_intervention_forces_eos_only_when_nothing_else():
    g = parse_grammar('start: "ab"\n')
    vocab = [b"a", b"b", b"ab", b"x", None]
    d = DominoDecoder(g, vocab, eos_id=4)
    m = d.mask()
    assert m[0] and m[2] and not m[1] and not m[3] and not m[4]
    d.advance(2)
    m = d.mask()
    assert list(np.where(m)[0]) == [4], "only EOS after full parse"
