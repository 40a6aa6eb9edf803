"""Property tests over RANDOM small CFGs (hypothesis):

1. completeness/minimal invasiveness — every sampled grammar string, under
   any byte-level tokenization, is accepted token-by-token and ends with
   legal EOS;
2. mask equality — DOMINO(k=inf) == full-vocabulary online checking;
3. soundness — following only-masked tokens never dead-ends.
"""
import random

import numpy as np
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.baselines import OnlineParserDecoder
from repro.core.domino import DominoDecoder
from repro.core.grammar import parse_grammar
from repro.core.sampling import GrammarSampler

TERM_POOL = [
    ('NUM', r'/[0-9]+/'),
    ('ID', r'/[a-z]+/'),
    ('STR', r'/"[a-z]*"/'),
    ('OPA', '"+"'),
    ('LP', '"("'),
    ('RP', '")"'),
    ('COMMA', '","'),
]

VOCAB = [bytes([i]) for i in range(33, 127)] + [
    b"ab", b'("', b'")', b"1,", b",,", b'+(', b"12", b'"a"', b"a1",
    b"((", b"))", None]
EOS = len(VOCAB) - 1


@st.composite
def random_grammar(draw):
    n_terms = draw(st.integers(3, len(TERM_POOL)))
    terms = TERM_POOL[:n_terms]
    lines = [f"{n}: {p}" for n, p in terms]
    names = [n for n, _ in terms]
    # start: one of three shapes over random terminals
    shape = draw(st.integers(0, 2))
    a = draw(st.sampled_from(names))
    b = draw(st.sampled_from(names))
    if shape == 0:
        lines.insert(0, f"start: {a} ({b} {a})*")
    elif shape == 1:
        lines.insert(0, f"start: e\ne: {a} | LP e RP" if "LP" in names
                     and "RP" in names else f"start: {a} {b}?")
    else:
        lines.insert(0, f"start: ({a} | {b})+")
    return "\n".join(lines)


def _random_tokenize(text: bytes, rng: random.Random):
    from repro.core.retokenize import prefix_tokens
    from repro.core.trees import VocabTrie
    trie = VocabTrie.build(list(VOCAB))
    out, rest = [], text
    while rest:
        cands = prefix_tokens(trie, rest)
        if not cands:
            return None
        out.append(rng.choice(cands))
        rest = rest[len(VOCAB[out[-1]]):]
    return out


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_sampled_strings_accepted_any_tokenization(gtext, seed):
    g = parse_grammar(gtext)
    sampler = GrammarSampler(g, seed=seed, max_depth=8)
    rng = random.Random(seed)
    d0 = DominoDecoder(g, VOCAB, eos_id=EOS)
    for _ in range(2):
        text = sampler.sample(max_ws=0.0)
        ids = _random_tokenize(text, rng)
        if ids is None:
            continue
        d = d0.clone()
        for t in ids:
            assert d.mask()[t], (gtext, text, VOCAB[t])
            assert d.advance(t)
        assert d.eos_legal(), (gtext, text)


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_domino_equals_online(gtext, seed):
    g = parse_grammar(gtext)
    d1 = DominoDecoder(g, VOCAB, eos_id=EOS)
    d2 = OnlineParserDecoder(g, VOCAB, eos_id=EOS)
    rng = random.Random(seed)
    for _ in range(5):
        m1, m2 = d1.mask(), d2.mask()
        assert (m1 == m2).all(), \
            (gtext, [VOCAB[i] for i in np.where(m1 != m2)[0]])
        legal = [t for t in np.where(m1)[0] if t != EOS]
        if not legal:
            break
        t = rng.choice(legal)
        assert d1.advance(t) and d2.advance(t)


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_soundness_no_dead_ends(gtext, seed):
    """Following masked tokens for 12 steps: the mask never goes empty
    (EOS counts), i.e. constrained decoding cannot paint itself into a
    corner."""
    g = parse_grammar(gtext)
    d = DominoDecoder(g, VOCAB, eos_id=EOS)
    rng = random.Random(seed)
    for _ in range(12):
        m = d.mask()
        assert m.any(), (gtext, "dead end")
        t = int(rng.choice(np.where(m)[0]))
        assert d.advance(t)
        if t == EOS:
            break
