"""Property tests tying the STATIC analyzer to RUNTIME decoding
(hypothesis):

1. certification soundness — when the analyzer certifies a random grammar
   trap-free with a finite closure, randomized legal decoding never
   dead-ends;
2. witness validity — every trap the analyzer reports on a seeded-trap
   variant of the grammar reproduces a dead end when its token path is
   replayed through a fresh DominoDecoder;
3. fan-out bound — the runtime hypothesis-set size along any legal decode
   never exceeds the analyzer's reported max fan-out (both measured on
   concrete decoders over the same quotient).
"""
import random

import numpy as np
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.analysis import analyze
from repro.core.domino import DominoDecoder
from repro.core.grammar import parse_grammar

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


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_certified_trap_free_never_dead_ends(gtext, seed):
    g = parse_grammar(gtext)
    rep = analyze(g, VOCAB, EOS, name="rand", max_states=512)
    if not rep.closure.finite:
        return                          # no certificate claimed: skip
    if rep.n_mask_conflicts:
        # the quotient conflated states with differing masks (e.g. deep
        # center-nesting): the analyzer must DOWNGRADE its own
        # certificate rather than claim trap-freedom
        assert not rep.ok(), gtext
        return
    assert not rep.traps, (gtext, [str(w) for w in rep.traps])
    # the certificate must hold at runtime: randomized legal decoding
    # from the start state never reaches an empty mask
    rng = random.Random(seed)
    d = DominoDecoder(g, VOCAB, EOS)
    for _ in range(16):
        m = d.mask()
        assert m.any(), (gtext, "runtime dead end on certified grammar")
        t = int(rng.choice(np.where(m)[0]))
        assert d.advance(t)
        if t == EOS:
            break


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_seeded_trap_witnesses_reproduce(gtext, seed):
    """Append a trap arm to a random grammar: after the normal body, an
    OPEN token leads into a terminal no byte string matches.  The
    analyzer must find reachable traps, and every witness must replay to
    a concrete dead end."""
    trapped = gtext + '\nDEADT: /[^\\x00-\\xff]/\n'
    trapped = trapped.replace("start:", "start: OPEN DEADT |", 1) \
        + 'OPEN: "{"\n'
    g = parse_grammar(trapped)
    rep = analyze(g, VOCAB, EOS, name="trapped", max_states=512)
    assert not rep.ok(), trapped
    assert rep.traps, trapped           # "{" then stuck is reachable
    for w in rep.traps:
        assert w.confirmed, (trapped, str(w))
        d = DominoDecoder(g, VOCAB, EOS)
        for t in w.token_ids:
            assert d.advance(t), (trapped, w.token_ids)
        assert not d.mask_bits().any(), (trapped, w.token_ids)


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(random_grammar(), st.integers(0, 10000))
def test_runtime_fanout_within_analyzer_bound(gtext, seed):
    g = parse_grammar(gtext)
    rep = analyze(g, VOCAB, EOS, name="rand", max_states=512)
    if not rep.closure.finite or rep.n_mask_conflicts:
        return      # bound only claimed for clean finite certificates
    rng = random.Random(seed)
    d = DominoDecoder(g, VOCAB, EOS)
    for _ in range(12):
        assert len(d.hyps) <= rep.max_abstract_fanout, gtext
        m = d.mask()
        if not m.any():
            break
        t = int(rng.choice(np.where(m)[0]))
        assert d.advance(t)
        if t == EOS:
            break
    assert d.n_hyp_truncations == 0, gtext
