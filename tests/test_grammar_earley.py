"""Grammar parsing + Earley recognizer."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import grammars
from repro.core.earley import EarleyParser, parse_terminals
from repro.core.grammar import GrammarSyntaxError, parse_grammar
from repro.core.sampling import GrammarSampler


def _tid(g, name):
    return {t.name: i for i, t in enumerate(g.terminals)}[name]


def test_json_sequences(json_grammar):
    g = json_grammar
    LB, RB = _tid(g, "'{'"), _tid(g, "'}'")
    CM, CL = _tid(g, "','"), _tid(g, "':'")
    LK, RK = _tid(g, "'['"), _tid(g, "']'")
    S, N = _tid(g, "STRING"), _tid(g, "NUMBER")
    assert parse_terminals(g, [LB, RB])
    assert parse_terminals(g, [LB, S, CL, LK, N, CM, N, RK, RB])
    assert not parse_terminals(g, [LB, S, CL, RB])
    assert not parse_terminals(g, [LB, CM, RB])
    assert not parse_terminals(g, [])


def test_allowed_terminals(json_grammar):
    g = json_grammar
    p = EarleyParser(g)
    names = {g.terminals[t].name for t in p.allowed_terminals()}
    assert names == {"'{'", "'['", "STRING", "NUMBER", "BOOL", "NULL"}
    assert p.advance(_tid(g, "'{'"))
    names = {g.terminals[t].name for t in p.allowed_terminals()}
    assert names == {"'}'", "STRING"}


def test_fork_isolation(json_grammar):
    g = json_grammar
    p = EarleyParser(g)
    p.advance(_tid(g, "'{'"))
    q = p.fork()
    assert q.advance(_tid(g, "'}'"))
    assert q.accepts()
    assert not p.accepts()
    assert p.position == 1 and q.position == 2


def test_ambiguous_grammar():
    g = parse_grammar("""
start: e
e: INT | e "+" e
INT: /[0-9]+/
""")
    i, pl = 0, 1
    tid = {t.name: j for j, t in enumerate(g.terminals)}
    seq = [tid["INT"], tid["'+'"], tid["INT"], tid["'+'"], tid["INT"]]
    assert parse_terminals(g, seq)
    assert not parse_terminals(g, seq[:-1])


def test_nullable_rules():
    g = parse_grammar("""
start: a b a
a: ("x")?
b: "y"
""")
    tid = {t.name: j for j, t in enumerate(g.terminals)}
    X, Y = tid["'x'"], tid["'y'"]
    assert parse_terminals(g, [Y])
    assert parse_terminals(g, [X, Y])
    assert parse_terminals(g, [Y, X])
    assert parse_terminals(g, [X, Y, X])
    assert not parse_terminals(g, [X, X, Y])


def test_syntax_errors():
    with pytest.raises(GrammarSyntaxError):
        parse_grammar("start: UNDEF\n")
    with pytest.raises(GrammarSyntaxError):
        parse_grammar("TERM: /a*/\nstart: TERM\n")  # empty-matching terminal


@pytest.mark.parametrize("name", list(grammars.GRAMMARS))
def test_workload_grammars_load(name):
    g = grammars.load(name)
    assert g.n_terminals > 0 and len(g.rules) > 0
    g.describe()


@pytest.mark.parametrize("name", ["json", "json_gsm8k", "xml_schema", "c"])
def test_sampled_strings_parse_at_terminal_level(name):
    """Property: sampling then re-lexing through DOMINO accepts (end-to-end
    check lives in test_domino); here we check the sampler+grammar agree."""
    from repro.core.domino import DominoDecoder
    g = grammars.load(name)
    vocab = [bytes([i]) for i in range(256)] + [None]
    d0 = DominoDecoder(g, vocab, eos_id=256)
    sampler = GrammarSampler(g, seed=11)
    for _ in range(5):
        s = sampler.sample()
        d = d0.clone()
        for b in s:
            assert d.advance(b), (name, s, bytes([b]))
        assert d.eos_legal(), (name, s)
