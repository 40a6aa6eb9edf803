"""Regex engine: unit tests + hypothesis property vs Python's re."""
import re as stdre

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.regex import RegexSyntaxError, compile_pattern, literal_dfa


CASES = [
    (r"[1-9][0-9]*|0+", ["0", "00", "7", "123"], ["", "012", "1a", "a"]),
    (r"a+b?c*", ["a", "ab", "aacc", "abccc"], ["", "b", "ba", "abab"]),
    (r"(ab|cd)+", ["ab", "abcd", "cdcdab"], ["", "a", "abc"]),
    (r"a{2,4}", ["aa", "aaa", "aaaa"], ["a", "aaaaa", ""]),
    (r"a{3}", ["aaa"], ["aa", "aaaa"]),
    (r"[^x]+", ["abc", " "], ["", "axb"]),
    (r"\d+\.\d+", ["3.14"], ["3.", ".14", "3"]),
    (r'"([^"\\]|\\(["\\/bfnrt]|u[0-9a-fA-F]{4}))*"',
     ['""', '"ab"', '"a\\"b"', '"\\u00Ff"'],
     ['"', '"a', '"\\q"', '"a"b"']),
    (r"(//)[^\n]*\n", ["// hi\n", "//\n"], ["//", "/ x\n"]),
]


@pytest.mark.parametrize("pattern,accepts,rejects", CASES)
def test_cases(pattern, accepts, rejects):
    dfa = compile_pattern(pattern)
    for s in accepts:
        assert dfa.matches(s.encode()), (pattern, s)
    for s in rejects:
        assert not dfa.matches(s.encode()), (pattern, s)


def test_literal():
    d = literal_dfa("while")
    assert d.matches(b"while")
    assert not d.matches(b"whil")
    assert not d.matches(b"whilex")


def test_syntax_errors():
    for bad in ["(", "[", "a|*", "*a"]:
        with pytest.raises(RegexSyntaxError):
            compile_pattern(bad)


def test_dead_state_pruning():
    # every state can reach acceptance -> can_continue is meaningful
    d = compile_pattern(r"ab|ac")
    for s in range(d.n_states):
        assert d.can_continue(s) or d.is_accept(s)


# a conservative pattern subset where our semantics == python re fullmatch
_ATOMS = ["a", "b", "c", "[ab]", "[^a]", "[a-c]", r"\d"]


@st.composite
def _patterns(draw, depth=2):
    if depth == 0:
        return draw(st.sampled_from(_ATOMS))
    kind = draw(st.integers(0, 4))
    if kind == 0:
        return draw(st.sampled_from(_ATOMS))
    if kind == 1:
        return "(" + draw(_patterns(depth=depth - 1)) + ")" + \
            draw(st.sampled_from(["*", "+", "?", ""]))
    if kind == 2:
        return "(" + draw(_patterns(depth=depth - 1)) + "|" + \
            draw(_patterns(depth=depth - 1)) + ")"
    return draw(_patterns(depth=depth - 1)) + draw(_patterns(depth=depth - 1))


@settings(max_examples=60, deadline=None)
@given(_patterns(), st.text(alphabet="abc0", max_size=6))
def test_matches_stdlib(pattern, text):
    ours = compile_pattern(pattern).matches(text.encode())
    theirs = stdre.fullmatch(pattern, text) is not None
    assert ours == theirs, (pattern, text)
