"""The traffic generator: a seed fixes the requests, another seed
changes them but not the sizes it deals out."""
import collections

import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import tokenizer, traffic


@pytest.fixture(scope="module")
def tok():
    return tokenizer.load()


def _closed(mix, seed, tok, n=40):
    gen = traffic.Generator(mix, seed, tok)
    gen.start(0.0)
    out, t = [], 0.0
    while len(out) < n:
        for turn in gen.due(t):
            out.append((turn.prompt, turn.max_tokens, turn.grammar))
            gen.completed(turn, "{}", t)
        t += 1.0
    return out


def _open(mix, seed, tok, horizon=300.0):
    gen = traffic.Generator(mix, seed, tok)
    gen.start(0.0)
    out, t = [], 0.0
    while t < horizon:
        for turn in gen.due(t):
            out.append((round(turn.due, 9), turn.session, turn.index,
                        turn.prompt[-200:], turn.max_tokens))
            gen.completed(turn, '{"a": 1}', t)
        t += 0.25
    return out


@pytest.mark.parametrize("mix", ["extract-json", "extract-none"])
def test_closed_loop_seeded(mix, tok):
    m = traffic.load_mix(mix)
    a = _closed(m, 3000000001, tok)
    assert a == _closed(m, 3000000001, tok)
    b = _closed(m, 3000000002, tok)
    assert a != b
    # one full pass of the grid deals the same sizes to every seed
    k = m["grid"]

    def sizes(seed):
        reqs = _closed(m, seed, tok, k)
        return (collections.Counter(len(tok.encode(p)) for p, _, _ in reqs),
                collections.Counter(n for _, n, _ in reqs),
                collections.Counter(g for _, _, g in reqs))
    assert sizes(3000000001) == sizes(3000000002) == sizes(7)


def test_open_loop_seeded(tok):
    m = traffic.load_mix("agent-fc")
    a = _open(m, 5, tok)
    assert a and a == _open(m, 5, tok)
    assert a != _open(m, 6, tok)
    sessions = collections.Counter(s for _, s, _, _, _ in a)
    assert max(sessions.values()) >= 2          # multi-turn sessions


def test_prompt_lengths_are_exact(tok):
    m = traffic.load_mix("extract-json")
    gen = traffic.Generator(m, 11, tok)
    want = set(gen.prompt_lengths(tok, 2047))
    got = {len(tok.encode(p)) for p, _, _ in _closed(m, 11, tok, 48)}
    assert got <= want and len(want) <= m["grid"]


def test_history_prompt_lengths_cover(tok):
    m = traffic.load_mix("agent-fc")
    want = set(traffic.Generator(m, 5, tok).prompt_lengths(tok, 12287))
    got = {len(tok.encode(p)) for *_, p, _ in _open_full(m, 5, tok)}
    assert got and got <= want


def _open_full(mix, seed, tok, horizon=200.0):
    gen = traffic.Generator(mix, seed, tok)
    gen.start(0.0)
    out, t = [], 0.0
    while t < horizon:
        for turn in gen.due(t):
            out.append((turn.session, turn.prompt, turn.max_tokens))
            # an answer of max_tokens words of one token each
            gen.completed(turn, " ok" * turn.max_tokens, t)
        t += 0.25
    return out


def test_quantiles():
    q = traffic.quantiles({"dist": "lognormal", "median": 384, "sigma": 0.9,
                           "min": 16, "max": 1536}, 48)
    assert len(q) == 48 and max(q) == 1536 and min(q) >= 16
    assert q[23] < 384 < q[24]
    u = traffic.quantiles({"dist": "uniform", "min": 32, "max": 256}, 4)
    assert u == [60.0, 116.0, 172.0, 228.0]
