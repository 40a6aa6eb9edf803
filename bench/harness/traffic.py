"""One traffic generator for every mix; a mix is a JSON file of numbers.

A mix describes sessions of one or more turns (keys of
``bench/traffic/<mix>.json``):

- ``loop``: ``"closed"`` (``clients`` sessions run back to back, each
  turn sent when the last one completes) or ``"open"`` (sessions arrive
  on a schedule at ``session_rate_per_s``, whether or not earlier ones
  finished);
- ``turns``: a distribution of turns per session (default 1);
- ``system``: ``{"tokens": n, "shared": bool, "pin": bool}``, a system
  prompt that opens every turn's prompt, the same text for every
  session when ``shared``; ``pin`` asks the server to pin it in its
  prefix cache, where it has one;
- ``user_tokens``, ``answer_tokens``: the tokens of each user turn, and
  each answer's ``max_tokens``;
- ``think_s``: the pause between a turn's answer and the next turn;
- ``grammars``: the constraint of each session, drawn in turn from this
  list (``null`` for an unconstrained row);
- ``history``: whether a turn's prompt carries the earlier turns.

A distribution is ``{"dist": "uniform", "min": a, "max": b}``,
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
``{"dist": "exponential", "mean": m}`` or a plain number.  Every
distribution is read as ``grid`` quantiles, dealt out in an order
shuffled by the seed, one shuffle per pass: each seed sends the same
sizes and the same arrival gaps, in another order, so seeds change what
the words are and when each size comes, not how much work there is.
Request ``k`` of a closed loop and session ``k`` of an open loop take
their sizes and words from the seed and ``k`` alone, so a seed always
sends the same requests in the same order.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import statistics
from typing import Dict, List, Optional

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")

# stream ids: each quantity draws from its own shuffled grid
_USER, _ANSWER, _TURNS, _THINK, _GAP, _GRAMMAR, _WORDS, _SYSTEM = range(8)


def load_mix(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def quantiles(dist, k: int) -> List[float]:
    """``k`` quantiles of ``dist`` at (i + 0.5) / k."""
    if isinstance(dist, (int, float)):
        return [float(dist)] * k
    ps = [(i + 0.5) / k for i in range(k)]
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return [lo + p * (hi - lo) for p in ps]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        qs = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(p))
              for p in ps]
        return [min(max(q, dist.get("min", 0)), dist.get("max", math.inf))
                for q in qs]
    if kind == "exponential":
        return [-dist["mean"] * math.log(1.0 - p) for p in ps]
    raise ValueError(f"unknown distribution {kind!r}")


class Grid:
    """Quantiles of one distribution, dealt in seeded shuffled passes."""

    def __init__(self, values, seed: int, stream: int):
        self.values = list(values)
        self.seed, self.stream = seed, stream
        self._perms: Dict[int, np.ndarray] = {}

    def __getitem__(self, i: int):
        k = len(self.values)
        cycle, j = divmod(i, k)
        perm = self._perms.get(cycle)
        if perm is None:
            rng = np.random.default_rng([self.seed, self.stream, cycle])
            perm = self._perms[cycle] = rng.permutation(k)
        return self.values[perm[j]]


@dataclasses.dataclass
class Turn:
    """One request: a turn of a session."""
    session: int
    index: int
    due: float                       # perf_counter time it is due
    prompt: str
    grammar: Optional[str]
    max_tokens: int
    last: bool                       # the session's last turn


class Words:
    """Words of the tokenizer that are one token each, with a leading
    space, so text of n words is n tokens."""

    def __init__(self, tok):
        pat = re.compile(rb"^ [a-z]{3,9}$")
        self.words = [v.decode() for v in tok.vocab
                      if v is not None and pat.match(v)
                      and len(tok.encode_bytes(v)) == 1]
        if len(self.words) < 100:
            raise ValueError("tokenizer has too few one-token words")

    def text(self, rng: np.random.Generator, n: int) -> str:
        idx = rng.integers(0, len(self.words), size=n)
        return "".join(self.words[i] for i in idx)


class Generator:
    """Deals out the turns of a mix for one seed.

    ``start(t0)`` fixes the clock; ``due(now)`` pops the turns due by
    ``now``; ``completed(turn, answer, now)`` tells the generator a turn
    finished, which schedules the session's next turn or, in a closed
    loop, the client's next session."""

    def __init__(self, mix: dict, seed: int, tok):
        self.mix = mix
        self.seed = int(seed)
        self.words = Words(tok)
        k = int(mix.get("grid", 64))
        g = lambda d, stream: Grid(quantiles(d, k), self.seed, stream)
        self.user = g(mix["user_tokens"], _USER)
        self.answer = g(mix["answer_tokens"], _ANSWER)
        self.turns = g(mix.get("turns", 1), _TURNS)
        self.think = g(mix.get("think_s", 0.0), _THINK)
        grammars = mix["grammars"]
        self.grammar = Grid([grammars[i % len(grammars)] for i in range(k)],
                            self.seed, _GRAMMAR)
        self.gap = (g({"dist": "exponential",
                       "mean": 1.0 / mix["session_rate_per_s"]}, _GAP)
                    if mix["loop"] == "open" else None)
        self.max_turns = int(math.ceil(max(quantiles(mix.get("turns", 1),
                                                     k))))
        sysd = mix.get("system") or {"tokens": 0}
        self.system_tokens = int(sysd["tokens"])
        self.system_shared = bool(sysd.get("shared", True))
        self.history = bool(mix.get("history", False))
        self._system: Dict[int, str] = {}
        self._pending: List[Turn] = []
        self._sessions: Dict[int, dict] = {}
        self._next_session = 0
        self._next_arrival = None

    # -- texts -------------------------------------------------------------

    def system_text(self, session: int) -> str:
        key = 0 if self.system_shared else session + 1
        if key not in self._system:
            rng = np.random.default_rng([self.seed, _SYSTEM, key])
            self._system[key] = ("System:"
                                 + self.words.text(rng, self.system_tokens))
        return self._system[key]

    def pinned_prompts(self) -> List[str]:
        """Prompts the server may pin: the shared system prompt."""
        sysd = self.mix.get("system") or {}
        if sysd.get("pin") and self.system_shared and self.system_tokens:
            return [self.system_text(0)]
        return []

    def prompt_lengths(self, tok, max_len: int) -> List[int]:
        """Every prompt length, in tokens, the mix can send: the system
        prompt, the fixed markers and one token per word.  With history,
        every length from the shortest first turn to the longest last
        one that fits ``max_len``, since a re-encoded answer can take
        up to three tokens a served token (bytes that do not decode come
        back as U+FFFD)."""
        fixed = (len(tok.encode(self.system_text(0)))
                 + len(tok.encode("\nUser:")) + len(tok.encode("\nAnswer:")))
        users = sorted({int(round(u)) for u in self.user.values})
        if not self.history:
            return [fixed + u for u in users]
        turn = fixed - len(tok.encode(self.system_text(0))) + users[-1] \
            + 3 * int(round(max(self.answer.values)))
        hi = fixed + users[-1] + (self.max_turns - 1) * turn
        return list(range(fixed + users[0], min(hi, max_len) + 1))

    def _open_session(self, now: float) -> None:
        s = self._next_session
        self._next_session += 1
        n_turns = max(1, int(round(self.turns[s])))
        self._sessions[s] = {
            "n_turns": n_turns,
            "grammar": self.grammar[s],
            "rng": np.random.default_rng([self.seed, _WORDS, s]),
            "history": "",
        }
        self._pending.append(self._turn(s, 0, now))

    def _turn(self, s: int, t: int, due: float) -> Turn:
        st = self._sessions[s]
        i = s * self.max_turns + t
        user = "\nUser:" + self.words.text(st["rng"],
                                           int(round(self.user[i])))
        prompt = self.system_text(s) + st["history"] + user + "\nAnswer:"
        st["last_user"] = user
        return Turn(session=s, index=t, due=due, prompt=prompt,
                    grammar=st["grammar"],
                    max_tokens=int(round(self.answer[i])),
                    last=t + 1 >= st["n_turns"])

    # -- clock -------------------------------------------------------------

    def start(self, t0: float) -> None:
        if self.mix["loop"] == "closed":
            for _ in range(int(self.mix["clients"])):
                self._open_session(t0)
        else:
            self._next_arrival = t0 + self.gap[0]

    def next_due(self) -> Optional[float]:
        dues = [t.due for t in self._pending]
        if self._next_arrival is not None:
            dues.append(self._next_arrival)
        return min(dues) if dues else None

    def due(self, now: float) -> List[Turn]:
        while self._next_arrival is not None and self._next_arrival <= now:
            self._open_session(self._next_arrival)
            self._next_arrival += self.gap[self._next_session]
        ready = sorted((t for t in self._pending if t.due <= now),
                       key=lambda t: (t.due, t.session))
        self._pending = [t for t in self._pending if t.due > now]
        return ready

    def completed(self, turn: Turn, answer: str, now: float) -> None:
        st = self._sessions[turn.session]
        if self.history:
            st["history"] += st["last_user"] + "\nAnswer:" + answer
        if not turn.last:
            think = self.think[turn.session * self.max_turns + turn.index]
            self._pending.append(self._turn(turn.session, turn.index + 1,
                                            now + think))
            return
        del self._sessions[turn.session]
        if self.mix["loop"] == "closed":
            self._open_session(now)
