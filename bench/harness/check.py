"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests due in it that
finished is drawn from the seed, with the longest answer always in it,
until it holds ``min_tokens`` served tokens.  The configuration's plain
reference runs once over each prompt with its served tokens, and at
every served position (end of sequence included, where the request
ended on it):

- the served token must be legal: accepted after the served prefix by
  the independent checker of the request's grammar
  (``harness.grammar_ref``), or any id of the tokenizer for an
  unconstrained row.  ``illegal_tokens`` counts those that are not;
- its gap is how far the served token's reference logit lies below the
  reference's best legal token.  ``max_logit_gap`` is the widest gap,
  ``mean_logit_gap`` the mean over the positions, and
  ``mismatch_share`` the share of positions (in %) whose served token
  is not the reference's best legal one.  A sound bfloat16 step and
  masked argmax leave only rounding here; a wrong mask, a wrong token,
  a wrong step or a step in a lower precision leave more.

A control, the reference itself one precision step down, is read the
same way over the same positions, of the token it puts first.  Each
number named in ``bench/checks/<workload>.json`` has its limit there;
``judge`` holds a set of numbers to those limits.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import grammar_ref

NUMBERS = ("illegal_tokens", "max_logit_gap", "mean_logit_gap",
           "mismatch_share")
CHECK_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "checks")


def load_limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(CHECK_DIR, workload + ".json")) as f:
        return json.load(f)["limits"]


def sample(records, seed: int, min_tokens: int) -> List:
    """Finished requests drawn from ``seed``: the one with the most
    served tokens first, then others in a seeded order, until their
    served tokens reach ``min_tokens``."""
    done = [r for r in records if r.status == "ok" and r.sess.out_ids]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.sess.out_ids),
                                       len(r.sess.prompt_ids)))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 17]).permutation(len(rest))
    picked, n = [longest], len(longest.sess.out_ids)
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].sess.out_ids)
    return picked


class Legal:
    """Legality of the next token after a served prefix, for one
    request: its grammar's independent checker, or any id."""

    def __init__(self, grammar: Optional[str], vocab: Sequence[Optional[bytes]],
                 eos_id: int):
        self.grammar, self.vocab, self.eos = grammar, vocab, eos_id
        self.state = None if grammar is None else grammar_ref.start(grammar)

    def ok(self, tok: int) -> bool:
        if self.grammar is None:
            return 0 <= tok < len(self.vocab)
        if tok == self.eos:
            return grammar_ref.complete(self.state)
        data = self.vocab[tok]
        return bool(data) and grammar_ref.extend(self.state,
                                                 data) is not None

    def advance(self, tok: int) -> None:
        if self.grammar is not None and tok != self.eos:
            self.state = grammar_ref.extend(self.state, self.vocab[tok])

    def best(self, logits: np.ndarray) -> int:
        """The legal id with the largest logit."""
        for tok in np.argsort(-logits, kind="stable"):
            if self.ok(int(tok)):
                return int(tok)
        raise ValueError("no legal token")


def targets(rec, eos_id: int) -> List[int]:
    """What the request served at each position: its tokens, then end
    of sequence if it ended on it."""
    out = list(rec.sess.out_ids)
    return out + [eos_id] if rec.sess.finished_eos else out


class _Gaps:
    """The numbers of one run of choices over the scored positions."""

    def __init__(self):
        self.illegal, self.gaps, self.missed = 0, [], 0

    def add(self, ref_row: np.ndarray, best: int, tok: int) -> None:
        self.gaps.append(float(ref_row[best] - ref_row[tok]))
        self.missed += tok != best

    def numbers(self) -> Dict[str, float]:
        n = len(self.gaps)
        return {"illegal_tokens": self.illegal,
                "max_logit_gap": max(self.gaps, default=0.0),
                "mean_logit_gap": sum(self.gaps) / n if n else 0.0,
                "mismatch_share": 100.0 * self.missed / n if n else 0.0}


def compare(ref, cfg: dict, seed: int, picked, vocab, eos_id: int,
            controls: Sequence[str] = ()) -> Dict[str, object]:
    """Run the reference over ``picked`` and read the program's numbers;
    under ``"controls"``, each control's (by name of precision), read
    of the token it puts first at the same positions."""
    seqs, score, tg = [], [], []
    for r in picked:
        t = targets(r, eos_id)
        p = list(r.sess.prompt_ids)
        seqs.append(p + list(r.sess.out_ids))
        score.append(list(range(len(p) - 1, len(p) - 1 + len(t))))
        tg.append(t)
    lg = ref.logits(cfg, seed, seqs, score, len(vocab),
                    ("fp32",) + tuple(controls))
    prog, ctl = _Gaps(), {m: _Gaps() for m in controls}
    n_pos = 0
    for i, r in enumerate(picked):
        legal = Legal(r.turn.grammar, vocab, eos_id)
        for j, tok in enumerate(tg[i]):
            ref_row = lg["fp32"][i][j]
            n_pos += 1
            if not legal.ok(tok):
                prog.illegal += 1
                break              # the prefix left the grammar
            best = legal.best(ref_row)
            prog.add(ref_row, best, tok)
            for m in controls:
                ctl[m].add(ref_row, best, legal.best(lg[m][i][j]))
            legal.advance(tok)
    out = dict(prog.numbers(), positions=n_pos, requests=len(picked))
    out["controls"] = {m: g.numbers() for m, g in ctl.items()}
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, object]:
    """``numbers`` held to ``limits``: each compared number beside its
    limit, and whether every one is within it (a missing number is
    not)."""
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    return {"checks": checks,
            "correct": all(c["value"] is not None and c["value"] <= c["limit"]
                           for c in checks.values())}
