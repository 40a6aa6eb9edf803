"""The program's own host spans in a profiler trace, and what they give.

The scheduler names each phase of a tick with a ``serve.*`` span
(``repro.serving.tracing``) in every profiler session.  The spans lie on
the profiler's host plane, on the clock of the device's operations, next
to the harness's ``bench.*`` spans that ``harness.trace.load`` keeps;
``load`` here keeps the ``serve.*`` ones.  Nesting follows from the
intervals: the scheduler runs on one thread.  On a ``TraceData`` and
these spans:

- ``idle_gaps``: the longest device gaps, each named by the deepest
  ``serve.*`` span that covers most of it (by the ``bench.*`` span that
  overlaps it most where no program span touches it);
- ``idle_by_span``: all device idle time in the window, each instant put
  down to the deepest span around it;
- ``advance_us_per_tok``: mean ``serve.advance`` time, one span per
  committed constrained token;
- ``tick_host_ms``: median host time of a decode tick, ``serve.tick``
  less the ``serve.wait`` inside it;
- ``wait_skew_ms``: median distance from the end of each ``serve.wait``
  to the end of the decode step it waited on, which reads ~0 when the
  host and device clocks agree.
"""
from __future__ import annotations

import bisect
import collections
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace
from harness.trace import Event, TraceData

PREFIX = "serve."
NAMES = frozenset(PREFIX + n for n in (
    "tick", "reap", "admit", "prefill", "readback", "select", "mask",
    "commit", "advance", "dispatch", "prebuild", "wait"))


def load(path: str) -> List[Event]:
    """The ``serve.*`` host spans of a profiler file, outermost first
    where two start together."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                out += [(e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def device_gaps(tr: TraceData) -> List[Tuple[int, int]]:
    """Stretches of the window with no operation on the first chip."""
    if not tr.ops:
        return []
    busy = trace._merge(tr.ops[sorted(tr.ops)[0]], tr.t_start, tr.t_stop)
    gaps, cur = [], tr.t_start
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if tr.t_stop > cur:
        gaps.append((cur, tr.t_stop))
    return gaps


def _overlap(s: int, e: int, span: Event) -> int:
    return min(e, span[2]) - max(s, span[1])


def _length(span: Event) -> int:
    return span[2] - span[1]


def gap_name(s: int, e: int, program: Sequence[Event],
             harness: Sequence[Event]) -> str:
    """The deepest program span covering more than half of [s, e); else
    the program span overlapping it most (the deeper on a tie); else the
    harness span overlapping it most; else ``idle``."""
    touching = [(o, p) for p in program if (o := _overlap(s, e, p)) > 0]
    if touching:
        # spans nest, so the deepest of those around a stretch is the
        # shortest
        most = [p for o, p in touching if 2 * o > e - s]
        if not most:
            top = max(o for o, _ in touching)
            most = [p for o, p in touching if o == top]
        return min(most, key=_length)[0]
    best, over = "idle", 0
    for h in harness:
        o = _overlap(s, e, h)
        if o > over:
            best, over = h[0], o
    return best


def idle_gaps(tr: TraceData, program: Sequence[Event],
              k: int = 10) -> List[List]:
    """The ``k`` longest device gaps as ``[name, seconds]``."""
    gaps = sorted(device_gaps(tr), key=lambda g: g[0] - g[1])[:k]
    return [[gap_name(s, e, program, tr.spans), (e - s) / 1e9]
            for s, e in gaps]


class _Layer:
    """Spans sorted by start, to find those around a stretch quickly."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda p: p[1])
        self.starts = [p[1] for p in self.spans]
        self.longest = max(map(_length, spans), default=0)

    def around(self, s: int, e: int) -> List[Event]:
        lo = bisect.bisect_left(self.starts, s - self.longest)
        hi = bisect.bisect_left(self.starts, e)
        return [p for p in self.spans[lo:hi] if p[2] > s]


def _credit(pieces, layer: _Layer, out: Dict[str, int]):
    """Credit each instant of ``pieces`` to the deepest span of
    ``layer`` around it; returns the stretches no span covers."""
    rest = []
    for s, e in pieces:
        near = layer.around(s, e)
        cuts = sorted({s, e} | {t for p in near for t in (p[1], p[2])
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            over = [p for p in near if p[1] <= a and b <= p[2]]
            if over:
                out[min(over, key=_length)[0]] += b - a
            else:
                rest.append((a, b))
    return rest


def idle_by_span(tr: TraceData, program: Sequence[Event]
                 ) -> Dict[str, float]:
    """Seconds of device idle time in the window by the deepest span
    around each instant: a ``serve.*`` span, else a ``bench.*`` span,
    else ``idle``; most first."""
    out: Dict[str, int] = collections.Counter()
    rest = device_gaps(tr)
    for layer in (_Layer(program), _Layer(tr.spans)):
        rest = _credit(rest, layer, out)
    out["idle"] += sum(b - a for a, b in rest)
    return {n: t / 1e9 for n, t in out.most_common() if t}


def _inside(program: Sequence[Event], name: str, lo: int, hi: int
            ) -> List[Event]:
    return [p for p in program if p[0] == name and lo <= p[1]
            and p[2] <= hi]


def advance_us_per_tok(program: Sequence[Event], lo: int, hi: int
                       ) -> Optional[float]:
    """Mean length of the ``serve.advance`` spans inside [lo, hi], us."""
    adv = _inside(program, PREFIX + "advance", lo, hi)
    if not adv:
        return None
    return sum(e - s for _, s, e in adv) / len(adv) / 1e3


def tick_host_ms(program: Sequence[Event], lo: int, hi: int
                 ) -> Optional[float]:
    """Median, over the ``serve.tick`` spans inside [lo, hi] that hold a
    ``serve.wait``, of the tick's length less its waits, ms."""
    waits = _inside(program, PREFIX + "wait", lo, hi)
    host = []
    for _, s, e in _inside(program, PREFIX + "tick", lo, hi):
        w = [b - a for _, a, b in waits if s <= a and b <= e]
        if w:
            host.append((e - s - sum(w)) / 1e6)
    return statistics.median(host) if host else None


def wait_skew_ms(tr: TraceData, program: Sequence[Event],
                 pattern: str) -> Optional[float]:
    """Median |end of a ``serve.wait`` - end of the execution of a
    program matching ``pattern`` that ends nearest it|, ms, over the
    waits in the window, on the first chip."""
    if not tr.modules:
        return None
    rx = re.compile(pattern)
    ends = sorted(e for n, _, e in tr.modules[sorted(tr.modules)[0]]
                  if rx.search(n))
    d = [min(abs(w - x) for x in ends) / 1e6
         for _, _, w in _inside(program, PREFIX + "wait", tr.t_start,
                                tr.t_stop)]
    return statistics.median(d) if d and ends else None
