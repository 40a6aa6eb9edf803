"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a ``TraceData``:
the device operations of each chip (from its ``XLA Ops`` line), the
executions of each jitted program (``XLA Modules``), the host spans the
harness annotated (``bench.*``) and the program's own (``serve.*``, one
per phase of a scheduler tick, ``repro.serving.tracing``).  Host spans
lie on the clock of the device's operations; the program's nest by
their intervals, as the scheduler runs on one thread.  The reductions
below work on that plain form only, so they can be checked on a small
synthetic trace:

- ``busy_s``: the union of the intervals in which an operation ran on
  the device, averaged over the chips;
- ``program_calls`` / ``kernel_calls``: executions and device time of a
  program or of the operations whose name matches a kernel's;
- ``idle_gaps``: the longest stretches with nothing running on the
  device, each named by the deepest program span that covers most of
  it, else by the harness span that overlaps it most (``gap_name``);
- ``inside``: the program spans of one name inside a stretch.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]        # (name, start_ns, end_ns)


@dataclasses.dataclass
class TraceData:
    ops: Dict[str, List[Event]]      # chip -> device operations
    modules: Dict[str, List[Event]]  # chip -> program executions
    spans: List[Event]               # host spans the harness annotated
    t_start: int                     # traced window, ns
    t_stop: int
    program_spans: List[Event] = dataclasses.field(default_factory=list)


def _merge(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                if e > lo and s < hi)
    out: List[List[int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: TraceData) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not tr.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in _merge(ev, tr.t_start, tr.t_stop))
              for ev in tr.ops.values())
    return tot / len(tr.ops) / 1e9


def window_s(tr: TraceData) -> float:
    return (tr.t_stop - tr.t_start) / 1e9


def program_calls(tr: TraceData, pattern: str) -> Tuple[int, float]:
    """(executions, device seconds) of programs whose name matches
    ``pattern``, summed over the chips."""
    rx = re.compile(pattern)
    n, t = 0, 0
    for ev in tr.modules.values():
        for name, s, e in ev:
            if rx.search(name):
                n += 1
                t += e - s
    return n, t / 1e9


def kernel_calls(tr: TraceData, pattern: str) -> Tuple[int, float]:
    """(calls, device seconds) of operations whose name matches."""
    rx = re.compile(pattern)
    n, t = 0, 0
    for ev in tr.ops.values():
        for name, s, e in ev:
            if rx.search(name):
                n += 1
                t += e - s
    return n, t / 1e9


def short_name(op: str) -> str:
    """``%copy.88 = bf16[...] copy(...)`` -> ``copy.88``."""
    return op.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Event], lo: int, hi: int
               ) -> Dict[str, int]:
    """Time of each operation (by short name) inside [lo, hi], less the
    time of the operations nested in it (a loop holds its body's)."""
    evs = sorted(((max(s, lo), min(e, hi), short_name(n))
                  for n, s, e in events if e > lo and s < hi),
                 key=lambda x: (x[0], -x[1]))
    own: Dict[str, int] = collections.Counter()
    stack: List[Tuple[int, int, str]] = []
    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            own[stack[-1][2]] -= e - s
        own[name] += e - s
        stack.append((s, e, name))
    return own


def top_ops(tr: TraceData, k: int = 10) -> List[List]:
    """The ``k`` device operations (by short name) with the most self
    time in the window, averaged over the chips."""
    tot: Dict[str, int] = collections.Counter()
    for ev in tr.ops.values():
        tot.update(self_times(ev, tr.t_start, tr.t_stop))
    n = max(1, len(tr.ops))
    return [[name, t / n / 1e9] for name, t in tot.most_common(k)]


def device_gaps(tr: TraceData) -> List[Tuple[int, int]]:
    """Stretches of the window with no operation on the first chip."""
    if not tr.ops:
        return []
    busy = _merge(tr.ops[sorted(tr.ops)[0]], tr.t_start, tr.t_stop)
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


def idle_gaps(tr: TraceData, k: int = 10) -> List[List]:
    """The ``k`` longest device gaps as ``[name, seconds]``, each named
    by ``gap_name``."""
    gaps = sorted(device_gaps(tr), key=lambda g: g[0] - g[1])[:k]
    return [[gap_name(s, e, tr.program_spans, tr.spans), (e - s) / 1e9]
            for s, e in gaps]


def inside(tr: TraceData, name: str, lo: int, hi: int) -> List[Event]:
    """The program spans called ``name`` that lie inside [lo, hi]."""
    return [p for p in tr.program_spans
            if p[0] == name and lo <= p[1] and p[2] <= hi]


# -- reading the profiler's file --------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "serve."


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, t_start_host: float, t_stop_host: float,
         anchor_host: float) -> TraceData:
    """Read a profiler file.  The traced window is given in host
    ``perf_counter`` seconds; ``anchor_host`` is the ``perf_counter``
    reading just before the first ``bench.*`` span opened, which ties
    the host clock to the trace's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    program: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns), int(e.end_ns))
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(ev)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append(ev)
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    first = min(s for _, s, _ in spans)
    off = first - int(anchor_host * 1e9)
    return TraceData(ops=ops, modules=modules, spans=spans,
                     t_start=int(t_start_host * 1e9) + off,
                     t_stop=int(t_stop_host * 1e9) + off,
                     program_spans=sorted(program,
                                          key=lambda e: (e[1], -e[2])))
