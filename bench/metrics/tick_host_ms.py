"""Scheduler: median host time of a decode tick, in ms: each
``serve.tick`` span inside the traced slice that holds a ``serve.wait``
(the tick's ``block_until_ready`` on its decode step), less those
waits."""
import statistics

from harness import trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.t_start, tr.t_stop
    waits = trace.inside(tr, trace.PROGRAM_PREFIX + "wait", lo, hi)
    host = []
    for _, s, e in trace.inside(tr, trace.PROGRAM_PREFIX + "tick", lo, hi):
        w = [b - a for _, a, b in waits if s <= a and b <= e]
        if w:
            host.append((e - s - sum(w)) / 1e6)
    return statistics.median(host) if host else None
