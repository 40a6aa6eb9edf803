"""Scheduler: median length of a ``serve.prefill_hit`` span inside the
traced slice, in ms: one prefix-cache hit from the dispatch of its tail
window to the host's read of its logits row, the stall a hit puts into
every resident row's gap."""
import statistics

from harness import trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    hits = trace.inside(tr, trace.PROGRAM_PREFIX + "prefill_hit",
                        tr.t_start, tr.t_stop)
    if not hits:
        return None
    return statistics.median(e - s for _, s, e in hits) / 1e6
