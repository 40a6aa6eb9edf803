"""Host checker: mean length of a ``serve.advance`` span, one for each
committed constrained token (the checker's ``advance()``, EOS included),
over the program's spans inside the traced slice, in us."""
from harness import trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    adv = trace.inside(tr, trace.PROGRAM_PREFIX + "advance", tr.t_start,
                       tr.t_stop)
    if not adv:
        return None
    return sum(e - s for _, s, e in adv) / len(adv) / 1e3
