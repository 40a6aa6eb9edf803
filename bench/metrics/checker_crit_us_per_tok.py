"""Host checker: mask-build time left on the critical path
(``mask_time_s - mask_overlap_s``, the program's own host-clock timers)
per output token, over the constrained requests due in the window that
finished.  Earley ``advance()`` has no timer yet and is not in it."""


def read(run):
    res = [r.sess.result for r in run.window.in_window()
           if r.turn.grammar is not None and r.sess.result is not None]
    n = sum(x.n_tokens for x in res)
    if not n:
        return None
    crit = sum(x.mask_time_s - x.mask_overlap_s for x in res)
    return 1e6 * crit / n
