"""Scheduler: p90 of the wait from a request's due time to the end of
its admission (``Session.t_admit``, host clock), over the requests due
in the window that were admitted."""
import numpy as np


def read(run):
    waits = [(r.sess.t_admit - r.t_due) * 1e3
             for r in run.window.in_window() if r.sess.t_admit > 0]
    return float(np.percentile(waits, 90)) if waits else None
