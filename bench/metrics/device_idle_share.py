"""Device: the share of the traced window in which no operation ran on
the chip, 100 * (1 - busy / window)."""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    w = trace.window_s(run.trace)
    return None if w <= 0 else 100.0 * (1.0 - trace.busy_s(run.trace) / w)
