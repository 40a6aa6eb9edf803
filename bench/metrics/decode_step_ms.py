"""Model step: device time of the batched decode-step program per
execution, from the trace."""
from harness import trace
from metrics._steps import DECODE_PROGRAM


def read(run):
    if run.trace is None:
        return None
    n, t = trace.program_calls(run.trace, DECODE_PROGRAM)
    return 1e3 * t / n if n else None
