"""Model step: the decode step's algorithmic FLOPs over the resident
rows, as the configuration's family counts them (``step_flops``), over
its device time times the chip's bf16 peak."""
import families
from harness import trace
from metrics._steps import DECODE_PROGRAM, share, steps


def read(run):
    st = steps(run)
    if run.trace is None or not st:
        return None
    n, t = trace.program_calls(run.trace, DECODE_PROGRAM)
    if not n:
        return None
    cfg = run.cell.config
    step_flops = families.of(cfg).step_flops
    per_call = sum(step_flops(cfg, k) for k in st) / len(st)
    return share(per_call * n / run.peaks["bf16_flops_per_s"], t)
