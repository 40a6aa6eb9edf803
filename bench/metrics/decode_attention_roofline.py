"""Kernels: the Pallas decode-attention kernel's share of its roofline.
Per call (one layer of one step), the least time is the larger of its
FLOPs over the bf16 peak and its logical bytes over HBM bandwidth, as
the configuration's family counts them (``attention_cost``); the share
is that over the kernel's mean device time per call."""
import families
from harness import flops, trace
from metrics._steps import share, steps

KERNEL = r"^%decode_attention_pallas"


def read(run):
    st = steps(run)
    if run.trace is None or not st:
        return None
    n, t = trace.kernel_calls(run.trace, KERNEL)
    if not n:
        return None
    cfg = run.cell.config
    cost = families.of(cfg).attention_cost
    mins = [flops.min_time(*cost(cfg, k), run.peaks)[0] for k in st]
    return share(sum(mins) / len(mins), t / n)
