"""Kernels: the Pallas decode-attention kernel's share of its roofline.
Per call (one layer of one step), the least time is the larger of its
FLOPs over the bf16 peak and its logical K/V, query and output bytes
over HBM bandwidth (``harness.flops.decode_attention``; memory bounds
it); the share is that over the kernel's mean device time per call."""
from harness import flops, trace
from metrics._steps import dims, share, steps

KERNEL = r"^%decode_attention_pallas"


def read(run):
    st = steps(run)
    if run.trace is None or not st:
        return None
    n, t = trace.kernel_calls(run.trace, KERNEL)
    if not n:
        return None
    _, _, nq, nkv, dh, _ = dims(run)
    mins = [flops.min_time(*flops.decode_attention(k, nq, nkv, dh),
                           run.peaks)[0] for k in st]
    return share(sum(mins) / len(mins), t / n)
