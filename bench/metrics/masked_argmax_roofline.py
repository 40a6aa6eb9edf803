"""Kernels: the packed masked-argmax kernel's share of its roofline.
Per call, the least time moves each resident row's float32 logits over
the tokenizer's ids and its packed mask words once
(``harness.flops.masked_argmax``; memory bounds it); the share is that
over the mean device time of the kernel's program per call (the
kernel and the padding of its tail tile)."""
from harness import flops, trace
from metrics._steps import share, steps

PROGRAM = r"^jit_masked_argmax_pallas_packed\("


def read(run):
    st = steps(run)
    if run.trace is None or not st:
        return None
    n, t = trace.program_calls(run.trace, PROGRAM)
    if not n:
        return None
    mins = [flops.min_time(*flops.masked_argmax(len(k), run.n_vocab),
                           run.peaks)[0] for k in st]
    return share(sum(mins) / len(mins), t / n)
