"""Kernels: the window-attention kernel's share of its roofline, on the
prefix-cache hit path.  Per call (one layer of one hit), the least time
is the larger of its FLOPs over the bf16 peak and its logical bytes over
HBM bandwidth (``harness.window_flops``), averaged over the hits
admitted inside the traced slice, each with its own cached keys and
tail; the share is that over the mean device time of the kernel's calls
inside the hit program (``jit_prefill_window``), so speculative verify
windows, which run the same kernel in the decode step, stay out."""
import re

from harness import flops, window_flops
from metrics._steps import share

KERNEL = re.compile(r"^%window_attention_pallas")
PROGRAM = re.compile(r"^jit_prefill_window\(")


def hits(run):
    """(cached keys, tail tokens) of each prefix-cache hit admitted
    inside the traced slice."""
    t = run.window.traced
    if not t or "t_stop" not in t:
        return []
    return [r.sess.hit_window for r in run.window.records
            if getattr(r.sess, "hit_window", None)
            and t["t_start"] <= r.sess.t_admit <= t["t_stop"]]


def calls(tr):
    """(calls, device seconds) of the kernel inside hit programs."""
    n, ns = 0, 0
    for chip, ops in tr.ops.items():
        progs = [(s, e) for name, s, e in tr.modules.get(chip, [])
                 if PROGRAM.search(name)]
        for name, s, e in ops:
            if KERNEL.search(name) and any(ps <= s and e <= pe
                                           for ps, pe in progs):
                n += 1
                ns += e - s
    return n, ns / 1e9


def read(run):
    hs = hits(run)
    if run.trace is None or not hs:
        return None
    n, t = calls(run.trace)
    if not n:
        return None
    heads = window_flops.heads(run.cell.config)
    mins = [flops.min_time(*window_flops.window_attention(s, k, *heads),
                           run.peaks)[0] for s, k in hs]
    return share(sum(mins) / len(mins), t / n)
