"""Model step: the decode step's algorithmic FLOPs (weights and head
for each resident row, attention over its keys; ``harness.flops``)
over its device time times the chip's bf16 peak."""
from harness import flops, trace
from metrics._steps import DECODE_PROGRAM, dims, share, steps


def read(run):
    st = steps(run)
    if run.trace is None or not st:
        return None
    n, t = trace.program_calls(run.trace, DECODE_PROGRAM)
    if not n:
        return None
    L, d, nq, nkv, dh, f = dims(run)
    vocab = run.cell.config["model"]["vocab_size"]
    per_call = sum(flops.decode_step(k, L, d, nq, nkv, dh, f, vocab)
                   for k in st) / len(st)
    return share(per_call * n / run.peaks["bf16_flops_per_s"], t)
