#!/usr/bin/env python3
"""Serve one cell traced, and sum the device's idle time by the host work
in it.

  python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

Set-up, the window and the traced slice are ``run.py --trace 1``'s, and
so is the reading of the trace (``harness/trace.py``), the program's
``serve.*`` spans with it.  The result line's ``idle_gaps`` name only
the longest gaps; this tool puts every instant of idle time down to the
deepest span around it: a ``serve.*`` span, else a ``bench.*`` span,
else ``idle``.  The last line of standard output is JSON: the window's
end-to-end metrics and the per-layer metrics the cell reads from a
trace, the idle seconds by span, the share of idle time inside a
``serve.*`` span, the number of each span in the slice, and
``wait_skew_ms``, the median distance from the end of each
``serve.wait`` to the end of the decode step nearest it (~0 when the
host and device clocks agree).  No reference check runs: spans do not
change what is served (``tests/test_tracing.py``).  Exits non-zero,
printing no result, without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

import run  # noqa: E402  (puts src/ and bench/ on the path)
from harness import trace  # noqa: E402
from harness.trace import Event, TraceData  # noqa: E402


class _Layer:
    """Spans sorted by start, to find those around a stretch quickly."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda p: p[1])
        self.starts = [p[1] for p in self.spans]
        self.longest = max((e - s for _, s, e in spans), default=0)

    def around(self, s: int, e: int) -> List[Event]:
        lo = bisect.bisect_left(self.starts, s - self.longest)
        hi = bisect.bisect_left(self.starts, e)
        return [p for p in self.spans[lo:hi] if p[2] > s]


def _credit(pieces, layer: _Layer, out: Dict[str, int]):
    """Credit each instant of ``pieces`` to the deepest span of
    ``layer`` around it; returns the stretches no span covers."""
    rest = []
    for s, e in pieces:
        near = layer.around(s, e)
        cuts = sorted({s, e} | {t for p in near for t in (p[1], p[2])
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            over = [p for p in near if p[1] <= a and b <= p[2]]
            if over:
                out[min(over, key=lambda p: p[2] - p[1])[0]] += b - a
            else:
                rest.append((a, b))
    return rest


def idle_by_span(tr: TraceData) -> Dict[str, float]:
    """Seconds of device idle time in the window by the deepest span
    around each instant: a program span, else a harness span, else
    ``idle``; most first."""
    out: Dict[str, int] = collections.Counter()
    rest = trace.device_gaps(tr)
    for layer in (_Layer(tr.program_spans), _Layer(tr.spans)):
        rest = _credit(rest, layer, out)
    out["idle"] += sum(b - a for a, b in rest)
    return {n: t / 1e9 for n, t in out.most_common() if t}


def wait_skew_ms(tr: TraceData, pattern: str) -> Optional[float]:
    """Median |end of a ``serve.wait`` - end of the execution of a
    program matching ``pattern`` that ends nearest it|, ms, over the
    waits in the window, on the first chip."""
    if not tr.modules:
        return None
    rx = re.compile(pattern)
    ends = sorted(e for n, _, e in tr.modules[sorted(tr.modules)[0]]
                  if rx.search(n))
    d = [min(abs(w - x) for x in ends) / 1e6
         for _, _, w in trace.inside(tr, trace.PROGRAM_PREFIX + "wait",
                                     tr.t_start, tr.t_stop)]
    return statistics.median(d) if d and ends else None


def attribute(cell: run.Cell, seed: int, seconds: float,
              require_tpu: bool = True, log=run.log):
    """One traced window of ``cell``.  Returns (exit code, result or
    None)."""
    import jax

    from harness import flops, window
    from metrics._steps import DECODE_PROGRAM
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell.chips):
        log(f"error: JAX sees {len(devs)} {devs[0].platform} device(s); "
            f"this cell needs {cell.chips} TPU chip(s)")
        return 3, None
    srv, gen, counter = run.prepare(cell, seed)
    setup_s = time.perf_counter() - T_START
    spec = {"dir": tempfile.mkdtemp(prefix="trace-"),
            "start_s": run.TRACE_START_FRAC * seconds,
            "length_s": min(run.TRACE_MAX_S,
                            (1 - run.TRACE_START_FRAC) * seconds)}
    try:
        win = window.run(srv, gen, seconds, counter, spec)
        t = win.traced
        tr = trace.load(trace.find_xplane(spec["dir"]), t["t_start"],
                        t["t_stop"], t["anchor"])
    finally:
        shutil.rmtree(spec["dir"], ignore_errors=True)
    data = run.RunData(
        cell=cell, window=win, trace=tr,
        peaks=flops.peaks(devs[0].device_kind) if require_tpu else {},
        n_vocab=srv.engine.tok.vocab_size,
        counters_open=win.counters_open, counters_close=win.counters_close)
    idle = idle_by_span(tr)
    idle_s = sum(idle.values())
    in_program = sum(v for k, v in idle.items()
                     if k.startswith(trace.PROGRAM_PREFIX))
    result = {
        "metrics": {**run.end_to_end(data, setup_s), **run.per_layer(data)},
        "spans": {
            "wait_skew_ms": wait_skew_ms(tr, DECODE_PROGRAM),
            "counts": dict(collections.Counter(
                n for n, s, e in tr.program_spans
                if tr.t_start <= s and e <= tr.t_stop)),
        },
        "idle_s": idle_s,
        "idle_in_program_share": (100.0 * in_program / idle_s
                                  if idle_s else None),
        "idle_by_span": idle,
        "device": {"kind": devs[0].device_kind,
                   "busy_s": trace.busy_s(tr),
                   "window_s": trace.window_s(tr)},
    }
    srv.close()
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.configure_cache()
    cell = run.load_cell(args.workload)
    rc, result = attribute(cell, args.seed, args.seconds)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
