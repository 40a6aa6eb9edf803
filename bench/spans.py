#!/usr/bin/env python3
"""Serve one cell with the program's spans on, and name the device's
idle time by the host work in it.

  python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

Set-up, the window and the traced slice are ``run.py --trace 1``'s; the
scheduler's spans (``repro.serving.tracing``) land in every profiler
session, so the slice holds them.  The last line of standard output is JSON: the window's end-to-end metrics,
the per-layer metrics the cell reads from a trace, the longest idle gaps
named by the deepest ``serve.*`` span in each (``harness/spans.py``),
the idle seconds by span, the share of idle time inside a ``serve.*``
span, and what the spans give: ``advance_us_per_tok``, ``tick_host_ms``
and ``wait_skew_ms``.  No reference check runs: spans do not change
what is served (``tests/test_tracing.py``).  Exits non-zero, printing no
result, without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import run  # noqa: E402  (puts src/ and bench/ on the path)


def attribute(cell: run.Cell, seed: int, seconds: float,
              require_tpu: bool = True, log=run.log):
    """One traced window of ``cell``.  Returns (exit code, result or
    None)."""
    import jax

    from harness import flops, spans, window
    from harness import trace as tracemod
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
        path = tracemod.find_xplane(spec["dir"])
        t = win.traced
        tr = tracemod.load(path, t["t_start"], t["t_stop"], t["anchor"])
        prog = spans.load(path)
    finally:
        shutil.rmtree(spec["dir"], ignore_errors=True)
    data = run.RunData(
        cell=cell, window=win, trace=tr,
        peaks=flops.peaks(devs[0].device_kind) if require_tpu else {},
        n_vocab=srv.engine.tok.vocab_size)
    idle = spans.idle_by_span(tr, prog)
    idle_s = sum(idle.values())
    in_program = sum(v for k, v in idle.items()
                     if k.startswith(spans.PREFIX))
    lo, hi = tr.t_start, tr.t_stop
    result = {
        "metrics": {**run.end_to_end(data, setup_s), **run.per_layer(data)},
        "spans": {
            "advance_us_per_tok": spans.advance_us_per_tok(prog, lo, hi),
            "tick_host_ms": spans.tick_host_ms(prog, lo, hi),
            "wait_skew_ms": spans.wait_skew_ms(tr, prog, DECODE_PROGRAM),
            "counts": dict(collections.Counter(
                n for n, s, e in prog if lo <= s and e <= hi)),
        },
        "idle_s": idle_s,
        "idle_in_program_share": (100.0 * in_program / idle_s
                                  if idle_s else None),
        "idle_by_span": idle,
        "idle_gaps": spans.idle_gaps(tr, prog),
        "device": {"kind": devs[0].device_kind,
                   "busy_s": tracemod.busy_s(tr),
                   "window_s": tracemod.window_s(tr)},
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
