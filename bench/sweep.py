#!/usr/bin/env python3
"""Find the highest session rate an open-loop cell sustains.

Runs the cell's traffic at each ``--rates`` value in one process (one
set-up), for ``--seconds`` each, and prints one JSON line per rate: the
turns due and finished in the window, the wait from due time to
admission in the window's first and last thirds, the latency tails and
the queue left at the window's end.  A rate is sustained while the last
third's wait stays near the first third's; the cell's traffic file then
takes about four fifths of the highest such rate.  The benchmark's own
runs never run this.

  python3 bench/sweep.py --workload yi-34b-6l.agent-fc --seconds 40 \\
      --seed 7 --rates 0.1 0.2 0.3
"""
import argparse
import json
import sys

import numpy as np

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    R.configure_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        R.log("error: the sweep runs on the chip")
        return 3
    from harness import traffic, window
    cell = R.load_cell(args.workload)
    srv, gen, counter = R.prepare(cell, args.seed)
    for rate in args.rates:
        cell.mix["session_rate_per_s"] = rate
        gen = traffic.Generator(cell.mix, args.seed, srv.engine.tok)
        win = window.run(srv, gen, args.seconds, counter)
        waiting = len(srv.sched.waiting)
        srv.drain()
        recs = win.in_window()
        third = args.seconds / 3

        def wait(lo, hi):
            w = [r.sess.t_admit - r.t_due for r in recs
                 if lo <= r.t_due - win.t0 < hi and r.sess.t_admit > 0]
            return float(np.median(w)) if w else None
        lat = [r.t_done - r.t_due for r in recs if r.t_done]
        print(json.dumps({
            "rate": rate, "due": len(recs),
            "done": sum(r.status == "ok" for r in recs),
            "wait_first_third_s": wait(0, third),
            "wait_last_third_s": wait(2 * third, 3 * third),
            "request_p50_s": float(np.percentile(lat, 50)) if lat else None,
            "request_p90_s": float(np.percentile(lat, 90)) if lat else None,
            "waiting_at_end": waiting}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
