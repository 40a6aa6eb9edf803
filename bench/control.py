#!/usr/bin/env python3
"""Read a cell's check numbers for the program and for its control.

The control is the configuration's reference put in the program's
place one precision step down: weights quantized to fp8
(``reference/dense.py``'s ``"fp8"`` mode) where the configuration
states bfloat16.  For each seed, in one process, the program serves the
cell's traffic for ``--seconds`` at the cell's own load; then, over the
same sample the run's check draws, the reference reads the program's
numbers and, at each served position, those of the token the control
puts first.  Both are held to the cell's limits as a run holds the
program.  One JSON line per seed.

  python3 bench/control.py --workload <name> --seconds 45 --seeds 11 12 13

The limits in ``bench/checks/<workload>.json`` are set from these
readings: above the program's largest, below the control's smallest.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time

import run as R

CONTROL = "fp8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    R.configure_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        R.log("error: the control is read on the chip")
        return 3
    from harness import check, traffic, window
    cell = R.load_cell(args.workload)
    srv, gen, counter = R.prepare(cell, args.seeds[0])
    tok = srv.engine.tok
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        if i:
            gen = traffic.Generator(cell.mix, seed, tok)
            srv.reseed(seed, gen.pinned_prompts())
            R.warm(srv, gen, cell)
        win = window.run(srv, gen, args.seconds, counter)
        recs = win.in_window()
        srv.close()
        gc.unfreeze()
        gc.collect()
        res = R.verify(cell, recs, seed, list(tok.vocab), tok.eos_id,
                       (CONTROL,))
        ctl = res["controls"][CONTROL]
        print(json.dumps({
            "seed": seed, "attempted": len(recs),
            "failed": sum(r.status != "ok" for r in recs),
            "requests": res["requests"], "positions": res["positions"],
            "seconds": time.perf_counter() - t0,
            "program": {k: res.get(k) for k in check.NUMBERS + ("correct",)},
            CONTROL: {k: ctl.get(k) for k in check.NUMBERS + ("correct",)}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
