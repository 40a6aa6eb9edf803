#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the workload's
configuration in ``bench/configs/<config>.json``, whose ``family`` names
the module that makes the model and counts its FLOPs,
``bench/families/<family>.py``, and its plain reference,
``bench/reference/<family>.py``; its traffic
mix in ``bench/traffic/<mix>.json``, the limits of its check in
``bench/checks/<workload>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

Set-up (``setup_s``) runs from the start of this process to the start of
the window: imports, weights made on the device from the seed, the
tokenizer loaded, grammar trees built, the KV pool allocated, and a
warm-up that compiles (or loads from ``.jax_cache``) every program the
traffic will use.  Then the window: ``--seconds`` of traffic, timed from
the client side.  With ``--trace 1`` the profiler records a slice of it
and the per-layer metrics are reported instead of the end-to-end ones.
Then, with the program's state freed, the reference checks a sample of
what was served (``harness/check.py``).

The last line of standard output is the result; the last lines of
standard error are the numbers compared, each beside its limit.  Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and when no result is printed ``DEADLINE_S`` after
the process started: then the last line of standard error names the
phase the run was in.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

MIN_CHECK_TOKENS = 384       # served tokens the reference compares, at least
TRACE_START_FRAC = 0.4       # the traced slice starts this far in ...
TRACE_MAX_S = 8.0            # ... and lasts at most this long
WARM_THREADS = 8             # compiles the warm-up runs side by side
DEADLINE_S = 900.0           # no result by then: fail, naming the phase
DEADLINE_RC = 4


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Deadline:
    """The phase a run is in, and, once started, a timer that ends the
    process ``DEADLINE_S`` after it started if no result has been
    printed by then, naming that phase on standard error."""

    def __init__(self):
        self.phase = "set-up"
        self._timer: Optional[threading.Timer] = None

    def enter(self, phase: str) -> None:
        self.phase = phase

    def start(self) -> None:
        left = DEADLINE_S - (time.perf_counter() - T_START)
        self._timer = threading.Timer(max(0.0, left), self._expire)
        self._timer.daemon = True
        self._timer.start()

    def cancel(self) -> None:
        if self._timer is not None:
            self._timer.cancel()

    def _expire(self) -> None:
        log(f"error: deadline of {DEADLINE_S:g} s after process start "
                 f"passed in {self.phase}")
        os._exit(DEADLINE_RC)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: Dict[str, float]
    end_to_end: Dict[str, str]        # metric -> unit
    per_layer: Dict[str, str]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    from harness import check, server, traffic
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = next((w for w in bench["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = {m["name"]: m["unit"] for m in bench["end_to_end"] if mine(m)}
    layer = {m["name"]: m["unit"] for m in bench["per_layer"]
             if mine(m) and m["moves"] in e2e}
    return Cell(name=workload, chips=int(wl["chips"]),
                config=server.load_config(wl["config"]),
                mix=traffic.load_mix(wl["traffic"]),
                limits=check.load_limits(workload),
                end_to_end=e2e, per_layer=layer)


def percentile(xs: List[float], q: float) -> Optional[float]:
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


@dataclasses.dataclass
class RunData:
    """What a metric reader may look at."""
    cell: Cell
    window: object                    # harness.window.Window
    trace: Optional[object]           # harness.trace.TraceData
    peaks: Dict[str, float]
    n_vocab: int                      # the tokenizer's ids
    counters_open: Dict[str, float]   # the scheduler's, as the window
    counters_close: Dict[str, float]  # opens and as it closes


def end_to_end(run: RunData, setup_s: float) -> Dict[str, float]:
    w = run.window
    recs = w.in_window()
    secs = w.t_end - w.t0
    n_tok = sum(1 for r in w.records for t in r.times
                if w.t0 <= t <= w.t_end)
    gaps = [(b - a) * 1e3 for r in w.records
            for a, b in zip(r.times, r.times[1:]) if w.t0 <= b <= w.t_end]
    t_last = max([r.t_done or 0.0 for r in w.records]
                 + [t for r in w.records for t in r.times] + [w.t_end])

    def first(r):
        t = r.times[0] if r.times else r.t_done
        return t if t is not None and r.status == "ok" else t_last

    def done(r):
        return r.t_done if r.status == "ok" else t_last
    out = {
        "setup_s": setup_s,
        "output_tok_s": n_tok / secs,
        "itl_p95_ms": percentile(gaps, 95),
        "ttft_p90_ms": percentile([(first(r) - r.t_due) * 1e3
                                   for r in recs], 90),
        "request_p90_ms": percentile([(done(r) - r.t_due) * 1e3
                                      for r in recs], 90),
    }
    return {k: v for k, v in out.items() if k in run.cell.end_to_end}


def per_layer(run: RunData) -> Dict[str, float]:
    out = {}
    for name in run.cell.per_layer:
        mod = importlib.import_module("metrics." + name)
        v = mod.read(run)
        if v is not None:
            out[name] = v
    return out


def warm(srv, gen, cell: Cell) -> int:
    """Serve one short request per prefill bucket the mix can reach, on
    every slot, each constraint of the mix among them, and convert a
    prompt of every length the mix can send, so the window finds every
    program compiled.  Returns the requests and lengths warmed."""
    import jax
    import jax.numpy as jnp

    from harness import window
    from harness.traffic import Turn, quantiles
    tok = srv.engine.tok
    mix, srv_cfg = cell.mix, cell.config["server"]
    k = int(mix.get("grid", 64))
    users = quantiles(mix["user_tokens"], k)
    answers = quantiles(mix["answer_tokens"], k)
    turns = max(quantiles(mix.get("turns", 1), k))
    hist = turns if mix.get("history") else 1
    fixed = len(tok.encode(gen.system_text(0))) + 8
    lo = fixed + int(min(users))
    hi = min(fixed + int(max(users) + (hist - 1) * (max(users)
                                                    + max(answers) + 8)),
             srv_cfg["max_len"] - int(max(answers)) - 1)
    lengths, b = [], 1
    while b < 2 * hi:
        if b >= lo:
            lengths.append(max(lo, min(b, hi)))
        b *= 2
    lengths = sorted(set(lengths))
    stem = len(tok.encode("Warm:"))
    rng = __import__("numpy").random.default_rng(0)
    grammars = mix["grammars"]
    n = max(srv_cfg["slots"], len(lengths) * len(grammars))
    for i in range(n):
        length = lengths[i % len(lengths)]
        prompt = "Warm:" + gen.words.text(rng, max(1, length - stem))
        window.submit(srv, Turn(session=-1, index=i, due=0.0, prompt=prompt,
                                grammar=grammars[i % len(grammars)],
                                max_tokens=2, last=True))
    srv.drain()
    # admission converts each prompt's ids to a device array with a
    # program per distinct length: convert every length the mix can send
    lengths = gen.prompt_lengths(tok, srv_cfg["max_len"] - 1)
    with ThreadPoolExecutor(WARM_THREADS) as ex:
        list(ex.map(lambda m: jax.block_until_ready(
            jnp.asarray([[0] * m], jnp.int32)), lengths))
    return n + len(lengths)


def prepare(cell: Cell, seed: int, deadline: Optional[Deadline] = None):
    """Everything set-up does, for ``seed``: (server, generator, compile
    counter)."""
    from harness import server, tokenizer, traffic, window
    counter = window.CompileCounter()
    tok = tokenizer.load()
    gen = traffic.Generator(cell.mix, seed, tok)
    srv = server.Server(cell.config, cell.mix["grammars"], tok, seed,
                        gen.pinned_prompts())
    if deadline is not None:
        deadline.enter("warm-up")
    t0 = time.perf_counter()
    n_warm = warm(srv, gen, cell)
    srv.setup["warm_s"] = time.perf_counter() - t0
    srv.setup["warm_programs"] = n_warm
    # set-up leaves half a million long-lived objects (the grammar trees):
    # keep them out of every later full collection, as a server would
    gc.collect()
    gc.freeze()
    return srv, gen, counter


def verify(cell: Cell, recs, seed: int, vocab, eos: int,
           controls=()) -> Dict[str, object]:
    """The reference over a sample of ``recs``; call it once the program's
    state is freed.  The numbers read, with ``correct`` and the
    ``checks`` beside their limits, for the program and for each
    control under ``"controls"``."""
    from harness import check
    picked = check.sample(recs, seed, MIN_CHECK_TOKENS)
    ref = importlib.import_module("reference." + cell.config["family"])
    res = (check.compare(ref, cell.config, seed, picked, vocab, eos,
                         controls) if picked else
           {"positions": 0, "requests": 0,
            "controls": {m: {} for m in controls}})
    res.update(check.judge(res, cell.limits))
    for nums in res["controls"].values():
        nums.update(check.judge(nums, cell.limits))
    return res


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, log=log,
            deadline: Optional[Deadline] = None):
    """One run of ``cell``.  Returns (exit code, result or None)."""
    import jax

    deadline = deadline or Deadline()
    devs = jax.devices()
    plat = devs[0].platform
    if require_tpu and (plat != "tpu" or len(devs) < cell.chips):
        log(f"error: JAX sees {len(devs)} {plat} device(s); this cell needs "
            f"{cell.chips} TPU chip(s)")
        return 3, None
    from harness import check, flops, window
    from harness import trace as tracemod
    kind = devs[0].device_kind
    peaks = flops.peaks(kind) if require_tpu else {}
    log(f"device: platform {plat}, kind {kind}, count {len(devs)}")
    srv, gen, counter = prepare(cell, seed, deadline)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f} s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in srv.setup.items())
        + f"; {counter.n} programs lowered, persistent cache "
        f"{counter.cache['hits']} hits, {counter.cache['misses']} misses")
    tr_spec = None
    if trace:
        tr_spec = {"dir": tempfile.mkdtemp(prefix="trace-"),
                   "start_s": TRACE_START_FRAC * seconds,
                   "length_s": min(TRACE_MAX_S,
                                   (1 - TRACE_START_FRAC) * seconds)}
    deadline.enter("window")
    win = window.run(srv, gen, seconds, counter, tr_spec)
    stats = devs[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    recs = win.in_window()
    failed = sum(1 for r in recs if r.status != "ok")
    log(f"window: {len(recs)} requests due, {failed} failed, "
        f"{len(win.records)} submitted in all; programs lowered in the "
        f"window: {len(win.compiled)} {sorted(set(win.compiled))}; "
        f"generator lateness p50/max "
        f"{percentile(win.lateness_s, 50) or 0:.4f}/"
        f"{max(win.lateness_s, default=0):.4f} s")
    tr = None
    if tr_spec is not None:
        deadline.enter("trace")
        if win.traced is not None and require_tpu:
            tr = tracemod.load(tracemod.find_xplane(tr_spec["dir"]),
                               win.traced["t_start"], win.traced["t_stop"],
                               win.traced["anchor"])
        shutil.rmtree(tr_spec["dir"], ignore_errors=True)
    run = RunData(cell=cell, window=win, trace=tr, peaks=peaks,
                  n_vocab=srv.engine.tok.vocab_size,
                  counters_open=win.counters_open,
                  counters_close=win.counters_close)
    metrics = per_layer(run) if trace else end_to_end(run, setup_s)
    units = cell.per_layer if trace else cell.end_to_end
    # the reference runs on a chip the program no longer holds
    tok = srv.engine.tok
    srv.close()
    gc.unfreeze()
    gc.collect()
    deadline.enter("reference check")
    t0 = time.perf_counter()
    res = verify(cell, recs, seed, list(tok.vocab), tok.eos_id)
    log(f"reference over {res['requests']} requests, {res['positions']} "
        f"positions, {time.perf_counter() - t0:.2f} s; " + ", ".join(
            f"{k} {res.get(k)}" for k in check.NUMBERS))
    device = {"platform": plat, "kind": kind, "count": len(devs),
              "memory_peak_bytes": mem_peak}
    result = {"correct": res["correct"], "attempted": len(recs),
              "failed": failed,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": device}
    if tr is not None:
        device["busy_s"] = tracemod.busy_s(tr)
        device["window_s"] = tracemod.window_s(tr)
        result["breakdown"] = {"device_ops": tracemod.top_ops(tr),
                               "idle_gaps": tracemod.idle_gaps(tr)}
    result["checks"] = res["checks"]
    for k, c in res["checks"].items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return 0, result


def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping
    every program, however quick to compile."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    deadline = Deadline()
    deadline.start()
    configure_cache()
    cell = load_cell(args.workload)
    rc, result = execute(cell, args.seed, args.seconds, bool(args.trace),
                         deadline=deadline)
    if result is not None:
        print(json.dumps(result), flush=True)
    deadline.cancel()
    return rc


if __name__ == "__main__":
    sys.exit(main())
