"""Drive the served path for a measured window, from the client side.

The harness, not the program, sends the traffic: each turn is submitted
when it falls due, ``ContinuousBatchingScheduler.step()`` is called
while anything is waiting or resident, and after every step each
request's newly committed tokens are stamped with the host clock.  A
request is timed from when it was due, so a late generator or a stalled
step shows in its latency.

After the window closes the traffic keeps coming, so the load does not
change, until every request that fell due inside the window has
finished, or ``DRAIN_CAP_S`` has passed; requests still open then count
as failed.

The scheduler's counters (the numeric entries of its ``stats()``) are
read when the window opens and when it closes, so a reader of a
cumulative count can take their difference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax

DRAIN_CAP_S = 60.0


@dataclasses.dataclass
class Record:
    """One request as the client saw it."""
    turn: object
    sess: object
    t_due: float
    times: List[float] = dataclasses.field(default_factory=list)
    t_done: Optional[float] = None
    status: Optional[str] = None


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    records: List[Record]
    compiled: List[str]               # programs lowered in the window
    lateness_s: List[float]           # submit - due, per request
    traced: Optional[dict] = None     # what the traced slice recorded
    counters_open: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters_close: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def in_window(self) -> List[Record]:
        return [r for r in self.records if self.t0 <= r.t_due < self.t_end]


class CompileCounter:
    """Counts programs JAX lowers (each jit cache miss lowers one), and
    the persistent compilation cache's hits and misses among them."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        self.names: List[str] = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event: str, _secs: float, fun_name: str = "?",
            **_kw) -> None:
        if event == self.EVENT:
            self.names.append(fun_name)

    def _on_cache(self, event: str, **_kw) -> None:
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1


def _span(name: str, on: bool):
    """A host span in the profiler's trace, while it records."""
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


def counters(sched) -> Dict[str, float]:
    """The numeric entries of the scheduler's ``stats()``."""
    return {k: v for k, v in sched.stats().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def submit(server, turn):
    """Queue one greedy turn, under DOMINO with unbounded lookahead when
    it names a grammar."""
    from repro.serving import ConstraintSpec, DecodeParams, Request
    spec = (ConstraintSpec() if turn.grammar is None else
            ConstraintSpec(grammar=turn.grammar, mode="domino"))
    req = Request(turn.prompt, spec,
                  DecodeParams(max_tokens=turn.max_tokens))
    return server.sched.submit(req)


def run(server, gen, seconds: float, counter: CompileCounter,
        trace: Optional[Dict] = None) -> Window:
    """Serve ``gen``'s traffic for ``seconds``.  ``trace``, when given,
    holds ``start_s``, ``length_s`` (offsets into the window) and
    ``dir``: the profiler records that slice, and each step inside it
    is annotated and its resident rows' key counts kept."""
    sched = server.sched
    live: Dict[int, Record] = {}
    records: List[Record] = []
    lateness: List[float] = []
    opened = counters(sched)
    closed = None
    t0 = time.perf_counter()
    t_end = t0 + seconds
    gen.start(t0)
    n_compiles0 = counter.n
    n_window = None
    traced = None
    tr_on = tr_off = None
    if trace is not None:
        tr_on, tr_off = t0 + trace["start_s"], t0 + trace["start_s"] \
            + trace["length_s"]
    while True:
        now = time.perf_counter()
        if trace is not None and traced is None and now >= tr_on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans: ours only
            jax.profiler.start_trace(trace["dir"], profiler_options=opts)
            traced = {"t_start": time.perf_counter(), "steps": []}
        if traced is not None and "t_stop" not in traced and now >= tr_off:
            traced["t_stop"] = time.perf_counter()
            jax.profiler.stop_trace()
        if now >= t_end:
            if n_window is None:
                n_window = counter.names[n_compiles0:]
                closed = counters(sched)
            open_ = [r for r in records
                     if r.t_done is None and r.t_due < t_end]
            if not open_ or now >= t_end + DRAIN_CAP_S:
                break
        in_trace = traced is not None and "t_stop" not in traced
        if in_trace:
            traced.setdefault("anchor", time.perf_counter())
        with _span("bench.submit", in_trace):
            for turn in gen.due(now):
                t_sub = time.perf_counter()
                sess = submit(server, turn)
                rec = Record(turn=turn, sess=sess, t_due=turn.due)
                records.append(rec)
                lateness.append(t_sub - turn.due)
                live[sess.rid] = rec
        if not server.busy():
            nxt = gen.next_due()
            if nxt is not None:
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.005)))
            continue
        with _span("bench.step", in_trace):
            done = sched.step()
        t = time.perf_counter()
        if in_trace:
            traced["steps"].append({
                "t": t,
                "keys": [len(s.prompt_ids) + len(s.out_ids)
                         for s in sched.slots if s is not None]})
        with _span("bench.record", in_trace):
            for rec in live.values():
                n = len(rec.sess.out_ids)
                while len(rec.times) < n:
                    rec.times.append(t)
            for sess in done:
                rec = live.pop(sess.rid, None)
                if rec is None:
                    continue
                rec.t_done = t
                rec.status = sess.result.status
                gen.completed(rec.turn, sess.result.text, t)
    if traced is not None and "t_stop" not in traced:
        traced["t_stop"] = time.perf_counter()
        jax.profiler.stop_trace()
    return Window(t0=t0, t_end=t_end, records=records,
                  compiled=n_window or [], lateness_s=lateness,
                  traced=traced, counters_open=opened,
                  counters_close=closed)
