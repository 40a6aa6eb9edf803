"""The scheduler's ``serve.*`` host spans (``serving/tracing.py``).

They change nothing that is served and, recorded by the profiler, sit
where the documentation puts them: inside the caller's annotation around
each tick, one ``serve.advance`` per committed constrained token.
"""
import glob
import os

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core import grammars
from repro.models import build_model
from repro.serving import (ConstraintSpec, ContinuousBatchingScheduler,
                           DecodeParams, EngineConfig, Request,
                           ServingEngine)
from repro.serving.tracing import PREFIX

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            dtype="float32", max_seq_len=512)
PROMPTS = ["A: ", "Q: compute 1 + 2\nA: ", "x", "record -> "]
# the phases of a plain tick, as the scheduler's documentation lists them
PLAIN_NAMES = {PREFIX + n for n in (
    "tick", "reap", "admit", "prefill", "readback", "select", "mask",
    "commit", "advance", "dispatch", "prebuild", "wait")}
STEP = "test.step"


@pytest.fixture(scope="module")
def model(small_tokenizer):
    cfg = ModelConfig(arch_id="tr", family="dense",
                      vocab_size=small_tokenizer.vocab_size, **BASE)
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(model, tok, speculative: bool) -> ServingEngine:
    m, params = model
    eng = ServingEngine(m, params, tok, grammars.load("json_gsm8k"),
                        EngineConfig(mode="domino", speculative=speculative,
                                     spec_s=4, spec_threshold=0.4,
                                     max_tokens=16), max_len=256)
    if speculative:
        eng.generate(PROMPTS[0])        # warm the shared count model
    return eng


def _requests():
    # engine-default (constrained) rows and one unconstrained row
    return PROMPTS[:3] + [Request(PROMPTS[3], ConstraintSpec(),
                                  DecodeParams(max_tokens=8))]


def _serve(eng):
    sched = ContinuousBatchingScheduler(eng, capacity=2)
    sessions = [sched.submit(r) for r in _requests()]
    while sched.waiting or any(s is not None for s in sched.slots):
        with jax.profiler.TraceAnnotation(STEP):
            sched.step()
    return sessions


def _outputs(sessions):
    return [(s.result.token_ids, s.result.status, s.result.n_forward_passes)
            for s in sessions]


def _recorded(eng, trace_dir):
    """Serve the batch inside a profiler session; returns the sessions
    and the host plane's ``serve.*`` and ``test.step`` spans as (name,
    start, end), in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        sessions = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, int(e.start_ns), int(e.end_ns))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(PREFIX) or e.name == STEP]
    return sessions, sorted(events, key=lambda e: (e[1], -e[2]))


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["greedy", "speculative"])
def test_tracer_changes_nothing_served(model, small_tokenizer, speculative,
                                       tmp_path):
    off = _serve(_engine(model, small_tokenizer, speculative))
    on, events = _recorded(_engine(model, small_tokenizer, speculative),
                           tmp_path)
    assert _outputs(on) == _outputs(off)
    assert any(n.startswith(PREFIX) for n, _, _ in events)
    if speculative:
        assert sum(s.result.n_spec_proposed for s in on) > 0


def test_recorded_spans_nest_in_the_callers_step(model, small_tokenizer,
                                                 tmp_path):
    on, events = _recorded(_engine(model, small_tokenizer, False), tmp_path)
    steps = [(s, e) for n, s, e in events if n == STEP]
    prog = [ev for ev in events if ev[0] != STEP]
    names = [n for n, _, _ in prog]
    # every documented phase of a plain tick shows, and nothing else
    assert set(names) == PLAIN_NAMES
    assert names.count(PREFIX + "tick") == len(steps)
    assert all(any(a <= s and e <= b for a, b in steps)
               for _, s, e in prog)
    # one advance per committed constrained token, EOS included
    constrained = [s for s in on if s.checker is not None]
    assert len(constrained) == 3
    assert names.count(PREFIX + "advance") == sum(
        len(s.out_ids) + int(s.finished_eos) for s in constrained)
    assert names.count(PREFIX + "prefill") == len(on)
