"""Fault-tolerant serving: status taxonomy, deadlines, cancellation,
graceful degradation, and seeded chaos storms.

Acceptance (ISSUE 7): with seeded faults injected into >= 3 distinct tick
phases, the scheduler leaks no pages or slots (invariant checker clean at
every tick boundary), every affected request reaches an explicit non-`ok`
terminal status, and every unaffected row's output is bitwise-identical
to a fault-free run.

One deliberate carve-out in the storm assertions: a row whose injected
NaN is erased by a recompute preemption BEFORE the selection phase reads
it (the preempted row is re-prefilled from scratch) legitimately
completes `ok` with fault-free output — so storm-affected rows must be
non-ok OR bitwise-equal, while the targeted tests (no page pressure, no
preemption) pin the strict non-ok outcome.
"""
import dataclasses
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import grammars
from repro.serving import (ConstraintSpec, ContinuousBatchingScheduler,
                           DecodeParams, DegradationSupervisor,
                           EngineConfig, FaultInjector, Request,
                           ServingEngine, check_invariants)
from repro.serving.faults import SITES, FaultRecord, InvariantViolation
from repro.models import build_model

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            dtype="float32", max_seq_len=512)

PROMPTS = ["a: ", "some much longer json prompt here: ", "x",
           "record -> ", "{", "data: "]


@pytest.fixture(scope="module")
def attn(small_tokenizer):
    cfg = ModelConfig(arch_id="f-attn", family="dense",
                      vocab_size=small_tokenizer.vocab_size, **BASE)
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(attn, tok, grammar, max_tokens=10, max_len=256, **cfg_kw):
    m, params = attn
    return ServingEngine(m, params, tok, grammar,
                         EngineConfig(mode="domino", max_tokens=max_tokens,
                                      **cfg_kw),
                         max_len=max_len)


def _by_rid(sessions):
    return {s.rid: s.result for s in sessions}


# -- lifecycle: statuses, cancel, deadlines, queue bounds ----------------------


def test_ok_status_on_normal_completion(attn, small_tokenizer,
                                        json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar)
    r = eng.generate("a: ")
    assert r.status == "ok" and r.ok and r.error is None


def test_cancel_waiting_and_resident(attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_tokens=50)
    sched = ContinuousBatchingScheduler(eng, capacity=1,
                                       debug_invariants=True)
    s0 = sched.submit("a: ")
    s1 = sched.submit("x")
    sched.step()                       # s0 resident, s1 waiting
    assert s0.slot >= 0 and s1.slot == -1
    assert sched.cancel(s0.rid) is True
    assert sched.cancel(s1.rid) is True
    assert sched.cancel(999) is False   # unknown rid
    sched.step()                       # cancellations honored at boundary
    assert s0.result.status == "cancelled"
    assert s1.result.status == "cancelled"
    assert "decoding" in s0.result.error
    assert "waiting" in s1.result.error
    # slot + pages back for reuse
    assert all(s is None for s in sched.slots)
    if sched.paged:
        assert sched.pool.available == sched.n_pages - 1
    assert sched.cancel(s0.rid) is False   # already terminal
    assert sched.run() == [s0.result, s1.result]   # reported in rid order


def test_deadline_in_queue_and_mid_flight(attn, small_tokenizer,
                                          json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_tokens=50)
    sched = ContinuousBatchingScheduler(eng, capacity=1,
                                       debug_invariants=True)
    # queued request with an already-expired deadline never runs
    s0 = sched.submit(Request("a: ", ConstraintSpec(grammar="default",
                                                    mode="domino"),
                              DecodeParams(max_tokens=50,
                                           deadline_s=1e-9)))
    sched.step()
    assert s0.result.status == "deadline_exceeded"
    assert s0.result.n_tokens == 0
    # resident request overruns mid-flight: terminated at the next tick
    # boundary with its partial output intact
    s1 = sched.submit(Request("a: ", ConstraintSpec(grammar="default",
                                                    mode="domino"),
                              DecodeParams(max_tokens=50,
                                           deadline_s=30.0)))
    sched.step()
    assert s1.slot >= 0 and s1.result is None
    s1.t_submit -= 100.0               # simulate elapsed wall time
    sched.step()
    assert s1.result.status == "deadline_exceeded"
    assert all(s is None for s in sched.slots)
    if sched.paged:
        assert sched.pool.available == sched.n_pages - 1


def test_default_deadline_applies_when_request_has_none(
        attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_tokens=50)
    sched = ContinuousBatchingScheduler(eng, capacity=1,
                                       default_deadline_s=1e-9)
    s0 = sched.submit("a: ")
    sched.step()
    assert s0.result.status == "deadline_exceeded"


def test_single_request_deadline(attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_tokens=200)
    r = eng.generate(Request("a: ",
                             ConstraintSpec(grammar="default",
                                            mode="domino"),
                             DecodeParams(max_tokens=200,
                                          deadline_s=1e-9)))
    assert r.status == "deadline_exceeded"
    assert not r.ok and r.error


def test_queue_limit_sheds_overflow(attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar)
    sched = ContinuousBatchingScheduler(eng, capacity=1, queue_limit=2)
    sessions = [sched.submit(p) for p in PROMPTS[:5]]
    shed = [s for s in sessions if s.result is not None]
    assert len(shed) == 3              # queue holds 2, rest rejected now
    assert all(s.result.status == "rejected" for s in shed)
    assert all("queue_limit" in s.result.error for s in shed)
    results = sched.run()
    assert len(results) == 5           # rejections are reported too
    ok = [s for s in sessions if s.result.status == "ok"]
    assert len(ok) == 2


def test_queue_wait_timeout(attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar)
    sched = ContinuousBatchingScheduler(eng, capacity=1,
                                       queue_timeout_s=0.0)
    s0 = sched.submit("a: ")
    sched.step()
    assert s0.result.status == "rejected"
    assert "timeout" in s0.result.error


# -- admission: livelock fix ---------------------------------------------------


def test_oversized_prompt_rejected_not_livelocked(attn, small_tokenizer,
                                                  json_grammar):
    """A prompt needing more pages than the POOL holds used to block the
    FIFO head forever; now it is rejected with a reason and the request
    behind it completes normally."""
    eng = _engine(attn, small_tokenizer, json_grammar)
    big = "{\"k\": [" + ", ".join(str(i) for i in range(80)) + "]} "
    sched = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                       page_size=16, n_pages=4,
                                       debug_invariants=True)
    n_big = len(small_tokenizer.encode(big))
    assert n_big + 1 > (sched.n_pages - 1) * sched.page_size
    baseline = eng.generate("a: ")
    s_big = sched.submit(big)
    s_ok = sched.submit("a: ")
    results = sched.run()
    assert len(results) == 2
    assert s_big.result.status == "rejected"
    assert "pool" in s_big.result.error
    assert s_ok.result.status == "ok"
    assert s_ok.result.token_ids == baseline.token_ids
    assert sched.pool.available == sched.n_pages - 1


def test_prompt_beyond_max_len_rejected_dense(attn, small_tokenizer,
                                              json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_len=32)
    big = "{\"k\": [" + ", ".join(str(i) for i in range(80)) + "]} "
    assert len(small_tokenizer.encode(big)) + 1 > 32
    sched = ContinuousBatchingScheduler(eng, capacity=1, paged=False)
    s_big = sched.submit(big)
    s_ok = sched.submit("a: ")
    sched.run()
    assert s_big.result.status == "rejected"
    assert "max_len" in s_big.result.error
    assert s_ok.result.status == "ok"


# -- targeted quarantine: one faulted row, batch-mates bitwise-identical -------


def _quarantine_run(attn, tok, grammar, site, target_rid, **inj_kw):
    """Run PROMPTS[:3] fault-free and with one targeted fault; return
    (baseline rid->result, faulted rid->result, scheduler)."""
    eng = _engine(attn, tok, grammar)
    base = ContinuousBatchingScheduler(eng, capacity=3)
    base_sess = [base.submit(p) for p in PROMPTS[:3]]
    base.run()
    inj = FaultInjector(seed=0, rates={site: 1.0}, targets={target_rid},
                        max_faults=1, **inj_kw)
    sched = ContinuousBatchingScheduler(eng, capacity=3,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in PROMPTS[:3]]
    sched.run()
    assert inj.n_fired(site) == 1
    assert inj.faulted_rids() == {target_rid}
    return _by_rid(base_sess), _by_rid(sess), sched


@pytest.mark.parametrize("site,err_frag", [
    ("mask_error", "checker failed"),
    ("decode_nan", "non-finite"),
    ("prefill_nan", "non-finite"),
    ("advance_error", "checker failed"),
])
def test_targeted_fault_quarantined_to_one_row(attn, small_tokenizer,
                                               json_grammar, site,
                                               err_frag):
    """Exactly the targeted row fails (explicit internal_error + reason);
    every batch-mate's output is bitwise-equal to the fault-free run.
    No page pressure here, so no preemption can erase the fault."""
    target = 1
    base, faulted, sched = _quarantine_run(
        attn, small_tokenizer, json_grammar, site, target)
    assert faulted[target].status == "internal_error"
    assert err_frag in faulted[target].error
    # partial output is a prefix of the fault-free output (never junk)
    n = faulted[target].n_tokens
    assert faulted[target].token_ids == base[target].token_ids[:n]
    if site == "prefill_nan":
        assert n == 0                  # corrupted before any commit
    for rid in (0, 2):
        assert faulted[rid].status == "ok"
        assert faulted[rid].token_ids == base[rid].token_ids
    if sched.paged:
        assert sched.pool.available == sched.n_pages - 1
    assert all(s is None for s in sched.slots)


def test_advance_error_during_speculation_quarantined(attn,
                                                      small_tokenizer):
    """Speculative rows: a checker failure inside the verify loop evicts
    only that row; the plain batch-mate is untouched."""
    m, params = attn
    g = grammars.load("json_gsm8k")
    eng = ServingEngine(m, params, small_tokenizer, g,
                        EngineConfig(mode="domino", speculative=True,
                                     spec_s=4, spec_threshold=0.4,
                                     max_tokens=16), max_len=256)
    prompts = ["A: ", "Q: compute 1 + 2\nA: "]
    base = ContinuousBatchingScheduler(eng, capacity=2)
    base_sess = [base.submit(p) for p in prompts]
    base.run()
    inj = FaultInjector(seed=0, rates={"advance_error": 1.0},
                        targets={1}, max_faults=1)
    sched = ContinuousBatchingScheduler(eng, capacity=2,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in prompts]
    sched.run()
    assert sess[1].result.status == "internal_error"
    assert sess[0].result.status == "ok"
    assert sess[0].result.token_ids == base_sess[0].result.token_ids
    assert sched.pool.available == sched.n_pages - 1


def test_page_exhaustion_storm_is_output_invariant(attn, small_tokenizer,
                                                   json_grammar):
    """Injected pool exhaustion only drives backpressure and recompute
    preemption — both output-invariant — so EVERY request still completes
    ok with fault-free output, and the pool drains leak-free."""
    eng = _engine(attn, small_tokenizer, json_grammar)
    base = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                      page_size=16, n_pages=12)
    base_sess = [base.submit(p) for p in PROMPTS]
    base.run()
    inj = FaultInjector(seed=3, rates={"page_exhaustion": 0.4},
                        max_faults=20)
    sched = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                       page_size=16, n_pages=12,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in PROMPTS]
    sched.run()
    assert inj.n_fired() > 0
    for b, f in zip(base_sess, sess):
        assert f.result.status == "ok"
        assert f.result.token_ids == b.result.token_ids
    assert sched.pool.available == sched.n_pages - 1
    assert not sched._page_tbl.any()


# -- invariant checker ---------------------------------------------------------


def test_invariant_checker_clean_then_detects_corruption(
        attn, small_tokenizer, json_grammar):
    eng = _engine(attn, small_tokenizer, json_grammar, max_tokens=30)
    sched = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                       page_size=16, n_pages=12)
    for p in PROMPTS[:2]:
        sched.submit(p)
    sched.step()
    sched.step()
    assert check_invariants(sched) == []
    # manufactured page leak: a free page vanishes from the free list
    leaked = sched.pool._free.pop()
    problems = check_invariants(sched)
    assert any("leak" in p for p in problems)
    sched.pool._free.append(leaked)
    assert check_invariants(sched) == []
    # manufactured slot corruption: resident session claims wrong slot
    resident = next(s for s in sched.slots if s is not None)
    old = resident.slot
    resident.slot = old + 7
    assert any("slot" in p for p in check_invariants(sched))
    resident.slot = old
    # debug_invariants wiring: a corrupted scheduler raises at the tick
    sched.debug_invariants = True
    sched.pool._free.pop()
    with pytest.raises(InvariantViolation):
        sched.step()


# -- deterministic injector ----------------------------------------------------


def test_injector_is_deterministic_and_validates_sites():
    with pytest.raises(ValueError):
        FaultInjector(rates={"nope": 1.0})
    a = FaultInjector(seed=7, rates={"decode_nan": 0.5})
    b = FaultInjector(seed=7, rates={"decode_nan": 0.5})
    fires_a = [a.fire("decode_nan", rid=i % 3) for i in range(50)]
    fires_b = [b.fire("decode_nan", rid=i % 3) for i in range(50)]
    assert fires_a == fires_b
    assert a.log == b.log
    assert all(isinstance(r, FaultRecord) for r in a.log)
    # max_faults bounds the storm
    c = FaultInjector(seed=7, rates={"decode_nan": 1.0}, max_faults=3)
    assert sum(c.fire("decode_nan", rid=0) for _ in range(10)) == 3


# -- chaos storm ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_storm_no_leaks_affected_fail_unaffected_bitwise(
        attn, small_tokenizer, json_grammar, seed):
    """The acceptance storm: faults across >= 3 distinct tick phases,
    invariants audited at every tick boundary, zero page/slot leaks,
    every request reaches SOME terminal status, affected rows are non-ok
    (or provably untouched: bitwise-equal, see module docstring), and
    unaffected rows are bitwise-identical to the fault-free run."""
    eng = _engine(attn, small_tokenizer, json_grammar)
    base = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                      page_size=16, n_pages=12)
    base_sess = [base.submit(p) for p in PROMPTS]
    base.run()
    baseline = _by_rid(base_sess)

    inj = FaultInjector(seed=seed, rates={
        "mask_error": 0.08, "decode_nan": 0.08, "advance_error": 0.08,
        "prefill_nan": 0.05, "page_exhaustion": 0.25,
    }, max_faults=25)
    sched = ContinuousBatchingScheduler(eng, capacity=2, paged=True,
                                       page_size=16, n_pages=12,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in PROMPTS]
    results = sched.run()               # invariants checked EVERY tick

    # every submission reaches a terminal status
    assert len(results) == len(PROMPTS)
    assert all(s.result is not None for s in sess)
    # the storm covered >= 3 distinct tick phases
    assert len({r.site for r in inj.log}) >= 3, inj.log
    # zero leaks: pool fully drained, all slots free, queue empty
    assert sched.pool.available == sched.n_pages - 1
    assert not sched._page_tbl.any()
    assert all(s is None for s in sched.slots)
    assert not sched.waiting
    # quarantine: unaffected rows bitwise-identical; affected rows carry
    # an explicit non-ok status unless preemption erased the fault before
    # it was observed (then they are bitwise-identical instead)
    affected = inj.faulted_rids("mask_error", "decode_nan",
                                "advance_error", "prefill_nan")
    for s in sess:
        r, b = s.result, baseline[s.rid]
        if s.rid in affected:
            assert (r.status != "ok" and r.error) \
                or r.token_ids == b.token_ids, (s.rid, r.status)
            if r.status != "ok":       # partial output is a valid prefix
                assert r.token_ids == b.token_ids[:r.n_tokens]
        else:
            assert r.status == b.status
            assert r.token_ids == b.token_ids
    # bookkeeping agrees with results
    assert sum(sched.status_counts.values()) == len(PROMPTS)
    assert sched.status_counts["ok"] == \
        len([s for s in sess if s.result.ok])


# -- degradation supervisor (ISSUE 9) ------------------------------------------


def test_durability_fault_sites_registered():
    for site in ("device_timeout", "device_error", "alloc_fail",
                 "table_corrupt", "journal_torn_write", "crash_point"):
        assert site in SITES
        FaultInjector(rates={site: 1.0})   # constructor validates names


def test_supervisor_guard_retries_with_exponential_backoff():
    sleeps = []
    sup = DegradationSupervisor(max_retries=2, backoff_s=0.01,
                                clock=lambda: 0.0, sleep=sleeps.append)
    failures = [RuntimeError("one"), RuntimeError("two")]
    calls = []

    def flaky():
        calls.append(1)
        if failures:
            raise failures.pop(0)
        return 42

    ok, value = sup.guard("op", flaky)
    assert ok and value == 42
    assert len(calls) == 3 and sup.n_retries == 2
    assert sleeps == [0.01, 0.02]          # 2^(attempt-1) backoff

    def hopeless():
        raise RuntimeError("always")

    sup2 = DegradationSupervisor(max_retries=1, backoff_s=0.0,
                                 sleep=lambda s: None)
    ok, err = sup2.guard("op", hopeless)
    assert not ok and isinstance(err, RuntimeError)
    assert sup2.n_retries == 1


def test_supervisor_guard_consults_injection_before_each_attempt():
    fires = [True, True, False]
    sup = DegradationSupervisor(max_retries=2, backoff_s=0.0,
                                sleep=lambda s: None)
    ok, value = sup.guard("op", lambda: 7, inject=lambda: fires.pop(0))
    assert ok and value == 7
    assert sup.n_retries == 2 and not fires


def test_supervisor_watchdog_trip_keeps_the_value():
    t = [0.0]

    def clock():
        t[0] += 1.0                        # every clock() call = +1s
        return t[0]

    sup = DegradationSupervisor(watchdog_s=0.5, clock=clock,
                                sleep=lambda s: None)
    ok, value = sup.guard("slow-op", lambda: "result")
    assert ok and value == "result"        # finished, just slowly
    assert sup.n_watchdog_trips == 1


def test_supervisor_ladder_degrade_recover_and_mttr():
    t = [0.0]
    sup = DegradationSupervisor(recover_after=2, clock=lambda: t[0],
                                sleep=lambda s: None)
    assert sup.level == 0 and sup.level_name == "fused"
    t[0] = 1.0
    assert sup.degrade("device_timeout") == 1
    sup.tick_ok()                          # dirty tick: does NOT count
    assert sup.level == 1
    assert sup.degrade("fused_block") == 2
    assert sup.degrade("again") == 2       # capped at dense
    assert sup.n_degrades == 2 and sup.level_name == "dense"
    sup.tick_ok()                          # dirty reset
    for _ in range(2):
        sup.tick_ok()
    assert sup.level == 1                  # 2 clean ticks -> one climb
    t[0] = 9.0                             # clock at the final climb
    for _ in range(2):
        sup.tick_ok()
    assert sup.level == 0 and sup.n_recovers == 2
    assert sup.mttr_s == pytest.approx(8.0)   # first degrade -> level 0
    s = sup.stats()
    assert s["level"] == 0 and s["n_degrades"] == 2
    assert s["mttr_s"] == pytest.approx(8.0)


def test_alloc_fail_shrinks_capacity_outputs_invariant(
        attn, small_tokenizer, json_grammar):
    """Injected allocation failure is PRESSURE, not a row fault: the
    supervisor shrinks effective capacity and preempts-to-queue, clean
    ticks grow it back, and every output stays bitwise-identical."""
    eng = _engine(attn, small_tokenizer, json_grammar)
    base = ContinuousBatchingScheduler(eng, capacity=3, paged=True,
                                      page_size=16, n_pages=12)
    base_sess = [base.submit(p) for p in PROMPTS]
    base.run()
    # page_size=4 forces page-boundary crossings every few tokens, so the
    # alloc_fail site (consulted only under a real shortfall) is hit
    inj = FaultInjector(seed=5, rates={"alloc_fail": 1.0}, max_faults=2)
    sched = ContinuousBatchingScheduler(eng, capacity=3, paged=True,
                                       page_size=4, n_pages=40,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in PROMPTS]
    sched.run()
    assert inj.n_fired("alloc_fail") > 0
    assert sched.n_capacity_shrinks > 0
    assert sched.stats()["n_capacity_shrinks"] == sched.n_capacity_shrinks
    for b, f in zip(base_sess, sess):
        assert f.result.status == "ok"
        assert f.result.token_ids == b.result.token_ids
    assert sched.pool.available == sched.n_pages - 1
    # clean ticks after the storm regrew the admission cap
    assert 1 <= sched._cap_eff <= sched.capacity


def test_device_error_storm_resets_engine_outputs_exact(
        attn, small_tokenizer, json_grammar):
    """A device_error storm on the host tick path: the guarded readback
    retries, then resets the engine surface (recompute-preempt all) and
    steps down the ladder.  Preemption invariance keeps every completed
    request bitwise-identical to the fault-free run."""
    eng = _engine(attn, small_tokenizer, json_grammar)
    base = ContinuousBatchingScheduler(eng, capacity=2)
    base_sess = [base.submit(p) for p in PROMPTS[:4]]
    base.run()
    inj = FaultInjector(seed=2, rates={"device_error": 1.0}, max_faults=8)
    sched = ContinuousBatchingScheduler(eng, capacity=2,
                                       fault_injector=inj,
                                       debug_invariants=True)
    sess = [sched.submit(p) for p in PROMPTS[:4]]
    sched.run()
    assert inj.n_fired("device_error") == 8
    assert sched.n_engine_resets >= 1
    assert sched.sup.n_degrades >= 1
    for b, f in zip(base_sess, sess):
        assert f.result.status == "ok"
        assert f.result.token_ids == b.result.token_ids
    if sched.paged:
        assert sched.pool.available == sched.n_pages - 1
    assert all(s is None for s in sched.slots)
    stats = sched.stats()
    assert stats["n_engine_resets"] == sched.n_engine_resets
    assert stats["level_name"] in ("fused", "host", "dense")


# -- lint: no swallowed exceptions in serving/ ---------------------------------


def test_lint_forbids_swallowed_excepts_in_serving(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_hotpath", os.path.join(root, "tools", "lint_hotpath.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"                       # R4: bare
        "        h()\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"             # R4: swallowed
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"        # fine: mapped to a status
        "        fail(e)\n")
    findings = lint.lint_serving_excepts(str(bad))
    assert len(findings) == 2
    assert all(f.rule == "R4" for f in findings)
    # the serving package itself is clean
    import repro.serving as srv
    pkg = os.path.dirname(os.path.abspath(srv.__file__))
    for fn in sorted(os.listdir(pkg)):
        if fn.endswith(".py"):
            assert lint.lint_serving_excepts(os.path.join(pkg, fn)) == []


# -- lint: host spans go through tracing.span --------------------------------


def test_lint_forbids_profiler_calls_on_the_tick_path(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sched_path = os.path.join(root, "src", "repro", "serving",
                              "scheduler.py")
    spec = importlib.util.spec_from_file_location(
        "lint_hotpath", os.path.join(root, "tools", "lint_hotpath.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    bad = tmp_path / "sched.py"
    bad.write_text(
        "import jax\n"
        "from jax.profiler import TraceAnnotation\n"
        "class S:\n"
        "    def _choose(self):\n"
        "        with jax.profiler.TraceAnnotation('serve.select'):\n"  # R7
        "            pick()\n"
        "        with TraceAnnotation('serve.select'):\n"               # R7
        "            pick()\n"
        "        with span('select'):\n"                                 # fine
        "            pick()\n"
        "    def _admit(self):\n"                 # not a tick function
        "        jax.profiler.start_trace('trace-dir')\n")
    findings = lint.lint_scheduler(str(bad))
    assert [f.rule for f in findings] == ["R7", "R7"]
    assert all("_choose()" in f.msg for f in findings)
    # the scheduler spans its ticks through tracing.span only
    assert lint.lint_scheduler(sched_path) == []
