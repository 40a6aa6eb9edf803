"""The harness end to end at a tiny size on the CPU: it refuses to run
without a TPU, a sound run is correct, and a run whose served tokens
are altered, or whose reference runs one precision step down, is not;
the readers see the scheduler's counters; a run past its deadline ends,
naming its phase; and the span tool reads the program's spans."""
import gc
import json
import os
import subprocess
import sys
import textwrap

import _tiny

SEED = 3000000019          # above 2**31: seeds need more than 32 signed bits


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(_tiny.BENCH, "run.py"),
         "--workload", "stablelm-1.6b.extract-json", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def _execute(cell, seconds=4.0, trace=False):
    import run
    return run.execute(cell, SEED, seconds, trace, require_tpu=False,
                       log=lambda _m: None)


def test_tiny_run_is_correct():
    rc, res = _execute(_tiny.cell(per_layer=("queue_wait_p90_ms",)))
    assert rc == 0 and res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["illegal_tokens"]["value"] == 0
    assert set(res["metrics"]) == {"setup_s", "output_tok_s", "itl_p95_ms",
                                   "ttft_p90_ms", "request_p90_ms"}
    assert res["device"]["count"] == 1
    json.dumps(res)


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: every third selection tick,
    each row's pick moves to the next id."""
    from repro.serving.scheduler import ContinuousBatchingScheduler
    orig = ContinuousBatchingScheduler._choose
    calls = {"n": 0}

    def altered(self):
        out = orig(self)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = {s: (t + 1) % self.eng._v for s, t in out.items()}
        return out

    monkeypatch.setattr(ContinuousBatchingScheduler, "_choose", altered)
    rc, res = _execute(_tiny.cell())
    assert rc == 0 and calls["n"] > 3
    assert not res["correct"], res["checks"]


def test_control_one_precision_down_is_not_correct():
    """The reference with fp8 weights in the program's place, held to the
    cell's limits over the same requests as the bf16 program, comes out
    not correct where the program comes out correct."""
    import run
    from harness import window
    cell = _tiny.cell()
    srv, gen, counter = run.prepare(cell, SEED)
    win = window.run(srv, gen, 4.0, counter)
    tok = srv.engine.tok
    srv.close()
    gc.unfreeze()
    gc.collect()
    res = run.verify(cell, win.in_window(), SEED, list(tok.vocab),
                     tok.eos_id, ("fp8",))
    ctl = res["controls"]["fp8"]
    assert res["positions"] >= 100
    assert res["correct"], res["checks"]
    assert not ctl["correct"], ctl["checks"]


def test_readers_see_the_counters_and_phases_are_named(monkeypatch):
    """A traced run of the tiny cell: the scheduler's counters as the
    window opened and closed reach the readers, and the run passes
    through its phases in order."""
    import run
    seen = {}
    orig = run.per_layer

    def per_layer(data):
        seen["open"], seen["close"] = data.counters_open, data.counters_close
        return orig(data)

    class Phases(run.Deadline):
        def enter(self, phase):
            seen.setdefault("phases", []).append(phase)
            super().enter(phase)

    monkeypatch.setattr(run, "per_layer", per_layer)
    cell = _tiny.cell(per_layer=("kv_inplace_layers",))
    rc, res = run.execute(cell, SEED, 4.0, True, require_tpu=False,
                          log=lambda _m: None, deadline=Phases())
    assert rc == 0 and res["correct"], res
    # the warm-up traced the paged decode step, so the count is there
    # when the window opens: both layers of the tiny model
    assert seen["open"]["kv_inplace_layers"] == 2
    assert seen["close"]["kv_inplace_layers"] == 2
    assert seen["close"]["n_prefix_hits"] >= seen["open"]["n_prefix_hits"]
    assert all(isinstance(v, (int, float)) for v in seen["close"].values())
    assert res["metrics"] == {"kv_inplace_layers": {"value": 2,
                                                    "unit": "%"}}
    assert seen["phases"] == ["warm-up", "window", "trace",
                              "reference check"]


def test_deadline_ends_the_run_naming_its_phase():
    """With the deadline a second after the process started, a run of
    the tiny cell is still setting up: it exits non-zero with no result,
    and its last word names the phase."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(_tiny.BENCH, "tests")!r})
        import _tiny
        import run
        run.DEADLINE_S = 1.0
        d = run.Deadline()
        d.start()
        rc, res = run.execute(_tiny.cell(), {SEED}, 30.0, False,
                              require_tpu=False, deadline=d)
        print(res)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], cwd=_tiny.ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    import run
    assert p.returncode == run.DEADLINE_RC
    assert p.stdout.strip() == ""
    assert p.stderr.strip().splitlines()[-1] == (
        "error: deadline of 1 s after process start passed in set-up")


def test_spans_tool_on_the_tiny_cell():
    """``bench/spans.py`` end to end on the CPU: the tick's spans come
    back from the recorded slice through ``harness.trace.load``, and the
    span readers read them (a CPU trace has no device plane, so nothing
    is idle to name)."""
    import spans as tool
    names = {"serve." + n for n in (
        "tick", "reap", "admit", "prefill", "readback", "select", "mask",
        "commit", "advance", "dispatch", "prebuild", "wait")}
    cell = _tiny.cell(per_layer=("checker_crit_us_per_tok",
                                 "advance_us_per_tok", "tick_host_ms"))
    rc, res = tool.attribute(cell, SEED, 4.0, require_tpu=False,
                             log=lambda _m: None)
    assert rc == 0
    counts = res["spans"]["counts"]
    assert set(counts) <= names
    assert counts["serve.tick"] > 0 and counts["serve.wait"] > 0
    assert counts["serve.advance"] > 0
    assert res["metrics"]["advance_us_per_tok"] > 0
    assert res["metrics"]["tick_host_ms"] > 0
    assert "output_tok_s" in res["metrics"]
    assert res["idle_by_span"] == {} and res["idle_in_program_share"] is None
