"""The harness end to end at a tiny size on the CPU: it refuses to run
without a TPU, a sound run is correct, and a run whose served tokens
are altered, or whose reference runs one precision step down, is not."""
import gc
import json
import os
import subprocess
import sys


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
