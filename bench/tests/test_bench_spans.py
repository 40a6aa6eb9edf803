"""The program's ``serve.*`` spans read back from a trace: gaps named by
the deepest span in them, idle time by span, and the host numbers the
spans give, on a small synthetic trace."""

import pytest

import _tiny  # noqa: F401
from harness import spans, trace

MS = 1_000_000


def ms(x):
    return int(round(x * MS))


def _synthetic(program=True):
    # one chip, window [0, 100) ms.  Two ticks, each inside a bench.step:
    # the first selects, commits three tokens and decodes (device 10-40,
    # then a small op 40-43); between the ticks a small op at 45-46; the
    # second admits one request (a prefill 48-57), commits two tokens and
    # decodes (device 66-90)
    def op(name, a, b):
        return (f"%{name} = bf16[16] fusion(bf16[16] %p)", ms(a), ms(b))
    ops = [op("fusion.1", 10, 40), op("fusion.7", 40, 43),
           op("fusion.8", 45, 46), op("fusion.1", 66, 90),
           op("fusion.9", 96, 100)]
    mods = [("jit_decode_step(12)", ms(10), ms(40)),
            ("jit_decode_step(12)", ms(66), ms(90))]
    bench = [("bench.step", ms(0), ms(44)), ("bench.step", ms(46), ms(98))]
    serve = [(spans.PREFIX + n, ms(a), ms(b)) for n, a, b in (
        ("tick", 1, 43), ("readback", 1, 2), ("select", 2, 3),
        ("mask", 2, 2.5), ("commit", 3, 9), ("advance", 3, 5),
        ("advance", 5, 7), ("advance", 7, 9), ("dispatch", 9, 10),
        ("prebuild", 10, 30), ("mask", 11, 12), ("wait", 30, 40.2),
        ("tick", 47, 94), ("admit", 47, 58), ("prefill", 48, 57),
        ("readback", 58, 59), ("select", 59, 60), ("commit", 60, 64),
        ("advance", 60, 62), ("advance", 62, 64), ("dispatch", 64, 66),
        ("prebuild", 66, 76), ("wait", 76, 90.4))]
    tr = trace.TraceData(ops={"/device:TPU:0": ops},
                         modules={"/device:TPU:0": mods}, spans=bench,
                         t_start=0, t_stop=ms(100))
    return tr, (sorted(serve, key=lambda e: (e[1], -e[2])) if program
                else [])


def test_gaps_named_by_the_deepest_covering_span():
    tr, prog = _synthetic()
    gaps = spans.idle_gaps(tr, prog)
    # 46-66: the tick and the admission each cover more than half, the
    # prefill inside it less; 0-10: the commit covers 6 of 10 ms; 90-96:
    # only the tick covers most; 43-45 lies between ticks
    assert [(g[0], round(g[1], 6)) for g in gaps] == [
        ("serve.admit", 0.02), ("serve.commit", 0.01),
        ("serve.tick", 0.006), ("bench.step", 0.002)]


def test_without_program_spans_gaps_read_as_before():
    tr, prog = _synthetic(program=False)
    assert prog == []
    assert spans.idle_gaps(tr, prog) == trace.idle_gaps(tr)


def test_idle_time_by_span():
    tr, prog = _synthetic()
    idle = spans.idle_by_span(tr, prog)
    want = {"serve.advance": 10, "serve.prefill": 9, "bench.step": 5,
            "serve.tick": 3.6, "serve.dispatch": 3, "serve.readback": 2,
            "serve.admit": 2, "idle": 1, "serve.select": 1.5,
            "serve.mask": 0.5, "serve.wait": 0.4}
    assert idle == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(
        trace.window_s(tr) - trace.busy_s(tr))


def test_host_numbers():
    tr, prog = _synthetic()
    lo, hi = tr.t_start, tr.t_stop
    # five advances of 2 ms each
    assert spans.advance_us_per_tok(prog, lo, hi) == pytest.approx(2000)
    # ticks of 42 and 47 ms less waits of 10.2 and 14.4 ms
    assert spans.tick_host_ms(prog, lo, hi) == pytest.approx(32.2)
    # the waits end 0.2 and 0.4 ms after their decode steps
    assert spans.wait_skew_ms(tr, prog, r"^jit_decode_step\(") == \
        pytest.approx(0.3)
    # a slice that holds neither tick whole
    assert spans.tick_host_ms(prog, ms(2), ms(60)) is None


def test_silent_without_program_spans():
    tr, _ = _synthetic(program=False)
    assert spans.advance_us_per_tok([], tr.t_start, tr.t_stop) is None
    assert spans.tick_host_ms([], tr.t_start, tr.t_stop) is None
    assert spans.wait_skew_ms(tr, [], r"^jit_decode_step\(") is None


def test_spans_tool_on_the_tiny_cell():
    """``bench/spans.py`` end to end on the CPU: the tick's spans come
    back from the recorded slice (a CPU trace has no device plane, so
    nothing is idle to name)."""
    import spans as tool
    cell = _tiny.cell(per_layer=("checker_crit_us_per_tok",))
    rc, res = tool.attribute(cell, 3000000019, 4.0, require_tpu=False,
                             log=lambda _m: None)
    assert rc == 0
    counts = res["spans"]["counts"]
    assert set(counts) <= spans.NAMES
    assert counts["serve.tick"] > 0 and counts["serve.wait"] > 0
    assert counts["serve.advance"] > 0
    assert res["spans"]["advance_us_per_tok"] > 0
    assert res["spans"]["tick_host_ms"] > 0
    assert "output_tok_s" in res["metrics"]
    assert res["idle_gaps"] == [] and res["idle_in_program_share"] is None
