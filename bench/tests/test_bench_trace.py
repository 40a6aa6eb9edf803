"""The reduction from a profiler trace to per-layer numbers, on small
synthetic traces, and the reader on a trace recorded here: device time,
gaps named by the deepest program span in them, idle time by span, and
the host numbers the program's spans give."""
import dataclasses
import importlib
import types

import pytest

import _tiny  # noqa: F401
import spans as tool
from harness import trace

MS = 1_000_000


def _synthetic():
    # one chip, window [0, 100) ms: decode steps at 10-20 and 40-55 with
    # two kernels inside, an overlapping pair of ops at 60-70, a masked
    # argmax at 80-82; host steps 5-35 and 38-90
    def op(name, a, b):
        return (f"%{name} = bf16[16] fusion(bf16[16] %p)", a * MS, b * MS)
    ops = [op("fusion.1", 10, 14),
           op("decode_attention_pallas.3", 14, 18),
           op("while.2", 40, 55),                  # holds the next two
           op("fusion.1", 40, 45),
           op("decode_attention_pallas.3", 45, 54),
           op("copy.2", 60, 68),
           op("copy.7", 62, 70),
           op("fusion.5", 80, 82),
           op("fusion.9", 95, 130)]                # runs past the window
    mods = [("jit_decode_step(12)", 10 * MS, 18 * MS),
            ("jit_decode_step(12)", 40 * MS, 55 * MS),
            ("jit_masked_argmax_pallas_packed(3)", 80 * MS, 82 * MS)]
    spans = [("bench.step", 5 * MS, 35 * MS), ("bench.step", 38 * MS, 90 * MS)]
    return trace.TraceData(ops={"/device:TPU:0": ops},
                           modules={"/device:TPU:0": mods}, spans=spans,
                           t_start=0, t_stop=100 * MS)


def test_busy_is_the_union():
    tr = _synthetic()
    # 10-18, 40-55, 60-70, 80-82, 95-100 (clipped)
    assert trace.busy_s(tr) == pytest.approx(0.040)
    assert trace.window_s(tr) == pytest.approx(0.1)


def test_program_and_kernel_time():
    tr = _synthetic()
    assert trace.program_calls(tr, r"^jit_decode_step\(") == (
        2, pytest.approx(0.023))
    assert trace.kernel_calls(tr, r"^%decode_attention_pallas") == (
        2, pytest.approx(0.013))
    assert trace.program_calls(tr, r"^jit_masked_argmax") == (
        1, pytest.approx(0.002))
    assert trace.kernel_calls(tr, r"^%nothing") == (0, 0.0)


def test_top_ops_and_gaps():
    tr = _synthetic()
    top = dict(trace.top_ops(tr, 10))
    # by name, self time only: the while loop's own time excludes the
    # fusion and kernel nested in it
    assert top["fusion.9"] == pytest.approx(0.005)     # clipped
    assert top["decode_attention_pallas.3"] == pytest.approx(0.013)
    assert top["while.2"] == pytest.approx(0.001)
    gaps = trace.idle_gaps(tr, 10)
    # 0-10, 18-40, 55-60, 70-80, 82-95: longest first, named by the host
    # span that overlaps each most
    assert [round(g[1], 6) for g in gaps] == [0.022, 0.013, 0.01, 0.01,
                                              0.005]
    assert [g[0] for g in gaps] == ["bench.step"] * 5


def test_load_recorded_trace(tmp_path):
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.step"):
        with jax.profiler.TraceAnnotation("serve.tick"):
            f(x).block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)), t0, t1, anchor)
    assert [s[0] for s in tr.spans] == ["bench.step"]
    assert [s[0] for s in tr.program_spans] == ["serve.tick"]
    assert tr.t_stop - tr.t_start == pytest.approx((t1 - t0) * 1e9, rel=1e-6)
    s = tr.spans[0]
    assert tr.t_start <= s[1] + 1000 and s[2] <= tr.t_stop + 1000
    assert trace.busy_s(tr) == 0.0             # no TPU plane on a CPU


# -- the program's serve.* spans ---------------------------------------------

def ms(x):
    return int(round(x * MS))


def _with_program(program=True):
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
    serve = [(trace.PROGRAM_PREFIX + n, ms(a), ms(b)) for n, a, b in (
        ("tick", 1, 43), ("readback", 1, 2), ("select", 2, 3),
        ("mask", 2, 2.5), ("commit", 3, 9), ("advance", 3, 5),
        ("advance", 5, 7), ("advance", 7, 9), ("dispatch", 9, 10),
        ("prebuild", 10, 30), ("mask", 11, 12), ("wait", 30, 40.2),
        ("tick", 47, 94), ("admit", 47, 58), ("prefill", 48, 57),
        ("readback", 58, 59), ("select", 59, 60), ("commit", 60, 64),
        ("advance", 60, 62), ("advance", 62, 64), ("dispatch", 64, 66),
        ("prebuild", 66, 76), ("wait", 76, 90.4))]
    return trace.TraceData(
        ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": mods},
        spans=bench, t_start=0, t_stop=ms(100),
        program_spans=(sorted(serve, key=lambda e: (e[1], -e[2]))
                       if program else []))


def _reader(name, tr):
    run = types.SimpleNamespace(trace=tr)
    return importlib.import_module("metrics." + name).read(run)


def test_gaps_named_by_the_deepest_covering_span():
    tr = _with_program()
    gaps = trace.idle_gaps(tr)
    # 46-66: the tick and the admission each cover more than half, the
    # prefill inside it less; 0-10: the commit covers 6 of 10 ms; 90-96:
    # only the tick covers most; 43-45 lies between ticks
    assert [(g[0], round(g[1], 6)) for g in gaps] == [
        ("serve.admit", 0.02), ("serve.commit", 0.01),
        ("serve.tick", 0.006), ("bench.step", 0.002)]


def test_without_program_spans_gaps_read_as_before():
    """With no program span, each gap is named by the harness span that
    overlaps it most, as the harness named every gap before it read the
    program's spans."""
    tr = _with_program(program=False)
    assert tr.program_spans == []

    def before(s, e):
        best, over = "idle", 0
        for name, hs, he in tr.spans:
            o = min(e, he) - max(s, hs)
            if o > over:
                best, over = name, o
        return best
    want = sorted(trace.device_gaps(tr), key=lambda g: g[0] - g[1])
    assert trace.idle_gaps(tr) == [[before(s, e), (e - s) / 1e9]
                                   for s, e in want]
    assert [g[0] for g in trace.idle_gaps(tr)] == ["bench.step"] * 4


def test_idle_time_by_span():
    tr = _with_program()
    idle = tool.idle_by_span(tr)
    want = {"serve.advance": 10, "serve.prefill": 9, "bench.step": 5,
            "serve.tick": 3.6, "serve.dispatch": 3, "serve.readback": 2,
            "serve.admit": 2, "idle": 1, "serve.select": 1.5,
            "serve.mask": 0.5, "serve.wait": 0.4}
    assert idle == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(
        trace.window_s(tr) - trace.busy_s(tr))


def test_host_numbers():
    tr = _with_program()
    # five advances of 2 ms each
    assert _reader("advance_us_per_tok", tr) == pytest.approx(2000)
    # ticks of 42 and 47 ms less waits of 10.2 and 14.4 ms
    assert _reader("tick_host_ms", tr) == pytest.approx(32.2)
    # the waits end 0.2 and 0.4 ms after their decode steps
    assert tool.wait_skew_ms(tr, r"^jit_decode_step\(") == \
        pytest.approx(0.3)
    # a slice that holds neither tick whole
    part = dataclasses.replace(tr, t_start=ms(2), t_stop=ms(60))
    assert _reader("tick_host_ms", part) is None


def test_silent_without_program_spans():
    tr = _with_program(program=False)
    assert _reader("advance_us_per_tok", tr) is None
    assert _reader("tick_host_ms", tr) is None
    assert tool.wait_skew_ms(tr, r"^jit_decode_step\(") is None
