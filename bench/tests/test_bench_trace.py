"""The reduction from a profiler trace to per-layer numbers, on a small
synthetic trace, and the reader on a trace recorded here."""

import pytest

import _tiny  # noqa: F401
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
        f(x).block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)), t0, t1, anchor)
    assert [s[0] for s in tr.spans] == ["bench.step"]
    assert tr.t_stop - tr.t_start == pytest.approx((t1 - t0) * 1e9, rel=1e-6)
    s = tr.spans[0]
    assert tr.t_start <= s[1] + 1000 and s[2] <= tr.t_stop + 1000
    assert trace.busy_s(tr) == 0.0             # no TPU plane on a CPU
