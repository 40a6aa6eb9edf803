"""Each per-layer reader against a hand computation on a synthetic run,
and silence where the run holds nothing to read."""
import importlib
import types

import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
import run
from harness import flops, server, trace

MS = 1_000_000
PEAK = flops.peaks("TPU v5 lite")


def _run(with_trace=True):
    cfg = server.load_config("stablelm-1.6b")
    cell = run.Cell(name="x", chips=1, config=cfg, mix={}, limits={},
                    end_to_end={}, per_layer={})
    ops = [("%decode_attention_pallas.1 = bf16[16] custom-call()",
            i * MS, i * MS + 200_000) for i in range(48)]     # 0.2 ms each
    mods = [("jit_decode_step(1)", 0, 30 * MS),
            ("jit_decode_step(1)", 40 * MS, 70 * MS),
            ("jit_masked_argmax_pallas_packed(2)", 80 * MS, 80 * MS + 5000),
            ("jit_masked_argmax_pallas_packed(2)", 90 * MS, 90 * MS + 7000)]
    tr = trace.TraceData(ops={"/device:TPU:0": ops},
                         modules={"/device:TPU:0": mods},
                         spans=[("bench.step", 0, 100 * MS)],
                         t_start=0, t_stop=100 * MS)
    sess = types.SimpleNamespace(t_admit=10.5, result=types.SimpleNamespace(
        n_tokens=100, mask_time_s=0.004, mask_overlap_s=0.001))
    rec = types.SimpleNamespace(t_due=10.0, sess=sess,
                                turn=types.SimpleNamespace(grammar="json"))
    win = types.SimpleNamespace(
        traced={"steps": [{"t": 1.0, "keys": [100, 300]},
                          {"t": 2.0, "keys": [101, 301]}]},
        in_window=lambda: [rec])
    return run.RunData(cell=cell, window=win,
                       trace=tr if with_trace else None, peaks=PEAK,
                       n_vocab=16387,
                       counters_open={"kv_inplace_layers": 24,
                                      "n_prefix_hits": 3},
                       counters_close={"kv_inplace_layers": 24,
                                       "n_prefix_hits": 9})


def read(name, r):
    return importlib.import_module("metrics." + name).read(r)


def test_decode_step_and_mfu():
    r = _run()
    assert read("decode_step_ms", r) == pytest.approx(30.0)
    per_call = (flops.decode_step([100, 300], 24, 2048, 32, 32, 64, 5632,
                                  100352)
                + flops.decode_step([101, 301], 24, 2048, 32, 32, 64, 5632,
                                    100352)) / 2
    want = 100 * per_call * 2 / PEAK["bf16_flops_per_s"] / 0.060
    assert read("decode_mfu", r) == pytest.approx(want)


def test_rooflines():
    r = _run()
    mins = [flops.min_time(*flops.decode_attention(k, 32, 32, 64), PEAK)[0]
            for k in ([100, 300], [101, 301])]
    assert read("decode_attention_roofline", r) == pytest.approx(
        100 * (sum(mins) / 2) / 0.0002)
    m = flops.min_time(*flops.masked_argmax(2, 16387), PEAK)[0]
    assert read("masked_argmax_roofline", r) == pytest.approx(
        100 * m / 6e-6)


def test_idle_queue_checker():
    r = _run()
    # 48 kernel calls of 0.2 ms in a 100 ms window
    assert read("device_idle_share", r) == pytest.approx(100 * (1 - 0.0096
                                                                / 0.1))
    assert read("queue_wait_p90_ms", r) == pytest.approx(500.0)
    assert read("checker_crit_us_per_tok", r) == pytest.approx(30.0)


def test_values_as_before():
    """What each trace reader read on this run before the FLOP and byte
    counts moved into the family modules, to the last digit."""
    r = _run()
    assert read("decode_step_ms", r) == 30.0
    assert read("decode_mfu", r) == 0.09870431296108291
    assert read("decode_attention_roofline", r) == 2.0154920634920637
    assert read("masked_argmax_roofline", r) == 2.7516483516483516
    assert read("device_idle_share", r) == 90.4


def test_counter_at_close():
    assert read("kv_inplace_layers", _run()) == 24
    r = _run()
    r.counters_close = {}
    assert read("kv_inplace_layers", r) is None


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_mfu",
                                  "decode_attention_roofline",
                                  "masked_argmax_roofline",
                                  "device_idle_share", "advance_us_per_tok",
                                  "tick_host_ms"])
def test_silent_without_trace(name):
    assert read(name, _run(with_trace=False)) is None
