"""The prefix-cache cell's pieces: the window-attention count against
hand counts, its three readers on synthetic runs (and silent where a
program without the hit path leaves them nothing to read), the new
configuration through ``families.of`` and the reference at a tiny size,
and a tiny agent cell end to end on the CPU: every turn after the first
a hit, nothing lowered in the window, correct."""
import copy
import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
import families
import run
from harness import flops, server, trace, weights, window, window_flops

SEED = 3000000019
MS = 1_000_000
PEAK = flops.peaks("TPU v5 lite")
YI = "yi-34b-6l-prefix"


def read(name, r):
    return importlib.import_module("metrics." + name).read(r)


# -- the count -------------------------------------------------------------------


def test_window_attention_hand_count():
    # a 300-token tail after 8,192 cached keys, at Yi's heads: query r
    # scores 8,193 + r keys, 2,457,600 + 45,150 pairs in all
    f, b = window_flops.window_attention(8192, 300, 56, 8, 128)
    assert f == 4 * 56 * 128 * 2_502_750 == 71_758_848_000
    # K and V of 8,492 keys over 8 KV heads, + 300 queries and outputs
    # over 56 query heads, 2 bytes each
    assert b == 34_783_232 + 8_601_600 == 43_384_832
    t, bound = flops.min_time(f, b, PEAK)
    assert bound == "compute" and t == pytest.approx(f / 197e12)


@pytest.mark.parametrize("start", [0, 63, 9000])
def test_one_token_window_is_a_decode_call(start):
    """A one-token tail is one decode query over ``start + 1`` keys."""
    assert window_flops.window_attention(start, 1, 56, 8, 128) == \
        flops.decode_attention([start + 1], 56, 8, 128)


def test_heads_of_the_configuration():
    assert window_flops.heads(server.load_config(YI)) == (56, 8, 128)


# -- the readers -----------------------------------------------------------------


def _run(with_trace=True, hit_spans=True, window_ops=True):
    cfg = server.load_config(YI)
    cell = run.Cell(name="x", chips=1, config=cfg, mix={}, limits={},
                    end_to_end={}, per_layer={})
    ops = [("%decode_attention_pallas.1 = bf16[16] custom-call()",
            i * MS, i * MS + 300_000) for i in range(12)]
    modules = []
    if window_ops:      # two hits of 6 layers, 0.5 ms a call
        ops += [("%window_attention_pallas.2 = bf16[1] custom-call()",
                 50 * MS + i * MS, 50 * MS + i * MS + 500_000)
                for i in range(12)]
        modules = [("jit_prefill_window(7)", 50 * MS, 56 * MS),
                   ("jit_prefill_window(7)", 56 * MS, 62 * MS)]
        # a speculative verify window inside the decode step: not a hit
        ops += [("%window_attention_pallas.3 = bf16[8] custom-call()",
                 70 * MS, 79 * MS)]
        modules += [("jit_decode_step(3)", 69 * MS, 80 * MS)]
    spans = [("serve.prefill_hit", 50 * MS, 58 * MS),
             ("serve.prefill_hit", 60 * MS, 64 * MS),
             ("serve.prefill_hit", 70 * MS, 80 * MS),
             ("serve.prefill_hit", 95 * MS, 120 * MS)]   # past the slice
    tr = trace.TraceData(ops={"/device:TPU:0": ops},
                         modules={"/device:TPU:0": modules},
                         spans=[("bench.step", 0, 100 * MS)],
                         t_start=0, t_stop=100 * MS,
                         program_spans=spans if hit_spans else [])

    def rec(start, n, t_admit, cached=None):
        sess = types.SimpleNamespace(
            n_cached_tokens=start if cached is None else cached,
            hit_window=(start, n) if start else None,
            prompt_ids=[0] * (start + n), t_admit=t_admit)
        return types.SimpleNamespace(sess=sess)
    records = [rec(8192, 300, 10.5),
               rec(8256, 100, 10.9, cached=2 * 8256),  # re-admitted hit
               rec(8192, 40, 12.0),          # admitted after the slice
               rec(0, 500, 10.6)]            # not a hit
    win = types.SimpleNamespace(
        traced={"t_start": 10.0, "t_stop": 11.0,
                "steps": [{"t": 10.2, "keys": [9000, 9500]}]},
        records=records, in_window=lambda: [])
    return run.RunData(cell=cell, window=win,
                       trace=tr if with_trace else None, peaks=PEAK,
                       n_vocab=16387,
                       counters_open={"n_prefix_tokens": 8192,
                                      "n_prompt_tokens": 8300},
                       counters_close={"n_prefix_tokens": 8192 + 24704,
                                       "n_prompt_tokens": 8300 + 25000})


def test_prefix_hit_share():
    assert read("prefix_hit_share", _run()) == pytest.approx(
        100 * 24704 / 25000)


def test_prefix_hit_share_silent_without_the_counter():
    """A program that does not count prompt tokens, or a window that
    admitted none, leaves it out."""
    r = _run()
    for c in (r.counters_open, r.counters_close):
        del c["n_prompt_tokens"]
    assert read("prefix_hit_share", r) is None
    r = _run()
    r.counters_close = dict(r.counters_open)
    assert read("prefix_hit_share", r) is None


def test_hit_prefill_ms():
    # the three spans inside the slice: 8, 4 and 10 ms
    assert read("hit_prefill_ms", _run()) == pytest.approx(8.0)


def test_window_attention_roofline():
    """The hits' own windows (a re-admitted session's latest) over the
    kernel's calls inside hit programs; the verify window's call, in
    the decode step, stays out."""
    heads = (56, 8, 128)
    mins = [flops.min_time(*window_flops.window_attention(s, n, *heads),
                           PEAK)[0] for s, n in ((8192, 300), (8256, 100))]
    assert read("window_attention_roofline", _run()) == pytest.approx(
        100 * (sum(mins) / 2) / 0.0005)


@pytest.mark.parametrize("kw", [dict(with_trace=False),
                                dict(hit_spans=False, window_ops=False)])
def test_hit_readers_silent_without_the_hit_path(kw):
    """No trace, or a program whose hits run under the decode kernel's
    name and open no hit span (the parent's): nothing to read."""
    r = _run(**kw)
    assert read("hit_prefill_ms", r) is None
    assert read("window_attention_roofline", r) is None


# -- the configuration -------------------------------------------------------


def test_configuration_builds_at_published_widths():
    from repro.configs.base import ModelConfig
    cfg = server.load_config(YI)
    assert families.of(cfg) is families.dense
    assert families.dense.model_config(cfg) == ModelConfig(
        arch_id=YI, family="dense", n_layers=6, d_model=7168, n_heads=56,
        n_kv_heads=8, d_ff=20480, vocab_size=64000, group=("attn",),
        rope_theta=5e6, rms_eps=1e-05, max_seq_len=200000,
        dtype="bfloat16", use_pallas_kernels=True)
    assert cfg["server"] == {"slots": 16, "max_len": 12288, "page_size": 64,
                             "pool_pages": 1536, "prefix_cache": True}
    assert cfg["reduced"] == ["num_hidden_layers", "tokenizer_vocab_size"]


def _tiny_yi():
    """The configuration cut to a CPU size, GQA 7:1 kept, in float32."""
    cfg = copy.deepcopy(server.load_config(YI))
    cfg["model"].update(num_hidden_layers=1, hidden_size=112,
                        num_attention_heads=14, num_key_value_heads=2,
                        intermediate_size=64, vocab_size=600,
                        torch_dtype="float32")
    return cfg


def test_tiny_configuration_matches_the_reference():
    """The program built from the configuration, over weights drawn as
    the reference draws them (bfloat16, served in float32), gives the
    reference's logits at every position to float32 rounding."""
    from repro.models import build_model
    from reference import dense as ref
    cfg = _tiny_yi()
    model = build_model(families.of(cfg).model_config(cfg))
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
        weights.program_params(model, SEED))
    ids = list(np.random.default_rng(0).integers(0, 600, 40))
    logits, _ = model.train_logits(params, {"tokens": jnp.asarray([ids])})
    want = ref.logits(cfg, SEED, [ids], [list(range(40))], 600)["fp32"][0]
    np.testing.assert_allclose(np.asarray(logits[0, :, :600]), want,
                               rtol=1e-4, atol=1e-4)


# -- a tiny agent cell, end to end on the CPU ------------------------------------


def _tiny_agent_cell():
    with open(os.path.join(_tiny.DATA, "tiny-agent.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(_tiny.DATA, "tiny-agent-mix.json")) as f:
        mix = json.load(f)
    with open(os.path.join(_tiny.DATA, "tiny-check.json")) as f:
        limits = json.load(f)["limits"]
    return run.Cell(name="tiny-agent", chips=1, config=cfg, mix=mix,
                    limits=limits, end_to_end={},
                    per_layer={"prefix_hit_share": "%"})


def test_tiny_agent_cell_serves_hits_compiled_before_the_window():
    """Closed-loop agent sessions on a pinned system prompt at GQA 7:1:
    after set-up nothing is lowered, every admission in the window is a
    hit prefilled as one window, and the reference finds it correct."""
    import gc
    cell = _tiny_agent_cell()
    srv, gen, counter = run.prepare(cell, SEED)
    win = window.run(srv, gen, 4.0, counter)
    assert win.compiled == []
    o, c = win.counters_open, win.counters_close
    hits = c["n_prefix_hits"] - o["n_prefix_hits"]
    assert hits > 4
    assert c["n_prefill_windows"] - o["n_prefill_windows"] == hits
    data = run.RunData(cell=cell, window=win, trace=None, peaks={},
                       n_vocab=srv.engine.tok.vocab_size,
                       counters_open=o, counters_close=c)
    assert 30 < run.per_layer(data)["prefix_hit_share"] < 100
    recs = win.in_window()
    assert recs and all(r.status == "ok" for r in recs)
    tok = srv.engine.tok
    srv.close()
    gc.unfreeze()
    gc.collect()
    res = run.verify(cell, recs, SEED, list(tok.vocab), tok.eos_id)
    assert res["correct"], res["checks"]
