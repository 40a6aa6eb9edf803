"""Prefix-cache hits prefilled as one query window (``Model.prefill_window``
through ``window_attention_pallas``), at GQA 7:1.

The window kernel is held to the plain oracle at any window length,
across several query tiles, over a paged pool with a vacant (-1) table
entry and ragged rows.  A scheduler with the prefix cache on, over a
tiny GQA 7:1 dense model with eight KV heads (so a query tile holds 32
positions, as at Yi-34B's heads), admits hits whose tails span several
tiles and end mid-page, then decodes through the cache; its logits are
held to the benchmark's float32 reference (``bench/reference/dense.py``,
weights remade from the same seed) and to the uncached path.  The model
runs in float32, so every gap is rounding: the tolerances are float32
ones.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import window_tile
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.serving import (ConstraintSpec, ContinuousBatchingScheduler,
                           DecodeParams, Request, ServingEngine)
from repro.serving import scheduler as sched_mod

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import families  # noqa: E402
from harness import weights  # noqa: E402
from reference import dense as reference  # noqa: E402

SEED = 3000000017
PAGE = 16
# the kernel at Yi-34B's query heads per group and head size, two KV heads
G, QH, D = 2, 7, 128


def _pool_case(s_win, rng):
    b, n_pages, mp = 2, 48, 40
    kp = jnp.asarray(rng.standard_normal((n_pages, PAGE, G, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, PAGE, G, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, s_win, G, QH, D)), jnp.float32)
    tbl = np.full((b, mp), -1, np.int32)
    tbl[0, :] = 0
    tbl[0, :36] = rng.permutation(np.arange(1, n_pages))[:36]
    tbl[1, :3] = [5, 9, 2]                 # then vacant (-1) entries
    # ragged: row 0 mid-page, row 1 shorter than one tile of its window
    ln = jnp.asarray([37, 2], jnp.int32)
    return q, kp, vp, ln, jnp.asarray(tbl)


@pytest.mark.parametrize("s_win", [1, 5, 63, 64, 200, 512])
def test_window_kernel_matches_oracle(s_win):
    """Window position s of row b attends keys t < len[b] + s, whatever
    the number of query tiles the window is cut into."""
    q, kp, vp, ln, tbl = _pool_case(s_win, np.random.default_rng(s_win))
    got = decode_attention(q, kp, vp, ln, block_tables=tbl)
    want = decode_attention_ref(q, kp, vp, ln, block_tables=tbl)
    # float32 throughout: the online softmax's rescaling is the only gap
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_window_tile_bounds_the_working_set():
    """The tile is the whole window while it fits the VMEM budget, then
    the largest power of two that does; at Yi-34B's heads (8 KV heads,
    7 query heads each, d_head 128, bfloat16) a tile is 32 positions,
    whatever the window's length."""
    yi = dict(block_t=64, g=8, qh=7, dks=(128,), dv=128, itemsize=2)
    assert window_tile(1, **yi) == 1
    assert window_tile(5, **yi) == 5
    assert {window_tile(s, **yi) for s in (64, 256, 512, 4096)} == {32}
    # two KV heads (the oracle test above): 128-position tiles, so its
    # windows of 200 and 512 are two and four tiles
    small = dict(yi, g=G, itemsize=4)
    assert window_tile(63, **small) == 63
    assert window_tile(200, **small) == window_tile(512, **small) == 128


def test_window_buckets():
    """A hit's tail pads to a power of two, at least one page and at
    most the cache; the warm-up list holds every such length."""
    wb = sched_mod._bucket_len
    assert [wb(n, 12288, 64) for n in (1, 64, 65, 300, 4095)] == \
        [64, 64, 128, 512, 4096]
    assert wb(9000, 12288, 64) == 12288
    assert [wb(n, 2048) for n in (1, 3, 2048, 3000)] == [1, 4, 2048, 2048]
    assert sched_mod._window_buckets(64, 4095, 12288) == \
        [64, 128, 256, 512, 1024, 2048, 4096]
    assert sched_mod._window_buckets(16, 10, 512) == [16]


# -- the scheduler at GQA 7:1 ---------------------------------------------------

CFG = {
    "name": "gqa7-tiny", "family": "dense",
    "model": {"num_hidden_layers": 2, "hidden_size": 896,
              "num_attention_heads": 56, "num_key_value_heads": 8,
              "intermediate_size": 128, "vocab_size": 0,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
              "max_position_embeddings": 1024, "torch_dtype": "float32"},
}
PINNED = '{"tools": [{"name": "search", "args": {"q": "str", "n": 3}}, ' \
         '{"name": "fetch", "args": {"url": "str"}}]}'
TAILS = [' {"a": 1}', ' [1, 2, 3, {"b": [true, false, null]}, "x"]' * 3,
         ' "k": {"key": "value", "n": [10, 20, 30], "ok": true},' * 6]
N_TOKENS = 4


def _params(model):
    """The program's weights drawn from ``SEED``, rounded to bfloat16 as
    the reference draws them, then served in float32."""
    return jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
        weights.program_params(model, SEED))


@pytest.fixture(scope="module")
def served(small_tokenizer):
    """One engine; two schedulers over it, with and without the prefix
    cache, each serving the pinned prompt extended by each tail."""
    tok = small_tokenizer
    cfg = dict(CFG, model=dict(CFG["model"], vocab_size=tok.vocab_size))
    from repro.models import build_model
    model = build_model(families.of(cfg).model_config(cfg))
    eng = ServingEngine(model, _params(model), tok, max_len=512)
    eng.pin_prompt(PINNED)
    out = {"cfg": cfg, "tok": tok}
    for cached in (True, False):
        sched = ContinuousBatchingScheduler(
            eng, capacity=2, page_size=PAGE, n_pages=96,
            prefix_cache=cached)
        sched._pin_prompts()
        spans = []
        real_span = sched_mod.span

        def recording(name, **meta):
            spans.append((name, meta))
            return real_span(name, **meta)
        sched_mod.span = recording
        try:
            runs = [_serve_one(sched, PINNED + t) for t in TAILS]
        finally:
            sched_mod.span = real_span
        out[cached] = {"runs": runs, "stats": sched.stats(),
                       "spans": [s for s in spans
                                 if s[0] == "prefill_hit"]}
    return out


def _serve_one(sched, prompt):
    """Admit one unconstrained greedy request, then decode ``N_TOKENS``;
    the logits row each token was chosen from, and the session."""
    req = Request(prompt, ConstraintSpec(),
                  DecodeParams(max_tokens=N_TOKENS))
    sess = sched.submit(req)
    sched._admit()
    rows = [np.asarray(sched._logits[sess.slot])]
    while sess.result is None:
        sched.step()
        if sess.result is None:
            rows.append(np.asarray(sched._logits[sess.slot]))
    return sess, rows


def test_hits_span_several_tiles_and_end_mid_page(served):
    ids_pinned = served["tok"].encode(PINNED)
    start = len(ids_pinned) // PAGE * PAGE
    tails = [len(s.prompt_ids) - start for s, _ in served[True]["runs"]]
    assert all(s.n_cached_tokens == start for s, _ in served[True]["runs"])
    assert max(tails) > 2 * 32 and all(n % PAGE for n in tails), tails


def test_hit_logits_match_reference_and_uncached_path(served):
    """At the prompt's last token and at each decoded one, a hit's
    logits lie within float32 rounding of the reference and of the
    uncached prefill, and all three choose the same tokens."""
    cfg, tok = served["cfg"], served["tok"]
    v = tok.vocab_size
    for (hit, rows), (cold, cold_rows) in zip(served[True]["runs"],
                                              served[False]["runs"]):
        assert hit.out_ids == cold.out_ids and len(hit.out_ids) == N_TOKENS
        p = list(hit.prompt_ids)
        seq = p + list(hit.out_ids)
        score = list(range(len(p) - 1, len(p) - 1 + len(rows)))
        ref = reference.logits(cfg, SEED, [seq], [score], v)["fp32"][0]
        for got, cold_got, want in zip(rows, cold_rows, ref):
            # float32 program against float32 HIGHEST: summation order
            np.testing.assert_allclose(got[:v], want, rtol=1e-4, atol=1e-4)
            # the same program, prefix read from the pool instead of
            # recomputed: the attention's summation order differs
            np.testing.assert_allclose(got[:v], cold_got[:v], rtol=1e-5,
                                       atol=1e-5)


def test_counters_and_hit_span(served):
    """Each hit is one window program, counted and spanned with its
    tail and window; every admitted prompt token is counted."""
    hits = served[True]
    runs = hits["runs"]
    st = hits["stats"]
    assert st["n_prefill_windows"] == st["n_prefix_hits"] == len(TAILS)
    assert st["n_prompt_tokens"] == sum(len(s.prompt_ids) for s, _ in runs)
    assert st["n_prefix_tokens"] == sum(s.n_cached_tokens for s, _ in runs)
    tails = [len(s.prompt_ids) - s.n_cached_tokens for s, _ in runs]
    assert [m["tokens"] for _, m in hits["spans"]] == tails
    assert [m["window"] for _, m in hits["spans"]] == [
        sched_mod._bucket_len(n, 512, PAGE) for n in tails]
    cold = served[False]
    assert cold["stats"]["n_prefill_windows"] == 0 and not cold["spans"]
    assert cold["stats"]["n_prompt_tokens"] == st["n_prompt_tokens"]


def _hit_scheduler(tok):
    from repro.models import build_model
    cfg = dict(CFG, model=dict(CFG["model"], vocab_size=tok.vocab_size,
                               num_hidden_layers=1))
    model = build_model(families.of(cfg).model_config(cfg))
    eng = ServingEngine(model, _params(model), tok, max_len=256)
    eng.pin_prompt(PINNED)
    sched = ContinuousBatchingScheduler(eng, capacity=2, page_size=PAGE,
                                        n_pages=48, prefix_cache=True)
    sched._pin_prompts()
    return sched


def test_hit_programs_compiled_before_serving(small_tokenizer):
    """Pinning a prompt compiles the window program of every tail bucket
    a prompt sharing it can reach, so a hit lowers nothing new."""
    sched = _hit_scheduler(small_tokenizer)
    start = len(small_tokenizer.encode(PINNED)) // PAGE * PAGE
    # the pinned pages are held; the warm-up's private page is free again
    assert sched.pool.available == 48 - 1 - start // PAGE
    warmed = sched._prefill_window._cache_size()
    assert warmed == len(sched_mod._window_buckets(PAGE, 255 - start, 256))
    for t in (TAILS[0], TAILS[1][:40]):
        sess, _ = _serve_one(sched, PINNED + t)
        assert sess.n_cached_tokens == start
    assert sched._prefill_window._cache_size() == warmed


def test_recording_the_hit_span_changes_nothing_served(small_tokenizer,
                                                       tmp_path):
    """Served inside a profiler session, the hits commit the same tokens
    as outside one, and the trace holds one ``serve.prefill_hit`` per
    hit, inside that admission's ``serve.admit``."""
    import glob
    from repro.serving.tracing import PREFIX
    prompts = [PINNED + t for t in (TAILS[0], TAILS[1][:60])]
    off = [_serve_one(_hit_scheduler(small_tokenizer), p)[0].out_ids
           for p in prompts]
    sched = _hit_scheduler(small_tokenizer)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        on = [_serve_one(sched, p)[0].out_ids for p in prompts]
    finally:
        jax.profiler.stop_trace()
    assert on == off
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, int(e.start_ns), int(e.end_ns))
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name in (PREFIX + "prefill_hit", PREFIX + "admit")]
    hits = [s for s in spans if s[0] == PREFIX + "prefill_hit"]
    admits = [s for s in spans if s[0] == PREFIX + "admit"]
    assert len(hits) == len(prompts)
    assert all(any(a <= s and e <= b for _, a, b in admits)
               for _, s, e in hits)


@pytest.mark.parametrize("cached", [True, False])
def test_admission_lowers_nothing_per_prompt_length(small_tokenizer, cached):
    """Once a prompt of one length has been served, a prompt of another
    length in the same bucket, a hit or a fresh prefill, lowers no
    program: the prompt's ids reach the device without a conversion
    program of their own."""
    from jax._src import monitoring
    sched = _hit_scheduler(small_tokenizer)
    if not cached:
        sched = ContinuousBatchingScheduler(
            sched.eng, capacity=2, page_size=PAGE, n_pages=48,
            prefix_cache=False)
    tok = small_tokenizer
    first, second = PINNED + TAILS[1][:40], PINNED + TAILS[1][:52]
    n1, n2 = len(tok.encode(first)), len(tok.encode(second))
    assert n1 != n2 and sched_mod._bucket_len(n1, 256) == \
        sched_mod._bucket_len(n2, 256)
    _serve_one(sched, first)
    lowered = []

    def on(event, _secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)
    monitoring.register_event_duration_secs_listener(on)
    try:
        sess, _ = _serve_one(sched, second)
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert (sess.n_cached_tokens > 0) == cached
    assert lowered == []
