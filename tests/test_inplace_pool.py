"""The paged decode updates its KV pool in place (``kvcache.pool_inplace``).

A GQA pool read by the Pallas decode kernel is handed to its block whole:
each layer's new tokens are scattered into the stacked pool at
``[layer, page, offset]`` and the kernel reads the stacked pool through a
reshape, its table offset by ``layer * n_pages``.  The pool's last axis is
d_head in whole lanes (``kvcache.pool_width``), zero beyond d_head.

Only addressing changes between the in-place path and the per-layer slice
path it replaced, so over the same pool the two must be BITWISE identical
— logits and every pool entry — on plain decode, verify windows, vacant
rows, negative table entries, the prefix cache's B=1 view and the fused
device loop.  The slice path is reached by patching ``pool_inplace`` off
in the test, after the pool is allocated.  Against a pool d_head wide (the
old layout) logits and pool agree to rounding: on the CPU the interpreted
kernel's dot products also run over the zero lanes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig
from repro.core import grammars
from repro.core.sampling import GrammarSampler
from repro.models import build_model, kvcache
from repro.serving import (ContinuousBatchingScheduler, EngineConfig,
                           ServingEngine)
from repro.tokenizer import train_bpe

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            dtype="float32", max_seq_len=512, use_pallas_kernels=True)


def _model(arch_id: str, vocab_size: int, **over):
    # a distinct arch_id per path keeps the two paths' traces apart
    cfg = ModelConfig(arch_id=arch_id, family="dense",
                      vocab_size=vocab_size, **{**BASE, **over})
    return build_model(cfg)


def _slice_path(monkeypatch):
    """From here on, every pool takes the per-layer slice path."""
    monkeypatch.setattr(kvcache, "pool_inplace", lambda cfg, kind: False)


def _pools(cache):
    return [np.asarray(a) for a in jax.tree.leaves(
        {k: cache[k] for k in ("head", "group", "tail")})]


def _assert_same(got, want):
    """Pools equal, up to zero lanes that ``got`` has beyond ``want``."""
    assert len(got) == len(want)
    for x, y in zip(got, want):
        d = y.shape[-1]
        np.testing.assert_array_equal(x[..., :d], y)
        assert not np.any(x[..., d:])


# -- the decode step ----------------------------------------------------------


def _step_inputs(m, s_win):
    """A 4-row paged batch over a random pool (zero beyond d_head): two
    live rows on shuffled pages, a vacant row whose first table entry is
    -1 (read by every layer: the clamp must come before the layer offset)
    and a live row whose table ends in -1 entries beyond its frontier."""
    rng = np.random.default_rng(3)
    ps, n_pages, dh = 8, 20, m.cfg.d_head

    def fill(a):
        x = np.zeros(a.shape, np.float32)
        x[..., :dh] = rng.normal(size=a.shape[:-1] + (dh,))
        return jnp.asarray(x, a.dtype)

    cache = m.init_cache(4, 64, page_size=ps, n_pages=n_pages)
    cache = jax.tree.map(
        lambda a: fill(a) if a.dtype != jnp.int32 else a, cache)
    lens = [13, 0, 30, 5]
    perm = list(rng.permutation(np.arange(1, n_pages)))
    tbl = np.zeros((4, 8), np.int32)
    for i, ln in enumerate(lens):
        if ln:
            n_pg = -(-(ln + s_win) // ps)
            tbl[i, :n_pg] = perm[:n_pg]
            del perm[:n_pg]
    tbl[1, 0] = -1
    tbl[3, 4:] = -1
    cache["len"] = jnp.asarray(lens, jnp.int32)
    cache["pages"] = jnp.asarray(tbl)
    toks = jnp.asarray(rng.integers(0, 64, size=(4, s_win)), jnp.int32)
    return cache, toks


def _decode(m, params, cache, toks):
    # a fresh function per call: each path is traced on its own
    lg, out = jax.jit(lambda p, c, t: m.decode_step(p, c, t))(
        params, cache, toks)
    return np.asarray(lg), _pools(out), np.asarray(out["len"])


@pytest.mark.parametrize("s_win", [1, 5])
def test_inplace_step_matches_slice_path(s_win, monkeypatch):
    """S=1 decode and an S=5 verify window: same logits, same pool; and
    against the d_head-wide pool, the same to rounding."""
    m = _model("inplace-step", 64)
    params = m.init(jax.random.PRNGKey(0))
    cache, toks = _step_inputs(m, s_win)
    assert cache["group"]["b0"]["k"].shape[-1] == kvcache.LANES
    got = _decode(m, params, cache, toks)
    assert m.kv_inplace_layers == 2
    _slice_path(monkeypatch)
    ref_m = _model("slice-step", 64)
    want = _decode(ref_m, params, cache, toks)
    assert ref_m.kv_inplace_layers == 0
    np.testing.assert_array_equal(got[0], want[0])
    _assert_same(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    # the vacant row wrote to the trash page, in every layer
    dh = m.cfg.d_head
    before = np.asarray(cache["group"]["b0"]["k"])[:, 0, :s_win, :, :dh]
    assert np.all(got[1][0][:, 0, :s_win, :, :dh] != before)
    narrow = jax.tree.map(lambda a: a[..., :dh], cache)
    narrow["len"], narrow["pages"] = cache["len"], cache["pages"]
    old = _decode(_model("narrow-step", 64), params, narrow, toks)
    np.testing.assert_allclose(got[0], old[0], rtol=1e-5, atol=1e-5)
    for x, y in zip(got[1], old[1]):
        np.testing.assert_allclose(x[..., :dh], y, rtol=1e-5, atol=1e-5)


# -- the scheduler ------------------------------------------------------------


def _serve(sched, reqs):
    sessions = [sched.submit(r) for r in reqs]
    sched.run()
    return ([s.result.token_ids for s in sessions], _pools(sched.cache),
            np.asarray(sched._logits), sched)


def _scheduler(eng, **kw):
    return ContinuousBatchingScheduler(eng, capacity=3, page_size=8,
                                       n_pages=64, debug_invariants=True,
                                       **kw)


HEADER = "Extract every field of the record below as JSON: "
FORKS = [HEADER + "alpha -> ", HEADER + "beta gamma -> ", HEADER + "d -> "]


@pytest.mark.parametrize("case", ["prefix_cache", "speculative"])
def test_inplace_scheduler_matches_slice_path(case, small_tokenizer,
                                              json_grammar, monkeypatch):
    """Whole runs: the prefix cache's B=1 tail decode over the shared
    pool, and speculative verify windows with rollback — same tokens,
    same final pool and logits as the slice path."""
    tok = small_tokenizer
    if case == "prefix_cache":
        kw, cfg = {"prefix_cache": True}, {"max_tokens": 6}
        grammar = json_grammar
    else:
        kw = {}
        cfg = {"max_tokens": 10, "speculative": True, "spec_s": 4,
               "spec_threshold": 0.4}
        grammar = grammars.load("json_gsm8k")
    params = _model("inplace-sched", tok.vocab_size).init(
        jax.random.PRNGKey(0))

    def build(arch_id):
        eng = ServingEngine(_model(arch_id, tok.vocab_size), params, tok,
                            grammar, EngineConfig(mode="domino", **cfg),
                            max_len=256)
        if case == "speculative":
            eng.generate("A: ")            # warm the count model
        return _scheduler(eng, **kw)

    got = _serve(build("inplace-sched"), FORKS)
    sched = got[3]
    assert sched.kv_inplace_layers == sched.stats()["kv_inplace_layers"] == 2
    if case == "prefix_cache":
        assert sched.n_prefix_hits > 0
    ref = build("slice-sched")
    _slice_path(monkeypatch)
    want = _serve(ref, FORKS)
    assert ref.kv_inplace_layers == 0
    assert got[0] == want[0]
    _assert_same(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_inplace_fused_block_matches_slice_path(json_grammar, monkeypatch):
    """The device-resident loop decodes whole blocks in one call, the
    pool riding its while-loop carry: same tokens and pool."""
    corpus = GrammarSampler(json_grammar, seed=7).corpus(80)
    tok = train_bpe(corpus, vocab_size=257)
    params = _model("inplace-fused", tok.vocab_size).init(
        jax.random.PRNGKey(0))

    def build(arch_id):
        eng = ServingEngine(_model(arch_id, tok.vocab_size), params, tok,
                            json_grammar,
                            EngineConfig(mode="domino", max_tokens=12),
                            max_len=256, device_tables=True)
        eng.register_grammar("json", json_grammar)
        eng.precompute()
        return _scheduler(eng, device_loop=True, sync_n=4)

    got = _serve(build("inplace-fused"), ["a: ", "some json: "])
    assert got[3].kv_inplace_layers == 2
    ref = build("slice-fused")
    _slice_path(monkeypatch)
    want = _serve(ref, ["a: ", "some json: "])
    for run in (got, want):
        assert run[3].n_device_tokens > 0
    assert got[0] == want[0]
    _assert_same(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# -- the counter and donation ------------------------------------------------


@pytest.mark.parametrize("kind,n", [("attn", 2), ("mla", 0), ("int8", 0),
                                    ("dense", 0)])
def test_kv_inplace_layers(kind, n):
    """Every layer of a GQA paged pool goes in place; an MLA latent pool,
    an int8 pool and a dense cache keep the slice path, d_head wide."""
    over = {}
    if kind == "mla":
        over = dict(group=("mla",),
                    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_head_dim=16, qk_rope_head_dim=8,
                                  v_head_dim=16))
    if kind == "int8":
        over = {"kv_cache_dtype": "int8"}
    m = _model(f"count-{kind}", 64, **over)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    page = None if kind == "dense" else 8
    cache = m.cache_spec(2, 64, page_size=page)
    cache["len"] = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.eval_shape(m.decode_step, params, cache,
                   jax.ShapeDtypeStruct((2, 1), jnp.int32))
    assert m.kv_inplace_layers == n == kvcache.inplace_layers(m.cfg, cache)
    pool = jax.tree.leaves(cache["group"])[0]
    assert (pool.shape[-1] == kvcache.LANES) == (n > 0)


def test_donated_pool_fails_loudly_after_a_step(small_tokenizer,
                                                json_grammar):
    """The paged decode donates the batch cache: a pool leaf held across
    a step is deleted, and reading it raises instead of returning stale
    K/V.  The engine's own single-request decode donates nothing."""
    tok = small_tokenizer
    m = _model("donate", tok.vocab_size)
    eng = ServingEngine(m, m.init(jax.random.PRNGKey(0)), tok, json_grammar,
                        EngineConfig(mode="domino", max_tokens=4),
                        max_len=256)
    sched = ContinuousBatchingScheduler(eng, capacity=2, page_size=8)
    sched.submit("a: ")
    sched.step()                          # admission + first decode
    held = sched.cache["group"]["b0"]["k"]
    sched.step()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(held)
    sched.run()
    cache = m.init_cache(1, 256)
    eng._decode(eng.params, cache, jnp.asarray([[1]], jnp.int32))
    np.asarray(cache["group"]["b0"]["k"])  # still readable
