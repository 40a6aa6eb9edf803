"""Packed-bitset mask pipeline (ISSUE 4 tentpole).

Covers every layer of the packed flow:
 - pack/unpack round-trips against bool masks (hypothesis property);
 - tree-node bitset segments vs the token-id lists they replace;
 - state-keyed memo hits returning masks identical to fresh tree walks
   (and to the pre-bitset scatter walk, kept as ``mask_dense``);
 - packed-kernel output bitwise-identical to the int8-mask kernel across
   mixed batches (empty / single-bit / dense rows, odd V tail tiles);
 - the scheduler's persistent packed staging buffer: no per-tick dense
   allocation, ``mask_cache_hits`` reported next to ``premask_hits``,
   and batched outputs unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.core import bitmask, grammars
from repro.core.domino import DominoDecoder
from repro.core.sampling import GrammarSampler
from repro.core.trees import TreeCache
from repro.kernels.masked_sample.kernel import (masked_argmax_pallas,
                                                masked_argmax_pallas_packed)
from repro.kernels.masked_sample.ops import masked_argmax
from repro.kernels.masked_sample.ref import masked_argmax_ref, unpack_bits
from repro.models import build_model
from repro.serving import (ContinuousBatchingScheduler, EngineConfig,
                           ServingEngine)

RNG = np.random.default_rng(7)


# -- bitmask layout -----------------------------------------------------------


def test_pack_bool_roundtrip_basic():
    for v in (1, 31, 32, 33, 420, 512, 1000):
        m = RNG.random(v) < 0.3
        bits = bitmask.pack_bool(m)
        assert bits.shape == (bitmask.n_words(v),)
        assert bits.dtype == np.uint32
        np.testing.assert_array_equal(bitmask.unpack(bits, v), m)


def test_pack_ids_matches_pack_bool():
    v = 420
    ids = RNG.choice(v, size=50, replace=False)
    m = np.zeros(v, bool)
    m[ids] = True
    np.testing.assert_array_equal(bitmask.pack_ids(ids, v),
                                  bitmask.pack_bool(m))
    # duplicate ids in one word must still accumulate, not overwrite
    np.testing.assert_array_equal(
        bitmask.pack_ids([3, 3, 4, 35], v),
        bitmask.pack_bool(np.isin(np.arange(v), [3, 4, 35])))


def test_tail_bits_are_zero():
    v = 33                              # one full word + one bit
    bits = bitmask.pack_bool(np.ones(v, bool))
    assert bits[1] == 1                 # only bit 0 of the tail word


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 260), st.integers(0, 2**32 - 1))
def test_pack_unpack_roundtrip_property(v, seed):
    rng = np.random.default_rng(seed)
    m = rng.random(v) < rng.random()
    bits = bitmask.pack_bool(m)
    np.testing.assert_array_equal(bitmask.unpack(bits, v), m)
    # pack(unpack(bits)) is the identity on canonical (tail-zeroed) rows
    np.testing.assert_array_equal(bitmask.pack_bool(bitmask.unpack(bits, v)),
                                  bits)
    # the jnp unpack used by the oracle agrees with the numpy one
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(jnp.asarray(bits), v)), m)


# -- tree-node segments -------------------------------------------------------


@pytest.fixture(scope="module")
def json_tree_cache(small_tokenizer):
    from repro.core.scanner import Scanner
    tok = small_tokenizer
    g = grammars.load("json")
    cache = TreeCache(Scanner(g), list(tok.vocab))
    cache.precompute()
    return tok, g, cache


def test_tree_node_bits_match_token_lists(json_tree_cache):
    """Every node's packed segments must be exactly the pack of the
    token-id lists they were built from."""
    tok, _g, cache = json_tree_cache
    v = len(tok.vocab)
    n_nodes = n_with_fresh = 0
    for tree in cache.trees.values():
        stack = [tree.root]
        while stack:
            node = stack.pop()
            n_nodes += 1
            if node.tokens_fresh:
                assert node.fresh_bits is not None
                n_with_fresh += 1
                np.testing.assert_array_equal(
                    node.fresh_bits, bitmask.pack_ids(node.tokens_fresh, v))
            else:
                assert node.fresh_bits is None
            assert set(node.partial_bits) == set(node.tokens_partial)
            for tids, toks in node.tokens_partial.items():
                np.testing.assert_array_equal(
                    node.partial_bits[tids], bitmask.pack_ids(toks, v))
            stack.extend(node.children.values())
    assert n_nodes > 0 and n_with_fresh > 0


# -- memoized mask assembly ---------------------------------------------------


def _advance_along(dec, tok, text):
    for t in tok.encode(text):
        assert dec.advance(t)


def test_mask_bits_equals_dense_walk(json_tree_cache):
    """Bitset-OR assembly == the scatter walk it replaced, at every step
    of a sampled generation and at several lookaheads."""
    tok, g, cache = json_tree_cache
    sampler = GrammarSampler(g, seed=5)
    for text in [sampler.sample() for _ in range(5)]:
        if isinstance(text, bytes):
            text = text.decode()
        dec = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
        for t in tok.encode(text):
            for k in (None, 0, 1):
                np.testing.assert_array_equal(
                    bitmask.unpack(dec.mask_bits(k), len(tok.vocab)),
                    dec.mask_dense(k))
            assert dec.advance(t), (text, tok.vocab[t])


def test_mask_memo_hit_returns_identical_mask(json_tree_cache):
    """A second decoder reaching the same immutable state gets the SAME
    packed row from the shared memo — and it equals a fresh walk."""
    tok, g, cache = json_tree_cache
    d1 = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    d2 = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    _advance_along(d1, tok, '{"a"')
    m1 = d1.mask_bits()
    hits_before = d2.n_mask_memo_hits
    _advance_along(d2, tok, '{"a"')
    m2 = d2.mask_bits()
    assert d2.n_mask_memo_hits == hits_before + 1
    assert m2 is m1                     # literally the shared memo row
    np.testing.assert_array_equal(bitmask.unpack(m2, len(tok.vocab)),
                                  d2.mask_dense())
    # memo rows are read-only: the serving path must never corrupt them
    with pytest.raises(ValueError):
        m2[0] = 0


def test_mask_memo_fifo_cap(json_tree_cache):
    """The shared memo evicts FIFO past mask_memo_max instead of growing
    without bound on a long-lived server; eviction only costs a rebuild."""
    tok, g, cache = json_tree_cache
    d = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    old_max = cache.mask_memo_max
    try:
        cache.mask_memo.clear()
        cache.mask_memo_max = 2
        m_fresh = d.mask_bits()
        d.mask_bits(0)
        d.mask_bits(1)                  # third entry -> evicts the first
        assert len(cache.mask_memo) == 2
        hits = d.n_mask_memo_hits
        m_rebuilt = d.mask_bits()       # miss again, rebuilt identically
        assert d.n_mask_memo_hits == hits
        np.testing.assert_array_equal(m_rebuilt, m_fresh)
    finally:
        cache.mask_memo_max = old_max
        cache.mask_memo.clear()


def test_mask_memo_distinguishes_lookahead(json_tree_cache):
    tok, g, cache = json_tree_cache
    d = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    m_inf = d.mask_bits()
    m_0 = d.mask_bits(0)
    n0 = int(bitmask.unpack(m_0, len(tok.vocab)).sum())
    ninf = int(bitmask.unpack(m_inf, len(tok.vocab)).sum())
    assert n0 <= ninf                   # k=0 is a subset of k=inf


def test_mask_memo_distinguishes_charts(json_tree_cache):
    """Two states with identical CURRENT parser item sets but different
    histories must not collide: the memo key uses the whole-history
    chart fingerprint, not state_key()."""
    tok, g, cache = json_tree_cache
    d1 = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    d2 = DominoDecoder(g, list(tok.vocab), tok.eos_id, tree_cache=cache)
    _advance_along(d1, tok, '[1')
    _advance_along(d2, tok, '[[1')      # one level deeper
    import math
    assert d1._memo_key(math.inf) != d2._memo_key(math.inf)
    m1 = bitmask.unpack(d1.mask_bits(), len(tok.vocab))
    m2 = bitmask.unpack(d2.mask_bits(), len(tok.vocab))
    np.testing.assert_array_equal(m1, d1.mask_dense())
    np.testing.assert_array_equal(m2, d2.mask_dense())


# -- fused kernel parity ------------------------------------------------------


def _mixed_batch(b, v, rng):
    """Rows exercising every regime: empty, single-bit, sparse, dense."""
    mask = np.zeros((b, v), bool)
    for i in range(b):
        kind = i % 4
        if kind == 1:
            mask[i, rng.integers(v)] = True
        elif kind == 2:
            mask[i] = rng.random(v) < 0.02
        elif kind == 3:
            mask[i] = rng.random(v) < 0.7
    return mask


@pytest.mark.parametrize("b,v,bv", [(4, 512, 128), (5, 1000, 256),
                                    (4, 4100, 2048), (3, 333, 128),
                                    (8, 8192, 2048)])
def test_packed_kernel_bitwise_identical_to_int8(b, v, bv):
    logits = jnp.asarray(RNG.normal(size=(b, v)).astype(np.float32))
    mask = _mixed_batch(b, v, RNG)
    i8 = jnp.asarray(mask.astype(np.int8))
    bits = jnp.asarray(bitmask.pack_bool(mask))
    i1, v1 = masked_argmax_pallas(logits, i8, block_v=bv)
    i2, v2 = masked_argmax_pallas_packed(logits, bits, block_v=bv)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # and both equal the unfused oracle (packed + dense operands)
    i3, v3 = masked_argmax_ref(logits, i8)
    i4, _ = masked_argmax_ref(logits, bits)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i3))
    np.testing.assert_array_equal(np.asarray(i3), np.asarray(i4))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v3), rtol=1e-6)


def test_ops_dispatch_on_dtype():
    """masked_argmax routes uint32 operands to the packed kernel and
    produces identical selections either way."""
    b, v = 3, 420
    logits = jnp.asarray(RNG.normal(size=(b, v)).astype(np.float32))
    mask = _mixed_batch(b, v, RNG)
    mask[0, 17] = True                  # no fully-empty ambiguity
    i1, _ = masked_argmax(logits, jnp.asarray(mask.astype(np.int8)))
    i2, _ = masked_argmax(logits, jnp.asarray(bitmask.pack_bool(mask)))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_tie_breaking_matches_reference():
    """Equal logits under the mask: both kernels and the oracle must all
    pick the lowest legal index, including across tile boundaries."""
    b, v, bv = 1, 256, 64
    logits = jnp.zeros((b, v), jnp.float32)
    mask = np.zeros((b, v), bool)
    mask[0, [70, 130, 200]] = True      # three tiles, all tied
    for m in (jnp.asarray(mask.astype(np.int8)),
              jnp.asarray(bitmask.pack_bool(mask))):
        i_k, _ = masked_argmax(logits, m, block_v=bv)
        i_r, _ = masked_argmax_ref(logits, m)
        assert int(np.asarray(i_k)[0]) == 70
        assert int(np.asarray(i_r)[0]) == 70


# -- scheduler integration ----------------------------------------------------


BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            dtype="float32", max_seq_len=512)


def test_scheduler_packed_staging_and_memo_hits(small_tokenizer,
                                                json_grammar):
    """The scheduler stages packed rows in ONE persistent uint32 buffer
    (8x fewer mask bytes than the dense int8 layout), reports
    mask_cache_hits next to premask_hits, and outputs still match the
    single-request path token-for-token."""
    tok = small_tokenizer
    cfg = ModelConfig(arch_id="s-attn-mb", family="dense",
                      vocab_size=tok.vocab_size, **BASE)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, tok, json_grammar,
                        EngineConfig(mode="domino", max_tokens=8),
                        max_len=256)
    prompts = ["a: ", "b: ", "c: "]
    singles = [eng.generate(p) for p in prompts]
    sched = ContinuousBatchingScheduler(eng, capacity=2)
    assert sched._mask_words.dtype == np.uint32
    assert sched._mask_words.shape == \
        (2, bitmask.n_words(tok.vocab_size))
    assert sched._mask_words.nbytes * 8 >= tok.vocab_size  # covers V
    buf_id = id(sched._mask_words)
    for p in prompts:
        sched.submit(p)
    results = sched.run()
    assert id(sched._mask_words) == buf_id      # never reallocated
    for r, s in zip(results, singles):
        assert r.token_ids == s.token_ids
    # three identical-grammar sessions revisit states: the shared memo
    # must have served some builds, and the per-request results carry it
    assert sched.mask_cache_hits > 0
    assert sum(r.mask_cache_hits for r in results) >= sched.mask_cache_hits
    assert sched._mask_words.nbytes <= -(-tok.vocab_size // 32) * 4 * 2


def test_vacant_slots_keep_sentinel_rows(small_tokenizer, json_grammar):
    tok = small_tokenizer
    cfg = ModelConfig(arch_id="s-attn-mb2", family="dense",
                      vocab_size=tok.vocab_size, **BASE)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, tok, json_grammar,
                        EngineConfig(mode="domino", max_tokens=4),
                        max_len=256)
    sched = ContinuousBatchingScheduler(eng, capacity=3)
    sched.submit("a: ")                 # only slot 0 ever occupied
    sched.run()
    for row in sched._mask_words[1:]:
        np.testing.assert_array_equal(row, sched._sentinel_row)
