"""Mask-pipeline micro-benchmarks (ISSUE 4): packed bitsets end to end.

Three sections, written to ``BENCH_mask.json`` (a CI artifact):

 - **build**: per-step full-mask assembly over real DOMINO states (every
   step of grammar-sampled JSON generations) three ways — the pre-bitset
   scatter walk (`mask_dense`, bool out + per-token fancy-index writes),
   the bitset-OR walk (`mask_bits` on a cold memo), and a state-keyed
   memo hit.  Asserts the memo-hit path is measurably faster than both.
 - **bytes**: host->device mask traffic per scheduler tick — the old
   dense (capacity, V) int8 staging array vs the persistent packed
   (capacity, ceil(V/32)) uint32 buffer, at the bench vocab and at real
   vocab sizes (gemma3 V=262144: 256 KiB -> 32 KiB per row).  Asserts
   the >=8x reduction the tentpole claims.
 - **parity**: the packed kernel is bitwise-identical to the int8-mask
   entry point (packed on device) and the jnp oracle on masks taken
   from the real decoder states (plus empty/single-bit rows), including
   an odd-V tail tile.

Pure host + interpret-mode work: no model, no training, fast enough for
a CI smoke step.
"""
from __future__ import annotations

import json
import pathlib
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import bitmask, grammars
from repro.core.domino import DominoDecoder
from repro.core.sampling import GrammarSampler
from repro.kernels.masked_sample.kernel import (masked_argmax_pallas,
                                                masked_argmax_pallas_packed)
from repro.kernels.masked_sample.ref import masked_argmax_ref
from repro.tokenizer import train_bpe

VOCAB_SIZE = 512                 # word-aligned: the exact 8x wire ratio
N_SAMPLES = 12
REAL_VOCABS = {"stablelm": 50304, "yi": 64000, "gemma3": 262144}


def _setup():
    g = grammars.load("json")
    sampler = GrammarSampler(g, seed=11)
    corpus = sampler.corpus(200)
    tok = train_bpe(corpus, vocab_size=VOCAB_SIZE)
    texts = []
    for _ in range(N_SAMPLES):
        t = sampler.sample()
        texts.append(t.decode() if isinstance(t, bytes) else t)
    return g, tok, texts


def _walk_states(g, tok, texts):
    """One decoder per text; yields the decoder at every step state."""
    from repro.core.scanner import Scanner
    from repro.core.trees import TreeCache
    cache = TreeCache(Scanner(g), list(tok.vocab))
    cache.precompute()
    for text in texts:
        dec = DominoDecoder(g, list(tok.vocab), tok.eos_id,
                            tree_cache=cache)
        yield dec
        for t in tok.encode(text):
            if not dec.advance(t):
                break
            yield dec


def run_build(g, tok, texts, verbose: bool = True):
    t_scatter = t_bitset = t_memo = 0.0
    n = 0
    masks = []
    for dec in _walk_states(g, tok, texts):
        memo = dec.trees.mask_memo
        t0 = time.perf_counter()
        dense = dec.mask_dense()
        t_scatter += time.perf_counter() - t0
        memo.clear()                       # force a cold bitset-OR build
        t0 = time.perf_counter()
        bits = dec.mask_bits()
        t_bitset += time.perf_counter() - t0
        t0 = time.perf_counter()
        bits2 = dec.mask_bits()            # state-keyed memo hit
        t_memo += time.perf_counter() - t0
        assert bits2 is bits
        assert (bitmask.unpack(bits, len(tok.vocab)) == dense).all()
        if len(masks) < 64:
            masks.append(np.asarray(bits))
        n += 1
    us = {"scatter_us": 1e6 * t_scatter / n,
          "bitset_or_us": 1e6 * t_bitset / n,
          "memo_hit_us": 1e6 * t_memo / n}
    out = dict(us, steps=n,
               speedup_bitset=us["scatter_us"] / us["bitset_or_us"],
               speedup_memo=us["scatter_us"] / us["memo_hit_us"])
    # the acceptance bar: memo hits must beat a fresh walk of either kind
    assert out["speedup_memo"] > 1.0, out
    assert us["memo_hit_us"] < us["bitset_or_us"], out
    if verbose:
        print(f"  [mask] build ({n} real JSON states): "
              f"scatter {us['scatter_us']:.0f}us, "
              f"bitset-OR {us['bitset_or_us']:.0f}us, "
              f"memo hit {us['memo_hit_us']:.1f}us "
              f"({out['speedup_memo']:.0f}x vs scatter)", flush=True)
    emit("mask_build_scatter", us["scatter_us"], f"steps={n}")
    emit("mask_build_bitset_or", us["bitset_or_us"],
         f"speedup={out['speedup_bitset']:.2f}")
    emit("mask_build_memo_hit", us["memo_hit_us"],
         f"speedup={out['speedup_memo']:.2f}")
    return out, masks


def run_bytes(verbose: bool = True):
    """Per-tick host->device mask traffic, dense int8 vs packed uint32."""
    cap = 8
    out = {}
    for name, v in dict(bench=VOCAB_SIZE, **REAL_VOCABS).items():
        dense = cap * v                              # int8 staging array
        packed = cap * bitmask.n_words(v) * 4        # uint32 rows
        out[name] = {"v": v, "dense_bytes": dense, "packed_bytes": packed,
                     "ratio": dense / packed}
        if verbose:
            print(f"  [mask] bytes/tick {name} V={v} cap={cap}: "
                  f"{dense/1024:.0f}KiB -> {packed/1024:.1f}KiB "
                  f"({dense/packed:.2f}x fewer)", flush=True)
        emit(f"mask_bytes_{name}", packed, f"dense={dense};"
             f"ratio={dense/packed:.3f}")
    # tentpole acceptance: >=8x on word-aligned vocabularies
    assert out["bench"]["ratio"] >= 8.0
    assert out["gemma3"]["ratio"] >= 8.0
    return out


def run_parity(masks, verbose: bool = True):
    """Packed kernel == int8 entry point == oracle on real grammar
    masks."""
    rng = np.random.default_rng(3)
    out = {}
    for case, v, bv in (("even", VOCAB_SIZE, 128), ("odd_tail", 420, 128)):
        rows = [m if v == VOCAB_SIZE else
                bitmask.pack_bool(bitmask.unpack(m, VOCAB_SIZE)[:v])
                for m in masks[:6]]
        bools = np.stack([bitmask.unpack(r, v) for r in rows])
        bools = np.concatenate([bools, np.zeros((1, v), bool)])  # empty row
        single = np.zeros((1, v), bool)
        single[0, v - 1] = True                      # last-token bit
        bools = np.concatenate([bools, single])
        b = bools.shape[0]
        logits = jnp.asarray(rng.normal(size=(b, v)).astype(np.float32))
        i8 = jnp.asarray(bools.astype(np.int8))
        pk = jnp.asarray(bitmask.pack_bool(bools))
        t0 = time.perf_counter()
        ii, vi = masked_argmax_pallas(logits, i8, block_v=bv)
        ip, vp = masked_argmax_pallas_packed(logits, pk, block_v=bv)
        ir, _ = masked_argmax_ref(logits, i8)
        dt = time.perf_counter() - t0
        exact = bool((np.asarray(ii) == np.asarray(ip)).all()
                     and (np.asarray(vi) == np.asarray(vp)).all()
                     and (np.asarray(ii) == np.asarray(ir)).all())
        assert exact, f"packed/int8/oracle disagree on {case}"
        out[case] = {"b": b, "v": v, "block_v": bv, "bitwise_identical":
                     exact, "wall_us": 1e6 * dt}
        if verbose:
            print(f"  [mask] kernel parity {case} (B={b} V={v}): "
                  f"packed == int8 == oracle", flush=True)
        emit(f"mask_kernel_parity_{case}", 1e6 * dt, f"identical={exact}")
    return out


def run(verbose: bool = True, json_path: str = "BENCH_mask.json"):
    g, tok, texts = _setup()
    build, masks = run_build(g, tok, texts, verbose=verbose)
    record = {
        "config": {"vocab_size": VOCAB_SIZE, "n_samples": N_SAMPLES,
                   "grammar": "json"},
        "build": build,
        "bytes_per_tick": run_bytes(verbose=verbose),
        "kernel_parity": run_parity(masks, verbose=verbose),
    }
    pathlib.Path(json_path).write_text(json.dumps(record, indent=2))
    if verbose:
        print(f"  [mask] wrote {json_path}", flush=True)
    return record


if __name__ == "__main__":
    run()
