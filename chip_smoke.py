#!/usr/bin/env python3
"""Start the constrained-serving main path on one TPU chip and check it.

Serves stablelm-1.6b at its published width (24 layers, d_model 2048, 32
heads, d_ff 5632, vocabulary 100,352; bf16 weights drawn from
``PRNGKey(--seed)``) through the entry points of ``repro.launch.serve``,
all in this one process:

  (a) mixed batch — 8 requests cycling json, c and unconstrained rows
      through ``generate_batch`` with paged KV, the Pallas kernels and
      the prefix cache.  Every constrained output must be accepted by a
      fresh checker of its grammar; a second, warm pass must repeat the
      token ids.
  (b) fused loop — a greedy certified-JSON batch on a byte vocabulary
      with ``device_loop=True, sync_n=8``; it must commit tokens on the
      device.  Whether its ids match ``device_loop=False`` is printed.
  (c) kernels against reference — the paged decode-attention kernel
      against its jnp oracle at the model's head shapes (within
      ``ATTN_TOL``), one decode step through the Pallas kernels against
      the jnp path on the same paged cache (max abs logit difference
      within ``LOGIT_TOL`` of the logit scale), and the packed masked
      argmax against its jnp oracle at the full vocabulary (identical
      picks).

The run fails — non-zero exit, last line ``{"ok": false, ...}`` — when
JAX's backend is not a TPU, on any degrade, retry, watchdog trip or
engine reset of the serving scheduler, or on any result whose status is
not ``ok``.  On success the last line is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage:
  python chip_smoke.py            # on the chip
  python chip_smoke.py --smoke    # CPU rehearsal at the smoke size: runs
                                  # every phase, then fails for want of a
                                  # TPU
"""
import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "stablelm-1.6b"
SLOTS = 8
MAX_TOKENS = 32
SYNC_N = 8
# (a): a shared preamble longer than one 64-token page, so every request
# after the first maps the cached pages and prefills only its tail
PREAMBLE = ("You are a structured-output service. Answer each request "
            "with one value in the requested format: no prose, no "
            "markdown. ")
# (b): byte-vocabulary prompts of 17-32 bytes, one prefill bucket
FUSED_PROMPTS = ("The user record as JSON: ", "Return the config object: ",
                 "Encode the reply as JSON: ", "List the results as JSON: ")
# (c): kernel and jnp paths see the same bf16 weights and cache; they
# differ only in accumulation order and where f32 is rounded to bf16.
# Allowed: 2^-5 of the reference logits' largest magnitude (8 bf16 ulps
# at that scale).
LOGIT_TOL = 2.0 ** -5
# The attention kernel alone against its jnp oracle: both accumulate in
# f32 and round the output to bf16 once, so they may differ by one bf16
# ulp, 2^-7 relative to the output scale.
ATTN_TOL = 2.0 ** -7
RAGGED_LENS = (47, 41, 35, 29, 23, 17, 11, 5)


def log(msg: str) -> None:
    print(msg, flush=True)


def scheduler_faults(stats) -> dict:
    """The counters that must all be zero on a healthy chip."""
    keys = ("n_degrades", "n_retries", "n_watchdog_trips",
            "n_engine_resets")
    return {k: stats[k] for k in keys if stats[k]}


def check_results(name, results, stats, grammars_of, vocab, eos_id):
    """Statuses ok, scheduler clean, and every constrained output a legal
    prefix (a complete sentence when it ended on EOS) of its grammar."""
    from repro.core.domino import DominoDecoder

    bad = [(i, r.status, r.error) for i, r in enumerate(results)
           if r.status != "ok"]
    if bad:
        raise RuntimeError(f"{name}: non-ok results {bad}")
    faults = scheduler_faults(stats)
    if faults:
        raise RuntimeError(f"{name}: scheduler faults {faults}")
    n_checked = 0
    for i, (r, g) in enumerate(zip(results, grammars_of)):
        if g is None:
            continue
        checker = DominoDecoder(g, vocab, eos_id)
        for t in r.token_ids:
            if not checker.advance(int(t)):
                raise RuntimeError(f"{name}: request {i} token {t} "
                                   f"rejected by its grammar")
        if r.finished and not checker.eos_legal():
            raise RuntimeError(f"{name}: request {i} ended on EOS "
                               f"outside a complete sentence")
        n_checked += 1
    return n_checked


def phase_mixed(opts):
    """(a) mixed json/c/none batch through serve's entry points."""
    from repro.core import grammars
    from repro.launch import serve

    argv = ["--arch", ARCH, "--grammar", "json,c,none",
            "--prompts", str(SLOTS), "--slots", str(SLOTS),
            "--max-tokens", str(MAX_TOKENS), "--seed", str(opts.seed),
            "--kernels", "--prefix-cache"]
    args = serve.build_parser().parse_args(
        argv + (["--smoke"] if opts.smoke else []))
    import jax
    t0 = time.perf_counter()
    engine = serve.build_engine(args, init_seed=opts.seed)
    jax.block_until_ready(engine.params)
    cfg = engine.model.cfg
    log(f"[a] set-up {time.perf_counter() - t0:.1f}s: {cfg.arch_id} "
        f"{cfg.n_layers}L d_model={cfg.d_model} heads={cfg.n_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"kernels={cfg.use_pallas_kernels}; tokenizer V={engine._v}")
    prompts = [PREAMBLE + p for p in serve.BASE_PROMPTS]
    log(f"[a] prompt tokens: "
        f"{[len(engine.tok.encode(p)) for p in prompts]}")
    requests, labels = serve.make_requests(args, prompts)
    gr = {n: grammars.load(n) for n in set(labels) if n != "none"}
    grammars_of = [gr.get(lbl) for lbl in labels]
    vocab = list(engine.tok.vocab)
    runs = []
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        results = serve.serve_batch(engine, requests, args)
        dt = time.perf_counter() - t0
        stats = engine.last_batch_stats
        n_checked = check_results(f"[a] {label}", results, stats,
                                  grammars_of, vocab, engine.tok.eos_id)
        n_tok = sum(r.n_tokens for r in results)
        log(f"[a] {label} batch {dt:.2f}s: {len(results)} requests "
            f"({','.join(labels)}), {n_tok} tokens, statuses all ok, "
            f"{n_checked} constrained outputs accepted, prefix hits "
            f"{stats['n_prefix_hits']} ({stats['n_prefix_tokens']} "
            f"prompt tokens), degrades/retries/resets 0")
        runs.append([list(r.token_ids) for r in results])
    if runs[0] != runs[1]:
        raise RuntimeError("[a] warm pass changed the greedy token ids")
    if not opts.smoke and stats["n_prefix_hits"] == 0:
        raise RuntimeError("[a] the prefix cache never hit")
    for lbl, r in zip(labels, results):
        log(f"[a]   {lbl:>4}: {r.text[:60]!r}")
    return engine


def phase_fused(engine):
    """(b) certified-JSON batch: fused device loop vs host loop."""
    from repro.core import grammars
    from repro.core.sampling import GrammarSampler
    from repro.serving import (ConstraintSpec, DecodeParams, Request,
                               ServingEngine)
    from repro.tokenizer import train_bpe

    g = grammars.load("json")
    t0 = time.perf_counter()
    tok = train_bpe(GrammarSampler(g, seed=5).corpus(80), vocab_size=257)
    eng = ServingEngine(engine.model, engine.params, tok,
                        max_len=engine.max_len, device_tables=True)
    eng.register_grammar("json", g)
    eng.precompute()
    if "json" not in eng.device_tables:
        raise RuntimeError("[b] json did not certify on the byte "
                           "vocabulary")
    log(f"[b] set-up {time.perf_counter() - t0:.1f}s: byte vocabulary "
        f"V={tok.vocab_size}, json device table "
        f"{eng.device_tables['json'].n_states} states")
    requests = [Request(FUSED_PROMPTS[i % len(FUSED_PROMPTS)],
                        ConstraintSpec(grammar="json", mode="domino"),
                        DecodeParams(max_tokens=MAX_TOKENS))
                for i in range(SLOTS)]
    vocab = list(tok.vocab)
    ids = {}
    for device_loop in (True, False):
        name = "fused" if device_loop else "host"
        for label in ("cold", "warm"):
            t0 = time.perf_counter()
            results = eng.generate_batch(requests, max_batch=SLOTS,
                                         device_loop=device_loop,
                                         sync_n=SYNC_N)
            dt = time.perf_counter() - t0
            check_results(f"[b] {name}", results, eng.last_batch_stats,
                          [g] * len(results), vocab, tok.eos_id)
            n_dev = sum(r.n_device_tokens for r in results)
            log(f"[b] {name} {label} {dt:.2f}s: "
                f"{sum(r.n_tokens for r in results)} tokens, "
                f"{n_dev} committed by fused blocks")
        if device_loop and n_dev == 0:
            raise RuntimeError("[b] the fused loop committed no token")
        ids[name] = [list(r.token_ids) for r in results]
    same = ids["fused"] == ids["host"]
    n_same = sum(a == b for a, b in zip(ids["fused"], ids["host"]))
    log(f"[b] fused ids match host loop: {same} "
        f"({n_same}/{len(requests)} rows identical)")


def phase_kernels(engine, opts):
    """(c) Pallas kernels against the jnp reference on the chip."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bitmask
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.masked_sample.ops import masked_argmax
    from repro.kernels.masked_sample.ref import masked_argmax_ref
    from repro.models import build_model, kvcache

    model, params = engine.model, engine.params
    ref_model = build_model(dataclasses.replace(model.cfg,
                                                use_pallas_kernels=False))
    ref_step = jax.jit(ref_model.decode_step)
    page = 64
    n_pages = kvcache.default_n_pages(SLOTS, engine.max_len, page)
    cache = model.init_cache(SLOTS, engine.max_len, page_size=page,
                             n_pages=n_pages)
    per_row = engine.max_len // page
    cache["pages"] = jnp.arange(1, 1 + SLOTS * per_row,
                                dtype=jnp.int32).reshape(SLOTS, per_row)
    cache["len"] = jnp.zeros((SLOTS,), jnp.int32)
    rng = np.random.default_rng(opts.seed)
    cfg = model.cfg
    g, dh = cfg.n_kv_heads, cfg.d_head
    dt = jnp.dtype(cfg.dtype)
    pool_k, pool_v = (jnp.asarray(rng.normal(size=(n_pages, page, g, dh)),
                                  dt) for _ in range(2))
    lens = jnp.asarray(RAGGED_LENS, jnp.int32)
    for s_win in (1, 5):                # plain decode, verify window
        q = jnp.asarray(rng.normal(
            size=(SLOTS, s_win, g, cfg.n_heads // g, dh)), dt)
        out_k, out_r = (np.asarray(decode_attention(
            q, pool_k, pool_v, lens, use_ref=use_ref,
            block_tables=cache["pages"]), np.float32)
            for use_ref in (False, True))
        diff = float(np.abs(out_k - out_r).max())
        scale = float(np.abs(out_r).max())
        log(f"[c] paged decode attention S={s_win}, G={g}, D={dh}, page "
            f"{page}: max |kernel - oracle| {diff:.6g}, max |oracle| "
            f"{scale:.6g} (tolerance {ATTN_TOL:g} of it)")
        if not np.isfinite(out_k).all() or diff > ATTN_TOL * scale:
            raise RuntimeError("[c] decode attention kernel disagrees "
                               "with its oracle")
    feed = rng.integers(0, engine._v, size=(SLOTS, max(RAGGED_LENS) + 1))
    t0 = time.perf_counter()
    for t in range(max(RAGGED_LENS)):   # fill the pool through the model
        _, cache = ref_step(params, cache,
                            jnp.asarray(feed[:, t:t + 1], jnp.int32))
    cache["len"] = jnp.asarray(RAGGED_LENS, jnp.int32)
    last = jnp.asarray(feed[:, -1:], jnp.int32)
    lg_ref, _ = ref_step(params, cache, last)
    lg_ker, _ = engine._decode(params, cache, last)
    v = model.cfg.vocab_size
    ref = np.asarray(lg_ref, np.float32)[:, 0, :v]
    ker = np.asarray(lg_ker, np.float32)[:, 0, :v]
    diff = float(np.abs(ker - ref).max())
    scale = float(np.abs(ref).max())
    agree = int((ker.argmax(-1) == ref.argmax(-1)).sum())
    log(f"[c] decode step, Pallas vs jnp, ragged lengths {RAGGED_LENS}: "
        f"max |logit diff| {diff:.6g}, max |ref logit| {scale:.6g}, "
        f"ratio {diff / max(scale, 1e-30):.6g} (tolerance {LOGIT_TOL:g}); "
        f"argmax agrees on {agree}/{SLOTS} rows "
        f"({time.perf_counter() - t0:.1f}s)")
    if not np.isfinite(ker).all() or diff > LOGIT_TOL * scale:
        raise RuntimeError("[c] kernel decode step disagrees with the "
                           "jnp reference")
    # the served step must run the kernels compiled, not interpreted
    hlo = engine._decode.lower(params, cache, last).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    log(f"[c] compiled Pallas calls in the served decode step: {n_calls}")
    if jax.default_backend() == "tpu" and n_calls == 0:
        raise RuntimeError("[c] the decode step runs no compiled kernel")
    bits = jnp.asarray(bitmask.pack_bool(rng.random((SLOTS, v)) < 0.3))
    logits = jnp.asarray(ker)
    idx_k, val_k = masked_argmax(logits, bits)
    idx_r, val_r = masked_argmax_ref(logits, bits)
    same = bool(np.array_equal(np.asarray(idx_k), np.asarray(idx_r))
                and np.array_equal(np.asarray(val_k), np.asarray(val_r)))
    log(f"[c] packed masked argmax at V={v}, B={SLOTS}: kernel == jnp "
        f"oracle: {same}")
    if not same:
        raise RuntimeError("[c] masked argmax kernel disagrees with the "
                           "jnp oracle")


def run(opts) -> dict:
    import jax

    from repro.launch import compile_cache

    backend = jax.default_backend()
    if backend != "tpu" and not opts.smoke:
        return {"ok": False, "error": f"JAX backend is {backend!r}, not "
                                      f"a TPU"}
    log(f"[chip_smoke] backend {backend}, devices {jax.devices()}, "
        f"compile cache {compile_cache.enable()}")
    t_all = time.perf_counter()
    t0 = time.perf_counter()
    engine = phase_mixed(opts)
    log(f"[a] done in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    phase_fused(engine)
    log(f"[b] done in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    phase_kernels(engine, opts)
    log(f"[c] done in {time.perf_counter() - t0:.1f}s")
    log(f"[chip_smoke] all phases passed in "
        f"{time.perf_counter() - t_all:.1f}s")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return {"ok": False, "error": f"phases passed on {dev.platform}, "
                                      f"not on a TPU"}
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, request sampling and the "
                         "kernel-phase inputs")
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse every phase at the smoke size (CPU); "
                         "the verdict still requires a TPU")
    opts = ap.parse_args()
    try:
        verdict = run(opts)
    except Exception as e:
        traceback.print_exc()
        verdict = {"ok": False, "error": repr(e)}
    sys.stdout.flush()
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
