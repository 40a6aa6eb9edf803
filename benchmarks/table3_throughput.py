"""Table 3 — constrained-generation overhead per grammar x method.

Reports, per (grammar, method):
  us/token        — wall time per generated token (CPU; absolute)
  rel_throughput  — tokens/s relative to unconstrained on the same model
  tok/fwd         — tokens per model forward (>1 = speculation wins; this
                    is the hardware-independent speedup driver of Table 3)
  mask_us/tok     — host-side constraint cost per token (DOMINO's
                    precomputation advantage vs the online baseline)

Plus a serving section: aggregate tokens/s of N concurrent constrained
requests through the continuous-batching scheduler (slot reuse, device-side
masking) vs serving the same requests sequentially.
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks.common import emit, get_model_and_params
from repro.core import grammars
from repro.models import build_model
from repro.serving import (ContinuousBatchingScheduler, EngineConfig,
                           ServingEngine)

GRAMMARS = {
    "json": ("A JSON file describing a person: ", "json"),
    "json_schema": ("Q: compute 3 + 4\nA: ", "json_gsm8k"),
    "c": ("A C program: ", "c"),
    "xml_schema": ("An XML file describing a person: ", "xml_schema"),
    "template": ("A character profile for an RPG game in JSON format: ",
                 "template_rpg"),
}

REPS = 4
MAX_TOKENS = 56


def methods(max_tokens):
    return [
        ("unconstrained", EngineConfig(mode="unconstrained",
                                       max_tokens=max_tokens)),
        ("online", EngineConfig(mode="online", max_tokens=max_tokens)),
        ("domino", EngineConfig(mode="domino", max_tokens=max_tokens)),
        ("domino_opp", EngineConfig(mode="domino", opportunistic=True,
                                    max_tokens=max_tokens)),
        ("domino_spec10", EngineConfig(mode="domino", speculative=True,
                                       spec_s=10, spec_threshold=0.4,
                                       max_tokens=max_tokens)),
    ]


def run(verbose: bool = True):
    model, params, tok = get_model_and_params()
    out = {}
    for gname, (prompt, gkey) in GRAMMARS.items():
        g = grammars.load(gkey)
        base_tps = None
        for mname, ecfg in methods(MAX_TOKENS):
            eng = ServingEngine(model, params, tok,
                                None if mname == "unconstrained" else g,
                                ecfg, max_len=1024)
            eng.generate(prompt)                   # warmup + spec prior
            toks = fwd = 0
            mask_t = model_t = wall = 0.0
            for _ in range(REPS):
                r = eng.generate(prompt)
                toks += max(1, r.n_tokens)
                fwd += r.n_forward_passes
                mask_t += r.mask_time_s
                model_t += r.model_time_s
                wall += r.wall_time_s
            tps = toks / wall
            if mname == "unconstrained":
                base_tps = tps
            row = {
                "us_per_token": 1e6 * wall / toks,
                "rel_throughput": tps / base_tps,
                "tok_per_fwd": toks / fwd,
                "mask_us_per_token": 1e6 * mask_t / toks,
            }
            out[(gname, mname)] = row
            if verbose:
                print(f"  [table3] {gname:12s} {mname:14s} "
                      f"rel={row['rel_throughput']:.2f}x "
                      f"tok/fwd={row['tok_per_fwd']:.2f} "
                      f"mask={row['mask_us_per_token']:.0f}us/tok",
                      flush=True)
            emit(f"table3_{gname}_{mname}", row["us_per_token"],
                 f"rel={row['rel_throughput']:.3f};"
                 f"tokfwd={row['tok_per_fwd']:.3f};"
                 f"maskus={row['mask_us_per_token']:.1f}")
    out.update(run_serving(model, params, tok, verbose=verbose))
    return out


N_REQUESTS = 6
SLOTS = 3


def run_serving(model, params, tok, verbose: bool = True):
    """Continuous-batching scheduler vs sequential single-request serving:
    N concurrent grammar-constrained requests, SLOTS decode slots."""
    g = grammars.load("json")
    prompts = [f"request {i}, a JSON value: " for i in range(N_REQUESTS)]
    eng = ServingEngine(model, params, tok, g,
                        EngineConfig(mode="domino", max_tokens=MAX_TOKENS),
                        max_len=1024)
    eng.precompute()                   # masks off the critical path
    eng.generate(prompts[0])           # compile warmup (prefill + decode)
    t0 = time.perf_counter()
    seq = [eng.generate(p) for p in prompts]
    seq_wall = time.perf_counter() - t0
    seq_toks = sum(max(1, r.n_tokens) for r in seq)
    # warm the batched path's compilations (B=SLOTS decode, slot scatter,
    # fused masked argmax) the same way the sequential path was warmed
    warm = ContinuousBatchingScheduler(eng, capacity=SLOTS)
    for p in prompts[:SLOTS]:
        warm.submit(p)
    warm.run()
    sched = ContinuousBatchingScheduler(eng, capacity=SLOTS)
    for p in prompts:
        sched.submit(p)
    t0 = time.perf_counter()
    batch = sched.run()
    batch_wall = time.perf_counter() - t0
    batch_toks = sum(max(1, r.n_tokens) for r in batch)
    row = {
        "seq_tok_per_s": seq_toks / seq_wall,
        "batch_tok_per_s": batch_toks / batch_wall,
        "speedup": (batch_toks / batch_wall) / (seq_toks / seq_wall),
        "fwd_seq": sum(r.n_forward_passes for r in seq),
        "fwd_batch": sched.n_fwd,
    }
    if verbose:
        print(f"  [table3] serving      continuous    "
              f"{row['batch_tok_per_s']:.1f} tok/s vs "
              f"{row['seq_tok_per_s']:.1f} sequential "
              f"({row['speedup']:.2f}x, "
              f"fwd {row['fwd_batch']} vs {row['fwd_seq']})", flush=True)
    emit("table3_serving_continuous", row["batch_tok_per_s"],
         f"speedup={row['speedup']:.3f};fwd={row['fwd_batch']}")
    out = {("serving", "continuous"): row}
    out.update(run_serving_fused(model, params, tok, verbose=verbose))
    out.update(run_serving_paged(model, params, tok, verbose=verbose))
    out.update(run_serving_mixed(model, params, tok, verbose=verbose))
    return out


def run_serving_mixed(model, params, tok, verbose: bool = True):
    """Mixed-traffic serving (ISSUE 5): the paper's near-zero-overhead
    claim should hold for a MIXED batch, not just a homogeneous one.

    One engine + grammar registry serves N requests through SLOTS slots
    twice: a homogeneous batch (all JSON-domino) and a mixed batch
    cycling {json domino, c domino, unconstrained} — same request count,
    same budgets, same pool.  The row records both aggregate throughputs;
    a mixed/homogeneous ratio near 1 means per-request constraint routing
    adds no serving cost."""
    from repro.serving import (ConstraintSpec, ContinuousBatchingScheduler,
                               DecodeParams, Request, ServingEngine)

    eng = ServingEngine(model, params, tok, max_len=1024)
    eng.register_grammar("json", grammars.load("json"))
    eng.register_grammar("c", grammars.load("c"))
    eng.precompute()
    dp = DecodeParams(max_tokens=MAX_TOKENS)
    prompts = [f"request {i}, a value: " for i in range(N_REQUESTS)]
    homo = [Request(p, ConstraintSpec(grammar="json", mode="domino"), dp)
            for p in prompts]
    cycle = [ConstraintSpec(grammar="json", mode="domino"),
             ConstraintSpec(grammar="c", mode="domino"),
             ConstraintSpec()]
    mixed = [Request(p, cycle[i % len(cycle)], dp)
             for i, p in enumerate(prompts)]

    rows = {}
    for label, reqs in (("homogeneous", homo), ("mixed", mixed)):
        warm = ContinuousBatchingScheduler(eng, capacity=SLOTS)
        for r in reqs:
            warm.submit(r)
        warm.run()                      # compile + tree/memo warmup
        sched = ContinuousBatchingScheduler(eng, capacity=SLOTS)
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        res = sched.run()
        wall = time.perf_counter() - t0
        toks = sum(max(1, r.n_tokens) for r in res)
        rows[label] = {"tok_per_s": toks / wall, "fwd": sched.n_fwd,
                       "mask_cache_hits": sched.mask_cache_hits}
    rows["mixed"]["rel_vs_homogeneous"] = (
        rows["mixed"]["tok_per_s"] / rows["homogeneous"]["tok_per_s"])
    for label, r in rows.items():
        if verbose:
            rel = (f" ({r['rel_vs_homogeneous']:.2f}x vs homogeneous)"
                   if "rel_vs_homogeneous" in r else "")
            print(f"  [table3] serving      traffic_{label:11s}"
                  f"{r['tok_per_s']:8.1f} tok/s "
                  f"(fwd {r['fwd']}, memo hits {r['mask_cache_hits']})"
                  f"{rel}", flush=True)
        emit(f"table3_serving_traffic_{label}", r["tok_per_s"],
             f"fwd={r['fwd']};memo={r['mask_cache_hits']}")
    return {("serving", "mixed_traffic"): rows}


def run_serving_fused(model, params, tok, verbose: bool = True):
    """Ragged-fused vs dense-fallback batched decode: the same continuous
    batch served with ``use_pallas_kernels`` on (every batched decode step
    reads the cache through the ragged flash-decode kernel) and off (dense
    jnp attention over the full cache).  On CPU the kernel runs
    interpreted, so absolute wall time is NOT the TPU story — the
    chip measures the kernel (``bench/run.py``, its
    ``decode_attention_roofline``); this row exists to pin the routing
    and track the two paths' trajectories."""
    g = grammars.load("json")
    prompts = [f"request {i}, a JSON value: " for i in range(3)]
    rows = {}
    for label, fused in (("dense_fallback", False), ("ragged_fused", True)):
        cfg = dataclasses.replace(model.cfg, use_pallas_kernels=fused)
        eng = ServingEngine(build_model(cfg), params, tok, g,
                            EngineConfig(mode="domino", max_tokens=24),
                            max_len=1024)
        eng.precompute()
        warm = ContinuousBatchingScheduler(eng, capacity=len(prompts))
        for p in prompts:
            warm.submit(p)
        warm.run()                      # compile warmup for this path
        sched = ContinuousBatchingScheduler(eng, capacity=len(prompts))
        for p in prompts:
            sched.submit(p)
        t0 = time.perf_counter()
        res = sched.run()
        wall = time.perf_counter() - t0
        toks = sum(max(1, r.n_tokens) for r in res)
        rows[label] = {"tok_per_s": toks / wall, "fwd": sched.n_fwd}
        if verbose:
            print(f"  [table3] serving      {label:14s}"
                  f"{rows[label]['tok_per_s']:8.1f} tok/s "
                  f"(fwd {sched.n_fwd})", flush=True)
        emit(f"table3_serving_{label}", rows[label]["tok_per_s"],
             f"fwd={sched.n_fwd}")
    return {("serving", "fused_vs_fallback"): rows}


def run_serving_paged(model, params, tok, verbose: bool = True):
    """Paged vs contiguous KV under the SAME HBM budget (ISSUE 3).

    The budget is two contiguous max_len stripes (pool HBM = 2 x 1024
    tokens < capacity x max_len).  The contiguous layout can only hold
    ``budget / max_len`` = 2 resident requests — admission queues the
    rest.  The paged layout spends the budget as 64-token pages, so every
    slot admits with just ``ceil(need/64)`` pages and 4 requests decode
    concurrently; the row records the achieved residency and aggregate
    throughput of each layout.
    """
    from repro.serving.scheduler import ContinuousBatchingScheduler

    g = grammars.load("json")
    prompts = [f"request {i}, a JSON value: " for i in range(N_REQUESTS)]
    max_len, ps = 1024, 64
    pool_tokens = 2 * max_len                 # HBM budget: 2 full stripes
    eng = ServingEngine(model, params, tok, g,
                        EngineConfig(mode="domino", max_tokens=24),
                        max_len=max_len)
    eng.precompute()

    def serve(label, **kw):
        warm = ContinuousBatchingScheduler(eng, **kw)
        for p in prompts:
            warm.submit(p)
        warm.run()                             # compile warmup
        sched = ContinuousBatchingScheduler(eng, **kw)
        for p in prompts:
            sched.submit(p)
        resident_max = 0
        t0 = time.perf_counter()
        done = []
        while sched.waiting or any(s is not None for s in sched.slots):
            done.extend(sched.step())
            resident_max = max(resident_max,
                               sum(s is not None for s in sched.slots))
        wall = time.perf_counter() - t0
        toks = sum(max(1, s.result.n_tokens) for s in done)
        return {"tok_per_s": toks / wall, "resident_max": resident_max,
                "fwd": sched.n_fwd}

    rows = {
        # contiguous: the budget holds 2 max_len stripes -> 2 slots
        "contiguous": serve("contiguous",
                            capacity=pool_tokens // max_len, paged=False),
        # paged: the same budget as 64-token pages serves 4 slots
        "paged": serve("paged", capacity=4, page_size=ps,
                       n_pages=pool_tokens // ps + 1),
    }
    assert rows["paged"]["resident_max"] > rows["contiguous"]["resident_max"], \
        "paged admission should out-admit contiguous under the same HBM"
    for label, r in rows.items():
        if verbose:
            print(f"  [table3] serving      kv_{label:10s}"
                  f"{r['tok_per_s']:8.1f} tok/s "
                  f"(resident {r['resident_max']}, fwd {r['fwd']}, "
                  f"HBM budget {pool_tokens} tokens)", flush=True)
        emit(f"table3_serving_kv_{label}", r["tok_per_s"],
             f"resident={r['resident_max']};fwd={r['fwd']};"
             f"pool_tokens={pool_tokens}")
    return {("serving", "paged_vs_contiguous"): rows}


if __name__ == "__main__":
    run()
