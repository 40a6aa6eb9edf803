"""Constrained-serving entry point: builds a model (the published config
at full width with seeded random weights, its ``--smoke`` variant, or a
``--checkpoint``) and serves batched requests through the per-request
constraint API.

``--grammar`` takes a comma-separated list ("none" = unconstrained rows);
every listed grammar is registered on ONE engine's grammar registry and
the prompts cycle through them, so a single continuous batch carries
mixed-grammar traffic:

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --grammar json,c,none --mode domino --prompts 6
"""
import argparse
import dataclasses
from typing import List, Optional, Sequence, Tuple

BASE_PROMPTS = ("A person encoded as a JSON object: ",
                "Results: ",
                "Config: ",
                "Data record: ")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced smoke variant of --arch, with "
                         "its vocabulary collapsed to the tokenizer's "
                         "(default: the published config at full width "
                         "and vocabulary, random weights from PRNGKey(0))")
    ap.add_argument("--grammar", default="json",
                    help="comma-separated grammar names cycled across "
                         "prompts; 'none' entries serve unconstrained rows")
    ap.add_argument("--mode", default="domino",
                    choices=["unconstrained", "domino", "naive", "online"])
    ap.add_argument("--k", type=int, default=-1, help="-1 = infinity")
    ap.add_argument("--opportunistic", action="store_true")
    ap.add_argument("--speculative", action="store_true")
    ap.add_argument("--spec-s", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request sampling seed base (request i uses "
                         "seed+i)")
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching decode slots")
    ap.add_argument("--kernels", action="store_true",
                    help="route decode through the fused Pallas kernels "
                         "(ragged flash-decode; interpret mode off-TPU)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged-KV pool page length in tokens (pageable "
                         "archs only; the fused kernel's BLOCK_T)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged-KV pool size in pages (default: "
                         "capacity-equivalent slots*max_len/page_size; "
                         "smaller pools trade admission backpressure for "
                         "HBM)")
    ap.add_argument("--no-paged", action="store_true",
                    help="force contiguous per-slot KV stripes")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in seconds "
                         "(queue wait included); overdue requests end "
                         "with status deadline_exceeded instead of "
                         "holding a slot (default: unbounded)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the scheduler's waiting queue: overflow "
                         "submissions are shed immediately with status "
                         "rejected (default: unbounded)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged KV pool: "
                         "admissions sharing a token prefix block-map "
                         "the cached pages copy-on-write and prefill "
                         "only the tail (outputs bitwise-identical to a "
                         "cold cache); requires paged KV")
    ap.add_argument("--device-tables", action="store_true",
                    help="build device grammar tables at precompute for "
                         "every registered grammar that certifies clean "
                         "(finite closure, no mask conflicts/truncations)")
    ap.add_argument("--device-loop", action="store_true",
                    help="run certified greedy rows through the fused "
                         "device-resident decode loop: one host sync per "
                         "--sync-n tokens instead of per token "
                         "(implies --device-tables)")
    ap.add_argument("--sync-n", type=int, default=8,
                    help="fused-loop block length: decode steps committed "
                         "on device between host syncs")
    ap.add_argument("--analyze", default="off",
                    choices=["off", "warn", "strict"],
                    help="registration-time grammar analysis policy: "
                         "'warn' reports traps/alignment gaps, 'strict' "
                         "refuses to serve a grammar with any")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="crash-consistent token journal (write-ahead "
                         "log): per-request lifecycle events and "
                         "committed-token batches, fsynced at tick "
                         "boundaries; restart with --restore to resume")
    ap.add_argument("--journal-sync-every", type=int, default=1,
                    help="fsync the journal every N ticks (larger = "
                         "less durable tail, less write amplification)")
    ap.add_argument("--restore", action="store_true",
                    help="replay --journal PATH instead of submitting "
                         "fresh prompts: live requests resume from "
                         "their validated committed prefix (greedy rows "
                         "bitwise-identical to an uninterrupted run)")
    ap.add_argument("--crash-after-syncs", type=int, default=None,
                    metavar="K",
                    help="fault drill: SIGKILL this process after the "
                         "journal's K-th fsync (exercised by "
                         "tools/restart_smoke.py)")
    ap.add_argument("--print-ids", action="store_true",
                    help="emit one machine-readable 'IDS <rid> "
                         "<token ids...>' line per result (restart-smoke "
                         "bitwise comparison)")
    ap.add_argument("--checkpoint", default=None)
    return ap


def grammar_names(args) -> List[str]:
    """The ``--grammar`` list in order ("none" = unconstrained rows)."""
    return [n.strip() for n in args.grammar.split(",") if n.strip()]


def build_engine(args, init_seed: int = 0):
    """One engine for ``args``: config, seeded tokenizer (or checkpoint),
    model and params (random weights from ``PRNGKey(init_seed)``), with
    every listed grammar registered on its registry and precomputed."""
    import jax

    from repro.configs import get_config
    from repro.core import grammars
    from repro.core.sampling import GrammarSampler
    from repro.models import build_model
    from repro.serving import ServingEngine
    from repro.tokenizer import BPETokenizer, train_bpe
    from repro.training import checkpoint

    loaded = {n: grammars.load(n) for n in grammar_names(args)
              if n != "none"}
    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, use_pallas_kernels=args.kernels)
    if args.checkpoint:
        import os
        tok = BPETokenizer.load(os.path.join(args.checkpoint,
                                             "tokenizer.json"))
        cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size,
                                  max_seq_len=4096)
        model = build_model(cfg)
        params, _, _ = checkpoint.load(
            args.checkpoint, model.init(jax.random.PRNGKey(init_seed)))
    else:
        corpus = b""
        for i, g in enumerate(loaded.values() or
                              [grammars.load("json")]):
            corpus += GrammarSampler(g, seed=i).corpus(
                200 // max(1, len(loaded)))
        tok = train_bpe(corpus, vocab_size=400)
        if args.smoke:
            cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size,
                                      max_seq_len=4096)
        # at full width the head keeps the published vocabulary; the
        # engine slices logits to the tokenizer's ids
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(init_seed))

    # ONE engine, one KV pool: constraints ride on each Request
    engine = ServingEngine(model, params, tok, max_len=1024,
                           analysis_policy=args.analyze,
                           device_tables=args.device_tables
                           or args.device_loop)
    for name, g in loaded.items():
        engine.register_grammar(name, g)   # analyzed per --analyze policy
    engine.precompute()                 # warm every registered grammar
    return engine


def make_requests(args, prompts: Sequence[str] = BASE_PROMPTS
                  ) -> Tuple[list, List[str]]:
    """``args.prompts`` requests cycling through ``prompts`` and the
    ``--grammar`` list; returns (requests, grammar label per request)."""
    from repro.serving import ConstraintSpec, DecodeParams, Request

    gnames = grammar_names(args)
    decode = DecodeParams(
        temperature=args.temperature, max_tokens=args.max_tokens,
        speculative=args.speculative, spec_s=args.spec_s,
        deadline_s=args.deadline_s)
    specs = []
    for name in gnames:
        if name == "none" or args.mode == "unconstrained":
            specs.append(ConstraintSpec())
        else:
            specs.append(ConstraintSpec(
                grammar=name, mode=args.mode,
                k=(None if args.k < 0 else args.k),
                opportunistic=args.opportunistic))
    requests = [
        Request(prompts[i % len(prompts)], specs[i % len(specs)],
                dataclasses.replace(decode, seed=args.seed + i))
        for i in range(args.prompts)]
    labels = [gnames[i % len(gnames)] for i in range(args.prompts)]
    return requests, labels


def serve_batch(engine, requests, args, journal=None):
    """Serve ``requests`` through continuous batching with ``args``'
    slots, KV layout, queue and fused-loop options."""
    return engine.generate_batch(
        requests, max_batch=args.slots,
        paged=False if args.no_paged else None,
        page_size=args.page_size, n_pages=args.pool_pages,
        queue_limit=args.queue_limit,
        device_loop=args.device_loop, sync_n=args.sync_n,
        journal=journal, prefix_cache=args.prefix_cache)


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    from repro.serving import TokenJournal

    compile_cache.enable()
    engine = build_engine(args)
    loaded = [n for n in grammar_names(args) if n != "none"]
    for name, rep in engine.analysis_reports.items():
        print(f"[analysis] {name}: "
              f"{'OK' if rep.ok() else 'PROBLEMS'} "
              f"({rep.closure.n_states} states, "
              f"{'finite' if rep.closure.finite else 'open'}, "
              f"{rep.analysis_time_s:.2f}s)")
    if engine.enable_device_tables:
        for name, tbl in engine.device_tables.items():
            print(f"[device-table] {name}: {tbl.n_states} states, "
                  f"{tbl.n_bytes / 1024:.0f} KiB uploaded")
        missing = set(loaded) - set(engine.device_tables)
        if missing:
            print(f"[device-table] not certified (host path): "
                  f"{','.join(sorted(missing))}")

    journal = None
    if args.journal:
        journal = TokenJournal(args.journal,
                               sync_every=args.journal_sync_every,
                               crash_after_syncs=args.crash_after_syncs)

    if args.restore:
        if journal is None:
            ap.error("--restore requires --journal PATH")
        # same deterministic engine (seeded tokenizer corpus + PRNGKey(0)
        # init) as the crashed run, so recompute-prefill regenerates the
        # exact logits; the reopened journal keeps the resumed run durable
        sched = engine.restore(
            args.journal, max_batch=args.slots, journal=journal,
            paged=False if args.no_paged else None,
            page_size=args.page_size, n_pages=args.pool_pages,
            device_loop=args.device_loop, sync_n=args.sync_n,
            prefix_cache=args.prefix_cache)
        n_live = len(sched.waiting)
        results = sched.run()
        print(f"[restore] {args.journal}: {len(results)} journaled "
              f"request(s), {n_live} resumed live; "
              f"stats={sched.stats()}")
        for r in results:
            print(f"--- out[status={r.status}, {r.n_tokens} toks, "
                  f"{r.n_replayed_tokens} replayed]: {r.text[:120]!r}"
                  + (f" error={r.error}" if r.error else ""))
        if args.print_ids:
            for s in sorted(sched.finished, key=lambda s: s.rid):
                print(f"IDS {s.rid} " + " ".join(
                    str(t) for t in s.result.token_ids))
        return

    requests, labels = make_requests(args)
    if len(requests) > 1 or journal is not None:
        # continuous batching covers every arch (SSM/SWA rows are admitted
        # by exact-length prefill; speculation refeeds per row); pure
        # full-attention/MLA stacks serve from a paged KV pool; rows mix
        # grammars/modes freely
        print(f"[continuous batching: {len(requests)} requests "
              f"({','.join(sorted(set(labels)))}), "
              f"{min(len(requests), args.slots)} slots, "
              f"{'contiguous KV' if args.no_paged else 'paged KV'}]")
        results = serve_batch(engine, requests, args, journal)
        print(f"[stats] {engine.last_batch_stats}")
    else:
        results = [engine.generate(r) for r in requests]
    for lbl, req, r in zip(labels, requests, results):
        print(f"--- prompt[{lbl}]: {req.prompt!r}")
        print(f"    out[status={r.status}, {r.n_tokens} toks, "
              f"{r.n_forward_passes} fwd, "
              f"{r.n_interventions} interventions, "
              f"spec {r.n_spec_accepted}/{r.n_spec_proposed}"
              + (f", {r.n_device_tokens} device-committed"
                 if args.device_loop else "")
              + f"]: {r.text[:120]!r}"
              + (f" error={r.error}" if r.error else ""))
    if args.print_ids:
        for rid, r in enumerate(results):
            print(f"IDS {rid} " + " ".join(str(t) for t in r.token_ids))


if __name__ == "__main__":
    main()
